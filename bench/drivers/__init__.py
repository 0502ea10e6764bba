"""Drivers, one per kind of traffic, found by the ``driver`` key of a
traffic file.  Each exposes ``run(cell, matrices, seed, seconds, trace_dir,
t0, program=None) -> dict`` (see :mod:`bench.drivers.solve`)."""
