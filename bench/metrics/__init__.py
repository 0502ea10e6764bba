"""Per-layer metric readers, one file per metric, found by the metric's name.
Each exposes ``read(ctx) -> float | None``: ``ctx`` holds what the driver
saw (``objects``: the program's solvers; host-clock seconds; counts; shapes),
``trace`` (the reduced profiler trace, or None) and ``peaks`` (the device's
row of ``bench/peaks.json``).  A reader that finds nothing returns None;
the harness then stops the run, since ``BENCHMARK.json`` lists the metric
only for cells in which it has something to read."""
