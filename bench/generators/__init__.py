"""Matrix generators, one module per family, found by the ``generator`` key
of a configuration file.  Each exposes ``make(config, seed) -> dict`` of
:class:`bench.sparse.Csr` matrices."""
