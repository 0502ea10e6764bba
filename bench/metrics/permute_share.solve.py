"""Device executor: share of the device's busy time in the traced window
spent in the permutations ``b[perm]`` and ``x[pos]`` (scope
``sptrsv.permute``), in % (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    got = spans.scoped(ctx, spans.PERMUTE)
    return None if got is None else 100.0 * got[0] / got[1]
