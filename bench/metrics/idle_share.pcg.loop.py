"""PCG loop: share of the traced window in which the device idled while the
host ran a loop iteration (``pcg.iter``: eager dispatches, the
preconditioner's solve calls), not in a readback, in %
(``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.idle_share(ctx, spans.PCG_ITER, spans.PCG_ORDER)
