"""PCG loop: share of the traced window in which the device idled while the
host read a device value back (``pcg.readback``: ``||r||``, ``p·Ap``), in %
(``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.idle_share(ctx, spans.PCG_READBACK, spans.PCG_ORDER)
