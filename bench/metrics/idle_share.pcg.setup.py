"""PCG loop: share of the traced window in which the device idled while the
host was in ``pcg``'s set-up (``pcg.setup``: ``build_ell``, the ``matvec``
jit, the first residual and norms), not in a readback, in %
(``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.idle_share(ctx, spans.PCG_SETUP, spans.PCG_ORDER)
