"""Host dispatch: share of the traced window in which the device idled while
the host was inside ``SpTRSV.solve`` (``sptrsv.solve``: checks, the RHS
transform, the executor's dispatch), in % (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    return spans.idle_share(ctx, spans.SOLVE, (spans.SOLVE,))
