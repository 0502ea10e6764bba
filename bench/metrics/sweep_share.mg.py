"""Device executor: share of the device's busy time in the traced window
spent in the SpTRSV executors' ops (scopes ``sptrsv.segment`` and
``sptrsv.permute``, which never nest), the multigrid's Gauss-Seidel sweeps,
in % (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    got = [spans.scoped(ctx, s) for s in (spans.SEGMENT, spans.PERMUTE)]
    got = [g for g in got if g is not None]
    if not got:
        return None
    return 100.0 * sum(inside for inside, _ in got) / got[0][1]
