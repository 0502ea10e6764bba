"""Device executor: share of the device's busy time in the traced window
spent in the V-cycle's own ops outside the SpTRSV executors (scopes
``mg.smooth``: SYMGS's SpMV and vector updates; ``mg.transfer``: the
restriction residual, injection and prolongation; separate programs, so
never nested), in % (``bench/spans.py``)."""
from bench import spans

# the program's scope names (repro.core.obs), kept as strings so that the
# benchmark can read a program that has none of them
MG_SMOOTH = "mg.smooth"
MG_TRANSFER = "mg.transfer"


def read(ctx):
    got = [spans.scoped(ctx, s) for s in (MG_SMOOTH, MG_TRANSFER)]
    got = [g for g in got if g is not None]
    if not got:
        return None
    return 100.0 * sum(inside for inside, _ in got) / got[0][1]
