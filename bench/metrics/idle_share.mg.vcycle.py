"""Host dispatch: share of the traced window in which the device idled while
the host was inside a multigrid V-cycle (span ``mg.vcycle``: enqueueing its
solves, SpMVs and transfers), in % (``bench/spans.py``; None where the
trace cannot be aligned)."""
from bench import spans

# the program's span name (repro.core.obs)
MG_VCYCLE = "mg.vcycle"


def read(ctx):
    return spans.idle_share(ctx, MG_VCYCLE, (MG_VCYCLE,))
