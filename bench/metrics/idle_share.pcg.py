"""Device: share of the traced window of PCG solves in which the device ran
no operation, in % (``bench/trace_reduce.py``)."""


def read(ctx):
    trace = ctx.get("trace")
    return None if not trace else 100.0 * trace["idle_share"]
