"""Device executor: device time of one segment of the level-set executor,
the time in scope ``sptrsv.segment`` over the traced calls and the solver's
``stats()["segments"]``, in microseconds (``bench/spans.py``)."""
from bench import spans


def read(ctx):
    objs = ctx.get("objects") or []
    got = spans.scoped(ctx, spans.SEGMENT)
    if got is None or len(objs) != 1 or not ctx.get("calls"):
        return None
    segments = objs[0].stats().get("segments")
    return 1e6 * got[0] / ctx["calls"] / segments if segments else None
