"""Planner: sequential segments of one IC(0) apply, the forward solver's
plus the transpose solver's (``SpTRSV.stats()["segments"]``)."""


def read(ctx):
    objs = ctx["objects"]
    if len(objs) != 2:
        return None
    counts = [o.stats().get("segments") for o in objs]
    return None if None in counts else float(sum(counts))
