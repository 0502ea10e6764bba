"""Planner: sequential segments (synchronisation points) of the cell's one
triangular solver, as ``SpTRSV.stats()["segments"]`` counts them."""


def read(ctx):
    objs = ctx["objects"]
    if len(objs) != 1:
        return None
    segments = objs[0].stats().get("segments")
    return None if segments is None else float(segments)
