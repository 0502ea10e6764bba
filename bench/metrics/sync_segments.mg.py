"""Planner: sequential segments of one multigrid V-cycle, each level's
solvers' ``SpTRSV.stats()["segments"]`` times the solves each runs in a
V-cycle (the driver's ``(solver, sweeps)`` objects)."""


def read(ctx):
    objs = ctx.get("objects") or []
    if not objs or not all(isinstance(o, tuple) for o in objs):
        return None
    counts = [(s.stats().get("segments"), sweeps) for s, sweeps in objs]
    if any(c is None for c, _ in counts):
        return None
    return float(sum(c * sweeps for c, sweeps in counts))
