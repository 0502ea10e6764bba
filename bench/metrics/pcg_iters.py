"""PCG loop: iterations per solve, the mean over the run's solves, as the
program's ``PCGResult.iters`` counts them."""


def read(ctx):
    iters = ctx.get("pcg_iters")
    return float(sum(iters)) / len(iters) if iters else None
