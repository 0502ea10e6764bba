"""PCG loop: host reads of device values per PCG iteration, the program's
``pcg.readbacks`` over ``pcg.iterations`` (``repro.core.obs``), over every
solve of the run, warm-up included."""


def read(ctx):
    try:
        from repro.core import obs
    except ImportError:   # a program without the counters
        return None
    counts = obs.snapshot()
    iters = counts.get(obs.ITERATIONS, 0)
    return counts.get(obs.READBACKS, 0) / iters if iters else None
