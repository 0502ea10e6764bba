"""Host dispatch: jitted programs one multigrid V-cycle enqueues, the
program's ``mg.dispatches`` over ``mg.vcycles`` (``repro.core.obs``), over
every V-cycle of the run, warm-up included."""


def read(ctx):
    try:
        from repro.core import obs
    except ImportError:   # a program without the counters
        return None
    names = getattr(obs, "MG_DISPATCHES", None), getattr(obs, "MG_VCYCLES",
                                                         None)
    if None in names:
        return None
    counts = obs.snapshot()
    cycles = counts.get(names[1], 0)
    return counts.get(names[0], 0) / cycles if cycles else None
