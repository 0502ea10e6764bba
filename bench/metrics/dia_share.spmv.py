"""Device executor: share of the entries of the SpMV operators the program
built that it stored by diagonals (DIA, an SpMV of shifted slices with no
gather) rather than as ELL, in %, from the program's counters
``spmv.dia_entries`` and ``spmv.ell_entries`` (``repro.core.obs``) over the
run, warm-up included.  A program that counts neither builds every SpMV
operator as ELL: 0."""

# the program's counter names (repro.core.obs), kept as strings so that the
# benchmark can read a program that has none of them
DIA = "spmv.dia_entries"
ELL = "spmv.ell_entries"


def read(ctx):
    try:
        from repro.core import obs
    except ImportError:   # a program without counters
        return 0.0
    counts = obs.snapshot()
    dia, ell = counts.get(DIA, 0), counts.get(ELL, 0)
    return 100.0 * dia / (dia + ell) if dia + ell else 0.0
