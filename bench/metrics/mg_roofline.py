"""Device executor: one MG-preconditioned CG iteration's share of its
bandwidth roofline, in %.

The bytes are the least that HPCG's algorithm must move for one V-cycle and
one CG iteration, computed from each level's ``n`` and ``nnz`` (the driver's
``mg_levels``) whatever implements it:

- each Gauss-Seidel sweep is a triangular solve with ``tril(A)`` or its
  transpose: :func:`sweep_bytes`, as ``solve_roofline`` counts a solve;
  four sweeps a level (a SYMGS before and after the coarse correction), two
  on the coarsest;
- the post-smoother's ``U x⁰`` reads the strict upper triangle once more,
  with ``x⁰`` in and the product out;
- the restriction reads ``A``'s rows at ``f2c``, ``x``, and ``r`` at
  ``f2c``, and writes the coarse residual; the prolongation reads the
  coarse correction and updates ``x`` at ``f2c``;
- CG's SpMV reads ``A`` once with ``p`` in and ``A p`` out, and its dot
  products and updates stream 14 vectors of ``n``.

Every stored entry moves its value and its 4-byte column index.  Their time
at the device's HBM bandwidth (``bench/peaks.json``) over the device busy
time per iteration of the traced window (the iterations of its solves) is
the share.
"""


def sweep_bytes(n: int, nnz: int, vb: int) -> int:
    return nnz * (vb + 4) + (n + 1) * 4 + 2 * n * vb


def iteration_bytes(levels: list, vb: int = 4) -> int:
    total = 0
    for i, lv in enumerate(levels):
        n, nnz_l = lv["n"], lv["nnz_L"]
        if i + 1 == len(levels):
            total += 2 * sweep_bytes(n, nnz_l, vb)
            continue
        nc = levels[i + 1]["n"]
        total += 4 * sweep_bytes(n, nnz_l, vb)
        total += sweep_bytes(n, nnz_l - n, vb)                 # U x0
        total += (lv["nnz_f2c_rows"] * (vb + 4) + (nc + 1) * 4
                  + n * vb + 2 * nc * vb)                       # restriction
        total += 3 * nc * vb                                    # prolongation
    top = levels[0]
    total += sweep_bytes(top["n"], top["nnz"], vb) + 14 * top["n"] * vb
    return total


def read(ctx):
    trace, levels = ctx.get("trace"), ctx.get("mg_levels")
    iters = sum(ctx.get("pcg_iters") or [])
    if not trace or not levels or not iters or trace["busy_s"] <= 0:
        return None
    least_s = (iteration_bytes(levels, ctx["value_bytes"])
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (trace["busy_s"] / iters)
