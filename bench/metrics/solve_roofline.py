"""Device executor: the solve's share of its bandwidth roofline, in %.

The bytes are the least any implementation must move for one call,
independent of layout: each stored entry's value and column index
(4 + 4 B), the row pointers ((n+1) x 4 B), and the right-hand sides read and
the answers written (m x 2n x 4 B).  Their time at the device's HBM
bandwidth (``bench/peaks.json``) over the device busy time per call in the
traced window is the share.  A change of layout or kernel moves only the
time, never these bytes.
"""


def min_bytes(n: int, nnz: int, m: int, value_bytes: int = 4) -> int:
    return nnz * (value_bytes + 4) + (n + 1) * 4 + m * 2 * n * value_bytes


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not ctx.get("calls") or trace["busy_s"] <= 0:
        return None
    least_s = (min_bytes(ctx["n"], ctx["nnz"], ctx["m"], ctx["value_bytes"])
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (trace["busy_s"] / ctx["calls"])
