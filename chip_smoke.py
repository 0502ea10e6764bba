#!/usr/bin/env python3
"""Smoke run of the SpTRSV main path on a TPU, through the user entry points.

    python3 chip_smoke.py             # phases a-e on one chip
    python3 chip_smoke.py --chips 4   # only the distributed lung2 solve on a
                                      # 4-chip mesh, against one chip

Phases (one chip):

a. device check — exit non-zero before any work unless JAX sees a TPU;
b. paper workload — ``lung2_like(scale=1.0)`` in f32 through
   ``SpTRSV.build_pair(strategy="auto")``: forward and transpose solves at
   one RHS and at m = 8, then a value ``refresh`` and the same solves again;
c. PCG — IC(0) of ``poisson2d(512, 512)`` (n = 262,144) through
   ``make_ic_preconditioner(strategy="auto")`` + ``pcg`` to a relative
   residual of 1e-6;
d. band — ``banded_lower(8192, bandwidth=24, fill=1.0)`` through
   ``strategy="blocked"``, whose compiled solve must hold the ``trsm_block``
   Pallas kernel (``tpu_custom_call``); also prints what ``auto`` plans;
e. serving — a ``SolveService`` with two tenants on the lung2 pattern and
   one IC(0) tenant, drained before and after every planned build promoted.

Every answer is checked in float64 on the host against the SciPy matrix:
the componentwise residual ``max |b - A x| / (|A||x| + |b|)`` must stay
within ``128 * eps(float32)`` (the guard's default acceptance for f32), and
the forward error against a float64 ``spsolve_triangular`` answer is
reported.  All data comes from fixed seeds.  Each phase prints one JSON line
of smoke timings (set-up seconds with compilation, seconds per solve): they
are not benchmark numbers.  The last line is ``{"ok": true, "device": ...}``;
any failure raises and the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

_SRC = Path(__file__).resolve().parent / "src"
if str(_SRC) not in sys.path:
    sys.path.insert(0, str(_SRC))

SEED = 0
# Acceptance for a componentwise residual of an f32 solve: the guard's
# default 128 * eps(f32).  Forward substitution is componentwise backward
# stable with |dL| <= gamma_k |L| (Higham, Thm 8.5); k <= 26 here, so
# gamma_k ~ k * eps / 2 <= 13 eps, and 128 eps leaves room for the executor's
# reordered sums and the f32 rounding of b.
RESIDUAL_TOL = 128 * float(np.finfo(np.float32).eps)
PCG_TOL = 1e-6


class SmokeFailure(AssertionError):
    """A phase produced a wrong or missing answer."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# a. device
# ---------------------------------------------------------------------------
def device_info(chips: int = 1) -> dict:
    """The device as JAX reports it; exits non-zero without a TPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices; "
                 f"JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# oracle + timing helpers
# ---------------------------------------------------------------------------
def _scipy(L, transpose: bool = False):
    import scipy.sparse as sp

    A = sp.csr_matrix((L.data.astype(np.float64), L.indices, L.indptr),
                      shape=L.shape)
    return A.T.tocsr() if transpose else A


def check_solve(L, b, x, *, transpose: bool = False) -> dict:
    """Componentwise residual of ``x`` against the float64 system, and its
    forward error against a float64 triangular solve.  Raises when the
    residual exceeds :data:`RESIDUAL_TOL` or ``x`` is not finite."""
    from scipy.sparse.linalg import spsolve_triangular

    A = _scipy(L, transpose)
    x = np.asarray(x, np.float64)
    b = np.asarray(b, np.float64)
    require(x.shape == b.shape, f"answer shape {x.shape} != rhs {b.shape}")
    require(np.isfinite(x).all(), "non-finite entries in the answer")
    r = np.abs(b - A @ x)
    denom = abs(A) @ np.abs(x) + np.abs(b)
    omega = float(np.max(np.where(denom > 0, r / np.where(denom > 0, denom, 1),
                                  r)))
    x_ref = spsolve_triangular(A, b, lower=not transpose)
    fwd = float(np.max(np.abs(x - x_ref)) / max(np.max(np.abs(x_ref)), 1e-300))
    require(omega <= RESIDUAL_TOL,
            f"componentwise residual {omega:.3e} > {RESIDUAL_TOL:.3e} "
            f"({'transpose' if transpose else 'forward'} solve)")
    return {"residual": omega, "forward_error": fwd}


def _worst(checks) -> dict:
    return {"max_residual": max(c["residual"] for c in checks),
            "max_forward_error": max(c["forward_error"] for c in checks),
            "residual_tol": RESIDUAL_TOL}


def _timed(fn, *args, reps: int = 3):
    """(result, median seconds) over ``reps`` calls that end in
    ``block_until_ready``."""
    import jax

    ts, out = [], None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return out, float(np.median(ts))


def plan_of(s) -> str:
    """The executor a solver runs, with the planner's transforms."""
    name = s.strategy
    if s.plan is not None:
        if s.plan.rewrite:
            name += f"+rewrite:{s.plan.rewrite}"
        if s.plan.coarsen:
            name += "+coarsen"
    return name


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def solve_and_check(s, L, b, B, *, transpose: bool, tag: str,
                    timings: dict) -> list:
    """One-RHS and m-RHS solves of ``s``: the first call of each width is
    timed with its compilation, the steady calls after it."""
    import jax
    import jax.numpy as jnp

    out = []
    for name, rhs in ((f"{tag}_m1", b), (f"{tag}_m{B.shape[1]}", B)):
        rhs_d = jnp.asarray(rhs)
        t0 = time.perf_counter()
        jax.block_until_ready(s.solve(rhs_d))
        timings[f"{name}_first_s"] = time.perf_counter() - t0
        x, timings[f"{name}_s"] = _timed(s.solve, rhs_d)
        out.append(check_solve(L, rhs, x, transpose=transpose))
    return out


# ---------------------------------------------------------------------------
# b. paper workload
# ---------------------------------------------------------------------------
def phase_paper(*, scale: float = 1.0, m: int = 8, seed: int = SEED) -> dict:
    from repro.core import CSRMatrix, SpTRSV
    from repro.sparse import lung2_like, refresh_values

    L = lung2_like(scale=scale, seed=seed, dtype=np.float32)
    t0 = time.perf_counter()
    fwd, bwd = SpTRSV.build_pair(L, strategy="auto")
    setup = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    B = rng.standard_normal((L.n, m)).astype(np.float32)
    b = B[:, 0].copy()
    timings: dict = {}
    checks = solve_and_check(fwd, L, b, B, transpose=False, tag="fwd",
                             timings=timings)
    checks += solve_and_check(bwd, L, b, B, transpose=True, tag="bwd",
                              timings=timings)

    new = refresh_values(L, seed=seed + 1)
    t0 = time.perf_counter()
    fwd.refresh(new)
    bwd.refresh(new)
    timings["refresh_s"] = time.perf_counter() - t0
    L2 = CSRMatrix(L.indptr, L.indices, new, L.shape)
    checks += solve_and_check(fwd, L2, b, B, transpose=False,
                              tag="refreshed_fwd", timings=timings)
    checks += solve_and_check(bwd, L2, b, B, transpose=True,
                              tag="refreshed_bwd", timings=timings)
    return {"phase": "paper", "n": L.n, "nnz": L.nnz,
            "plan_fwd": plan_of(fwd), "plan_bwd": plan_of(bwd),
            "backend": fwd.backend, "setup_s": setup, **timings,
            **_worst(checks)}


# ---------------------------------------------------------------------------
# c. PCG with IC(0)
# ---------------------------------------------------------------------------
def ic0_problem(grid: int):
    """(A, L): the 5-point Laplacian on a grid x grid mesh and its IC(0)
    factor, both f32."""
    from repro.sparse import ic0_factor, poisson2d

    A = poisson2d(grid, grid, dtype=np.float32)
    return A, ic0_factor(A)


def phase_pcg(problem, *, tol: float = PCG_TOL, maxiter: int = 2000,
              seed: int = SEED) -> dict:
    """PCG on ``problem = (A, L)`` from :func:`ic0_problem`."""
    import jax.numpy as jnp

    from repro.core.pcg import make_ic_preconditioner, pcg

    A, L = problem
    t0 = time.perf_counter()
    M = make_ic_preconditioner(L, strategy="auto", rewrite=None)
    setup = time.perf_counter() - t0
    # A manufactured solution: b = A x* for a random x*.  An f32 iterate
    # cannot get its true relative residual below about
    # eps(f32)/2 * ||A|| ||x|| / ||b||; with a random b that ratio is ~500
    # on this grid (a floor near 3e-5), with b = A x* it is ~2, so 1e-6 is
    # reachable.
    A64 = _scipy(A)
    x_star = np.random.default_rng(seed).standard_normal(A.n)
    b = (A64 @ x_star).astype(np.float32)
    runs = []
    for _ in range(2):   # the first run compiles, the second is steady
        t0 = time.perf_counter()
        res = pcg(A, jnp.asarray(b), M, tol=tol, maxiter=maxiter)
        runs.append(time.perf_counter() - t0)
        require(res.converged, f"PCG did not converge in {res.iters} "
                f"iterations (residual {res.residual:.3e})")
        x = np.asarray(res.x, np.float64)
        require(np.isfinite(x).all(), "non-finite entries in the PCG answer")
        true_rel = float(np.linalg.norm(b - A64 @ x) / np.linalg.norm(b))
        # the recurrence stops at tol; its f32 drift from the true residual
        # is what the host check sees
        require(true_rel <= 2 * tol,
                f"PCG true relative residual {true_rel:.3e} > {2 * tol:.1e}")
    return {"phase": "pcg", "n": A.n, "nnz_L": L.nnz, "iters": res.iters,
            "true_relative_residual": true_rel, "tol": tol,
            "setup_s": setup,
            "first_run_s": runs[0], "run_s": runs[1],
            "s_per_iteration": runs[1] / max(res.iters, 1)}


# ---------------------------------------------------------------------------
# d. band through the blocked executor and its Pallas kernel
# ---------------------------------------------------------------------------
def phase_band(*, n: int = 8192, bandwidth: int = 24, m: int = 8,
               require_kernel: bool = True, seed: int = SEED) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import SpTRSV
    from repro.sparse import banded_lower

    L = banded_lower(n, bandwidth=bandwidth, fill=1.0, seed=seed,
                     dtype=np.float32)
    t0 = time.perf_counter()
    auto = SpTRSV.build(L, strategy="auto")
    auto_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    s = SpTRSV.build(L, strategy="blocked")
    setup = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    B = rng.standard_normal((n, m)).astype(np.float32)
    b = B[:, 0].copy()
    hlo = jax.jit(s.solve).lower(jnp.asarray(b)).compile().as_text()
    has_kernel = "tpu_custom_call" in hlo
    if require_kernel:
        require(has_kernel, "blocked solve compiled without the trsm_block "
                "Pallas kernel (no tpu_custom_call in its HLO)")
    timings: dict = {}
    checks = solve_and_check(s, L, b, B, transpose=False, tag="blocked",
                             timings=timings)
    return {"phase": "band", "n": n, "bandwidth": bandwidth,
            "plan": plan_of(s), "auto_plan": plan_of(auto),
            "auto_build_s": auto_s, "tpu_custom_call": has_kernel,
            "segments": s.stats()["segments"], "setup_s": setup, **timings,
            **_worst(checks)}


# ---------------------------------------------------------------------------
# e. serving
# ---------------------------------------------------------------------------
def phase_serving(ic0, *, lung2=None, scale: float = 1.0,
                  lung2_requests: int = 4, ic0_requests: int = 1,
                  ready_timeout: float = 900.0, seed: int = SEED) -> dict:
    """Two tenants share the lung2 pattern (one engine queue), a third holds
    the IC(0) factor ``ic0``.  Each lung2 tenant submits ``lung2_requests`` forward
    and as many transpose requests, the IC(0) tenant ``ic0_requests`` of
    each; they are drained once while the planned builds may still be cold
    and once after every entry promoted.  The defaults make the engine's
    batch buckets the widths phases b and c already compiled (8 for lung2,
    1 for IC(0)), so the compile cache serves the promoted pairs."""
    from repro.serve import SolveService
    from repro.sparse import lung2_like

    L = lung2 if lung2 is not None else lung2_like(
        scale=scale, seed=seed, dtype=np.float32)
    tenants = {"lung2-a": (L, lung2_requests), "lung2-b": (L, lung2_requests),
               "ic0": (ic0, ic0_requests)}
    svc = SolveService(strategy="auto")
    t0 = time.perf_counter()
    for tenant, (F, _) in tenants.items():
        svc.register(tenant, F)
    admit = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    checks = []

    def burst() -> float:
        reqs = []
        for tenant, (F, count) in tenants.items():
            for transpose in (False, True):
                for _ in range(count):
                    b = rng.standard_normal(F.n).astype(np.float32)
                    reqs.append((F, b, transpose, svc.submit(
                        tenant, b, transpose=transpose)))
        t0 = time.perf_counter()
        svc.run()
        drained = time.perf_counter() - t0
        for F, b, transpose, r in reqs:
            if r.error is not None:
                raise r.error
            require(r.done and r.x is not None, "request left unanswered")
            checks.append(check_solve(F, b, r.x, transpose=transpose))
        return drained

    first = burst()
    t0 = time.perf_counter()
    plans = {}
    for key in svc.registry.keys():
        e = svc.registry.lookup(key)
        require(e.wait_ready(timeout=ready_timeout),
                f"planned build of {key} not ready in {ready_timeout} s")
        require(e.build_error is None,
                f"planned build of {key} failed: {e.build_error!r}")
        require(e.state == "ready", f"entry {key} still {e.state}")
        pair = (e.engine.solver, e.engine.solver_t)
        require(all(s.strategy != "serial" for s in pair),
                f"entry {key} promoted a serial pair")
        plans[key[:12]] = [plan_of(s) for s in pair]
    ready = time.perf_counter() - t0
    second = burst()
    require(svc.registry.wait_idle(timeout=ready_timeout),
            "registry build threads still running")
    st = svc.stats()
    require(st["failed"] == 0, f"{st['failed']} requests failed")
    require(st["completed"] == st["submitted"],
            f"{st['completed']} of {st['submitted']} requests answered")
    require(st["registry"]["build_failures"] == 0, "a planned build failed")
    return {"phase": "serving", "tenants": st["tenants"],
            "entries": st["registry"]["entries"],
            "submitted": st["submitted"], "completed": st["completed"],
            "failed": st["failed"],
            "promotions": st["registry"]["promotions"], "plans": plans,
            "admit_s": admit, "cold_drain_s": first,
            "wait_ready_s": ready, "ready_drain_s": second,
            **_worst(checks)}


# ---------------------------------------------------------------------------
# --chips 4: the distributed executor over a mesh
# ---------------------------------------------------------------------------
def phase_distributed(*, chips: int = 4, scale: float = 1.0, m: int = 8,
                      seed: int = SEED) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.core import SpTRSV
    from repro.sparse import lung2_like

    devices = jax.devices()[:chips]
    require(len(devices) == chips, f"need {chips} devices, have "
            f"{len(devices)}")
    mesh = Mesh(np.array(devices), ("data",))
    L = lung2_like(scale=scale, seed=seed, dtype=np.float32)
    t0 = time.perf_counter()
    dist = SpTRSV.build(L, strategy="distributed", mesh=mesh, coarsen=True)
    setup = time.perf_counter() - t0
    one = SpTRSV.build(L, strategy="levelset", coarsen=True)
    spanned = {len(a.sharding.device_set)
               for a in jax.tree.leaves(dist._values)}
    require(spanned == {chips},
            f"value buffers span {sorted(spanned)} devices, not {chips}")
    rng = np.random.default_rng(seed)
    B = rng.standard_normal((L.n, m)).astype(np.float32)
    b = B[:, 0].copy()
    timings: dict = {}
    checks = solve_and_check(dist, L, b, B, transpose=False, tag="dist",
                             timings=timings)
    checks += solve_and_check(one, L, b, B, transpose=False, tag="one",
                              timings=timings)
    agree = 0.0
    for rhs in (b, B):
        xd = dist.solve(jnp.asarray(rhs))
        require(len(xd.sharding.device_set) == chips,
                f"result lives on {len(xd.sharding.device_set)} devices")
        xd, x1 = np.asarray(xd), np.asarray(one.solve(jnp.asarray(rhs)))
        agree = max(agree, float(np.max(np.abs(xd - x1))
                                 / np.max(np.abs(x1))))
    require(agree <= RESIDUAL_TOL,
            f"distributed and one-device answers differ by {agree:.3e}")
    return {"phase": "distributed", "chips": chips, "n": L.n,
            "segments": dist.stats()["segments"], "setup_s": setup,
            "max_relative_difference_vs_one_device": agree, **timings,
            **_worst(checks)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the distributed phase on a 4-chip mesh")
    args = ap.parse_args(argv)
    device = device_info(args.chips)

    from repro.compile_cache import enable_compile_cache

    emit({"phase": "setup", "compile_cache": enable_compile_cache(),
          "smoke_timings_not_benchmarks": True})
    if args.chips == 4:
        emit(phase_distributed(chips=4))
    else:
        emit(phase_paper())
        t0 = time.perf_counter()
        problem = ic0_problem(512)
        emit({"phase": "ic0_factor", "n": problem[1].n,
              "nnz": problem[1].nnz, "host_s": time.perf_counter() - t0})
        emit(phase_pcg(problem))
        emit(phase_band())
        emit(phase_serving(problem[1]))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
