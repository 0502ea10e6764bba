"""SolveEngine serving-tier regressions.

Bugfixes pinned here:

* ``refresh`` must drain the admission queue before swapping factor values —
  an in-flight request is answered with the factor that existed when it was
  enqueued, never silently re-priced against values from the future;
* ``_solve_group`` allocates the batch buffer in the **solver's** dtype, not
  ``np.result_type`` over the requests — one float64 request must not up-cast
  the bucket and miss every jit-cache entry compiled at the solver's dtype;
* ``step`` must count errored requests in ``failed``, not ``solved`` —
  ``stats()["solved"]`` means answers, not attempts;
* ``__init__``/``submit`` validation raises ``ValueError`` (asserts are
  stripped under ``python -O`` and a wrong-length RHS would silently
  corrupt the batch buffer);
* the ``_solve_group`` fallback routes per-request re-solves through the
  width-1 *bucket* (no per-RHS retrace) and counts each executor dispatch
  in ``batches`` — counters stay consistent between paths.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from jax import enable_x64
from repro.core import CSRMatrix, GuardBreakdownError, GuardConfig, SpTRSV
from repro.serve import SolveEngine
from repro.sparse import chain_matrix, random_lower


def _regen_values(L, seed):
    rng = np.random.default_rng(seed)
    data = rng.normal(size=L.nnz).astype(L.dtype)
    diag_mask = np.zeros(L.nnz, bool)
    for i in range(L.n):  # keep the factor well-conditioned
        diag_mask[L.indptr[i + 1] - 1] = True
    data[diag_mask] = np.abs(data[diag_mask]) + 1.0
    return data


def test_refresh_drains_queue_before_value_swap():
    L = chain_matrix(80, dtype=np.float64)
    eng = SolveEngine.from_matrix(L, strategy="levelset", transpose_too=False,
                                  max_batch=8)
    rng = np.random.default_rng(3)
    b = rng.normal(size=L.n)
    # submit against the ORIGINAL factor, then refresh without running
    inflight = eng.submit(b)
    data2 = _regen_values(L, seed=9)
    eng.refresh(data2)
    # the drain inside refresh must have answered the in-flight request
    # against the old values
    assert inflight.done
    old = SpTRSV.build(L, strategy="levelset")
    np.testing.assert_allclose(
        inflight.x, np.asarray(old.solve(jnp.asarray(b))),
        rtol=1e-12, atol=1e-12)
    # a post-refresh submit is answered with the NEW values
    after = eng.submit(b)
    eng.run()
    new = SpTRSV.build(CSRMatrix(L.indptr, L.indices, data2, L.shape),
                       strategy="levelset")
    np.testing.assert_allclose(
        after.x, np.asarray(new.solve(jnp.asarray(b))),
        rtol=1e-12, atol=1e-12)
    # and the two factors genuinely differ, or the test proves nothing
    assert not np.allclose(inflight.x, after.x)


def test_mixed_dtype_request_does_not_retrace():
    L = chain_matrix(64, dtype=np.float32)
    s = SpTRSV.build(L, strategy="levelset")
    eng = SolveEngine(s, max_batch=4)
    rng = np.random.default_rng(5)
    # warm the m=4 bucket at the solver's dtype
    f32_reqs = [eng.submit(rng.normal(size=L.n).astype(np.float32))
                for _ in range(4)]
    assert eng.run() == 4
    if not hasattr(s._solve_fn, "_cache_size"):
        pytest.skip("jit cache introspection unavailable on this JAX")
    before = s._solve_fn._cache_size()
    # a float64 request in an otherwise-f32 bucket must be solved at the
    # solver's dtype, hitting the already-compiled bucket
    mixed = [eng.submit(rng.normal(size=L.n).astype(np.float64))
             for _ in range(4)]
    assert eng.run() == 4
    assert s._solve_fn._cache_size() == before
    for r in f32_reqs + mixed:
        assert r.done
        assert r.x.dtype == np.float32
        np.testing.assert_allclose(
            r.x, np.asarray(s.solve(jnp.asarray(r.b, jnp.float32))),
            rtol=1e-6, atol=1e-6)


def _guarded_engine(n=48, seed=1, strategy="levelset", max_batch=8):
    L = random_lower(n, seed=seed)
    s = SpTRSV.build(L, strategy=strategy,
                     guard=GuardConfig(on_breakdown="raise"))
    return L, SolveEngine(s, max_batch=max_batch)


def test_failed_requests_counted_as_failed_not_solved():
    """An errored request used to count in ``solved`` — a breakdown-heavy
    tenant read as healthy throughput.  ``step``'s return stays the number
    of requests *completed* (either way)."""
    with enable_x64():
        L, eng = _guarded_engine()
        rng = np.random.default_rng(2)
        good = [eng.submit(rng.standard_normal(L.n)) for _ in range(3)]
        bad_b = rng.standard_normal(L.n)
        bad_b[0] = np.nan
        bad = eng.submit(bad_b)
        assert eng.step() == 4
        assert (eng.solved, eng.failed) == (3, 1)
        st = eng.stats()
        assert (st["solved"], st["failed"]) == (3, 1)
        assert isinstance(bad.error, GuardBreakdownError) and bad.x is None
        for r in good:
            assert r.error is None and r.x is not None


def test_engine_validation_raises_value_errors():
    L = chain_matrix(16)
    s = SpTRSV.build(L, strategy="serial")
    other = SpTRSV.build(chain_matrix(8), strategy="serial")
    with pytest.raises(ValueError, match="max_batch"):
        SolveEngine(s, max_batch=0)
    with pytest.raises(ValueError, match="must share one factor"):
        SolveEngine(s, other)
    eng = SolveEngine(s)   # no transpose solver
    with pytest.raises(ValueError, match=r"\(16,\)"):
        eng.submit(np.zeros(17))
    with pytest.raises(ValueError, match=r"\(16,\)"):
        eng.submit(np.zeros((16, 1)))
    with pytest.raises(ValueError, match="transpose"):
        eng.submit(np.zeros(16), transpose=True)
    with pytest.raises(ValueError, match="promoted solver solves"):
        eng.swap_solvers(other)
    with pytest.raises(ValueError, match="no transpose solver"):
        SolveEngine(s, s).swap_solvers(s)


def test_fallback_counts_batches_consistently():
    """3 requests, one bad: 1 failed batched attempt + 3 width-1 re-solves
    = 4 executor dispatches, and exactly the culprit carries the error."""
    with enable_x64():
        L, eng = _guarded_engine()
        rng = np.random.default_rng(3)
        reqs = [eng.submit(rng.standard_normal(L.n)) for _ in range(2)]
        bad_b = rng.standard_normal(L.n)
        bad_b[5] = np.inf
        bad = eng.submit(bad_b)
        assert eng.batches == 0
        assert eng.step() == 3
        assert eng.batches == 4
        assert (eng.solved, eng.failed) == (2, 1)
        assert isinstance(bad.error, GuardBreakdownError)
        for r in reqs:
            assert r.error is None and r.x is not None
        # a clean follow-up batch adds exactly one dispatch
        eng.submit(rng.standard_normal(L.n))
        eng.run()
        assert eng.batches == 5 and eng.solved == 3


def test_fallback_resolves_through_width1_bucket():
    """The per-request re-solves must reuse the compiled width-1 bucket —
    a bare 1-D solve would trace a fresh executor per RHS and bypass the
    bounded jit-cache discipline."""
    with enable_x64():
        L, eng = _guarded_engine()
        s = eng.solver
        rng = np.random.default_rng(4)
        # warm the width-1 and width-4 buckets
        eng.submit(rng.standard_normal(L.n))
        assert eng.run() == 1
        for _ in range(4):
            eng.submit(rng.standard_normal(L.n))
        assert eng.run() == 4
        if not hasattr(s._solve_fn, "_cache_size"):
            pytest.skip("jit cache introspection unavailable on this JAX")
        before = s._solve_fn._cache_size()
        # now a failing 4-wide batch: fallback re-solves all 4 at width 1
        bad_b = rng.standard_normal(L.n)
        bad_b[0] = np.nan
        eng.submit(bad_b)
        for _ in range(3):
            eng.submit(rng.standard_normal(L.n))
        assert eng.step() == 4
        assert (eng.solved, eng.failed) == (5 + 3, 1)
        assert s._solve_fn._cache_size() == before
