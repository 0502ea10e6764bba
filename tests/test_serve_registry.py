"""SolverRegistry: the pattern-keyed memory tier of the solve service.

What must hold (PR-10 acceptance):

* admission is keyed by sparsity pattern + dtype — same pattern, new
  values is a *hit* (O(nnz) refresh onto the resident compiled pair),
  different pattern or dtype is a *miss*;
* LRU + byte-budget eviction in recency order, never evicting the
  just-touched entry or one with queued requests;
* a value refresh that lands while the planned build is in flight is
  re-applied to the built pair before promotion — promotion must never
  resurrect stale numerics;
* the cold serial pair and the promoted planned pair answer the same RHS
  identically (vs the NumPy dense oracle), including when the planned
  build runs on a background worker thread (which does NOT inherit the
  main thread's thread-local ``jax.enable_x64`` — the registry has to
  propagate it);
* a failed planned build leaves the entry serving through the cold pair
  with ``build_error`` set — it never takes down admission.
"""
import threading

import numpy as np
import pytest

from jax import enable_x64
from repro.core import CSRMatrix, SpTRSV
from repro.serve import SolverRegistry, pattern_key
from repro.sparse import random_lower, refresh_values


def _dense_solve(L, b):
    return np.linalg.solve(L.to_dense(), b)


def _revalued(L, seed):
    return CSRMatrix(L.indptr, L.indices, refresh_values(L, seed=seed),
                     L.shape)


# --------------------------------------------------------------------------
# keying: pattern + dtype
# --------------------------------------------------------------------------
def test_pattern_key_ignores_values_but_not_dtype():
    L = random_lower(48, seed=0)
    same_pattern = _revalued(L, seed=9)
    other_pattern = random_lower(48, seed=1)
    f32 = CSRMatrix(L.indptr, L.indices, L.data.astype(np.float32), L.shape)
    assert pattern_key(L) == pattern_key(same_pattern)
    assert pattern_key(L) != pattern_key(other_pattern)
    assert pattern_key(L) != pattern_key(f32)


def test_hit_refreshes_values_onto_resident_pair():
    with enable_x64():
        L = random_lower(64, seed=2)
        reg = SolverRegistry(strategy="levelset", background=False)
        e1 = reg.get(L)
        L2 = _revalued(L, seed=11)
        e2 = reg.get(L2)
        assert e2 is e1
        assert (reg.hits, reg.misses) == (1, 1)
        assert e1.value_refreshes == 1
        b = np.random.default_rng(3).standard_normal(L.n)
        req = e1.engine.submit(b)
        e1.engine.run()
        np.testing.assert_allclose(req.x, _dense_solve(L2, b),
                                   rtol=1e-10, atol=1e-12)
        # bit-identical values → refresh skipped (cheap no-op hit)
        e3 = reg.get(L2)
        assert e3 is e1 and e1.value_refreshes == 1


# --------------------------------------------------------------------------
# LRU + byte-budget eviction
# --------------------------------------------------------------------------
def test_lru_eviction_order_and_touch_protection():
    with enable_x64():
        mats = [random_lower(48, seed=s) for s in range(3)]
        reg = SolverRegistry(strategy="serial", background=False,
                             max_entries=2)
        e0, e1 = reg.get(mats[0]), reg.get(mats[1])
        # touch mats[0] so mats[1] becomes LRU
        assert reg.get(mats[0]) is e0
        reg.get(mats[2])
        assert reg.evictions == 1
        assert e1.evicted and not e0.evicted
        assert reg.keys() == [pattern_key(mats[0]), pattern_key(mats[2])]
        # the evicted pattern re-admits as a fresh miss
        e1b = reg.get(mats[1])
        assert e1b is not e1 and reg.misses == 4


def test_byte_budget_enforced_on_admission():
    with enable_x64():
        mats = [random_lower(64, seed=10 + s) for s in range(3)]
        probe = SolverRegistry(strategy="serial", background=False)
        entry_bytes = probe.get(mats[0]).packed_bytes
        assert entry_bytes > 0
        # room for two entries, not three
        reg = SolverRegistry(strategy="serial", background=False,
                             max_bytes=int(entry_bytes * 2.5))
        for m in mats:
            reg.get(m)
            assert reg.resident_bytes() <= reg.max_bytes
        assert reg.evictions == 1
        assert reg.keys() == [pattern_key(mats[1]), pattern_key(mats[2])]


def test_eviction_skips_entries_with_queued_requests():
    with enable_x64():
        mats = [random_lower(48, seed=20 + s) for s in range(2)]
        reg = SolverRegistry(strategy="serial", background=False,
                             max_entries=1)
        e0 = reg.get(mats[0])
        rng = np.random.default_rng(0)
        req = e0.engine.submit(rng.standard_normal(mats[0].n))
        # e0 is LRU but has queued work — admission must defer, not evict
        reg.get(mats[1])
        assert reg.evictions == 0 and len(reg.keys()) == 2
        e0.engine.run()
        assert req.done
        # once drained, the next admission evicts down to the budget
        m3 = random_lower(48, seed=30)
        reg.get(m3)
        assert reg.evictions == 2
        assert reg.keys() == [pattern_key(m3)]


# --------------------------------------------------------------------------
# cold serial pair vs promoted planned pair
# --------------------------------------------------------------------------
def test_cold_answers_match_promoted_vs_numpy_oracle():
    """The gate pins 'answered while cold' as a fact, not a race; the
    promoted pair must then agree with both the cold answer and the dense
    oracle at f64 tightness — which also pins the x64 propagation onto the
    background build worker (jax.enable_x64 is thread-local)."""
    with enable_x64():
        L = random_lower(96, seed=4)
        gate = threading.Event()
        reg = SolverRegistry(strategy="levelset", background=True,
                             build_gate=gate)
        entry = reg.get(L)
        b = np.random.default_rng(7).standard_normal(L.n)
        req_cold = entry.engine.submit(b)
        entry.engine.run()
        assert req_cold.done and entry.state == "cold"
        assert entry.engine.solver.strategy == "serial"
        oracle = _dense_solve(L, b)
        np.testing.assert_allclose(req_cold.x, oracle, rtol=1e-10,
                                   atol=1e-12)
        gate.set()
        assert entry.wait_ready(timeout=120)
        assert entry.state == "ready" and entry.build_error is None
        assert entry.engine.solver.strategy == "levelset"
        assert entry.cold_completed == 1
        req_warm = entry.engine.submit(b)
        entry.engine.run()
        np.testing.assert_allclose(req_warm.x, oracle, rtol=1e-10,
                                   atol=1e-12)
        np.testing.assert_allclose(req_warm.x, req_cold.x, rtol=1e-12,
                                   atol=1e-13)
        assert reg.wait_idle(timeout=120)


def test_refresh_during_inflight_build_reapplied_before_promotion():
    """Values refreshed while the planned build is in flight must be
    re-applied to the built pair before the swap — promotion may never
    resurrect the admission-time numerics."""
    with enable_x64():
        L = random_lower(72, seed=5)
        reg = SolverRegistry(strategy="levelset", background=True)
        started, proceed = threading.Event(), threading.Event()
        inner = reg._build_planned

        def stalled(snapshot):
            started.set()
            assert proceed.wait(timeout=120)
            return inner(snapshot)

        reg._build_planned = stalled
        entry = reg.get(L)
        assert started.wait(timeout=120)
        # the build snapshotted L's values; move them while it runs
        L2 = _revalued(L, seed=41)
        assert reg.get(L2) is entry    # hit → refresh, version bump
        proceed.set()
        assert entry.wait_ready(timeout=120)
        assert entry.state == "ready" and entry.build_error is None
        b = np.random.default_rng(9).standard_normal(L.n)
        req = entry.engine.submit(b)
        entry.engine.run()
        np.testing.assert_allclose(req.x, _dense_solve(L2, b),
                                   rtol=1e-10, atol=1e-12)
        assert reg.wait_idle(timeout=120)


def test_failed_planned_build_keeps_serving_cold():
    with enable_x64():
        L = random_lower(48, seed=6)
        reg = SolverRegistry(strategy="levelset", background=True)

        def boom(snapshot):
            raise RuntimeError("planner exploded")

        reg._build_planned = boom
        entry = reg.get(L)
        assert entry.wait_ready(timeout=120)      # fires on failure too
        assert entry.state == "cold"
        assert isinstance(entry.build_error, RuntimeError)
        assert reg.build_failures == 1 and reg.promotions == 0
        b = np.random.default_rng(1).standard_normal(L.n)
        req = entry.engine.submit(b)
        entry.engine.run()
        np.testing.assert_allclose(req.x, _dense_solve(L, b),
                                   rtol=1e-10, atol=1e-12)
        assert entry.stats()["build_error"] is not None


def test_evicted_entry_discards_inflight_build():
    with enable_x64():
        L = random_lower(48, seed=7)
        gate = threading.Event()
        reg = SolverRegistry(strategy="levelset", background=True,
                             build_gate=gate, max_entries=1)
        entry = reg.get(L)
        reg.get(random_lower(48, seed=8))      # evicts L (no queued work)
        assert entry.evicted
        gate.set()
        assert reg.wait_idle(timeout=120)
        # the build completed but must not have promoted the evicted entry
        assert entry.state == "cold"
        assert reg.promotions <= 1             # only the survivor's build


def test_registry_stats_shape():
    with enable_x64():
        reg = SolverRegistry(strategy="serial", background=False,
                             max_entries=4)
        L = random_lower(32, seed=0)
        entry = reg.get(L)
        st = reg.stats()
        assert st["entries"] == 1 and st["misses"] == 1
        assert st["resident_packed_bytes"] == entry.packed_bytes > 0
        es = st["per_entry"][entry.key]
        assert es["state"] == "ready"          # serial: promoted in place
        assert es["strategy"] == "serial"
        assert es["cold_build_s"] > 0
        assert st["cold_build"]["count"] == 1


def test_registry_validates_bounds():
    with pytest.raises(ValueError, match="max_entries"):
        SolverRegistry(max_entries=0)
    with pytest.raises(ValueError, match="max_bytes"):
        SolverRegistry(max_bytes=-1)
