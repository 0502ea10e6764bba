"""SolveService: the multi-tenant continuous-batching front-end.

Pins the tenancy contract on top of the registry tests:

* tenants sharing a (pattern, dtype) share one numeric factor and one
  engine queue — their requests are co-batched and a refresh by one is
  visible to all;
* a tenant whose entry was evicted while idle is transparently
  re-admitted on its next submit (cold path again);
* failures are isolated per request AND per tenant: one tenant's
  ``GuardBreakdownError`` (bad RHS under ``on_breakdown="raise"``) lands
  on that tenant's counters only — co-batched neighbours from other
  tenants still get oracle-correct answers;
* the deterministic mixed-traffic stream (:func:`repro.sparse.
  serve_traffic`) drains completely with every answer matching the dense
  oracle for the values in effect at submission time.
"""
import numpy as np
import pytest

from jax import enable_x64
from repro.core import CSRMatrix, GuardBreakdownError, GuardConfig
from repro.serve import SolveService, SolverRegistry
from repro.sparse import random_lower, refresh_values, serve_traffic


def _dense_solve(L, b, transpose=False):
    A = L.to_dense()
    return np.linalg.solve(A.T if transpose else A, b)


def _revalued(L, seed):
    return CSRMatrix(L.indptr, L.indices, refresh_values(L, seed=seed),
                     L.shape)


def test_tenants_sharing_pattern_share_factor_and_batch():
    with enable_x64():
        L = random_lower(64, seed=0)
        svc = SolveService(strategy="levelset", background=False)
        ka = svc.register("a", L)
        kb = svc.register("b", _revalued(L, seed=5))  # same pattern: hit
        assert ka == kb
        assert (svc.registry.misses, svc.registry.hits) == (1, 1)
        # b's registration refreshed the shared values — both tenants now
        # solve against b's factor (the documented sharing semantics)
        L_now = _revalued(L, seed=5)
        rng = np.random.default_rng(1)
        ba, bb = rng.standard_normal(L.n), rng.standard_normal(L.n)
        ra, rb = svc.submit("a", ba), svc.submit("b", bb)
        done = svc.step()          # ONE drained batch answers both tenants
        assert done == 2 and svc.batches_completed == 1
        np.testing.assert_allclose(ra.x, _dense_solve(L_now, ba),
                                   rtol=1e-10, atol=1e-12)
        np.testing.assert_allclose(rb.x, _dense_solve(L_now, bb),
                                   rtol=1e-10, atol=1e-12)
        st = svc.stats()
        assert st["completed"] == 2 and st["failed"] == 0
        assert st["per_tenant"]["a"]["completed"] == 1


def test_refresh_visible_across_tenants_and_counted():
    with enable_x64():
        L = random_lower(56, seed=2)
        svc = SolveService(strategy="levelset", background=False)
        svc.register("a", L)
        svc.register("b", L)
        new_vals = refresh_values(L, seed=9)
        svc.refresh("a", new_vals)
        b = np.random.default_rng(3).standard_normal(L.n)
        req = svc.submit("b", b)
        svc.run()
        L2 = CSRMatrix(L.indptr, L.indices, new_vals, L.shape)
        np.testing.assert_allclose(req.x, _dense_solve(L2, b),
                                   rtol=1e-10, atol=1e-12)
        st = svc.stats()
        assert st["per_tenant"]["a"]["refreshes"] == 1
        assert st["per_tenant"]["b"]["refreshes"] == 0


def test_evicted_tenant_readmitted_on_submit():
    with enable_x64():
        La, Lb = random_lower(48, seed=4), random_lower(48, seed=5)
        svc = SolveService(strategy="serial", background=False,
                           max_entries=1)
        svc.register("a", La)
        svc.register("b", Lb)                 # evicts a's entry
        assert svc.registry.evictions == 1
        b = np.random.default_rng(6).standard_normal(La.n)
        req = svc.submit("a", b)              # transparent re-admission
        svc.run()
        assert svc.registry.misses == 3
        np.testing.assert_allclose(req.x, _dense_solve(La, b),
                                   rtol=1e-10, atol=1e-12)


def test_breakdown_isolated_per_tenant():
    """One tenant's GuardBreakdownError must not poison a co-batched
    neighbour from another tenant — the neighbour's answer stays
    oracle-correct and only the offender's failed counter moves."""
    with enable_x64():
        L = random_lower(64, seed=7)
        svc = SolveService(strategy="levelset", background=False,
                           guard=GuardConfig(on_breakdown="raise"))
        svc.register("good", L)
        svc.register("bad", L)
        rng = np.random.default_rng(8)
        b_good = rng.standard_normal(L.n)
        b_bad = rng.standard_normal(L.n)
        b_bad[L.n // 2] = np.nan
        r_good = svc.submit("good", b_good)
        r_bad = svc.submit("bad", b_bad)
        done = svc.step()
        assert done == 2
        assert r_good.done and r_good.error is None
        np.testing.assert_allclose(r_good.x, _dense_solve(L, b_good),
                                   rtol=1e-10, atol=1e-12)
        assert r_bad.done and isinstance(r_bad.error, GuardBreakdownError)
        assert r_bad.x is None
        st = svc.stats()
        assert st["per_tenant"]["good"] == dict(
            st["per_tenant"]["good"], completed=1, failed=0)
        assert st["per_tenant"]["bad"] == dict(
            st["per_tenant"]["bad"], completed=0, failed=1)
        assert st["completed"] == 1 and st["failed"] == 1


def test_transpose_requests_route_to_backward_solver():
    with enable_x64():
        L = random_lower(56, seed=9)
        svc = SolveService(strategy="levelset", background=False)
        svc.register("t", L)
        b = np.random.default_rng(10).standard_normal(L.n)
        req = svc.submit("t", b, transpose=True)
        svc.run()
        np.testing.assert_allclose(req.x, _dense_solve(L, b, transpose=True),
                                   rtol=1e-10, atol=1e-12)


def test_mixed_traffic_drains_with_oracle_answers():
    """Drive the shared deterministic workload end to end (inline builds)
    and check every solve against the dense oracle for the values in
    effect when it was submitted."""
    with enable_x64():
        patterns, events = serve_traffic(num_patterns=2, num_tenants=3,
                                         num_events=40, n=48, seed=13)
        svc = SolveService(strategy="levelset", background=False,
                           max_batch=8)
        current = {}                     # tenant -> dense factor snapshot
        shared_key = {}                  # tenant -> registry key
        expected = []
        for ev in events:
            t = ev["tenant"]
            if ev["op"] == "register":
                key = svc.register(t, ev["matrix"])
                dense = ev["matrix"].to_dense()
                # registration refreshes shared values: every tenant on
                # this key sees the new factor
                shared_key[t] = key
                for other, k in shared_key.items():
                    if k == key:
                        current[other] = dense
            elif ev["op"] == "refresh":
                svc.refresh(t, ev["values"])
                m = svc.registry.lookup(shared_key[t]).pattern
                dense = m.to_dense()
                for other, k in shared_key.items():
                    if k == shared_key[t]:
                        current[other] = dense
            else:
                req = svc.submit(t, ev["b"], transpose=ev["transpose"])
                A = current[t].T if ev["transpose"] else current[t]
                expected.append((req, np.linalg.solve(A, ev["b"])))
                svc.step()
        svc.run()
        st = svc.stats()
        assert st["queue_depth"] == 0 and st["failed"] == 0
        assert st["completed"] == len(expected) > 0
        for req, x_ref in expected:
            np.testing.assert_allclose(req.x, x_ref, rtol=1e-9, atol=1e-11)
        assert st["solve_latency"]["count"] == svc.batches_completed > 0


def test_service_validates_tenancy_and_construction():
    svc = SolveService(strategy="serial", background=False)
    with pytest.raises(ValueError, match="no registered factor"):
        svc.submit("ghost", np.zeros(4))
    with pytest.raises(ValueError, match="no registered factor"):
        svc.refresh("ghost", np.zeros(4))
    with pytest.raises(ValueError, match="not both"):
        SolveService(registry=SolverRegistry(), strategy="serial")
