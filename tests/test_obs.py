"""The profiler spans and scopes of the solve and PCG paths
(``repro.core.obs``): host spans in a CPU profiler trace, scopes in the
executor's HLO metadata."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import SpTRSV, obs
from repro.core.pcg import make_ic_preconditioner, pcg
from repro.sparse import ic0_factor, lung2_like, poisson2d


def test_cpu_trace_holds_the_solve_and_pcg_spans(tmp_path):
    from jax.profiler import ProfileData

    A = poisson2d(8, 8, dtype=np.float32)
    L = ic0_factor(A)
    solver = SpTRSV.build(L, strategy="levelset")
    M = make_ic_preconditioner(L, rewrite=None)
    b = jnp.ones(A.n, jnp.float32)
    with jax.profiler.trace(str(tmp_path)):
        solver.solve(b).block_until_ready()
        res = pcg(A, b, M, tol=1e-6)
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    names = [e.name for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:CPU")
             for line in p.lines for e in line.events]
    # one solve, then two per preconditioner apply: one before the loop and
    # one in each iteration that does not converge
    assert names.count(obs.SOLVE) == 1 + 2 * res.iters
    assert names.count(obs.PCG_SETUP) == 1
    assert names.count(obs.PCG_ITER) == res.iters
    assert names.count(obs.PCG_READBACK) == 2 * res.iters + 2


def test_packed_levelset_hlo_names_permute_and_segment_scopes():
    L = lung2_like(scale=0.01, seed=0)
    solver = SpTRSV.build(L, strategy="levelset", coarsen=True)
    assert solver.stats()["layout"] == "permuted"
    b = jnp.ones(L.n, jnp.float32)
    hlo = jax.jit(solver._solve_fn).lower(b, solver._values).compile() \
        .as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    assert any(obs.PERMUTE in n.split("/") for n in names)
    assert any(obs.SEGMENT in n.split("/") for n in names)
