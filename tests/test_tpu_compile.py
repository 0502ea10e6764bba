"""v5e compiles of the main path, from a described topology.

Nothing here runs on a chip: each test lowers and compiles for one v5e chip
described by ``topologies.get_topology_desc``, which raises what the chip's
compiler would raise.  The topology is described inside a fixture (never at
import, in a ``skipif`` or in ``parametrize``), so every xdist worker
collects the same tests and only the worker given this file loads the TPU
compiler.  The persistent compile cache is off around the compiles: what a
described device compiles cannot be read back without a chip.

The gather kernels (``sptrsv_fused``, ``sptrsv_level``, ``spmv_ell``) are
strict xfails on Mosaic's refusal: a kernel change that makes one compile
turns its case into a failure until the test is made a passing one.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import SpTRSV
from repro.sparse import lung2_like


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("T", [128, 64])
def test_trsm_block_compiles_for_v5e(one_chip, T):
    from repro.kernels.trsm_block import lowering_tpu

    compiled = jax.jit(
        lambda d, r: lowering_tpu.block_apply(d, r, batch_block=8,
                                              interpret=False)
    ).lower(_sds(one_chip, (64, T, T)), _sds(one_chip, (64, T))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m", [1, 8])
def test_coarsened_levelset_executor_compiles_for_v5e(one_chip, m):
    L = lung2_like(scale=0.25, dtype=np.float32)
    s = SpTRSV.build(L, strategy="levelset", coarsen=True, backend="tpu")
    assert s.layout == "permuted" and s.stats()["segments"] < \
        s.analysis.num_levels
    b = _sds(one_chip, (L.n,) if m == 1 else (L.n, m))
    compiled = jax.jit(s.solve).lower(b).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("m", [1, 8])
def test_pcg_ell_matvec_compiles_for_v5e(one_chip, m):
    """PCG's SpMV, its ELL arrays arguments of the program, at the 512x512
    5-point Laplacian's size (n = 262,144, K = 5)."""
    from repro.core.pcg import _matvec

    n, K = 512 * 512, 5
    v = _sds(one_chip, (n,) if m == 1 else (n, m))
    compiled = _matvec.lower(_sds(one_chip, (K, n), jnp.int32),
                             _sds(one_chip, (K, n)), v).compile()
    assert compiled.memory_analysis() is not None


def _offsets_27(edge):
    """The ``col − row`` offsets of the 27-point stencil on an ``edge^3``
    grid, ascending."""
    r = (-1, 0, 1)
    return tuple(sorted(x + edge * (y + edge * z)
                        for x in r for y in r for z in r))


@pytest.mark.parametrize("m", [1, 8])
@pytest.mark.parametrize("n, offsets", [
    (512 ** 2, (-512, -1, 0, 1, 512)), (104 ** 3, _offsets_27(104))],
    ids=["p512", "s104"])
def test_pcg_dia_matvec_compiles_for_v5e(one_chip, n, offsets, m):
    """PCG's SpMV by diagonals, its values an argument and its offsets
    static, at the 512x512 5-point Laplacian (5 offsets) and HPCG's 104^3
    27-point stencil (27 offsets)."""
    from repro.core.codegen import Diagonals
    from repro.core.pcg import _matvec

    v = _sds(one_chip, (n,) if m == 1 else (n, m))
    compiled = _matvec.lower(Diagonals(offsets),
                             _sds(one_chip, (len(offsets), n)), v).compile()
    assert compiled.memory_analysis() is not None
    assert "gather" not in compiled.as_text()


@pytest.mark.parametrize("edge", [104, 52])
def test_mg_programs_compile_for_v5e(one_chip, edge):
    """The multigrid V-cycle's own programs (``repro.core.mg``: SYMGS's
    SpMV and updates, the restriction and the prolongation), their arrays
    arguments, at HPCG's 104^3 and 52^3 levels: ``U`` by its 13 diagonals,
    the rows of ``A`` at ``f2c`` as ELL of up to 27 entries a row."""
    from repro.core import mg
    from repro.core.codegen import Diagonals

    n, nc = edge ** 3, (edge // 2) ** 3
    upper = Diagonals(tuple(o for o in _offsets_27(edge) if o > 0))
    assert len(upper.offsets) == 13

    def f32(*shape):
        return _sds(one_chip, shape)

    def i32(*shape):
        return _sds(one_chip, shape, jnp.int32)

    lowered = [
        mg._diag_times.lower(f32(n), f32(n)),
        mg._upper_rhs.lower(upper, f32(13, n), f32(n), f32(n)),
        mg._backward_rhs.lower(f32(n), f32(n), f32(n)),
        mg._restrict.lower(i32(27, nc), f32(27, nc), i32(nc), f32(n), f32(n)),
        mg._prolong.lower(i32(nc), f32(n), f32(nc)),
    ]
    for program in lowered:
        assert program.compile().memory_analysis() is not None


_K, _N_PAD, _CHUNK = 5, 4096, 512


def _fused(kernel):
    def f(sh):
        from repro.kernels.sptrsv_fused import lowering_tpu

        m_shape = (_N_PAD,) if kernel == "fused_solve" else (_N_PAD, 8)
        fn = getattr(lowering_tpu, kernel)
        return jax.jit(
            lambda b, c, v, d: fn(b, c, v, d, chunk=_CHUNK, interpret=False)
        ).lower(_sds(sh, m_shape), _sds(sh, (_K, _N_PAD), jnp.int32),
                _sds(sh, (_K, _N_PAD)), _sds(sh, (_N_PAD,)))
    return f


def _level(sh):
    from repro.kernels.sptrsv_level import lowering_tpu

    R = 1024
    return jax.jit(
        lambda x, b, c, v, d: lowering_tpu.level_solve_blocks(
            x, b, c, v, d, block_rows=512, interpret=False)
    ).lower(_sds(sh, (_N_PAD,)), _sds(sh, (R,)), _sds(sh, (_K, R), jnp.int32),
            _sds(sh, (_K, R)), _sds(sh, (R,)))


def _spmv(sh):
    from repro.kernels.spmv_ell import lowering_tpu

    return jax.jit(
        lambda v, c, w: lowering_tpu.spmv(v, c, w, block=1024,
                                          interpret=False)
    ).lower(_sds(sh, (_N_PAD,)), _sds(sh, (_K, _N_PAD), jnp.int32),
            _sds(sh, (_K, _N_PAD)))


def _refused(raises, match):
    return pytest.mark.xfail(strict=True, raises=raises,
                             reason=f"Mosaic refuses the gather: {match}")


@pytest.mark.parametrize("lower, match", [
    pytest.param(_fused("fused_solve"), "Only 2D gather is supported",
                 marks=_refused(NotImplementedError, "2-D gather"),
                 id="sptrsv_fused"),
    pytest.param(_fused("fused_solve_batched"),
                 "Shape mismatch in input, indices and output",
                 marks=_refused(ValueError, "batched gather shape"),
                 id="sptrsv_fused_batched"),
    pytest.param(_level, "Only 2D gather is supported",
                 marks=_refused(NotImplementedError, "2-D gather"),
                 id="sptrsv_level"),
    pytest.param(_spmv, "Only 2D gather is supported",
                 marks=_refused(NotImplementedError, "2-D gather"),
                 id="spmv_ell"),
])
def test_gather_kernel_compiles_for_v5e(one_chip, lower, match):
    try:
        lower(one_chip).compile()
    except (NotImplementedError, ValueError) as e:
        # only the known refusal may xfail; any other error is a failure
        assert match in str(e), f"unexpected compiler error: {e}"
        raise
