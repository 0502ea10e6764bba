"""Distribution features on 8 virtual devices: MoE-EP == dense-local,
flash attention == naive reference, GPipe == sequential, compressed
all-reduce ≈ mean with bounded error, sharded train step == single-device."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import smoke_config
from repro.launch.mesh import make_mesh
from repro.models.layers import flash_attention
from repro.models.model import DistContext, Model
from repro.models.moe import init_moe, moe_apply
from repro.models.sharding import param_specs, batch_specs


# --------------------------------------------------------------------------
# flash attention vs naive
# --------------------------------------------------------------------------
def _naive_attn(q, k, v, kind, window=0, prefix_len=0, softcap_val=0.0):
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    qf = q.reshape(B, S, Hkv, g, hd).astype(np.float64)
    kf = np.asarray(k, np.float64)
    vf = np.asarray(v, np.float64)
    s = np.einsum("bqhgd,bkhd->bhgqk", qf, kf) / np.sqrt(hd)
    if softcap_val:
        s = softcap_val * np.tanh(s / softcap_val)
    qpos = np.arange(S)[:, None]
    kpos = np.arange(S)[None, :]
    if kind == "causal":
        mask = kpos <= qpos
    elif kind == "window":
        mask = (kpos <= qpos) & (kpos > qpos - window)
    elif kind == "prefix":
        mask = (kpos <= qpos) | (kpos < prefix_len)
    else:
        mask = np.ones((S, S), bool)
    s = np.where(mask[None, None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    o = np.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return o.reshape(B, S, Hq, hd)


@pytest.mark.parametrize("kind,window,prefix", [
    ("causal", 0, 0), ("window", 7, 0), ("full", 0, 0), ("prefix", 0, 5),
])
@pytest.mark.parametrize("gqa", [1, 4])
def test_flash_matches_naive(kind, window, prefix, gqa):
    rng = np.random.default_rng(0)
    B, S, Hkv, hd = 2, 37, 2, 8
    q = rng.normal(size=(B, S, Hkv * gqa, hd)).astype(np.float32)
    k = rng.normal(size=(B, S, Hkv, hd)).astype(np.float32)
    v = rng.normal(size=(B, S, Hkv, hd)).astype(np.float32)
    got = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          kind=kind, window=window, prefix_len=prefix,
                          block_q=16, block_k=8, softcap_val=2.0)
    ref = _naive_attn(q, k, v, kind, window, prefix, softcap_val=2.0)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------------
# MoE: expert-parallel shard_map path == dense local path
# --------------------------------------------------------------------------
def test_moe_ep_matches_local():
    cfg = smoke_config("llama4-scout-17b-a16e")   # 4 experts top-1 + shared
    key = jax.random.key(0)
    params = init_moe(key, cfg)
    x = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model), jnp.float32)
    y_local, aux_local = moe_apply(params, cfg, x, mesh=None)
    mesh = make_mesh((4, 2), ("data", "model"))
    with mesh:
        y_ep, aux_ep = jax.jit(
            lambda p, x: moe_apply(p, cfg, x, mesh=mesh))(params, x)
    np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_ep),
                               rtol=2e-4, atol=2e-4)
    # aux: local computes E·Σ f_e·p_e over all tokens; EP pmeans per-shard
    # estimates — mean-of-products ≠ product-of-means, both are unbiased
    # Switch estimators, so only require same scale
    np.testing.assert_allclose(float(aux_local), float(aux_ep), rtol=0.3)


def test_moe_top2_dense_residual():
    cfg = smoke_config("arctic-480b")             # 4 experts top-2 + dense
    params = init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 8, cfg.d_model), jnp.float32)
    y, aux = moe_apply(params, cfg, x, mesh=None)
    assert y.shape == x.shape
    assert jnp.isfinite(y).all() and jnp.isfinite(aux)


# --------------------------------------------------------------------------
# sharded forward == single-device forward
# --------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["gemma3-1b", "llama4-scout-17b-a16e"])
def test_sharded_forward_matches_local(arch):
    import dataclasses
    cfg = smoke_config(arch)
    if cfg.n_experts:
        # EP shards tokens before computing capacity, so which tokens drop
        # differs from the local path at tight capacity; test equality in
        # the no-drop regime (drop behaviour is covered by test_moe_*)
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    model = Model(cfg, remat=False)
    params = model.init(jax.random.key(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (4, 16)))
    batch = {"tokens": toks}
    logits_local, _ = jax.jit(model.forward)(params, batch)

    mesh = make_mesh((4, 2), ("data", "model"))
    specs = param_specs(params, mesh, cfg)
    sharded = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs))
    dist = DistContext(mesh=mesh, dp_axes=("data",))
    with mesh:
        logits_sh, _ = jax.jit(
            lambda p, b: model.forward(p, b, dist=dist))(sharded, batch)
    np.testing.assert_allclose(
        np.asarray(logits_sh, np.float32), np.asarray(logits_local, np.float32),
        rtol=5e-2, atol=5e-2)


# --------------------------------------------------------------------------
# GPipe
# --------------------------------------------------------------------------
def test_gpipe_matches_sequential():
    from repro.distributed.pipeline import make_gpipe

    mesh = make_mesh((8,), ("pipe",))
    P_, M, mb, d = 8, 4, 2, 16
    rng = np.random.default_rng(0)
    stage_w = jnp.asarray(rng.normal(size=(P_, d, d)).astype(np.float32) * 0.3)

    def stage_apply(w, x):
        return jnp.tanh(x @ w)

    xs = jnp.asarray(rng.normal(size=(M, mb, d)).astype(np.float32))
    fn = make_gpipe(stage_apply, mesh, "pipe")
    with mesh:
        out = jax.jit(fn)(stage_w, xs)
    ref = xs
    for s in range(P_):
        ref = jnp.tanh(ref @ stage_w[s])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------
# compressed gradient all-reduce
# --------------------------------------------------------------------------
def test_compressed_allreduce_error_feedback():
    from jax import shard_map
    from repro.distributed.compress import compressed_allreduce

    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    gs = rng.normal(size=(8, 64)).astype(np.float32)
    target = gs.mean(0)

    def body(g, r):
        out, rr = compressed_allreduce(g[0], r[0], "data")
        return out, rr[None]

    f = shard_map(body, mesh=mesh, in_specs=(P("data", None), P("data", None)),
                  out_specs=(P(None), P("data", None)), check_vma=False)
    resid = jnp.zeros((8, 64), jnp.float32)
    out, resid = f(jnp.asarray(gs), resid)
    # single round: int8-quantized mean close to true mean
    np.testing.assert_allclose(np.asarray(out), target, atol=0.1)
    # error feedback: residual bounded by a quant step
    assert float(jnp.abs(resid).max()) < 0.2
    # accumulated over rounds, EF keeps the *sum* unbiased
    total_err = np.zeros(64)
    resid = jnp.zeros((8, 64), jnp.float32)
    for _ in range(20):
        out, resid = f(jnp.asarray(gs), resid)
        total_err += np.asarray(out) - target
    assert np.abs(total_err / 20).max() < 0.02
