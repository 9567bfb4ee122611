"""Parallelism library tests on the 8-device CPU mesh (SURVEY §4 test
strategy: all mesh/sharding logic exercised multi-device without TPU).
Every strategy is checked against a single-device oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from learningorchestra_tpu.parallel import (moe, pipeline, ring, sharding,
                                            ulysses)
from learningorchestra_tpu.runtime import mesh as mesh_lib


def _mesh(spec: str) -> Mesh:
    return mesh_lib.build_mesh(spec, devices=jax.devices())


def _qkv(b=2, s=32, h=4, d=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(b, s, h, d)).astype(np.float32))
    return mk(), mk(), mk()


# ----------------------------------------------------------------------
# ring attention
# ----------------------------------------------------------------------
@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    mesh = _mesh("dp=2,sp=4")
    q, k, v = _qkv()
    want = ring.full_attention_reference(q, k, v, causal=causal)
    got = ring.ring_attention_sharded(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_grads_flow():
    mesh = _mesh("sp=8")
    q, k, v = _qkv(b=1, s=16, h=2, d=8)

    def loss_ring(q, k, v):
        return jnp.sum(ring.ring_attention_sharded(
            q, k, v, mesh, causal=True) ** 2)

    def loss_full(q, k, v):
        return jnp.sum(ring.full_attention_reference(
            q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring)(q, k, v)
    g_full = jax.grad(loss_full)(q, k, v)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_full),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_full(causal):
    """Ring with the PALLAS kernel as the per-hop block (interpret
    mode on CPU): values must equal the full-softmax oracle."""
    mesh = _mesh("sp=4")
    q, k, v = _qkv(s=32)
    want = ring.full_attention_reference(q, k, v, causal=causal)
    got = ring.ring_attention_sharded(q, k, v, mesh, causal=causal,
                                      block_impl="flash")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_grads_match_oracle():
    """Backward through hop merges + the lse-aware kernel VJP."""
    mesh = _mesh("sp=4")
    q, k, v = _qkv(b=1, s=16, h=2, d=8, seed=3)

    def loss_rf(q, k, v):
        return jnp.sum(ring.ring_attention_sharded(
            q, k, v, mesh, causal=True, block_impl="flash") ** 2)

    def loss_full(q, k, v):
        return jnp.sum(ring.full_attention_reference(
            q, k, v, causal=True) ** 2)

    g_rf = jax.grad(loss_rf, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_rf, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------------------
# ulysses
# ----------------------------------------------------------------------
@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(causal):
    mesh = _mesh("dp=2,sp=4")  # heads=4 divisible by sp=4
    q, k, v = _qkv()
    want = ring.full_attention_reference(q, k, v, causal=causal)
    got = ulysses.ulysses_attention_sharded(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_with_flash_blocks_matches_full():
    """The TPU default: after the head-scatter all-to-all, local
    attention runs the Pallas kernel (interpret mode here)."""
    import functools

    from learningorchestra_tpu.ops import attention as attn_ops

    mesh = _mesh("sp=4")
    q, k, v = _qkv(s=32, seed=11)
    want = ring.full_attention_reference(q, k, v, causal=True)
    spec = P(None, "sp", None, None)
    fn = mesh_lib.shard_map(
        functools.partial(
            ulysses.ulysses_attention, causal=True,
            attn_fn=functools.partial(attn_ops.flash_attention,
                                      causal=True)),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    got = fn(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_sharded_entry_takes_flash_like_an_accelerator(
        monkeypatch):
    """The path every backend but the CPU takes THROUGH the sharded
    entry point: flash kernel (interpreted here) inside its shard_map.
    The chip-only branch used to keep the vma check on, which a
    pallas_call's outputs cannot pass — it failed at trace time on the
    first accelerator run."""
    monkeypatch.setattr(ulysses, "_flash_local", lambda: True)
    mesh = _mesh("dp=2,sp=4")
    q, k, v = _qkv(s=32, seed=5)
    want = ring.full_attention_reference(q, k, v, causal=True)
    got = ulysses.ulysses_attention_sharded(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------------------
# pipeline
# ----------------------------------------------------------------------
def test_pipeline_matches_sequential():
    n_stages, d, batch = 4, 16, 24
    mesh = _mesh("dp=2,pp=4")
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(n_stages, d, d)).astype(np.float32)
                    * 0.3)
    b = jnp.asarray(rng.normal(size=(n_stages, d)).astype(np.float32) * 0.1)
    x = jnp.asarray(rng.normal(size=(batch, d)).astype(np.float32))

    def stage_fn(params, h):
        return jnp.tanh(h @ params["w"] + params["b"])

    got = pipeline.pipeline_apply(stage_fn, {"w": w, "b": b}, x, mesh,
                                  num_microbatches=4)
    want = x
    for i in range(n_stages):
        want = jnp.tanh(want @ w[i] + b[i])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_batch_not_divisible_raises():
    mesh = _mesh("pp=8")
    w = jnp.zeros((8, 4, 4))
    x = jnp.zeros((6, 4))
    with pytest.raises(Exception):
        pipeline.pipeline_apply(lambda p, h: h @ p["w"], {"w": w}, x, mesh,
                                num_microbatches=4)


# ----------------------------------------------------------------------
# MoE / expert parallelism
# ----------------------------------------------------------------------
def _naive_moe(params, x, k):
    """Per-token loop over the chosen experts (gated, no capacity)."""
    probs = jax.nn.softmax(x @ params["gate"], axis=-1)
    vals, idx = jax.lax.top_k(probs, k)
    vals = vals / vals.sum(axis=-1, keepdims=True)
    ex = params["experts"]
    want = np.zeros(x.shape, np.float32)
    for ti in range(x.shape[0]):
        for c in range(k):
            e = int(idx[ti, c])
            h = jax.nn.silu(x[ti] @ ex["w_gate"][e]) * (x[ti] @ ex["w_up"][e])
            want[ti] += float(vals[ti, c]) * np.asarray(h @ ex["w_down"][e])
    return want


def test_moe_every_copy_reaches_its_expert():
    """No capacity: the grouped schedule's output equals the naive
    per-token loop over each token's top-k gated experts."""
    d_model, d_ff, n_experts, t = 8, 16, 4, 12
    params = moe.init_moe_params(jax.random.PRNGKey(0), d_model, d_ff,
                                 n_experts)
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(t, d_model)).astype(np.float32))
    out, aux, counts = moe.moe_layer(params, x, k=2)
    assert out.shape == x.shape and np.isfinite(float(aux))
    assert int(counts.sum()) == 2 * t
    np.testing.assert_allclose(np.asarray(out), _naive_moe(params, x, 2),
                               rtol=2e-4, atol=2e-4)


def _capacity(params, x, mesh=None, **kw):
    idx, weights, _ = moe.route(x @ params["gate"], 2)
    return moe.capacity_experts(params["experts"], x, idx, weights,
                                mesh=mesh, **kw)


def test_moe_sharded_matches_unsharded():
    mesh = _mesh("dp=2,ep=4")
    d_model, d_ff, n_experts, t = 8, 16, 4, 64
    params = moe.init_moe_params(jax.random.PRNGKey(1), d_model, d_ff,
                                 n_experts)
    x = jnp.asarray(np.random.default_rng(3).normal(
        size=(t, d_model)).astype(np.float32))
    out_plain = jax.jit(_capacity)(params, x)

    sharded_params = sharding.shard_params(params, mesh, fsdp=False)
    out_sharded, _, _ = jax.jit(
        lambda p, x: moe.moe_layer(p, x, k=2, mesh=mesh)
    )(sharded_params, x)
    np.testing.assert_allclose(np.asarray(out_sharded),
                               np.asarray(out_plain),
                               rtol=2e-5, atol=2e-5)


def test_moe_ep_schedule_drops_past_capacity_and_the_grouped_one_not():
    d_model, d_ff, n_experts, t = 8, 16, 2, 32
    params = moe.init_moe_params(jax.random.PRNGKey(2), d_model, d_ff,
                                 n_experts)
    x = jnp.ones((t, d_model), jnp.float32)  # all tokens identical
    idx, weights, _ = moe.route(x @ params["gate"], 1)
    out = moe.capacity_experts(params["experts"], x, idx, weights,
                               capacity_factor=0.25)
    # identical tokens all route to one expert; only `capacity` survive
    nonzero = np.asarray(jnp.any(jnp.abs(out) > 1e-12, axis=-1))
    assert 0 < nonzero.sum() < t
    kept, _, counts = moe.moe_layer(params, x, k=1)
    assert np.asarray(jnp.any(jnp.abs(kept) > 1e-12, axis=-1)).all()
    assert sorted(np.asarray(counts)) == [0, t]


def test_moe_routes_8k_tokens_32_experts():
    """T=8k, E=32: the sorted schedule runs it in bounded memory,
    differentiably, and drops nothing."""
    d_model, d_ff, n_experts, t = 32, 64, 32, 8192
    params = moe.init_moe_params(jax.random.PRNGKey(6), d_model, d_ff,
                                 n_experts)
    x = jnp.asarray(np.random.default_rng(7).normal(
        size=(t, d_model)).astype(np.float32))

    def loss(p, x):
        out, aux, counts = moe.moe_layer(p, x, k=2)
        return jnp.mean(out ** 2) + 0.01 * aux, counts

    (val, counts), grads = jax.jit(
        jax.value_and_grad(loss, has_aux=True))(params, x)
    assert np.isfinite(float(val)) and int(counts.sum()) == 2 * t
    gnorm = sum(float(jnp.sum(jnp.abs(g)))
                for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


def test_moe_ep_schedule_with_ample_capacity_is_the_grouped_one():
    """With room for every copy the ``ep`` schedule (sharded) and the
    grouped schedule (one device) give the same layer."""
    mesh = _mesh("dp=2,ep=4")
    d_model, d_ff, n_experts, t = 8, 16, 4, 64
    params = moe.init_moe_params(jax.random.PRNGKey(8), d_model, d_ff,
                                 n_experts)
    x = jnp.asarray(np.random.default_rng(9).normal(
        size=(t, d_model)).astype(np.float32))
    out_plain, _, _ = jax.jit(lambda p, x: moe.moe_layer(p, x, k=2))(
        params, x)
    sharded_params = sharding.shard_params(params, mesh, fsdp=False)
    out_sharded, _, _ = jax.jit(
        lambda p, x: moe.moe_layer(p, x, k=2, mesh=mesh,
                                   capacity_factor=float(n_experts))
    )(sharded_params, x)
    np.testing.assert_allclose(np.asarray(out_sharded),
                               np.asarray(out_plain),
                               rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------------------
# sharding rules
# ----------------------------------------------------------------------
def test_transformer_rules_tp_specs():
    mesh = _mesh("dp=2,tp=4")
    assert sharding.spec_for("decoder/l0/attn/q_proj/kernel", (64, 64),
                             mesh, fsdp=False) == P(None, "tp")
    assert sharding.spec_for("decoder/l0/attn/o_proj/kernel", (64, 64),
                             mesh, fsdp=False) == P("tp", None)
    assert sharding.spec_for("decoder/l0/mlp/wo/bias", (64,),
                             mesh, fsdp=False) == P()


def test_fsdp_shards_largest_free_dim():
    mesh = _mesh("fsdp=8")
    spec = sharding.spec_for("anything/kernel", (16, 64), mesh)
    assert spec == P(None, "fsdp")
    # dims not divisible by 8 stay replicated
    assert sharding.spec_for("x/kernel", (7, 9), mesh) == P()


def test_shard_params_places_on_mesh():
    mesh = _mesh("dp=2,tp=4")
    params = {"layer/q_proj/kernel": jnp.zeros((32, 32))}
    # tree_map_with_path on a flat dict uses the dict key as path
    shardings = sharding.param_shardings(params, mesh, fsdp=False)
    sh = shardings["layer/q_proj/kernel"]
    assert isinstance(sh, NamedSharding)
    assert sh.spec == P(None, "tp")


@pytest.mark.parametrize("block_impl", ["dense", "flash"])
@pytest.mark.parametrize("window", [5, 9, 64])
def test_ring_windowed_matches_banded_oracle(block_impl, window):
    """Sliding-window ring attention (dense tiles AND per-hop flash
    with static position offsets) must equal the global banded
    oracle; W=64 >= seq degenerates to plain causal. W smaller than a
    shard (5 < 32/4) exercises the wholly-below-band hop skip."""
    mesh = _mesh("sp=4")
    q, k, v = _qkv(b=1, s=32, h=2, d=8)
    want = ring.full_attention_reference(q, k, v, causal=True,
                                         window=window)
    got = ring.ring_attention_sharded(q, k, v, mesh, causal=True,
                                      window=window,
                                      block_impl=block_impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_ring_windowed_flash_grads_match_oracle():
    mesh = _mesh("sp=4")
    q, k, v = _qkv(b=1, s=32, h=2, d=8)
    W = 9

    def loss_ring(q, k, v):
        return jnp.sum(ring.ring_attention_sharded(
            q, k, v, mesh, causal=True, window=W,
            block_impl="flash") ** 2)

    def loss_full(q, k, v):
        return jnp.sum(ring.full_attention_reference(
            q, k, v, causal=True, window=W) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


def test_ring_windowed_multi_tile_shards():
    """Per-shard seq (40) spanning several kernel tiles (auto block 8)
    with W=10 < shard: cross-shard hops have q-bands that start before
    row 0 for early kv tiles — the index-map floor must keep DMA
    indices in bounds while values still match the banded oracle
    (fwd AND grads)."""
    mesh = _mesh("sp=4")
    q, k, v = _qkv(b=1, s=160, h=2, d=8)
    W = 10
    want = ring.full_attention_reference(q, k, v, causal=True, window=W)
    got = ring.ring_attention_sharded(q, k, v, mesh, causal=True,
                                      window=W, block_impl="flash")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)

    g_ring = jax.grad(lambda a, b_, c: jnp.sum(
        ring.ring_attention_sharded(a, b_, c, mesh, causal=True,
                                    window=W, block_impl="flash") ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(lambda a, b_, c: jnp.sum(
        ring.full_attention_reference(a, b_, c, causal=True,
                                      window=W) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=3e-4, atol=3e-4)


def test_ulysses_gqa_native_matches_oracle():
    """Ulysses with kv-width K/V (kvh=2 over sp=2): the head scatter
    moves grouped K/V and local attention consumes the group — must
    equal the repeat-based banded oracle (fwd + grads, windowed)."""
    from learningorchestra_tpu.parallel import ulysses

    mesh = _mesh("sp=2")
    q, _, _ = _qkv(b=1, s=32, h=4, d=8)
    k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 32, 2, 8),
                              jnp.float32) * 0.2 for i in (7, 8))

    def oracle(q, k, v):
        return ring.full_attention_reference(
            q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2),
            causal=True, window=9)

    got = ulysses.ulysses_attention_sharded(q, k, v, mesh, causal=True,
                                            window=9)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle(q, k, v)),
                               rtol=3e-5, atol=3e-5)
    g_u = jax.grad(lambda a, b_, c: jnp.sum(
        ulysses.ulysses_attention_sharded(a, b_, c, mesh, causal=True,
                                          window=9) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_o = jax.grad(lambda a, b_, c: jnp.sum(oracle(a, b_, c) ** 2),
                   argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(g_u, g_o):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)
