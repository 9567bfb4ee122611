"""The ``sdar_moe`` block and its block-diffusion objective, program
against the plain reference (``benchmark/reference/sdar_moe.py``), at a
small size on the CPU with the seed's weights: the gated expert layer
and its shares, attention under the block-diffusion mask through the
flash kernels, the objective through ``fit``, ``head_dim`` and QK-norm.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_sdar
from benchmark.reference import sdar_moe
from learningorchestra_tpu.models import LanguageModel
from learningorchestra_tpu.ops import attention as attn_ops
from learningorchestra_tpu.ops import grouped_matmul as gmm_ops
from learningorchestra_tpu.parallel import moe

SEED = 2600000007
EPS = 1e-6
LM = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
          head_dim=16, qk_norm=True, d_ff=24, n_experts=8, moe_k=2,
          experts_held=4, expert_offset=2, max_len=32, attention="dot",
          rope_base=1e6, objective="block_diffusion", block_length=4,
          aux_coef=0.0, remat="full")
OPTIMIZER = {"kind": "adamw", "learning_rate": 3e-4, "weight_decay": 1e-4}


@pytest.fixture(autouse=True)
def _one_device_float32(tmp_path):
    """One device (the reference's rows are the step's rows: no data-
    parallel padding), float32 compute."""
    from learningorchestra_tpu import config as config_mod

    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo"), mesh_shape="dp=1",
        compute_dtype="float32"))
    yield
    config_mod.reset_config()


def _layer(lm, seed=SEED, layer=0):
    """(program's params of one expert layer, the reference's)."""
    flat = sdar_moe.flat_weights(seed, lm)
    w = sdar_moe.layer_weights(flat, layer)
    return ({"gate": w["router"],
             "experts": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}, w)


def _tokens(t, d, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (t, d), jnp.float32)


# ----------------------------------------------------------------------
# the expert layer
# ----------------------------------------------------------------------
def test_expert_layer_matches_the_reference_forward_and_gradients():
    params, w = _layer(LM)
    x = _tokens(96, LM["d_model"])

    def prog(p, x):
        out, _, counts = moe.moe_layer(p, x, k=LM["moe_k"],
                                       expert_offset=LM["expert_offset"])
        return out, counts

    def ref(p, x):
        return sdar_moe.experts(x, dict(w, router=p["gate"], **p["experts"]),
                                LM, None)

    with jax.default_matmul_precision("highest"):
        got, got_counts = prog(params, x)
        want, want_counts = ref(params, x)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(got_counts, want_counts)
        g_prog = jax.grad(lambda p, x: jnp.sum(jnp.sin(prog(p, x)[0])),
                          (0, 1))(params, x)
        g_ref = jax.grad(lambda p, x: jnp.sum(jnp.sin(ref(p, x)[0])),
                         (0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_prog),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_no_copy_is_dropped_under_a_skewed_router():
    """Every token's two choices are the two held experts, 2 and 3: 2T
    copies where an even router would send T/2, the buffer's worst
    case, and every copy is computed."""
    lm = dict(LM, experts_held=2, expert_offset=2)
    params, w = _layer(lm)
    x = jnp.abs(_tokens(64, lm["d_model"]))
    params["gate"] = params["gate"].at[:, 2].add(9.0).at[:, 3].add(8.0)
    out, _, counts = moe.moe_layer(params, x, k=2, expert_offset=2)
    np.testing.assert_array_equal(counts, [64, 64])
    want, _ = sdar_moe.experts(x, dict(w, router=params["gate"]), lm, None)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    assert float(jnp.min(jnp.sum(jnp.abs(out), axis=-1))) > 0


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight ranks of two experts each: the parts their layers give sum
    to what the uncut reference gives for the whole layer."""
    whole = dict(LM, n_experts=16, moe_k=4, experts_held=0, expert_offset=0)
    x = _tokens(80, whole["d_model"], seed=3)
    _, w_all = _layer(whole)
    want, counts_all = sdar_moe.experts(x, w_all, whole, None)
    total = jnp.zeros_like(x)
    copies = []
    for rank in range(8):
        share = dict(whole, experts_held=2, expert_offset=2 * rank)
        params, _ = _layer(share)
        np.testing.assert_array_equal(
            params["experts"]["w_up"], w_all["w_up"][2 * rank:2 * rank + 2])
        out, _, counts = moe.moe_layer(params, x, k=4,
                                       expert_offset=2 * rank)
        total = total + out
        copies.extend(np.asarray(counts))
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(copies, counts_all)
    assert sum(copies) == 80 * 4


def test_grouped_layout_pads_every_expert_to_whole_tiles():
    idx = jnp.asarray([[0, 5], [1, 5], [5, 6], [5, 2], [7, 5]], jnp.int32)
    src, valid, tile_group, n_active = moe.grouped_layout(
        idx, held=3, offset=4, rows=48, tile_m=4)
    # experts 4, 5, 6 receive 0, 5 and 1 copies: 1, 2 and 1 tiles
    assert int(n_active[0]) == 4
    np.testing.assert_array_equal(tile_group[:4], [0, 1, 1, 2])
    assert np.all(np.diff(np.asarray(tile_group)) >= 0)
    rows = np.flatnonzero(np.asarray(valid))
    np.testing.assert_array_equal(rows, [4, 5, 6, 7, 8, 12])
    np.testing.assert_array_equal(np.asarray(idx).reshape(-1)[
        np.asarray(src)[rows]], [5, 5, 5, 5, 5, 6])
    # token order within an expert
    np.testing.assert_array_equal(np.asarray(src)[rows] // 2,
                                  [0, 1, 2, 3, 4, 2])


def test_grouped_matmul_matches_a_product_a_group():
    tile, groups = 8, 3
    tile_group = jnp.asarray([0, 0, 1, 2, 2, 2], jnp.int32)
    n_active = jnp.asarray([5], jnp.int32)   # the last tile is unused
    x = _tokens(48, 16, seed=5)
    w = jax.random.normal(jax.random.PRNGKey(6), (groups, 16, 24))

    def plain(x, w):
        rows = jnp.repeat(tile_group, tile)
        y = jnp.einsum("rk,rkn->rn", x, w[rows])
        return jnp.where((jnp.arange(48) < 40)[:, None], y, 0.0)

    def kernel(x, w):
        return gmm_ops.grouped_matmul(x, w, tile_group, n_active,
                                      tile_m=tile)

    np.testing.assert_allclose(kernel(x, w), plain(x, w), rtol=1e-5,
                               atol=1e-5)
    got = jax.grad(lambda x, w: jnp.sum(jnp.cos(kernel(x, w))), (0, 1))(x, w)
    want = jax.grad(lambda x, w: jnp.sum(jnp.cos(plain(x, w))), (0, 1))(x, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_active", [1, 3, 6])
def test_grouped_matmul_never_reads_a_tile_past_the_active_ones(n_active):
    """The rows past the active tiles are not numbers: the forward
    reads zero there and both gradients stay finite and are those of
    the active rows alone (the worst-case buffer is mostly such rows)."""
    tile = 8
    tile_group = jnp.asarray([0, 1, 1, 2, 2, 2], jnp.int32)[:6]
    tile_group = jnp.where(jnp.arange(6) < n_active, tile_group,
                           tile_group[n_active - 1])
    live = (jnp.arange(48) < n_active * tile)[:, None]
    x = jnp.where(live, _tokens(48, 16, seed=7), jnp.nan)
    w = jax.random.normal(jax.random.PRNGKey(8), (3, 16, 24))

    def kernel(x, w):
        return gmm_ops.grouped_matmul(
            x, w, tile_group, jnp.asarray([n_active], jnp.int32),
            tile_m=tile)

    y = kernel(x, w)
    assert not np.isnan(np.asarray(y)).any()
    np.testing.assert_array_equal(np.asarray(y)[n_active * tile:], 0.0)
    rows = jnp.repeat(tile_group, tile)
    want = jnp.einsum("rk,rkn->rn", jnp.where(live, x, 0.0), w[rows])
    np.testing.assert_allclose(y, jnp.where(live, want, 0.0), rtol=1e-5,
                               atol=1e-5)
    dx, dw = jax.grad(lambda x, w: jnp.sum(kernel(x, w)), (0, 1))(x, w)
    assert np.isfinite(np.asarray(dx)[:n_active * tile]).all()
    seen = np.unique(np.asarray(tile_group)[:n_active])
    assert np.isfinite(np.asarray(dw)[seen]).all()
    np.testing.assert_allclose(
        np.asarray(dw)[seen],
        np.asarray(jax.grad(lambda w: jnp.sum(jnp.where(
            live, jnp.einsum("rk,rkn->rn", jnp.where(live, x, 0.0),
                             w[rows]), 0.0)))(w))[seen],
        rtol=1e-5, atol=1e-5)


def test_the_row_buffer_is_the_worst_case_and_nothing_else():
    """One path: the layer's forward holds its three grouped products
    once (no second buffer behind a ``cond``), and its buffer has a row
    for every choice of every token plus a tile a held expert."""
    params, _ = _layer(LM)
    x = _tokens(96, LM["d_model"])
    jaxpr = jax.make_jaxpr(lambda p, x: moe.moe_layer(
        p, x, k=LM["moe_k"], expert_offset=LM["expert_offset"])[0])(params, x)
    text = str(jaxpr)
    assert text.count("pallas_call[") == 3
    tile = moe._auto_tile(96 * LM["moe_k"] / LM["n_experts"])
    held = params["experts"]["w_gate"].shape[0]
    rows = -(-96 * min(LM["moe_k"], held) // tile) * tile + held * tile
    assert f"[{rows},{LM['d_model']}]" in text.replace(" ", "")


def _routing(case):
    """(idx (40, k), held, offset) of a routing whose used tiles (of 8
    rows, in chunks of two) are what the case's name says."""
    t = 40
    away = jnp.full((t,), 7, jnp.int32)      # an expert not held here
    if case == "one_tile":                   # 5 copies on the one held
        first = jnp.where(jnp.arange(t) < 5, 3, 6)
        return jnp.stack([first, away], axis=1), 1, 3
    if case == "ends_inside_a_chunk":        # 9, 3 and 10 copies: 5 tiles
        first = jnp.select([jnp.arange(t) < 9, jnp.arange(t) < 12,
                            jnp.arange(t) < 22], [2, 3, 4], 6)
        return jnp.stack([first, away], axis=1), 3, 2
    # every expert held, every copy kept
    idx = jax.lax.top_k(jax.random.normal(jax.random.PRNGKey(4), (t, 4)),
                        2)[1].astype(jnp.int32)
    return idx, 4, 0


@pytest.mark.parametrize("case", ["one_tile", "ends_inside_a_chunk",
                                  "every_copy_held"])
def test_the_passes_over_the_used_chunks_are_the_whole_buffer_passes(case):
    """The loops over the used chunks give what ``take``, ``silu(g) *
    u`` and ``.at[].add`` over the whole buffer give, in value and in
    the gradients towards the tokens, the products' rows and the
    router's weights; what lies past the used tiles is no number on
    their side, to show that it is never read."""
    tile, chunk, d = 8, 16, 16
    idx, held, offset = _routing(case)
    t, k = idx.shape
    rows = -(-t * min(k, held) // tile) * tile + held * tile
    rows = -(-rows // chunk) * chunk
    src, valid, _, n_active = moe.grouped_layout(idx, held, offset, rows,
                                                 tile)
    used = int(n_active[0]) * tile
    assert {"one_tile": used == tile,
            "ends_inside_a_chunk": used % chunk and chunk < used < rows,
            "every_copy_held": int(jnp.sum(valid)) == t * k}[case]
    copy = jnp.where(valid, src, t * k)
    live = (jnp.arange(rows) < used)[:, None]
    passes = (n_active, tile, chunk)
    inputs = {"tokens": _tokens(t, d, seed=11),
              "weights": jax.nn.softmax(_tokens(t, k, seed=12)),
              "ys": _tokens(rows, d, seed=13), "g": _tokens(rows, d, seed=14),
              "u": _tokens(rows, d, seed=15)}
    in_buffer = ("ys", "g", "u")
    cot = [_tokens(rows, d, seed=16 + i) for i in range(3)] \
        + [_tokens(t, d, seed=19)]

    def chunked(a):
        xs_gate, xs_up = moe.dispatch(a["tokens"], copy, k, *passes)
        return (xs_gate, xs_up, moe.gated(a["g"], a["u"], *passes),
                moe.combine(a["ys"], a["weights"], copy, *passes))

    def whole(a):
        xs = jnp.take(a["tokens"], copy // k, axis=0, mode="fill",
                      fill_value=0)
        w_row = jnp.take(a["weights"].reshape(-1), copy, mode="fill",
                         fill_value=0)
        return (xs, xs, jax.nn.silu(a["g"]) * a["u"],
                jnp.zeros((t, d)).at[copy // k].add(
                    a["ys"] * w_row[:, None], mode="drop"))

    def run(fn, past):
        args = {name: jnp.where(live, a, past) if name in in_buffer else a
                for name, a in inputs.items()}
        weigh = [jnp.where(live, c, past) for c in cot[:3]] + cot[3:]
        grads = jax.grad(lambda a: sum(
            jnp.sum(out * c) for out, c in zip(fn(a), weigh)))(args)
        return dict(zip(("xs_gate", "xs_up", "h", "out"), fn(args)),
                    **{"d " + name: g for name, g in grads.items()})

    got, want = run(chunked, jnp.nan), run(whole, 0.0)
    assert got.keys() == want.keys() and len(got) == 9
    for name in got:
        a, b = got[name], want[name]
        if name in ("h", "d ys", "d g", "d u"):
            # past the used tiles nothing reads them
            a, b = a[:used], b[:used]
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                   err_msg=name)


def _primitives(jaxpr, into):
    """The primitives of a jaxpr and of what it calls, the kernels'
    own bodies left out (``pl.when`` is a ``cond`` there)."""
    for eqn in jaxpr.eqns:
        into.append((eqn.primitive.name, eqn.params.get("name")))
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) \
                    else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, into)
    return into


def test_the_layer_is_one_path_of_loops_around_three_products():
    """Three ``while`` (dispatch, ``silu x up`` and combine: the
    passes' trip count is observed) and no ``cond`` (no second buffer
    to fall back on); a gradient of the
    layer calls each product's ``moe_gmm_dw`` once, three in all: the
    kernels are not in the loops."""
    params, _ = _layer(LM)
    x = _tokens(96, LM["d_model"])

    def layer(p, x):
        return jnp.sum(moe.moe_layer(
            p, x, k=LM["moe_k"], expert_offset=LM["expert_offset"])[0])

    forward = _primitives(jax.make_jaxpr(layer)(params, x).jaxpr, [])
    names = [name for name, _ in forward]
    assert names.count("while") == 3 and "cond" not in names
    backward = _primitives(
        jax.make_jaxpr(jax.grad(layer, (0, 1)))(params, x).jaxpr, [])
    assert "cond" not in [name for name, _ in backward]
    kernels = [label for name, label in backward if name == "pallas_call"]
    assert kernels.count("moe_gmm_dw") == 3
    assert kernels.count("moe_gmm_dx") == 3
    assert kernels.count("moe_gmm_fwd") == 3


# ----------------------------------------------------------------------
# attention under the block-diffusion mask
# ----------------------------------------------------------------------
@pytest.mark.parametrize("seq,heads,kv,tile", [
    (40, 4, 2, 16),    # L no multiple of the tile, grouped heads
    (24, 2, 2, 8),
    (64, 4, 1, 32)], ids=["L40_gqa", "L24_mha", "L64_mqa"])
def test_flash_kernels_under_the_bd_mask_match_the_dense_softmax(
        seq, heads, kv, tile):
    ks = jax.random.split(jax.random.PRNGKey(seq), 4)
    q = jax.random.normal(ks[0], (2, 2 * seq, heads, 16))
    k = jax.random.normal(ks[1], (2, 2 * seq, kv, 16))
    v = jax.random.normal(ks[2], (2, 2 * seq, kv, 16))
    g = jax.random.normal(ks[3], q.shape)
    flash = lambda q, k, v: attn_ops.flash_bd_attention(  # noqa: E731
        q, k, v, block_length=4, block_q=tile, block_k=tile)
    dense = lambda q, k, v: attn_ops.bd_attention_reference(  # noqa: E731
        q, k, v, block_length=4)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), rtol=1e-5,
                               atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * g), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * g), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_the_programs_bd_mask_is_the_references():
    np.testing.assert_array_equal(attn_ops.bd_visible_mask(12, 4),
                                  sdar_moe.visible(12, 4))
    with pytest.raises(ValueError, match="block_length"):
        attn_ops.flash_bd_attention(jnp.zeros((1, 12, 2, 8)),
                                    jnp.zeros((1, 12, 2, 8)),
                                    jnp.zeros((1, 12, 2, 8)),
                                    block_length=4)


# ----------------------------------------------------------------------
# the objective through fit
# ----------------------------------------------------------------------
def _rows(n=4, seq=32, seed=0):
    return np.random.default_rng(seed).integers(
        1, LM["vocab_size"] - 1, size=(n, seq)).astype(np.int32)


def _fit(attention="dot", epochs=2, **kw):
    lm = dict(LM, attention=attention, **kw)
    model = LanguageModel(**lm)
    model.params = weights_sdar.make_tree(SEED, lm)
    model.compile(OPTIMIZER)
    hist = model.fit(_rows(), batch_size=2, epochs=epochs, shuffle=False)
    return model, hist.history


@pytest.mark.parametrize("attention", ["dot", "flash"])
def test_fit_follows_the_reference_loss_gradients_and_counters(attention):
    model, hist = _fit(attention)
    x = _rows()
    ref = sdar_moe.follow_steps(SEED, LM, EPS,
                                np.concatenate([x.reshape(2, 2, 32)] * 2),
                                OPTIMIZER)
    want = [np.mean(ref["losses"][:2]), np.mean(ref["losses"][2:])]
    np.testing.assert_allclose(hist["loss"], want, rtol=2e-5)
    copies = ref["copies"].sum(-1).reshape(2, 2, 2).mean(1)
    for layer in range(2):
        np.testing.assert_allclose(hist[f"moeHeldCopies_l{layer}"],
                                   copies[:, layer])
    np.testing.assert_allclose(
        hist["maskedPositions"], np.reshape(ref["masked"], (2, 2)).mean(1))
    # four AdamW steps on the reference's gradients end where fit ends,
    # a held expert a leaf
    key = weights_sdar.seed_key(SEED)
    moved = {}
    for path, shape, kind in weights_sdar.leaf_table(LM):
        node = model.params
        for part in path:
            node = node[part]
        moved["/".join(path)] = jnp.asarray(node) \
            - weights_sdar.make_leaf(key, path, shape, kind)
    moved = sdar_moe.leaf_norms(moved)
    assert "layer_1/moe/experts/w_down#3" in moved
    assert moved.keys() == ref["change_norm"].keys()
    for name, want in ref["change_norm"].items():
        np.testing.assert_allclose(moved[name], want, rtol=2e-2, atol=1e-7,
                                   err_msg=name)


def test_two_fits_of_one_call_draw_the_same_noise():
    _, a = _fit(epochs=1)
    _, b = _fit(epochs=1)
    assert a["loss"] == b["loss"]
    assert a["maskedPositions"] == b["maskedPositions"]
    t, masked = sdar_moe.step_noise(0, 0, 2, 32)
    t2, masked2 = sdar_moe.step_noise(0, 1, 2, 32)
    assert 0.1 <= float(t.min()) and float(t.max()) < 1.0
    assert not np.array_equal(masked, masked2)


def test_head_dim_and_qk_norm_against_the_reference():
    """A causal model of the same block (next-token objective): 4 heads
    of 16 on a 32-wide model, q and k normed per head."""
    lm = dict(LM, objective="next_token", experts_held=0, expert_offset=0)
    model = LanguageModel(**lm)
    model.params = weights_sdar.make_tree(SEED, lm)
    x = _rows(2, 16)
    got = model.predict(x, batch_size=2)
    flat = sdar_moe.flat_weights(SEED, lm)
    with jax.default_matmul_precision("highest"):
        for row in range(2):
            want = sdar_moe.causal_logits(flat, jnp.asarray(x[row]), lm, EPS)
            np.testing.assert_allclose(got[row], want, rtol=2e-4, atol=2e-4)
    assert model.params["layer_0"]["attn"]["q_proj"]["kernel"].shape == (32, 64)
    assert model.params["layer_0"]["attn"]["q_norm"]["scale"].shape == (16,)
    # without the norm the logits differ: the scale is not a no-op
    plain = LanguageModel(**dict(lm, qk_norm=False))
    plain.params = jax.tree_util.tree_map(lambda a: a, model.params)
    for layer in ("layer_0", "layer_1"):
        plain.params[layer]["attn"] = {
            k: v for k, v in plain.params[layer]["attn"].items()
            if k not in ("q_norm", "k_norm")}
    assert np.abs(plain.predict(x, batch_size=2) - got).max() > 1e-3


def test_a_saved_expert_model_loads_as_what_it_was(tmp_path):
    model, _ = _fit(epochs=1)
    os.makedirs(tmp_path / "artifact")
    model.__lo_save__(str(tmp_path / "artifact"))
    loaded = LanguageModel.__lo_load__(str(tmp_path / "artifact"))
    for key in ("head_dim", "qk_norm", "experts_held", "expert_offset",
                "objective", "block_length", "mask_token_id", "n_experts",
                "moe_k"):
        assert getattr(loaded, key) == getattr(model, key), key
    assert loaded.num_params() == model.num_params()
    a = model.evaluate(_rows(), batch_size=2)
    b = loaded.evaluate(_rows(), batch_size=2)
    assert a["loss"] == pytest.approx(b["loss"], rel=1e-6)
    with pytest.raises(NotImplementedError, match="denoising"):
        loaded.generate(_rows()[0, :4], max_new_tokens=4)
    with pytest.raises(NotImplementedError, match="denoising"):
        loaded.predict(_rows(), batch_size=2)


@pytest.mark.parametrize("bad,match", [
    ({"experts_held": 4, "expert_offset": 6}, "not among"),
    ({"objective": "denoise"}, "objective"),
    ({"head_dim": 15}, "head_dim"),
    ({"block_length": 5}, "whole blocks"),
    ({"attention": "ring"}, "dot and flash"),
    ({"mask_token_id": 64}, "inside the vocabulary")])
def test_settings_that_do_not_go_together_are_refused(bad, match):
    from learningorchestra_tpu.analysis import preflight

    with pytest.raises(ValueError, match=match):
        LanguageModel(**dict(LM, **bad))
    findings = preflight.check_model("learningorchestra_tpu.models",
                                     "LanguageModel", dict(LM, **bad))
    assert [f.rule for f in findings] == ["language-model-config"]
    assert preflight.check_model("learningorchestra_tpu.models",
                                 "LanguageModel", dict(LM)) == []


def test_flops_floor_counts_the_doubled_row_and_the_routed_share():
    model, _ = _fit(epochs=1)
    floor = model._get_engine()._flops_floor_fn
    bd = floor({"x": np.zeros((2, 32), np.int32)})
    causal = LanguageModel(**dict(LM, objective="next_token"))
    causal.params = model.params
    ar = causal._get_engine()._flops_floor_fn({"x": np.zeros((2, 32),
                                                            np.int32)})
    assert 1.5 * ar < bd < 2.0 * ar   # the head runs over L, not 2L


def test_counters_reach_the_epoch_record_and_the_epoch_end_span():
    from learningorchestra_tpu.observability import trace as obs_trace

    with obs_trace.span("job", trace="sdar_counters"):
        _, hist = _fit(epochs=1)
    ends = [s for s in obs_trace.spans_of("sdar_counters")
            if s.name == "epochEnd"]
    assert len(ends) == 1
    attrs = ends[0].attrs
    for key in ("moeHeldCopies_l0", "moeBusiestCopies_l1",
                "moeTilesUsed_l0", "maskedPositions"):
        assert attrs[key] == pytest.approx(hist[key][0], abs=1e-3), key
    # 2 rows of 64 positions, 2 choices each, 4 of 8 experts held
    assert 0 < attrs["moeBusiestCopies_l0"] <= attrs["moeHeldCopies_l0"] \
        <= 2 * 64 * 2
    # the copies in whole tiles, a tile at least for each held expert,
    # within the buffer's tiles
    tile, rows, _ = moe.buffer_shape(2 * 64, 2, 8, 4)
    assert max(4, attrs["moeHeldCopies_l0"] / tile) \
        <= attrs["moeTilesUsed_l0"] <= rows // tile


# ----------------------------------------------------------------------
# what a job builds (PERF.md section 6, PR 26: the run's time limit)
# ----------------------------------------------------------------------
def _job_spans(trace, **kw):
    from learningorchestra_tpu.observability import trace as obs_trace

    with obs_trace.span("job", trace=trace):
        model, _ = _fit(epochs=3, **kw)
    return model, obs_trace.spans_of(trace)


def test_a_fit_on_one_device_builds_its_epoch_program_once():
    """The optimizer's step count used to start as a single-device
    array and come back under the mesh's sharding, so the second epoch
    was traced and built again (PERF.md F7)."""
    _, spans = _job_spans("sdar_one_build")
    compiles = [s for s in spans if s.name == "compile"]
    assert [s.attrs["epoch"] for s in compiles] == [0]
    assert len([s for s in spans if s.name == "dispatch"]) == 3


def test_a_second_model_of_the_same_settings_runs_the_firsts_steps(
        monkeypatch):
    """A job's model is a new instance loaded from the artifact: with
    the settings, the optimizer and the LO_* environment equal it must
    find the first's jitted steps, and with one of them changed, not."""
    first, _ = _job_spans("sdar_shared_a")
    _, spans = _job_spans("sdar_shared_b")
    assert not [s for s in spans if s.name in ("compile", "measureFlops")]
    def key(optimizer=OPTIMIZER, **kw):
        model = LanguageModel(**dict(LM, **kw))
        model.compile(optimizer)
        return model._engine_cache_key()

    assert first._engine_cache_key() == key() != key(block_length=8)
    assert key(dict(OPTIMIZER, learning_rate=1e-3)) != key()
    before = key()
    monkeypatch.setenv("LO_LM_HEAD_CHUNK", "16")
    _, spans = _job_spans("sdar_shared_c")
    assert [s.attrs["epoch"] for s in spans if s.name == "compile"] == [0]
    assert key() != before


def test_loading_an_artifact_initialises_no_parameters(tmp_path,
                                                       monkeypatch):
    """``paramInit`` is the tree's structure alone: every leaf comes
    from the file (an eager initialisation was some hundred small
    programs on the chip, to be overwritten)."""
    from learningorchestra_tpu.observability import trace as obs_trace

    model, _ = _fit(epochs=1)
    os.makedirs(tmp_path / "artifact")
    model.__lo_save__(str(tmp_path / "artifact"))

    def refuse(self, sample):
        raise AssertionError("parameters initialised to be overwritten")

    monkeypatch.setattr(LanguageModel, "_build_params", refuse)
    with obs_trace.span("job", trace="sdar_load"):
        loaded = LanguageModel.__lo_load__(str(tmp_path / "artifact"))
    names = [s.name for s in obs_trace.spans_of("sdar_load")]
    assert "paramInit" in names and "weightsRead" in names
    want = jax.tree_util.tree_leaves_with_path(model.params)
    got = jax.tree_util.tree_leaves_with_path(loaded.params)
    assert [p for p, _ in got] == [p for p, _ in want]
    for (_, a), (_, b) in zip(got, want):
        assert isinstance(a, np.ndarray)
        np.testing.assert_array_equal(a, np.asarray(b))
