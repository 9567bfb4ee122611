"""The hybrid state-space stack (granite-4.0-h-micro; docs/STATE_SPACE.md)
at a small size on the CPU with the seed's weights: the chunked scan of
``ops/ssd.py`` (plain ``jax.numpy`` and the Pallas kernels in interpret
mode) against the recurrence stepped a position at a time; the Mamba-2
mixer and a two-period hybrid ``LanguageModel`` against the plain
reference (``benchmark/reference/granite_hybrid.py``); and what the new
settings leave alone: an all-attention model is the model of before.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_granite
from benchmark.reference import granite_hybrid
from learningorchestra_tpu.models import LanguageModel
from learningorchestra_tpu.models import transformer as tlm
from learningorchestra_tpu.ops import ssd as ssd_ops

SEED = 3200000007
EPS = 1e-5
PERIOD = ["mamba", "mamba", "attention", "mamba"]
LM = dict(vocab_size=96, d_model=32, n_layers=8, n_heads=4, n_kv_heads=2,
          head_dim=8, d_ff=48, max_len=32, attention="dot",
          layer_types=PERIOD * 2, ssm_heads=4, ssm_head_dim=16,
          ssm_state=8, ssm_conv=4, ssm_chunk=8, rms_norm_eps=EPS,
          position_embedding="nope", attention_scale=0.125,
          embedding_multiplier=12.0, residual_multiplier=0.22,
          logits_scaling=8.0, tie_embeddings=True, aux_coef=0.0,
          head_chunk=16, remat="full")
OPTIMIZER = {"kind": "adamw", "learning_rate": 3e-4, "weight_decay": 1e-4}


@pytest.fixture(autouse=True)
def _one_device_float32(tmp_path):
    """One device (the reference's rows are the step's rows), float32
    compute."""
    from learningorchestra_tpu import config as config_mod

    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo"), mesh_shape="dp=1",
        compute_dtype="float32"))
    yield
    config_mod.reset_config()


def _rows(n, seq, seed=0, vocab=96):
    return np.random.default_rng(seed).integers(
        1, vocab, size=(n, seq)).astype(np.int32)


# ----------------------------------------------------------------------
# ops/ssd.py
# ----------------------------------------------------------------------
def _scan_inputs(b, s, heads, p, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, s, heads, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, heads)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0,
                                    maxval=2.7))
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    D = jax.random.normal(ks[5], (heads,))
    return x, dt, A, B, C, D


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize(
    "seq,heads,p", [(32, 4, 8), (27, 4, 8), (5, 4, 8), (32, 16, 16),
                    (27, 16, 64)],
    ids=["whole_chunks", "ragged", "under_a_chunk",
         "two_head_blocks_of_16", "two_head_blocks_of_head_pairs"])
def test_chunked_scan_matches_the_stepped_recurrence(impl, seq, heads, p):
    """Values, the state held at the row's end and every gradient, at
    lengths that are and are not a multiple of the chunk; the kernels
    run in interpret mode. 16 heads make two blocks of 8 (x's lanes
    taken a block at a time): 8 heads of 16 share a lane tile, heads of
    64 go in pairs (the cell's width)."""
    args = _scan_inputs(2, seq, heads, p, 16)
    w = jax.random.normal(jax.random.PRNGKey(9), (2, seq, heads, p))

    def chunked(*a):
        return ssd_ops.ssd(*a, chunk=8, impl=impl, interpret=True)

    def loss(fn, *a):
        return jnp.sum(fn(*a)[0] * w)

    y_ref, s_ref = ssd_ops.ssd_recurrence(*args)
    y, state = chunked(*args)
    np.testing.assert_allclose(y, y_ref, atol=2e-5)
    np.testing.assert_allclose(state, s_ref, atol=2e-5)
    want = jax.grad(lambda *a: loss(ssd_ops.ssd_recurrence, *a),
                    argnums=range(6))(*args)
    got = jax.grad(lambda *a: loss(chunked, *a), argnums=range(6))(*args)
    for g, r, name in zip(got, want, "x dt A B C D".split()):
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(g, r, atol=2e-5 * scale, err_msg=name)


def test_the_scans_state_carries_no_gradient_and_auto_is_jnp_here():
    args = _scan_inputs(1, 16, 2, 4, 4)
    g = jax.grad(lambda x: jnp.sum(ssd_ops.ssd(x, *args[1:], chunk=8)[1]))(
        args[0])
    assert float(jnp.max(jnp.abs(g))) == 0.0
    assert ssd_ops.resolve_impl("auto") == "jnp"    # the CPU backend
    with pytest.raises(ValueError):
        ssd_ops.resolve_impl("cuda")


# ----------------------------------------------------------------------
# the mixer and the model against the plain reference
# ----------------------------------------------------------------------
def test_mamba2_mixer_matches_the_reference_forward_and_gradients():
    flat = granite_hybrid.flat_weights(SEED, LM)
    w = granite_hybrid.layer_weights(flat, 0, "mamba")
    params = {"in_proj": {"kernel": w["in_proj"]},
              "conv_kernel": w["conv_kernel"], "conv_bias": w["conv_bias"],
              "dt_bias": w["dt_bias"], "A_log": w["A_log"], "D": w["D"],
              "norm": {"scale": w["gate_norm"]},
              "out_proj": {"kernel": w["out_proj"]}}
    mixer = tlm._Mamba2(4, 16, 8, 4, 8, eps=EPS)
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 27, 32))
    t = jax.random.normal(jax.random.PRNGKey(4), (2, 27, 32))

    def program(p, u_):
        out, stats = mixer.apply({"params": p}, u_)
        return jnp.sum(out * t), (out, stats)

    def reference(p_, u_):
        ws = dict(w, in_proj=p_["in_proj"]["kernel"], A_log=p_["A_log"],
                  dt_bias=p_["dt_bias"], conv_kernel=p_["conv_kernel"])
        outs = [granite_hybrid.mamba(row, ws, LM, EPS, None) for row in u_]
        out = jnp.stack([o[0] for o in outs])
        return jnp.sum(out * t), (out, outs)

    with jax.default_matmul_precision("highest"):
        (_, (out, stats)), g = jax.value_and_grad(
            program, argnums=(0, 1), has_aux=True)(params, u)
        (_, (want, rows)), g_ref = jax.value_and_grad(
            reference, argnums=(0, 1), has_aux=True)(params, u)
    np.testing.assert_allclose(out, want, atol=2e-5)
    rms = np.sqrt(np.mean([np.mean(np.square(r[1])) for r in rows]))
    assert float(stats[0]) == pytest.approx(rms, rel=1e-4)
    assert float(stats[1]) == pytest.approx(
        np.mean([float(r[2]) for r in rows]), rel=1e-5)
    np.testing.assert_allclose(g[1], g_ref[1], atol=2e-5)
    for name in ("A_log", "dt_bias", "conv_kernel"):
        scale = float(jnp.max(jnp.abs(g_ref[0][name])))
        np.testing.assert_allclose(g[0][name], g_ref[0][name],
                                   atol=1e-4 * scale, err_msg=name)
    np.testing.assert_allclose(g[0]["in_proj"]["kernel"],
                               g_ref[0]["in_proj"]["kernel"], atol=2e-5)


@pytest.fixture(scope="module")
def fitted():
    """A two-period hybrid ``LanguageModel`` from the seed's weights
    after two epochs of three AdamW steps, and the reference's account
    of the same steps."""
    from learningorchestra_tpu import config as config_mod

    config_mod.set_config(config_mod.Config(mesh_shape="dp=1",
                                            compute_dtype="float32"))
    try:
        lm = LanguageModel(**LM)
        lm.params = weights_granite.make_tree(SEED, LM)
        start = jax.tree_util.tree_map(np.asarray, lm.params)
        rows = _rows(6, 27)
        lm.compile(OPTIMIZER)
        history = lm.fit(rows, batch_size=2, epochs=2, shuffle=False)
        batches = np.concatenate([rows.reshape(3, 2, 27)] * 2)
        ref = granite_hybrid.follow_steps(SEED, LM, EPS, batches, OPTIMIZER)
        return lm, start, history.history, ref, rows
    finally:
        config_mod.reset_config()


def test_hybrid_logits_match_the_reference():
    lm = LanguageModel(**LM)
    lm.params = weights_granite.make_tree(SEED, LM)
    rows = _rows(2, 27, seed=5)
    flat = granite_hybrid.flat_weights(SEED, LM)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([granite_hybrid.forward_logits(
            flat, jnp.asarray(r), LM, EPS) for r in rows])
        got = lm.module.apply({"params": lm.params}, jnp.asarray(rows))[0]
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert lm.predict(rows, batch_size=2).shape == (2, 27, 96)


def test_hybrid_fit_follows_the_reference_over_two_epochs(fitted):
    lm, start, history, ref, _ = fitted
    want = [np.mean(ref["losses"][:3]), np.mean(ref["losses"][3:])]
    np.testing.assert_allclose(history["loss"], want, rtol=2e-6)
    layers = ref["mamba_layers"]
    assert layers == [0, 1, 3, 4, 5, 7]
    for j, layer in enumerate(layers):
        for name, key in (("state_rms", "ssmStateRms"),
                          ("decay_mean", "ssmDecayMean")):
            steps = np.asarray(ref[name])[:, j]
            np.testing.assert_allclose(
                history[f"{key}_l{layer}"],
                [steps[:3].mean(), steps[3:].mean()], rtol=2e-4)
        assert 0.3 < history[f"ssmDecayMean_l{layer}"][0] < 0.999
    assert "ssmStateRms_l2" not in history          # an attention layer
    flat = jax.tree_util.tree_flatten_with_path(lm.params)[0]
    for path, leaf in flat:
        name = "/".join(str(k.key) for k in path)
        node = start
        for k in path:
            node = node[k.key]
        change = float(np.sqrt(np.sum(np.square(
            np.asarray(leaf, np.float64) - node))))
        assert change == pytest.approx(ref["change_norm"][name], rel=2e-3), \
            name


def test_parameter_tree_is_the_leaf_table_and_counts(fitted):
    lm = fitted[0]
    table = {"/".join(p): shape
             for p, shape, _ in weights_granite.leaf_table(LM)}
    flat = {"/".join(str(k.key) for k in path): leaf.shape
            for path, leaf in
            jax.tree_util.tree_flatten_with_path(lm.params)[0]}
    assert flat == table
    assert "lm_head" not in lm.params              # one table
    fresh = LanguageModel(**LM)
    fresh._build_params(_rows(1, 8))
    assert jax.tree_util.tree_structure(fresh.params) == \
        jax.tree_util.tree_structure(lm.params)
    a_log = np.asarray(fresh.params["layer_0"]["ssm"]["A_log"])
    assert (np.exp(a_log) >= 1.0).all() and (np.exp(a_log) <= 16.0).all()


def test_tied_table_takes_its_gradient_from_both_ends():
    """One table: the lookup's gradient and the head's add up."""
    lm = LanguageModel(**LM)
    params = weights_granite.make_tree(SEED, LM)
    rows = jnp.asarray(_rows(2, 16, seed=3))
    module = lm._module_for(16)
    loss_fn = tlm.next_token_loss(0.0, head_chunk=16)

    def loss(p, head_table=None):
        out = module.apply({"params": p}, rows, train=True)
        assert isinstance(out, tlm.FusedHeadOut)
        if head_table is not None:
            out = out._replace(kernel=head_table.T)
        return loss_fn(out, {"x": rows}, None)[0]

    table = params["embed"]["embedding"]
    whole = jax.grad(loss)(params)["embed"]["embedding"]
    lookup = jax.grad(lambda p: loss(p, jax.lax.stop_gradient(table)))(
        params)["embed"]["embedding"]
    head = jax.grad(lambda t: loss(params, t))(table)
    assert float(jnp.max(jnp.abs(lookup))) > 0
    assert float(jnp.max(jnp.abs(head))) > 0
    np.testing.assert_allclose(whole, lookup + head, atol=1e-6)
    # ids the rows never show get their gradient from the head alone
    unseen = np.setdiff1d(np.arange(96), np.asarray(rows))
    assert float(jnp.max(jnp.abs(lookup[unseen]))) == 0.0
    assert float(jnp.max(jnp.abs(whole[unseen]))) > 0.0


# ----------------------------------------------------------------------
# what the new settings leave alone
# ----------------------------------------------------------------------
DENSE = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
             d_ff=48, max_len=16, attention="dot")


def test_all_attention_layer_types_is_the_model_of_before():
    rows = _rows(4, 16, seed=2, vocab=64)
    plain = LanguageModel(**DENSE)
    typed = LanguageModel(**DENSE, layer_types=["attention"] * 2)
    h_plain = plain.fit(rows, batch_size=2, epochs=2, shuffle=False)
    h_typed = typed.fit(rows, batch_size=2, epochs=2, shuffle=False)
    assert h_plain.history["loss"] == h_typed.history["loss"]
    assert jax.tree_util.tree_structure(plain.params) == \
        jax.tree_util.tree_structure(typed.params)
    for a, b in zip(jax.tree_util.tree_leaves(plain.params),
                    jax.tree_util.tree_leaves(typed.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert sorted(plain.params["layer_0"]) == ["attn", "attn_norm", "mlp",
                                               "mlp_norm"]
    assert "lm_head" in plain.params
    out = typed.generate(rows[:1, :4], max_new_tokens=2)
    np.testing.assert_array_equal(
        out, plain.generate(rows[:1, :4], max_new_tokens=2))


def test_the_defaults_trace_the_program_of_before():
    """Every new setting at its default leaves the jitted step's HLO as
    it was: the same text as with the settings spelled out."""
    rows = jnp.asarray(_rows(2, 16, seed=2, vocab=64))

    def text(**kw):
        lm = LanguageModel(**DENSE, **kw)
        module = lm._module_for(16)
        params = jax.eval_shape(lambda: module.init(
            jax.random.PRNGKey(0), rows[:1], train=False))["params"]
        return jax.jit(lambda p: module.apply({"params": p}, rows)[0]).lower(
            params).as_text()

    assert text() == text(rms_norm_eps=1e-6, position_embedding="rope",
                          attention_scale=0.0, embedding_multiplier=1.0,
                          residual_multiplier=1.0, logits_scaling=1.0,
                          tie_embeddings=False, layer_types=None)
    assert text() != text(rms_norm_eps=1e-5)
    assert text() != text(position_embedding="nope")


@pytest.mark.parametrize("call", [
    lambda lm, x: lm.generate(x[:1, :4], max_new_tokens=2),
    lambda lm, x: lm.generate(x[:1, :4], max_new_tokens=2, num_beams=2),
    lambda lm, x: lm.serve_fns(2, 16, 0.0),
    lambda lm, x: lm.serve_fns_paged(2, 16, 8, 8, 0.0),
    lambda lm, x: lm.serve_fns_spec(2, 16, 8, 8, 2, 0.0),
    lambda lm, x: lm.serve_fns_draft(2, 16, 2),
], ids=["generate", "beam", "serve", "serve_paged", "serve_spec",
        "serve_draft"])
def test_decoding_a_model_with_mamba_layers_raises(fitted, call):
    lm, rows = fitted[0], fitted[4]
    with pytest.raises(NotImplementedError, match="Mamba-2 layers"):
        call(lm, rows)


def test_decoding_raises_for_the_settings_the_decode_paths_lack():
    lm = LanguageModel(**DENSE, position_embedding="nope",
                       logits_scaling=8.0)
    lm.fit(_rows(2, 16, vocab=64), batch_size=2, epochs=1)
    with pytest.raises(NotImplementedError, match="position_embedding"):
        lm.generate(_rows(1, 4, vocab=64), max_new_tokens=2)
    assert lm.predict(_rows(2, 16, vocab=64), batch_size=2).shape == \
        (2, 16, 64)


def test_bad_layer_specs_are_refused_at_construction():
    with pytest.raises(ValueError, match="layer_types"):
        LanguageModel(**DENSE, layer_types=["attention"])
    with pytest.raises(ValueError, match="layer_types"):
        LanguageModel(**DENSE, layer_types=["attention", "lstm"])
    with pytest.raises(ValueError, match="ssm_heads"):
        LanguageModel(**DENSE, layer_types=["mamba", "attention"])
    with pytest.raises(ValueError, match="position_embedding"):
        LanguageModel(**DENSE, position_embedding="alibi")


def test_save_and_load_keep_the_new_settings(fitted, tmp_path):
    lm, rows = fitted[0], fitted[4]
    path = tmp_path / "artifact"
    path.mkdir()
    lm.__lo_save__(str(path))
    loaded = LanguageModel.__lo_load__(str(path))
    for key in LanguageModel._CONFIG_KEYS:
        assert getattr(loaded, key) == getattr(lm, key), key
    assert loaded.layer_types == tuple(PERIOD * 2)
    assert loaded.has_mamba and loaded.tie_embeddings
    for a, b in zip(jax.tree_util.tree_leaves(lm.params),
                    jax.tree_util.tree_leaves(loaded.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert loaded.evaluate(rows, batch_size=2)["loss"] == pytest.approx(
        lm.evaluate(rows, batch_size=2)["loss"], rel=1e-6)
    assert loaded._engine_cache_key() == lm._engine_cache_key()


def test_decay_parameters_stay_float32_under_a_bf16_step():
    """The engine casts every floating leaf to the compute dtype but a
    Mamba-2 mixer's ``A_log`` and ``dt_bias``."""
    from learningorchestra_tpu import config as config_mod

    config_mod.set_config(config_mod.Config(mesh_shape="dp=1",
                                            compute_dtype="bfloat16"))
    lm = LanguageModel(**LM)
    lm.params = weights_granite.make_tree(SEED, LM)
    cast = lm._get_engine()._cast(lm.params)
    ssm = cast["layer_0"]["ssm"]
    assert ssm["A_log"].dtype == jnp.float32
    assert ssm["dt_bias"].dtype == jnp.float32
    assert ssm["in_proj"]["kernel"].dtype == jnp.bfloat16
    assert cast["embed"]["embedding"].dtype == jnp.bfloat16
    dense = LanguageModel(**DENSE)
    assert dense._get_engine()._float32_leaves == ()
