"""The plain reference of the hybrid state-space stack
(``benchmark/reference/granite_hybrid.py``) and its seeded weights
(``benchmark/weights_granite.py``), checked for what they are on their
own: the recurrence against a sum written out by hand, the weights'
ranges, the leaf table against the program's tree at the cell's size,
and each planted fault changing what it is for. That the PROGRAM follows
the reference is ``tests/test_granite_hybrid.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, weights_granite
from benchmark.reference import granite_hybrid as gh

CONFIG = harness.load_json("configs", "granite-4.0-h-micro-l10.json")
FULL = CONFIG["language_model"]
TINY = harness.load_json("traffic", "train-ssm-seq8k.json")[
    "rehearsal"]["language_model"]
EPS = float(CONFIG["rms_norm_eps"])
SEED = 2**31 + 32
OPTIMIZER = {"learning_rate": 3e-4, "weight_decay": 1e-4}


def test_recurrence_is_the_sum_written_out():
    """y_t = sum_{j<=t} (prod_{j<k<=t} a_k) (C_t . B_j) u_j."""
    rng = np.random.default_rng(0)
    s, heads, p, n = 7, 2, 3, 4
    u = rng.normal(size=(s, heads, p)).astype(np.float32)
    a = rng.uniform(0.5, 1.0, size=(s, heads)).astype(np.float32)
    B = rng.normal(size=(s, n)).astype(np.float32)
    C = rng.normal(size=(s, n)).astype(np.float32)
    y, state = gh.recurrence(jnp.asarray(u), jnp.asarray(a), jnp.asarray(B),
                             jnp.asarray(C), jnp.ones((s,)))
    want = np.zeros((s, heads, p))
    for t in range(s):
        for j in range(t + 1):
            decay = np.prod(a[j + 1:t + 1], axis=0)          # (heads,)
            want[t] += (C[t] @ B[j]) * decay[:, None] * u[j]
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    held = sum(np.prod(a[j + 1:], axis=0)[:, None, None]
               * u[j][:, :, None] * B[j][None, None, :] for j in range(s))
    np.testing.assert_allclose(state, held, rtol=1e-4, atol=1e-5)
    # keep = 0 at a position starts the state from nought there
    keep = jnp.asarray([1, 1, 1, 0, 1, 1, 1], jnp.float32)
    y_cut, _ = gh.recurrence(jnp.asarray(u), jnp.asarray(a), jnp.asarray(B),
                             jnp.asarray(C), keep)
    y_tail, _ = gh.recurrence(jnp.asarray(u[3:]), jnp.asarray(a[3:]),
                              jnp.asarray(B[3:]), jnp.asarray(C[3:]),
                              jnp.ones((4,)))
    np.testing.assert_allclose(y_cut[3:], y_tail, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y_cut[:3], y[:3], rtol=1e-6)


def test_recurrence_blocks_change_nothing(monkeypatch):
    """The blocks are the backward's rematerialisation alone."""
    rng = np.random.default_rng(1)
    args = [jnp.asarray(rng.normal(size=sh).astype(np.float32))
            for sh in ((19, 2, 3), (19, 2), (19, 4), (19, 4))]
    args[1] = jax.nn.sigmoid(args[1])

    def loss(u):
        y, _ = gh.recurrence(u, *args[1:], jnp.ones((19,)))
        return jnp.sum(y * y), y

    (_, y), g = jax.value_and_grad(loss, has_aux=True)(args[0])
    monkeypatch.setattr(gh, "SCAN_BLOCK", 4)
    (_, y4), g4 = jax.value_and_grad(loss, has_aux=True)(args[0])
    np.testing.assert_allclose(y4, y, rtol=1e-6)
    np.testing.assert_allclose(g4, g, rtol=1e-5, atol=1e-6)


def test_leaf_table_is_the_programs_tree_at_the_cells_size():
    """772.2 M parameters, leaf for leaf what ``LanguageModel`` builds
    from the configuration's ``language_model`` (shapes alone)."""
    from learningorchestra_tpu.models import LanguageModel

    lm = LanguageModel(**FULL)
    shapes = jax.eval_shape(lambda: lm.module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"]
    got = {"/".join(str(k.key) for k in path): leaf.shape for path, leaf in
           jax.tree_util.tree_flatten_with_path(shapes)[0]}
    table = {"/".join(p): shape
             for p, shape, _ in weights_granite.leaf_table(FULL)}
    assert got == table
    total = sum(int(np.prod(s)) for s in table.values())
    assert total == 772_160_448
    assert [i for i, t in enumerate(FULL["layer_types"])
            if t == "attention"] == [5]
    assert table["layer_0/ssm/in_proj/kernel"] == (2048, 8512)
    assert table["layer_5/attn/k_proj/kernel"] == (2048, 512)
    assert "lm_head/kernel" not in table


def test_configuration_holds_the_sources_widths():
    """Every width as published; the three cuts and nothing else."""
    assert set(CONFIG["reduced"]) == {"num_hidden_layers", "vocab_size",
                                      "max_position_embeddings"}
    assert CONFIG["published"] == {"num_hidden_layers": 40,
                                   "vocab_size": 100352,
                                   "max_position_embeddings": 131072}
    for key, value in (("hidden_size", 2048), ("intermediate_size", 8192),
                       ("shared_intermediate_size", 8192),
                       ("mamba_n_heads", 64), ("mamba_d_head", 64),
                       ("mamba_d_state", 128), ("mamba_d_conv", 4),
                       ("mamba_chunk_size", 256), ("mamba_expand", 2),
                       ("num_attention_heads", 32),
                       ("num_key_value_heads", 8),
                       ("attention_multiplier", 0.015625),
                       ("embedding_multiplier", 12),
                       ("residual_multiplier", 0.22), ("logits_scaling", 8),
                       ("rms_norm_eps", 1e-5),
                       ("tie_word_embeddings", True),
                       ("position_embedding_type", "nope")):
        assert CONFIG[key] == value, key
    assert CONFIG["vocab_size"] * 8 == 100352
    lm = FULL
    assert (lm["d_model"], lm["d_ff"], lm["ssm_heads"], lm["ssm_head_dim"],
            lm["ssm_state"], lm["ssm_conv"], lm["ssm_chunk"], lm["n_heads"],
            lm["n_kv_heads"], lm["head_dim"]) == (2048, 8192, 64, 64, 128,
                                                  4, 256, 32, 8, 64)
    # the source's whole list of 40 is kept; the first ten are run
    assert len(CONFIG["layer_types"]) == 40
    assert lm["layer_types"] == CONFIG["layer_types"][:10] \
        == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert (lm["attention_scale"], lm["embedding_multiplier"],
            lm["residual_multiplier"], lm["logits_scaling"],
            lm["rms_norm_eps"]) == (0.015625, 12.0, 0.22, 8.0, 1e-5)
    assert lm["remat"] == "full" and lm["tie_embeddings"] is True
    assert CONFIG["departures"] and CONFIG["assumed"]


def test_seeded_weights_make_the_scan_matter():
    """dt after the softplus 0.001 to 0.1, exp(A_log) 1 to 16, the dt
    columns of in_proj a quarter of a kernel's size; a leaf depends on
    the seed and its path alone, and a seed past 2**31 is taken."""
    key = weights_granite.seed_key(SEED)
    table = {"/".join(p): (p, shape, kind)
             for p, shape, kind in weights_granite.leaf_table(FULL)}
    dt = jax.nn.softplus(weights_granite.make_leaf(
        key, *table["layer_3/ssm/dt_bias"]))
    a = jnp.exp(weights_granite.make_leaf(key, *table["layer_3/ssm/A_log"]))
    assert 1e-3 <= float(dt.min()) and float(dt.max()) <= 1e-1
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    assert float(dt.max() / dt.min()) > 10       # the heads differ
    decay = jnp.exp(-a * dt)
    assert 0.3 < float(decay.mean()) < 0.999
    # a head's memory, 1 / (A dt), from a few positions to hundreds
    memory = 1.0 / (a * dt)
    assert float(memory.min()) < 10 and float(memory.max()) > 100
    assert int(jnp.sum(memory > 64)) >= 4
    tiny = {"/".join(p): (p, shape, kind)
            for p, shape, kind in weights_granite.leaf_table(TINY)}
    w = weights_granite.make_leaf(key, *tiny["layer_0/ssm/in_proj/kernel"])
    heads = TINY["ssm_heads"]
    assert float(jnp.std(w[:, -heads:])) == pytest.approx(
        0.25 * float(jnp.std(w[:, :-heads])), rel=0.2)
    tree = weights_granite.make_tree(SEED, TINY)
    np.testing.assert_array_equal(tree["layer_0"]["ssm"]["in_proj"]["kernel"],
                                  w)
    other = weights_granite.make_leaf(weights_granite.seed_key(SEED + 1),
                                      *tiny["layer_0/ssm/in_proj/kernel"])
    assert float(jnp.max(jnp.abs(other - w))) > 0.1


@pytest.fixture(scope="module")
def steps():
    rows = np.random.default_rng(3).integers(
        1, TINY["vocab_size"], size=(3, 1, 32)).astype(np.int32)
    batches = np.concatenate([rows] * 2)
    follow = lambda **kw: gh.follow_steps(  # noqa: E731
        SEED, TINY, EPS, batches, OPTIMIZER, **kw)
    return follow, follow()


def test_follow_steps_reports_every_leaf_and_mamba_layer(steps):
    _, ref = steps
    names = {"/".join(p) for p, _, _ in weights_granite.leaf_table(TINY)}
    assert set(ref["mu_norm"]) == set(ref["change_norm"]) == names
    assert ref["mamba_layers"] == [0, 2, 3]
    assert np.asarray(ref["state_rms"]).shape == (6, 3)
    assert (np.asarray(ref["state_rms"]) > 0).all()
    decay = np.asarray(ref["decay_mean"])
    assert ((decay > 0.3) & (decay < 0.999)).all()
    assert len(ref["losses"]) == 6 and ref["losses"][3] < ref["losses"][0]
    assert all(v > 0 for v in ref["change_norm"].values())


def test_a_frozen_state_changes_nothing(steps):
    follow, ref = steps
    frozen = follow(freeze=True)
    assert max(frozen["change_norm"].values()) == 0.0
    assert frozen["losses"][:3] == pytest.approx(frozen["losses"][3:])
    assert frozen["losses"][0] == pytest.approx(ref["losses"][0])


@pytest.mark.parametrize("fault,moves,leaves", [
    ("half", "losses", "state_rms"),
    ("drop_state", "state_rms", None),
    ("no_gate", "losses", None)])
def test_a_planted_fault_moves_what_it_is_for(steps, fault, moves, leaves):
    follow, ref = steps
    alt = follow(fault=fault)
    gap = np.max(np.abs(np.asarray(alt[moves][:3]) / np.asarray(
        ref[moves][:3]) - 1.0))
    assert gap > (0.05 if moves == "state_rms" else 1e-4), gap
    if leaves:   # the first step's states do not know of the loss
        np.testing.assert_allclose(alt[leaves][0], ref[leaves][0],
                                   rtol=1e-6)
    with pytest.raises(ValueError):
        follow(fault="no_such_fault")


def test_lower_precisions_are_farther_from_the_reference(steps):
    follow, ref = steps
    gaps = {}
    for precision in ("bf16", "fp8"):
        alt = follow(precision=precision)
        gaps[precision] = abs(np.mean(alt["losses"][3:])
                              / np.mean(ref["losses"][3:]) - 1.0)
    assert 0 < gaps["bf16"] < gaps["fp8"]
