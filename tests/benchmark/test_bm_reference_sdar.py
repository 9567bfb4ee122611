"""``benchmark/reference/sdar_moe.py`` against a tiny case written out
by hand (numpy, loops over positions, heads and experts), its mask and
noise against their text, and its faults against itself."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sdar_moe

LM = {"vocab_size": 12, "d_model": 4, "n_layers": 1, "n_heads": 2,
      "n_kv_heads": 1, "head_dim": 2, "d_ff": 3, "n_experts": 4,
      "moe_k": 2, "experts_held": 2, "expert_offset": 1,
      "rope_base": 100.0, "block_length": 2, "mask_token_id": 11}
EPS = 1e-6
SEED = 26


def _by_hand_layer(x, w, mask, positions):
    """One layer, a position at a time."""
    s, d = x.shape
    heads, kv, hd = 2, 1, 2

    def norm(v, scale):
        return v / math.sqrt(float(np.mean(v * v)) + EPS) * scale

    def rot(v, pos):
        ang = pos / (100.0 ** (0.0 / 1.0))   # hd 2: one pair, freq 1
        return np.array([v[0] * math.cos(ang) - v[1] * math.sin(ang),
                         v[0] * math.sin(ang) + v[1] * math.cos(ang)])

    q = np.zeros((s, heads, hd)); k = np.zeros((s, kv, hd))
    v = np.zeros((s, kv, hd))
    for i in range(s):
        u = norm(x[i], w["attn_norm"])
        qi = (u @ w["q_proj"]).reshape(heads, hd)
        ki = (u @ w["k_proj"]).reshape(kv, hd)
        v[i] = (u @ w["v_proj"]).reshape(kv, hd)
        for h in range(heads):
            q[i, h] = rot(norm(qi[h], w["q_norm"]), positions[i])
        k[i, 0] = rot(norm(ki[0], w["k_norm"]), positions[i])
    out = np.zeros((s, d))
    for i in range(s):
        o = np.zeros((heads, hd))
        for h in range(heads):
            keys = [j for j in range(s) if mask[i, j]]
            sc = np.array([q[i, h] @ k[j, 0] / math.sqrt(hd) for j in keys])
            p = np.exp(sc - sc.max()); p /= p.sum()
            o[h] = sum(pj * v[j, 0] for pj, j in zip(p, keys))
        hres = x[i] + o.reshape(-1) @ w["o_proj"]
        z = norm(hres, w["mlp_norm"])
        logits = z @ w["router"]
        probs = np.exp(logits - logits.max()); probs /= probs.sum()
        top = np.argsort(-probs)[:2]
        y = hres.copy()
        for e in top:
            if 1 <= e < 3:   # held: experts 1 and 2
                g = z @ w["w_gate"][e - 1]
                act = g / (1.0 + np.exp(-g)) * (z @ w["w_up"][e - 1])
                y += probs[e] / probs[top].sum() * (act @ w["w_down"][e - 1])
        out[i] = y
    return out


def test_the_block_against_a_case_worked_by_hand():
    flat = sdar_moe.flat_weights(SEED, LM)
    w = sdar_moe.layer_weights(flat, 0)
    seq = 4
    mask = sdar_moe.visible(seq, 2)
    positions = np.tile(np.arange(seq), 2)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (2 * seq, 4)),
                   np.float64)
    with jax.default_matmul_precision("highest"):
        got, counts = sdar_moe.block(jnp.asarray(x, jnp.float32), w, LM,
                                     EPS, None, mask, jnp.asarray(positions))
    want = _by_hand_layer(x, {k: np.asarray(v, np.float64)
                              for k, v in w.items()}, np.asarray(mask),
                          positions)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert counts.shape == (2,) and 0 < int(counts.sum()) <= 2 * 2 * seq


def test_the_mask_by_hand():
    # L = 4, blocks of 2: rows/columns n0 n1 n2 n3 | c0 c1 c2 c3
    want = np.array([
        [1, 1, 0, 0, 0, 0, 0, 0],
        [1, 1, 0, 0, 0, 0, 0, 0],
        [0, 0, 1, 1, 1, 1, 0, 0],
        [0, 0, 1, 1, 1, 1, 0, 0],
        [0, 0, 0, 0, 1, 1, 0, 0],
        [0, 0, 0, 0, 1, 1, 0, 0],
        [0, 0, 0, 0, 1, 1, 1, 1],
        [0, 0, 0, 0, 1, 1, 1, 1]], bool)
    np.testing.assert_array_equal(sdar_moe.visible(4, 2), want)


def test_the_noise_is_a_function_of_seed_step_row_and_position():
    t, masked = sdar_moe.step_noise(0, 3, 4, 64)
    again_t, again = sdar_moe.step_noise(0, 3, 4, 64)
    np.testing.assert_array_equal(masked, again)
    np.testing.assert_array_equal(t, again_t)
    # a row's noise does not depend on the rows beside it
    t2, two = sdar_moe.step_noise(0, 3, 2, 64)
    np.testing.assert_array_equal(masked[:2], two)
    assert not np.array_equal(masked, sdar_moe.step_noise(1, 3, 4, 64)[1])
    assert not np.array_equal(masked, sdar_moe.step_noise(0, 4, 4, 64)[1])
    assert np.all(np.asarray(t) >= 0.1) and np.all(np.asarray(t) < 1.0)
    # the masked share follows t
    big = sdar_moe.step_noise(5, 0, 8, 4096)
    np.testing.assert_allclose(np.asarray(big[1]).mean(1), big[0], atol=0.03)


def test_row_loss_by_hand():
    """One row: (1/t) times the cross-entropies of the masked
    positions, logits taken from the noisy half at the same position."""
    flat = sdar_moe.flat_weights(SEED, LM)
    x0 = jnp.asarray([3, 5, 7, 2], jnp.int32)
    masked = jnp.asarray([True, False, False, True])
    t = 0.5
    with jax.default_matmul_precision("highest"):
        got, _ = sdar_moe.row_loss(flat, x0, t, masked, LM, EPS, None)
        xt = jnp.where(masked, 11, x0)
        hidden, _ = sdar_moe.hidden_states(
            flat, jnp.concatenate([xt, x0]), LM, EPS, None,
            sdar_moe.visible(4, 2), jnp.tile(jnp.arange(4), 2))
        logits = np.asarray(hidden[:4] @ flat["lm_head/kernel"], np.float64)
    ce = [math.log(np.exp(logits[i]).sum()) - logits[i, int(x0[i])]
          for i in (0, 3)]
    assert float(got) == pytest.approx(sum(ce) / t, rel=1e-5)


@pytest.mark.parametrize("fault", [{"rows": [0]}, {"drop_expert": 0},
                                   {"precision": "fp8"}],
                         ids=["half_batch", "dropped_expert", "fp8"])
def test_a_planted_fault_changes_the_step(fault):
    # two layers: in one, every masked position (one embedding) meets
    # the same experts, and a dropped one may be none of them
    lm = dict(LM, d_model=8, head_dim=4, d_ff=6, n_layers=2)
    batches = np.random.default_rng(0).integers(1, 11, size=(2, 2, 8))
    opt = {"learning_rate": 3e-4, "weight_decay": 1e-4}
    sound = sdar_moe.follow_steps(SEED, lm, EPS, batches, opt)
    broken = sdar_moe.follow_steps(SEED, lm, EPS, batches, opt, **fault)
    gaps = [abs(broken["mu_norm"][k] - v) / v
            for k, v in sound["mu_norm"].items() if v > 0]
    assert max(gaps) > 1e-3
    assert np.asarray(sound["copies"]).shape == (2, 2, 2)
    assert len(sound["masked"]) == 2


def test_the_faults_share_the_sound_references_compiled_step():
    """``rows`` and ``drop_expert`` are arguments of one jitted step,
    so the readings of the control and the faults cost one compilation
    of the sound reference and one of the fp8 one."""
    sdar_moe._GRAD_FNS.clear()
    lm = dict(LM, d_model=8, head_dim=4, d_ff=6, n_layers=2)
    flat = sdar_moe.flat_weights(SEED, lm)
    toks = np.random.default_rng(0).integers(1, 11, size=(2, 8))
    t, masked = sdar_moe.step_noise(0, 0, 2, 8)
    args = (flat, toks, t, masked, lm, EPS)
    sound = sdar_moe.batch_loss_and_grads(*args)
    for fault in ({"rows": [0]}, {"drop_expert": 0}):
        assert sdar_moe.batch_loss_and_grads(*args, **fault)[0] != sound[0]
    assert len(sdar_moe._GRAD_FNS) == 1
    again = sdar_moe.batch_loss_and_grads(*args, rows=[0, 1])
    assert again[0] == sound[0]
    np.testing.assert_array_equal(again[2], sound[2])


def test_a_held_expert_is_a_leaf_of_its_own():
    flat = {"layer_0/moe/experts/w_up": np.arange(24.).reshape(3, 2, 4),
            "layer_0/moe/gate": np.ones((2, 3))}
    norms = sdar_moe.leaf_norms(flat)
    assert set(norms) == {"layer_0/moe/gate",
                          "layer_0/moe/experts/w_up#0",
                          "layer_0/moe/experts/w_up#1",
                          "layer_0/moe/experts/w_up#2"}
    np.testing.assert_allclose(norms["layer_0/moe/experts/w_up#1"],
                               np.linalg.norm(np.arange(8., 16.)))
    from benchmark import harness
    driver = harness.load_module("drivers", "train_fit_bd")
    import jax.numpy as jnp
    on_device = driver.leaf_norms({k: jnp.asarray(v, jnp.float32)
                                   for k, v in flat.items()})
    assert on_device.keys() == norms.keys()
    for k in norms:
        np.testing.assert_allclose(on_device[k], norms[k], rtol=1e-6)


@pytest.mark.parametrize("d", [32, 256])
def test_the_seeded_weights_keep_mask_out_of_the_expert_branch(d):
    """``weights_sdar``'s rules: MASK's embedding in its own channels
    at the norm of a row, ``mlp_norm`` blind there and ``attn_norm``
    not, small writes to the stream, selective QK scales."""
    import jax.numpy as jnp
    from benchmark import weights_sdar
    lm = dict(LM, d_model=d, vocab_size=64, mask_token_id=63)
    tree = weights_sdar.make_tree(9, lm)
    mine = np.asarray(weights_sdar.mask_channels(d))
    assert mine.sum() == max(1, d // 16) and mine[-1] and not mine[0]
    emb = np.asarray(tree["embed"]["embedding"])
    assert (emb[63][~mine] == 0).all() and (emb[63][mine] != 0).all()
    assert (emb[5][~mine] != 0).all()
    layer = tree["layer_0"]
    assert (np.asarray(layer["mlp_norm"]["scale"])[mine] == 0).all()
    assert (np.asarray(layer["mlp_norm"]["scale"])[~mine] != 0).all()
    assert (np.asarray(layer["attn_norm"]["scale"])[mine] != 0).all()
    # a masked position's input to the router and experts holds no MASK
    u = sdar_moe.rmsnorm(jnp.asarray(emb[63]), layer["mlp_norm"]["scale"],
                         EPS)
    assert float(jnp.max(jnp.abs(u))) == 0.0
    o = np.asarray(layer["attn"]["o_proj"]["kernel"])
    q = np.asarray(layer["attn"]["q_proj"]["kernel"])
    np.testing.assert_allclose(o.std() * o.shape[0] ** 0.5,
                               weights_sdar.WRITE_SCALE, rtol=0.1)
    np.testing.assert_allclose(q.std() * q.shape[0] ** 0.5, 1.0, rtol=0.1)
    down = np.asarray(layer["moe"]["experts"]["w_down"])
    np.testing.assert_allclose(down.std() * down.shape[1] ** 0.5,
                               weights_sdar.WRITE_SCALE, rtol=0.1)
    np.testing.assert_allclose(
        np.asarray(layer["attn"]["q_norm"]["scale"]).mean(), 2.5, rtol=0.1)


def test_the_pipelined_adamw_is_decoders_adamw():
    """The moments' copies overlap the next leaves' updates; the
    numbers are ``decoder.AdamW``'s, leaf for leaf, moments included."""
    from benchmark.reference.decoder import AdamW

    rng = np.random.default_rng(3)
    shapes = {"a/kernel": (8, 5), "b/scale": (7,), "c/experts/w_up": (3, 4, 6),
              "d/kernel": (5, 8), "e/kernel": (2, 9), "f/scale": (3,),
              "g/kernel": (4, 4)}
    params = {k: jnp.asarray(rng.standard_normal(s), jnp.float32)
              for k, s in shapes.items()}
    plain, piped = AdamW(3e-4, 1e-4), sdar_moe.PipelinedAdamW(3e-4, 1e-4)
    assert len(shapes) > piped.DEPTH + 1
    p_a, p_b = dict(params), dict(params)
    for step in range(3):
        grads = {k: jnp.asarray(rng.standard_normal(s), jnp.float32)
                 for k, s in shapes.items()}
        p_a = plain.step(p_a, dict(grads))
        p_b = piped.step(p_b, dict(grads))
    for k in shapes:
        np.testing.assert_array_equal(np.asarray(p_a[k]), np.asarray(p_b[k]))
        np.testing.assert_array_equal(plain.mu[k], piped.mu[k])
        np.testing.assert_array_equal(plain.nu[k], piped.nu[k])
        assert isinstance(piped.mu[k], np.ndarray)
