"""The plain reference against ``LanguageModel`` at a tiny size, given
the same weights: the forward pass's logits, the training loss and its
gradients, and AdamW against optax."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import harness, weights
from benchmark.reference import decoder
from learningorchestra_tpu.models import LanguageModel
from learningorchestra_tpu.models import transformer as tlm

LM = {"vocab_size": 128, "d_model": 64, "n_layers": 2, "n_heads": 4,
      "n_kv_heads": 2, "d_ff": 96, "max_len": 64, "attention": "dot",
      "sliding_window": 12, "rope_base": 10000.0}
EPS = 1e-6
SEED = 2**31 + 3


@pytest.fixture(scope="module")
def model():
    lm = LanguageModel(**LM)
    lm.params = weights.make_tree(SEED, LM)
    return lm


def test_prefill_logits_agree(model):
    toks = np.random.default_rng(0).integers(1, 128, size=(1, 40))
    with jax.default_matmul_precision("highest"):
        got, _ = model._module_for(40).apply(
            {"params": model.params}, jnp.asarray(toks, jnp.int32))
        want = decoder.forward_logits(decoder.flat_weights(SEED, LM),
                                      jnp.asarray(toks[0], jnp.int32),
                                      LM, EPS)
    assert np.max(np.abs(np.asarray(got[0]) - np.asarray(want))) < 2e-4


def test_loss_and_gradients_agree(model):
    batch = np.random.default_rng(2).integers(0, 128, size=(2, 33))
    loss_fn = tlm.next_token_loss(aux_coef=0.0)

    def program_loss(params):
        out = model._module_for(33).apply(
            {"params": params}, jnp.asarray(batch, jnp.int32), train=True)
        res = loss_fn(out, {"x": jnp.asarray(batch, jnp.int32)}, None)
        return res[0] if isinstance(res, tuple) else res

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(program_loss)(model.params)
    ref_loss, ref_grads = decoder.batch_loss_and_grads(
        decoder.flat_weights(SEED, LM), batch, LM, EPS)
    assert abs(float(loss) - ref_loss) < 1e-5 * ref_loss
    for path, _, _ in weights.leaf_table(LM):
        node = grads
        for part in path:
            node = node[part]
        want = np.asarray(ref_grads["/".join(path)])
        scale = np.max(np.abs(want)) + 1e-12
        assert np.max(np.abs(np.asarray(node) - want)) < 2e-4 * scale, path


def test_adamw_is_optax_adamw():
    rng = np.random.default_rng(3)
    params = {"w": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32),
              "s": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
    tx = optax.adamw(3e-4, weight_decay=1e-4,
                     mask=lambda p: jax.tree_util.tree_map(
                         lambda a: a.ndim >= 2, p))
    state = tx.init(params)
    mine = decoder.AdamW(3e-4, 1e-4)
    theirs, ours = params, dict(params)
    for i in range(3):
        g = {k: jnp.asarray(rng.normal(size=v.shape), jnp.float32)
             for k, v in params.items()}
        upd, state = tx.update(g, state, theirs)
        theirs = optax.apply_updates(theirs, upd)
        ours = mine.step(ours, dict(g))
    for k in params:
        assert np.allclose(np.asarray(theirs[k]), np.asarray(ours[k]),
                           rtol=0, atol=1e-7)
    assert np.allclose(mine.mu["w"], np.asarray(state[0].mu["w"]),
                       atol=1e-7)


def test_the_lower_precision_control_reads_apart():
    """The reference computed in fp8 in the program's place: its loss
    and its first moment stand off from the reference's own by far more
    than the program does (tests at this size see under 1e-4)."""
    batches = np.random.default_rng(4).integers(
        1, 128, size=(2, 2, 33))
    opt = {"learning_rate": 3e-4, "weight_decay": 1e-4}
    ref = decoder.follow_steps(SEED, LM, EPS, batches, opt)
    low = decoder.follow_steps(SEED, LM, EPS, batches, opt,
                               precision="fp8")
    half = decoder.follow_steps(SEED, LM, EPS, batches, opt, rows=[0])
    still = decoder.follow_steps(SEED, LM, EPS, batches, opt, freeze=True)
    train = harness.load_module("drivers", "train_fit")

    def gap(a):
        return train.worst_gap(a["mu_norm"], ref["mu_norm"])

    assert gap(low) > 0.01
    assert gap(half) > 0.1
    assert train.worst_gap(still["change_norm"], ref["change_norm"]) \
        == pytest.approx(1.0)
