"""Plain reference of the ``granitemoehybrid`` stack (granite-4.0-h-micro;
ISSUE 32 has the equations) and its next-token training step.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``, a row at a time, no kernels. It
imports nothing of the program and makes its own weights from the seed
(``benchmark/weights_granite.py``). With ``x`` the residual stream and
``m`` the ``residual_multiplier``:

* model: ``x = embed[tokens] * embedding_multiplier``; the layers;
  ``h = RMSNorm(x)``; ``logits = (h @ embed^T) / logits_scaling`` (one
  tied table); the next-token loss over the vocabulary held.
* every layer: ``x = x + m * mixer(RMSNorm(x))``, then ``x = x + m *
  mlp(RMSNorm(x))``, ``mlp(u) = (silu(u W_g) * (u W_u)) W_d``.
* attention mixer: H heads on KV heads of hd, no bias, NO position term,
  a causal softmax of ``q k^T * attention_scale`` over the full masked
  scores, ``o_proj``.
* Mamba-2 mixer (``Hs`` heads of ``P``, state ``N``, one group):
  ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC) + b)``, a causal
  depthwise conv of kernel 4; ``[x | B | C] = xBC``; ``dt = softplus(dt +
  dt_bias)``; ``a_t = exp(-exp(A_log) dt_t)``; **the recurrence position
  by position** (``lax.scan`` over ``t``): ``S_t = a_t S_{t-1} + dt_t x_t
  B_t^T``, ``S_0 = 0``; ``y_t = S_t C_t + D x_t``; ``y = RMSNorm(y *
  silu(z))`` over all channels with a learned scale; ``y W_out``.

The program computes the scan in chunks (``ops/ssd.py``); this file
steps it, so that it is independent of what it judges. The steps are
grouped in blocks only so that the backward pass can recompute a block's
states from the state at its start: a state a position would be 16 GB a
layer at the cell's size. A block is no chunk: nothing is reordered.

Departures from the published description: the configuration's file
lists them (``departures``); none is in this file.

``precision = "fp8"`` is the lower-precision CONTROL (``decoder.mm``):
both operands of every matrix product rounded to float8_e4m3, and the
scan's operands (``dt x``, ``B``, ``C``) with them. ``precision = "bf16"``
is a second implementation in the precision the configuration states,
which has to be judged correct: every product's operands in bfloat16,
forward and backward, the scan's ``dt x``, ``B`` and ``C`` rounded to
bfloat16, the decays and the state float32.

The planted faults, each for the number of ``correct`` that has to find
it: ``half`` (the second half of each row's targets left out of the
loss: a step is one row, so this is the half of the batch that a step
can lose), ``drop_state`` (the state reset at every chunk boundary: ``S``
starts from nought each ``chunk`` positions), ``no_gate`` (``y`` in place
of ``y * silu(z)``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from benchmark.reference.decoder import _round_fp8, mm, rmsnorm
from benchmark.reference.sdar_moe import PipelinedAdamW, leaf_norms

NEG = -1e30
SCAN_BLOCK = 128     # positions whose states the backward recomputes
FAULTS = ("half", "drop_state", "no_gate")


def _jnp():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def _sizes(lm: Dict[str, Any]) -> Dict[str, Any]:
    from benchmark import weights_granite

    return weights_granite.sizes(lm)


def _operand(x, precision: Optional[str]):
    """An operand of the scan's products, as ``mm`` would round it."""
    _, jnp = _jnp()
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        return _round_fp8(x)
    return x


# ----------------------------------------------------------------------
# the mixers
# ----------------------------------------------------------------------
def attention(q, k, v, scale: float, precision: Optional[str]):
    """q: (s, H, hd); k, v: (s, KV, hd). The full masked softmax, one
    query head at a time (rematerialised: one head's (s, s) scores are
    the peak)."""
    jax, jnp = _jnp()
    s, heads, hd = q.shape
    group = heads // k.shape[1]
    pos = jnp.arange(s)
    visible = pos[None, :] <= pos[:, None]

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh = args
        sc = mm(qh, kh.T, precision) * scale
        p = jax.nn.softmax(jnp.where(visible, sc, NEG), axis=-1)
        return mm(p, vh, precision)

    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    out = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                 kr.transpose(1, 0, 2),
                                 vr.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2).reshape(s, heads * hd)


def recurrence(u, a, B, C, keep):
    """``S_t = keep_t a_t S_{t-1} + u_t B_t^T`` with ``u_t = dt_t x_t``,
    ``y_t = S_t C_t`` over one row, a position at a time. u: (s, H, P);
    a: (s, H); B, C: (s, N); ``keep`` (s,) is 1, or 0 where a fault
    drops the state. Returns (y (s, H, P), the state after the last
    position (H, P, N))."""
    jax, jnp = _jnp()
    s, heads, p = u.shape
    n = B.shape[-1]
    block = min(SCAN_BLOCK, s)
    pad = -s % block

    def padded(t, value=0.0):   # a = 1, u = 0: a padded position moves nothing
        return jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1),
                       constant_values=value)

    xs = (padded(u), padded(a * keep[:, None], 1.0), padded(B), padded(C))
    xs = tuple(t.reshape((-1, block) + t.shape[1:]) for t in xs)

    def step(S, inp):
        u_t, a_t, B_t, C_t = inp
        S = a_t[:, None, None] * S + u_t[:, :, None] * B_t[None, None, :]
        return S, jnp.einsum("hpn,n->hp", S, C_t)

    @jax.checkpoint
    def one_block(S, inp):
        return jax.lax.scan(step, S, inp)

    S, ys = jax.lax.scan(one_block, jnp.zeros((heads, p, n), jnp.float32),
                         xs)
    return ys.reshape(-1, heads, p)[:s], S


def mamba(u, w: Dict[str, Any], lm: Dict[str, Any], eps: float,
          precision: Optional[str], fault: Optional[str] = None):
    """One Mamba-2 mixer over one row u: (s, d) -> (out (s, d), the
    state held at the row's end (H, P, N), the mean decay)."""
    jax, jnp = _jnp()
    z_ = _sizes(lm)
    heads, p, n = z_["ssm_heads"], z_["ssm_hd"], z_["state"]
    d_inner, conv_dim, k = z_["d_inner"], z_["conv_dim"], z_["conv"]
    s = u.shape[0]
    zxbcdt = mm(u, w["in_proj"], precision)
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:d_inner + conv_dim]
    dt = zxbcdt[:, d_inner + conv_dim:]
    # conv_kernel[j] weighs position t - (k - 1) + j
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[j:j + s] * w["conv_kernel"][j]
                          for j in range(k)) + w["conv_bias"])
    x = xbc[:, :d_inner].reshape(s, heads, p)
    B, C = xbc[:, d_inner:d_inner + n], xbc[:, d_inner + n:]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = jnp.exp(-jnp.exp(w["A_log"]) * dt)
    keep = jnp.ones((s,), jnp.float32)
    if fault == "drop_state":
        keep = (jnp.arange(s) % z_["chunk"] != 0).astype(jnp.float32)
    # the products' operands: dt x, B and C
    y, state = recurrence(_operand(x * dt[..., None], precision), a,
                          _operand(B, precision), _operand(C, precision),
                          keep)
    y = (y + w["D"][:, None] * x).reshape(s, d_inner)
    if fault != "no_gate":
        y = y * jax.nn.silu(z)
    y = rmsnorm(y, w["gate_norm"], eps)
    return mm(y, w["out_proj"], precision), state, jnp.mean(a)


def attention_mixer(u, w, lm, precision):
    z = _sizes(lm)
    s = u.shape[0]
    q = mm(u, w["q_proj"], precision).reshape(s, z["heads"], z["hd"])
    k = mm(u, w["k_proj"], precision).reshape(s, z["kv"], z["hd"])
    v = mm(u, w["v_proj"], precision).reshape(s, z["kv"], z["hd"])
    scale = float(lm.get("attention_scale") or 0.0) or z["hd"] ** -0.5
    return mm(attention(q, k, v, scale, precision), w["o_proj"], precision)


def block(x, w: Dict[str, Any], kind: str, lm: Dict[str, Any], eps: float,
          precision: Optional[str], fault: Optional[str] = None):
    """One layer over one row x: (s, d) -> (x, stats (2,): the RMS of
    the state held at the row's end and the mean decay; noughts under
    attention)."""
    jax, jnp = _jnp()
    m = float(lm.get("residual_multiplier", 1.0))
    if kind == "mamba":
        h, state, decay = mamba(rmsnorm(x, w["ssm_norm"], eps), w, lm, eps,
                                precision, fault)
        stats = jnp.stack([jnp.mean(jnp.square(state)), decay])
    else:
        h = attention_mixer(rmsnorm(x, w["attn_norm"], eps), w, lm,
                            precision)
        stats = jnp.zeros((2,), jnp.float32)
    x = x + m * h
    u = rmsnorm(x, w["mlp_norm"], eps)
    h = mm(jax.nn.silu(mm(u, w["gate"], precision))
           * mm(u, w["up_proj"], precision), w["down_proj"], precision)
    return x + m * h, stats


_MAMBA_LEAVES = (("ssm_norm", "ssm_norm/scale"),
                 ("in_proj", "ssm/in_proj/kernel"),
                 ("conv_kernel", "ssm/conv_kernel"),
                 ("conv_bias", "ssm/conv_bias"),
                 ("dt_bias", "ssm/dt_bias"), ("A_log", "ssm/A_log"),
                 ("D", "ssm/D"), ("gate_norm", "ssm/norm/scale"),
                 ("out_proj", "ssm/out_proj/kernel"))
_ATTENTION_LEAVES = (("attn_norm", "attn_norm/scale"),
                     ("q_proj", "attn/q_proj/kernel"),
                     ("k_proj", "attn/k_proj/kernel"),
                     ("v_proj", "attn/v_proj/kernel"),
                     ("o_proj", "attn/o_proj/kernel"))
_MLP_LEAVES = (("mlp_norm", "mlp_norm/scale"), ("gate", "mlp/gate/kernel"),
               ("up_proj", "mlp/up_proj/kernel"),
               ("down_proj", "mlp/down_proj/kernel"))


def flat_weights(seed: int, lm: Dict[str, Any]) -> Dict[str, Any]:
    """All parameters as {"a/b/c": array}, each made by its own call."""
    from benchmark import weights_granite

    key = weights_granite.seed_key(seed)
    return {"/".join(p): weights_granite.make_leaf(key, p, shape, kind)
            for p, shape, kind in weights_granite.leaf_table(lm)}


def layer_weights(flat: Dict[str, Any], i: int, kind: str) -> Dict[str, Any]:
    leaves = (_MAMBA_LEAVES if kind == "mamba" else _ATTENTION_LEAVES) \
        + _MLP_LEAVES
    return {short: flat[f"layer_{i}/{tail}"] for short, tail in leaves}


def hidden_states(flat, tokens, lm, eps, precision, fault=None,
                  remat: bool = True):
    """(final-norm hidden states over ``logits_scaling`` (s, d), stats
    (layers, 2)) of one row of ``tokens`` (s,)."""
    jax, jnp = _jnp()
    x = flat["embed/embedding"][tokens] \
        * float(lm.get("embedding_multiplier", 1.0))
    stats = []
    for i, kind in enumerate(_sizes(lm)["types"]):
        fn = lambda x_, w_, kind=kind: block(  # noqa: E731
            x_, w_, kind, lm, eps, precision, fault)
        x, st = (jax.checkpoint(fn) if remat else fn)(
            x, layer_weights(flat, i, kind))
        stats.append(st)
    return rmsnorm(x, flat["final_norm/scale"], eps), jnp.stack(stats)


def forward_logits(flat, tokens, lm, eps, precision=None):
    """Logits (s, vocab) of one row: the whole model at once."""
    h, _ = hidden_states(flat, tokens, lm, eps, precision, remat=False)
    return mm(h, flat["embed/embedding"].T, precision) \
        / float(lm.get("logits_scaling", 1.0))


# ----------------------------------------------------------------------
# the loss, gradients, AdamW
# ----------------------------------------------------------------------
def row_loss(flat, tokens, lm, eps, precision, fault=None,
             head_chunk: int = 512):
    """(sum of the row's next-token cross-entropies, its count of
    targets, stats (layers, 2)). Token id 0 is padding and predicts
    nothing; under ``half`` the second half of the targets count for
    nothing either."""
    jax, jnp = _jnp()
    h, stats = hidden_states(flat, tokens, lm, eps, precision, fault)
    h = h[:-1]
    tgt = tokens[1:]
    mask = (tgt != 0).astype(jnp.float32)
    s = h.shape[0]
    if fault == "half":
        mask = mask * (jnp.arange(s) < s // 2)
    chunk = max(1, min(head_chunk, s))
    n = -(-s // chunk)
    pad = n * chunk - s
    h = jnp.pad(h, ((0, pad), (0, 0)))
    tgt = jnp.pad(tgt, (0, pad))
    mask = jnp.pad(mask, (0, pad))
    head = flat["embed/embedding"].T
    scaling = float(lm.get("logits_scaling", 1.0))

    @jax.checkpoint
    def chunk_loss(args):
        hc, tc, mc = args
        logits = mm(hc, head, precision) / scaling
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        got = jnp.take_along_axis(logits, tc[:, None], 1)[:, 0]
        return jnp.sum((lse - got) * mc)

    sums = jax.lax.map(chunk_loss, (h.reshape(n, chunk, -1),
                                    tgt.reshape(n, chunk),
                                    mask.reshape(n, chunk)))
    return jnp.sum(sums), jnp.sum(mask), stats


_GRAD_FNS: Dict[Any, Any] = {}


def _grad_fn(lm, eps, precision, fault):
    """The jitted loss and gradient of one batch, built once for each
    (configuration, precision, fault)."""
    jax, jnp = _jnp()
    key = (tuple(sorted((k, str(v)) for k, v in lm.items())), eps,
           precision, fault)
    if key not in _GRAD_FNS:
        def mean_loss(p, toks):
            sums, counts, stats = jax.lax.map(
                jax.checkpoint(lambda t: row_loss(p, t, lm, eps, precision,
                                                  fault)), toks)
            # stats: the states' mean squares and the decays, over rows
            return (jnp.sum(sums) / jnp.maximum(jnp.sum(counts), 1e-9),
                    jnp.mean(stats, axis=0))

        _GRAD_FNS[key] = jax.jit(jax.value_and_grad(mean_loss,
                                                    has_aux=True))
    return _GRAD_FNS[key]


def batch_loss_and_grads(flat, batch, lm, eps, precision=None, fault=None):
    """(loss, gradient, stats (layers, 2): the RMS of the states held
    at the rows' end and the mean decay, noughts at attention layers)."""
    jax, jnp = _jnp()
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}, got {fault!r}")
    batch = jnp.asarray(batch, jnp.int32)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = _grad_fn(lm, eps, precision, fault)(
            flat, batch)
    stats = np.asarray(stats, np.float64)
    stats[:, 0] = np.sqrt(stats[:, 0])
    return float(loss), grads, stats


def follow_steps(seed: int, lm: Dict[str, Any], eps: float, batches,
                 optimizer: Dict[str, Any],
                 precision: Optional[str] = None,
                 fault: Optional[str] = None,
                 freeze: bool = False) -> Dict[str, Any]:
    """Drive the reference through ``batches`` (steps, batch, seq) from
    the seed's weights. Returns each step's loss, each step's
    ``state_rms`` and ``decay_mean`` by Mamba-2 layer (steps, layers of
    that kind) and, per leaf, the norms of Adam's first moment and of
    the parameters' change after the last step. ``fault`` is one of
    ``FAULTS``; ``freeze`` a step that returns its state unchanged."""
    from benchmark import weights_granite

    params = flat_weights(seed, lm)
    opt = PipelinedAdamW(optimizer["learning_rate"],
                         optimizer["weight_decay"])
    mamba_layers = list(_sizes(lm)["mamba_layers"])
    losses: List[float] = []
    stats = []
    for batch in batches:
        loss, grads, st = batch_loss_and_grads(params, batch, lm, eps,
                                               precision, fault)
        losses.append(loss)
        stats.append(st[mamba_layers])
        stepped = opt.step(params, grads)
        if not freeze:
            params = stepped
        del grads
    key = weights_granite.seed_key(seed)
    change = leaf_norms({
        "/".join(p): params["/".join(p)]
        - weights_granite.make_leaf(key, p, shape, kind)
        for p, shape, kind in weights_granite.leaf_table(lm)})
    stats = np.stack(stats)                      # (steps, mamba layers, 2)
    return {"losses": losses, "state_rms": stats[:, :, 0].tolist(),
            "decay_mean": stats[:, :, 1].tolist(),
            "mamba_layers": mamba_layers,
            "mu_norm": leaf_norms(opt.mu), "change_norm": change}
