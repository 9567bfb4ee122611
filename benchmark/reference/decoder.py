"""Plain reference of the dense decoder both configurations run.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: RoPE (half-split pairs), GQA,
one sliding window, RMSNorm (eps as the configuration states), SwiGLU,
no biases, untied head. No cache, no batching tricks, no kernels. It
imports nothing of the program and makes its own weights from the seed
(``benchmark/weights.py``), a layer at a time.

``precision`` selects the lower-precision CONTROL: ``"fp8"`` rounds
both operands of every matrix product to float8_e4m3 (per-tensor
amax scaling) before multiplying; ``None`` is the reference itself.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

NEG = -1e30


def _jnp():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def _round_fp8(x):
    jax, jnp = _jnp()
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = amax / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    # straight-through: the product is taken of rounded operands, the
    # gradient flows as if it were not
    return x + jax.lax.stop_gradient(q * scale - x)


def mm(a, b, precision: Optional[str]):
    _, jnp = _jnp()
    if precision == "fp8":
        a, b = _round_fp8(a), _round_fp8(b)
    return jnp.matmul(a, b)


def rmsnorm(x, scale, eps: float):
    _, jnp = _jnp()
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * (1.0 / jnp.sqrt(var + eps)) * scale


def rope(x, positions, base: float):
    """x: (s, heads, hd); rotate the pairs (x[i], x[i + hd/2])."""
    _, jnp = _jnp()
    half = x.shape[-1] // 2
    freqs = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, window: int, precision: Optional[str]):
    """q: (s, H, hd); k, v: (s, KV, hd). Causal, banded by ``window``
    (query p sees keys p-window+1 .. p; 0 = unbounded). One query head
    at a time, so the (s, s) scores of a single head are the peak."""
    jax, jnp = _jnp()
    s, heads, hd = q.shape
    group = heads // k.shape[1]
    pos = jnp.arange(s)
    visible = pos[None, :] <= pos[:, None]
    if window > 0:
        visible = visible & (pos[None, :] > pos[:, None] - window)

    def one_head(args):
        qh, kh, vh = args
        sc = mm(qh, kh.T, precision) / np.sqrt(hd)
        sc = jnp.where(visible, sc, NEG)
        p = jax.nn.softmax(sc, axis=-1)
        return mm(p, vh, precision)

    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    out = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                 kr.transpose(1, 0, 2),
                                 vr.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2).reshape(s, heads * hd)


def block(x, w: Dict[str, Any], lm: Dict[str, Any], eps: float,
          precision: Optional[str]):
    """One decoder layer over one sequence x: (s, d)."""
    jax, jnp = _jnp()
    heads = int(lm["n_heads"])
    kv = int(lm.get("n_kv_heads") or heads)
    hd = int(lm["d_model"]) // heads
    s = x.shape[0]
    pos = jnp.arange(s)
    h = rmsnorm(x, w["attn_norm"], eps)
    q = mm(h, w["q_proj"], precision).reshape(s, heads, hd)
    k = mm(h, w["k_proj"], precision).reshape(s, kv, hd)
    v = mm(h, w["v_proj"], precision).reshape(s, kv, hd)
    base = float(lm.get("rope_base", 10000.0))
    q, k = rope(q, pos, base), rope(k, pos, base)
    o = attention(q, k, v, int(lm.get("sliding_window") or 0), precision)
    x = x + mm(o, w["o_proj"], precision)
    h = rmsnorm(x, w["mlp_norm"], eps)
    g = mm(h, w["gate"], precision)
    u = mm(h, w["up_proj"], precision)
    return x + mm(jax.nn.silu(g) * u, w["down_proj"], precision)


_LAYER_LEAVES = (("attn_norm", ("attn_norm", "scale")),
                 ("q_proj", ("attn", "q_proj", "kernel")),
                 ("k_proj", ("attn", "k_proj", "kernel")),
                 ("v_proj", ("attn", "v_proj", "kernel")),
                 ("o_proj", ("attn", "o_proj", "kernel")),
                 ("mlp_norm", ("mlp_norm", "scale")),
                 ("gate", ("mlp", "gate", "kernel")),
                 ("up_proj", ("mlp", "up_proj", "kernel")),
                 ("down_proj", ("mlp", "down_proj", "kernel")))


def flat_weights(seed: int, lm: Dict[str, Any]) -> Dict[str, Any]:
    """All parameters as {"a/b/c": array}, each made by its own call."""
    from benchmark import weights

    key = weights.seed_key(seed)
    return {"/".join(p): weights.make_leaf(key, p, shape, kind)
            for p, shape, kind in weights.leaf_table(lm)}


def layer_weights(flat: Dict[str, Any], i: int) -> Dict[str, Any]:
    return {short: flat["/".join((f"layer_{i}",) + tail)]
            for short, tail in _LAYER_LEAVES}


def forward_logits(flat: Dict[str, Any], tokens, lm: Dict[str, Any],
                   eps: float, precision: Optional[str] = None):
    """Logits (s, vocab) of one sequence: the whole model at once."""
    x = flat["embed/embedding"][tokens]
    for i in range(int(lm["n_layers"])):
        x = block(x, layer_weights(flat, i), lm, eps, precision)
    x = rmsnorm(x, flat["final_norm/scale"], eps)
    return mm(x, flat["lm_head/kernel"], precision)


# ----------------------------------------------------------------------
# training: loss, gradients, AdamW
# ----------------------------------------------------------------------
def sequence_loss_sum(flat: Dict[str, Any], tokens, lm: Dict[str, Any],
                      eps: float, precision: Optional[str],
                      head_chunk: int = 512):
    """Sum of next-token cross-entropies of one row and the count of
    targets (token id 0 is padding and predicts nothing). Layers are
    rematerialised and the head runs in chunks of positions, so the
    row's peak is one layer's activations plus one chunk of logits."""
    jax, jnp = _jnp()
    x = flat["embed/embedding"][tokens]
    for i in range(int(lm["n_layers"])):
        x = jax.checkpoint(
            lambda x_, w_: block(x_, w_, lm, eps, precision))(
                x, layer_weights(flat, i))
    x = rmsnorm(x, flat["final_norm/scale"], eps)[:-1]
    tgt = tokens[1:]
    mask = (tgt != 0).astype(jnp.float32)
    s = x.shape[0]
    chunk = max(1, min(head_chunk, s))
    n = -(-s // chunk)
    pad = n * chunk - s
    x = jnp.pad(x, ((0, pad), (0, 0)))
    tgt = jnp.pad(tgt, (0, pad))
    mask = jnp.pad(mask, (0, pad))
    head = flat["lm_head/kernel"]

    @jax.checkpoint
    def chunk_loss(args):
        xc, tc, mc = args
        logits = mm(xc, head, precision)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        got = jnp.take_along_axis(logits, tc[:, None], 1)[:, 0]
        return jnp.sum((lse - got) * mc)

    sums = jax.lax.map(chunk_loss, (x.reshape(n, chunk, -1),
                                    tgt.reshape(n, chunk),
                                    mask.reshape(n, chunk)))
    return jnp.sum(sums), jnp.sum(mask)


_GRAD_FNS: Dict[Any, Any] = {}


def _grad_fn(lm: Dict[str, Any], eps: float, precision: Optional[str],
             use: Tuple[int, ...]):
    """The jitted loss-and-gradient of a batch, built once for each
    (configuration, precision, rows) and kept, so that following eight
    steps traces and lowers it once."""
    jax, jnp = _jnp()
    key = (tuple(sorted(lm.items())), eps, precision, use)
    if key not in _GRAD_FNS:
        def mean_loss(p, toks):
            # one row after another, each rematerialised as a whole
            sums, counts = jax.lax.map(
                jax.checkpoint(lambda t_: sequence_loss_sum(
                    p, t_, lm, eps, precision)), toks[jnp.asarray(use)])
            return jnp.sum(sums) / jnp.maximum(jnp.sum(counts), 1e-9)

        _GRAD_FNS[key] = jax.jit(jax.value_and_grad(mean_loss))
    return _GRAD_FNS[key]


def batch_loss_and_grads(flat: Dict[str, Any], batch, lm: Dict[str, Any],
                         eps: float, precision: Optional[str] = None,
                         rows: Optional[Sequence[int]] = None):
    """Mean loss over the batch's targets and its gradient. Each row
    is rematerialised as a whole, so one row's activations are live at
    a time and the gradient is accumulated in place. ``rows`` plants
    the fault "half of the batch left out, the mean taken over the
    rest"."""
    jax, jnp = _jnp()
    batch = jnp.asarray(batch, jnp.int32)
    use = tuple(range(batch.shape[0])) if rows is None else tuple(rows)
    with jax.default_matmul_precision("highest"):
        loss, grads = _grad_fn(lm, eps, precision, use)(flat, batch)
    return float(loss), grads


class AdamW:
    """optax.adamw as the program builds it: decay on matrices only.
    The two moments live on the HOST between steps and pass through the
    device a leaf at a time, so the device holds the parameters, one
    gradient and the step's activations, and no more."""

    def __init__(self, learning_rate: float, weight_decay: float,
                 b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8):
        self.lr, self.wd = float(learning_rate), float(weight_decay)
        self.b1, self.b2, self.eps = b1, b2, eps
        self.count = 0
        self.mu: Dict[str, np.ndarray] = {}
        self.nu: Dict[str, np.ndarray] = {}

        self._leaf_step = None

    def _build(self):
        jax, jnp = _jnp()
        b1, b2, eps, lr, wd = self.b1, self.b2, self.eps, self.lr, self.wd

        @jax.jit
        def leaf_step(p, g, m, v, c1, c2):
            m = b1 * m + (1.0 - b1) * g
            v = b2 * v + (1.0 - b2) * g * g
            upd = (m / c1) / (jnp.sqrt(v / c2) + eps)
            if p.ndim >= 2:
                upd = upd + wd * p
            return p - lr * upd, m, v

        return leaf_step

    def step(self, params: Dict[str, Any], grads: Dict[str, Any]):
        if self._leaf_step is None:
            self._leaf_step = self._build()  # one trace for each shape
        self.count += 1
        c1 = np.float32(1.0 - self.b1 ** self.count)
        c2 = np.float32(1.0 - self.b2 ** self.count)
        new = {}
        for k in list(params):
            p = params[k]
            g = grads.pop(k)  # freed leaf by leaf
            zero = np.zeros(p.shape, np.float32)
            new[k], m, v = self._leaf_step(p, g, self.mu.get(k, zero),
                                           self.nu.get(k, zero), c1, c2)
            self.mu[k], self.nu[k] = np.asarray(m), np.asarray(v)
        return new


def follow_steps(seed: int, lm: Dict[str, Any], eps: float,
                 batches, optimizer: Dict[str, Any],
                 precision: Optional[str] = None,
                 rows: Optional[Sequence[int]] = None,
                 freeze: bool = False) -> Dict[str, Any]:
    """Drive the reference through ``batches`` (steps, batch, seq) from
    the seed's weights. Returns each step's loss and, per leaf, the
    norms of Adam's first moment and of the parameters' change after
    the last step. ``rows`` and ``freeze`` plant faults: half a batch
    left out; a step that returns its state unchanged."""
    jax, jnp = _jnp()
    from benchmark import weights

    params = flat_weights(seed, lm)
    opt = AdamW(optimizer["learning_rate"], optimizer["weight_decay"])
    losses = []
    for batch in batches:
        loss, grads = batch_loss_and_grads(params, batch, lm, eps,
                                           precision, rows)
        losses.append(loss)
        stepped = opt.step(params, grads)
        if not freeze:
            params = stepped
        del grads
    norm = lambda a: float(np.sqrt(np.sum(np.square(  # noqa: E731
        np.asarray(a, np.float64)))))
    key = weights.seed_key(seed)
    # the start weights are made again, a leaf at a time, not kept
    change = {"/".join(p): norm(params["/".join(p)]
                                - weights.make_leaf(key, p, shape, kind))
              for p, shape, kind in weights.leaf_table(lm)}
    return {"losses": losses,
            "mu_norm": {k: norm(v) for k, v in opt.mu.items()},
            "change_norm": change}
