"""Plain reference of the ``sdar_moe`` block and its block-diffusion
training step (SDAR-30B-A3B-Chat; ISSUE 26 has the equations).

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``, a row at a time, no kernels,
no sorting: every held expert is applied to every position and weighted
by the router's weight for it, which is zero where the position did not
choose it. It imports nothing of the program, makes its own weights
from the seed (``benchmark/weights_sdar.py``), and draws the step's
noise itself, from the text of docs/DIFFUSION.md.

The layer (``u = RMSNorm(x)``, eps as the configuration states, no
biases): ``q = W_q u`` as H heads of hd, ``k, v`` as KV heads of hd; q
and k RMS-normed per head with a learned scale of hd; RoPE over all hd
(half-split pairs); each KV head serves H/KV query heads; ``softmax(q
k^T / sqrt(hd) + mask) v``; ``h = x + W_o o``. Experts: ``p =
softmax(W_r RMSNorm(h))`` over all E; the k largest; ``w = p_top / sum
p_top``; ``y = h + sum over chosen AND held e of w_e W_down,e
(silu(W_gate,e v) * W_up,e v)``. Final RMSNorm, untied head.

Departures from the published model: none in the layer. What the
experts that are not held would add is left out (the configuration's
cut: one expert-parallel rank's share), as in the program. ``precision
= "fp8"`` is the lower-precision CONTROL (``decoder.mm``): both
operands of every matrix product rounded to float8_e4m3.
"""

from __future__ import annotations

import collections
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from benchmark.reference.decoder import AdamW, mm, rmsnorm, rope

NEG = -1e30
T_LOW = 0.1


def _jnp():
    import jax
    import jax.numpy as jnp

    return jax, jnp


# ----------------------------------------------------------------------
# the objective's noise and mask (docs/DIFFUSION.md, implemented here a
# second time)
# ----------------------------------------------------------------------
def step_noise(fit_seed: int, step: int, rows: int, seq: int):
    """(t (rows,), masked (rows, seq)) of global step ``step`` of a fit
    whose seed is ``fit_seed``: K = fold_in(PRNGKey(fit_seed), step);
    row r draws t[r], uniform on [0.1, 1), from fold_in(fold_in(K, 1),
    r) and its ``seq`` uniforms u[r, :] from fold_in(fold_in(K, 2), r);
    position p of row r is masked where u[r, p] < t[r]."""
    jax, jnp = _jnp()
    k = jax.random.fold_in(jax.random.PRNGKey(fit_seed), step)
    k_t, k_u = jax.random.fold_in(k, 1), jax.random.fold_in(k, 2)
    t = jnp.stack([jax.random.uniform(jax.random.fold_in(k_t, r), (),
                                      jnp.float32, T_LOW, 1.0)
                   for r in range(rows)])
    u = jnp.stack([jax.random.uniform(jax.random.fold_in(k_u, r), (seq,),
                                      jnp.float32) for r in range(rows)])
    return t, u < t[:, None]


def visible(seq: int, block: int):
    """(2L, 2L) bool over a row [noisy ; clean]: query i sees key j iff
    both noisy and in one block; or i noisy, j clean and blk(j) <
    blk(i); or both clean and blk(j) <= blk(i)."""
    _, jnp = _jnp()
    out = np.zeros((2 * seq, 2 * seq), bool)
    blk = np.arange(seq) // block
    out[:seq, :seq] = blk[:, None] == blk[None, :]
    out[:seq, seq:] = blk[None, :] < blk[:, None]
    out[seq:, seq:] = blk[None, :] <= blk[:, None]
    return jnp.asarray(out)


# ----------------------------------------------------------------------
# the layer
# ----------------------------------------------------------------------
def attention(q, k, v, mask, precision: Optional[str]):
    """q: (s, H, hd); k, v: (s, KV, hd); mask (s, s) bool. One query
    head at a time."""
    jax, jnp = _jnp()
    s, heads, hd = q.shape
    group = heads // k.shape[1]

    # rematerialised: 32 heads' (s, s) scores of a doubled row would
    # be 8 GB of residuals; one head's are live at a time
    @jax.checkpoint
    def one_head(args):
        qh, kh, vh = args
        sc = mm(qh, kh.T, precision) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mask, sc, NEG), axis=-1)
        return mm(p, vh, precision)

    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    out = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                 kr.transpose(1, 0, 2),
                                 vr.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2).reshape(s, heads * hd)


def experts(v, w: Dict[str, Any], lm: Dict[str, Any],
            precision: Optional[str], drop_expert: Optional[int] = None):
    """(sum over held experts of their weighted outputs (s, d), copies
    each held expert received (held,)). ``drop_expert`` plants the
    fault "one held expert's output left out"."""
    jax, jnp = _jnp()
    k = int(lm["moe_k"])
    offset = int(lm.get("expert_offset") or 0)
    held = w["w_gate"].shape[0]
    p = jax.nn.softmax(mm(v, w["router"], precision), axis=-1)
    top, idx = jax.lax.top_k(p, k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)

    def one_expert(acc, xs):
        e, wg, wu, wd = xs
        chose = idx == e + offset
        weight = jnp.sum(jnp.where(chose, top, 0.0), axis=-1)
        if drop_expert is not None:   # may be traced; -1 drops none
            weight = jnp.where(e == drop_expert, 0.0, weight)
        y = mm(jax.nn.silu(mm(v, wg, precision)) * mm(v, wu, precision),
               wd, precision)
        return acc + weight[:, None] * y, jnp.sum(chose)

    out, counts = jax.lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(v),
        (jnp.arange(held), w["w_gate"], w["w_up"], w["w_down"]))
    return out, counts


def block(x, w: Dict[str, Any], lm: Dict[str, Any], eps: float,
          precision: Optional[str], mask, positions,
          drop_expert: Optional[int] = None):
    """One layer over one row x: (s, d) -> (y, held experts' copies)."""
    heads = int(lm["n_heads"])
    kv = int(lm.get("n_kv_heads") or heads)
    hd = int(lm.get("head_dim") or int(lm["d_model"]) // heads)
    s = x.shape[0]
    u = rmsnorm(x, w["attn_norm"], eps)
    q = mm(u, w["q_proj"], precision).reshape(s, heads, hd)
    k = mm(u, w["k_proj"], precision).reshape(s, kv, hd)
    v = mm(u, w["v_proj"], precision).reshape(s, kv, hd)
    q, k = rmsnorm(q, w["q_norm"], eps), rmsnorm(k, w["k_norm"], eps)
    base = float(lm.get("rope_base", 10000.0))
    q, k = rope(q, positions, base), rope(k, positions, base)
    h = x + mm(attention(q, k, v, mask, precision), w["o_proj"], precision)
    out, counts = experts(rmsnorm(h, w["mlp_norm"], eps), w, lm, precision,
                          drop_expert)
    return h + out, counts


_LAYER_LEAVES = (("attn_norm", "attn_norm/scale"),
                 ("q_proj", "attn/q_proj/kernel"),
                 ("k_proj", "attn/k_proj/kernel"),
                 ("v_proj", "attn/v_proj/kernel"),
                 ("q_norm", "attn/q_norm/scale"),
                 ("k_norm", "attn/k_norm/scale"),
                 ("o_proj", "attn/o_proj/kernel"),
                 ("mlp_norm", "mlp_norm/scale"),
                 ("router", "moe/gate"),
                 ("w_gate", "moe/experts/w_gate"),
                 ("w_up", "moe/experts/w_up"),
                 ("w_down", "moe/experts/w_down"))


def flat_weights(seed: int, lm: Dict[str, Any]) -> Dict[str, Any]:
    """All parameters as {"a/b/c": array}, each made by its own call."""
    from benchmark import weights_sdar

    key = weights_sdar.seed_key(seed)
    return {"/".join(p): weights_sdar.make_leaf(key, p, shape, kind)
            for p, shape, kind in weights_sdar.leaf_table(lm)}


def layer_weights(flat: Dict[str, Any], i: int) -> Dict[str, Any]:
    return {short: flat[f"layer_{i}/{tail}"] for short, tail in _LAYER_LEAVES}


def hidden_states(flat, tokens, lm, eps, precision, mask, positions,
                  drop_expert=None, remat: bool = True):
    """(final-norm hidden states (s, d), copies (layers, held)) of one
    row of ``tokens`` (s,) under ``mask``."""
    jax, jnp = _jnp()
    x = flat["embed/embedding"][tokens]
    counts = []
    for i in range(int(lm["n_layers"])):
        fn = lambda x_, w_: block(x_, w_, lm, eps, precision,  # noqa: E731
                                  mask, positions, drop_expert)
        x, c = (jax.checkpoint(fn) if remat else fn)(
            x, layer_weights(flat, i))
        counts.append(c)
    return rmsnorm(x, flat["final_norm/scale"], eps), jnp.stack(counts)


def causal_logits(flat, tokens, lm, eps, precision=None):
    """Logits (s, vocab) of a row under the plain causal mask: the
    layer without the objective (tests of head_dim and QK-norm)."""
    _, jnp = _jnp()
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x, _ = hidden_states(flat, tokens, lm, eps, precision,
                         pos[None, :] <= pos[:, None], pos, remat=False)
    return mm(x, flat["lm_head/kernel"], precision)


# ----------------------------------------------------------------------
# the objective, gradients, AdamW
# ----------------------------------------------------------------------
def row_loss(flat, x0, t, masked, lm, eps, precision, drop_expert=None,
             head_chunk: int = 512):
    """One row's ``(1/t) * sum over masked positions of CE`` and the
    held experts' copies (layers, held). The model runs once over
    [xt ; x0], both halves at positions 0..L-1; logits are taken at the
    noisy half and predict x0 at the same position."""
    jax, jnp = _jnp()
    seq = x0.shape[0]
    block_length = int(lm.get("block_length") or 4)
    mask_id = lm.get("mask_token_id")
    mask_id = int(lm["vocab_size"]) - 1 if mask_id is None else int(mask_id)
    xt = jnp.where(masked, mask_id, x0)
    positions = jnp.tile(jnp.arange(seq), 2)
    x, counts = hidden_states(flat, jnp.concatenate([xt, x0]), lm, eps,
                              precision, visible(seq, block_length),
                              positions, drop_expert)
    x = x[:seq]
    weight = masked.astype(jnp.float32)
    chunk = max(1, min(head_chunk, seq))
    n = -(-seq // chunk)
    pad = n * chunk - seq
    x = jnp.pad(x, ((0, pad), (0, 0)))
    tgt = jnp.pad(x0, (0, pad))
    weight = jnp.pad(weight, (0, pad))
    head = flat["lm_head/kernel"]

    @jax.checkpoint
    def chunk_loss(args):
        xc, tc, wc = args
        logits = mm(xc, head, precision)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        got = jnp.take_along_axis(logits, tc[:, None], 1)[:, 0]
        return jnp.sum((lse - got) * wc)

    sums = jax.lax.map(chunk_loss, (x.reshape(n, chunk, -1),
                                    tgt.reshape(n, chunk),
                                    weight.reshape(n, chunk)))
    return jnp.sum(sums) / t, counts


_GRAD_FNS: Dict[Any, Any] = {}


def _grad_fn(lm, eps, precision):
    """The jitted loss and gradient of one batch. The planted faults
    are arguments, not programs of their own, so that the sound
    reference and both faults share one compilation: ``use`` (rows,)
    weighs each row's sum (0 leaves it out; the mean is over the rows
    used), ``drop`` is the held expert whose output is left out (-1:
    none)."""
    jax, jnp = _jnp()
    key = (tuple(sorted((k, str(v)) for k, v in lm.items())), eps,
           precision)
    if key not in _GRAD_FNS:
        def mean_loss(p, toks, t, masked, use, drop):
            sums, counts = jax.lax.map(
                jax.checkpoint(lambda a: row_loss(
                    p, a[0], a[1], a[2], lm, eps, precision, drop)),
                (toks, t, masked))
            # loss = sum over rows / (rows * L)
            return (jnp.sum(sums * use) / (jnp.sum(use) * toks.shape[1]),
                    jnp.sum(counts * use[:, None, None].astype(counts.dtype),
                            axis=0))

        _GRAD_FNS[key] = jax.jit(jax.value_and_grad(mean_loss,
                                                    has_aux=True))
    return _GRAD_FNS[key]


def batch_loss_and_grads(flat, batch, t, masked, lm, eps,
                         precision=None, rows=None, drop_expert=None):
    """(loss, gradient, copies (layers, held) summed over the rows) of
    one step's batch under its noise. ``rows`` plants the fault "half
    of the batch left out, the mean taken over the rest"."""
    jax, jnp = _jnp()
    batch = jnp.asarray(batch, jnp.int32)
    use = np.zeros(batch.shape[0], np.float32)
    use[list(range(batch.shape[0])) if rows is None else list(rows)] = 1.0
    drop = -1 if drop_expert is None else int(drop_expert)
    with jax.default_matmul_precision("highest"):
        (loss, counts), grads = _grad_fn(lm, eps, precision)(
            flat, batch, t, masked, jnp.asarray(use), jnp.int32(drop))
    return float(loss), grads, np.asarray(counts)


def leaf_norms(flat: Dict[str, Any]) -> Dict[str, float]:
    """The norm of every leaf of ``{"a/b/c": array}``; a stack of held
    experts gives one norm an expert, ``a/b/c#j``, so that one expert
    left untouched reads as a leaf left untouched."""
    def norm(a) -> float:   # summed in float64, without a float64 copy
        a = np.asarray(a, np.float32).ravel()
        return float(np.sqrt(np.einsum("i,i->", a, a, dtype=np.float64)))

    out: Dict[str, float] = {}
    for name, leaf in flat.items():
        if "/experts/" in name:
            out.update({f"{name}#{j}": norm(e) for j, e in enumerate(leaf)})
        else:
            out[name] = norm(leaf)
    return out


class PipelinedAdamW(AdamW):
    """``decoder.AdamW`` (its jitted leaf update, its moments on the
    host between steps) with the moments' trip overlapped: a leaf's new
    moments start their copy to the host when its update is dispatched
    and are waited for ``DEPTH`` leaves later, while the next leaves'
    uploads and updates run. Leaf by leaf it took 3.3 s of a step's 9.7
    on the chip (my chip run, PR 26, call 39)."""

    DEPTH = 4

    def step(self, params: Dict[str, Any], grads: Dict[str, Any]):
        if self._leaf_step is None:
            self._leaf_step = self._build()
        self.count += 1
        c1 = np.float32(1.0 - self.b1 ** self.count)
        c2 = np.float32(1.0 - self.b2 ** self.count)
        new: Dict[str, Any] = {}
        pending: collections.deque = collections.deque()

        def settle():
            k, m, v = pending.popleft()
            self.mu[k], self.nu[k] = np.asarray(m), np.asarray(v)

        for k in list(params):
            p = params[k]
            g = grads.pop(k)  # freed leaf by leaf
            zero = np.zeros(p.shape, np.float32)
            new[k], m, v = self._leaf_step(p, g, self.mu.get(k, zero),
                                           self.nu.get(k, zero), c1, c2)
            m.copy_to_host_async()
            v.copy_to_host_async()
            pending.append((k, m, v))
            if len(pending) > self.DEPTH:
                settle()
        while pending:
            settle()
        return new


def follow_steps(seed: int, lm: Dict[str, Any], eps: float, batches,
                 optimizer: Dict[str, Any], fit_seed: int = 0,
                 precision: Optional[str] = None,
                 rows: Optional[Sequence[int]] = None,
                 drop_expert: Optional[int] = None) -> Dict[str, Any]:
    """Drive the reference through ``batches`` (steps, batch, seq) from
    the seed's weights, step ``i`` under the noise of global step ``i``
    of a fit seeded ``fit_seed``. Returns each step's loss, each step's
    copies (layers, held) and masked positions and, per leaf
    (``leaf_norms``), the norms of Adam's first moment and of the
    parameters' change after the last step."""
    from benchmark import weights_sdar

    params = flat_weights(seed, lm)
    opt = PipelinedAdamW(optimizer["learning_rate"],
                         optimizer["weight_decay"])
    losses: List[float] = []
    copies, masked_counts = [], []
    for step, batch in enumerate(batches):
        t, masked = step_noise(fit_seed, step, batch.shape[0],
                               batch.shape[1])
        loss, grads, counts = batch_loss_and_grads(
            params, batch, t, masked, lm, eps, precision, rows, drop_expert)
        losses.append(loss)
        copies.append(counts)
        use = slice(None) if rows is None else list(rows)
        masked_counts.append(int(np.asarray(masked)[use].sum()))
        params = opt.step(params, grads)
        del grads
    key = weights_sdar.seed_key(seed)
    start = lambda p, shape, kind: weights_sdar.make_leaf(  # noqa: E731
        key, p, shape, kind)
    table = weights_sdar.leaf_table(lm)
    change = leaf_norms({"/".join(p): params["/".join(p)] - start(p, s, k)
                         for p, s, k in table})
    return {"losses": losses, "copies": np.stack(copies),
            "masked": masked_counts,
            "mu_norm": leaf_norms(opt.mu), "change_norm": change}
