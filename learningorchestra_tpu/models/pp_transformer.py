"""Pipeline-parallel transformer LM (GPipe over the ``pp`` axis).

The fifth parallelism axis, integrated with a real model: decoder
blocks are the pipelined middle (one or more layers per stage, stage
params stacked on a leading ``n_stages`` dim and sharded over ``pp``
by :func:`parallel.pipeline.pipeline_apply`), while the embedding and
the tied output head run outside the pipeline where activation shapes
change. Blocks are pure-jnp (pre-norm causal attention + gated MLP) so
one ``stage_fn`` serves every stage — the GPipe schedule requires
uniform activation shapes across stage boundaries.

Backward is plain autodiff through the pipelined scan: the transpose
of ``ppermute`` is the reverse rotation, so XLA derives the backward
fill/drain schedule from the forward one.

The reference has no pipeline (or any) model parallelism
(SURVEY §2.4); this module plus ``parallel/pipeline.py`` is the
net-new PP component pair.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from learningorchestra_tpu.parallel import pipeline as pp_lib
from learningorchestra_tpu.runtime import mesh as mesh_lib

NEG_INF = -1e30


def init_params(rng, vocab_size: int, d_model: int, n_layers: int,
                d_ff: Optional[int] = None) -> Dict[str, Any]:
    """Param pytree: ``embed`` (V, D) + per-layer tensors stacked on a
    leading ``n_layers`` dim (the layout PP stage-sharding wants)."""
    d_ff = d_ff or 4 * d_model
    ke, kq, ko, ki, kw = jax.random.split(rng, 5)
    s_in = 1.0 / math.sqrt(d_model)
    s_ff = 1.0 / math.sqrt(d_ff)

    def stack(key, shape, scale):
        return (jax.random.normal(key, (n_layers,) + shape) *
                scale).astype(jnp.float32)

    return {
        "embed": (jax.random.normal(ke, (vocab_size, d_model)) *
                  s_in).astype(jnp.float32),
        "blocks": {
            "ln1": jnp.ones((n_layers, d_model), jnp.float32),
            "qkv": stack(kq, (d_model, 3 * d_model), s_in),
            "o": stack(ko, (d_model, d_model), s_in),
            "ln2": jnp.ones((n_layers, d_model), jnp.float32),
            "wi": stack(ki, (d_model, d_ff), s_in),
            "wo": stack(kw, (d_ff, d_model), s_ff),
        },
    }


def _rms_norm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * scale


def _block(p: Dict[str, jnp.ndarray], x: jnp.ndarray,
           n_heads: int, attention: str = "auto",
           window: int = 0) -> jnp.ndarray:
    """One decoder block, (b, s, d) -> (b, s, d). Pure jnp so it can be
    the uniform GPipe stage body; on TPU the attention runs the Pallas
    flash kernel (no (s, s) score tensor per microbatch — the same
    long-context property as the main LM family), the dense einsum
    elsewhere."""
    if attention not in ("auto", "flash", "dense"):
        raise ValueError(
            f"unknown attention impl: {attention!r} "
            f"(auto|flash|dense)")
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    b, s, d = x.shape
    h = _rms_norm(x, p["ln1"])
    q, k, v = jnp.split(h @ p["qkv"], 3, axis=-1)

    def heads(t):
        return t.reshape(b, s, n_heads, d // n_heads)

    q, k, v = heads(q), heads(k), heads(v)
    scale = 1.0 / math.sqrt(d // n_heads)
    # same crossover as the LM families (not measured on today's
    # code): flash from seq 1024 on TPU, dense oracle below
    use_flash = (attention == "flash" or
                 (attention == "auto" and s >= 1024 and
                  jax.default_backend() == "tpu"))
    if use_flash:
        from learningorchestra_tpu.ops import attention as attn_ops

        attn = attn_ops.flash_attention(
            q, k, v, causal=True, scale=scale,
            window=window).reshape(b, s, d)
    else:
        from learningorchestra_tpu.parallel import ring as ring_lib

        # the dense oracle (and its banded-window mask) lives in ONE
        # place — the same fallback _dispatch_attention uses
        attn = ring_lib.full_attention_reference(
            q, k, v, causal=True, scale=scale,
            window=window).reshape(b, s, d)
    x = x + attn @ p["o"]
    h = _rms_norm(x, p["ln2"])
    return x + (jax.nn.silu(h @ p["wi"]) @ p["wo"])


def _stage_fn_for(n_heads: int, layers_per_stage: int,
                  attention: str = "auto", window: int = 0):
    """Uniform stage body: run this stage's ``layers_per_stage`` blocks
    in order. ``pipeline_apply_local`` already stripped the leading
    local-shard dim, so leaves arrive as (layers_per_stage, ...)."""
    def stage_fn(stage_params, x):
        if layers_per_stage == 1:
            lp = jax.tree_util.tree_map(lambda a: a[0], stage_params)
            return _block(lp, x, n_heads, attention, window)
        x, _ = jax.lax.scan(
            lambda carry, lp: (_block(lp, carry, n_heads, attention,
                                      window),
                               None),
            x, stage_params)
        return x

    return stage_fn


def _embed_in(embed: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Embedding + fixed sinusoidal positions (params-free positions
    keep the pipelined stages uniform)."""
    x = embed[tokens]
    d = x.shape[-1]
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    freqs = jnp.exp(-jnp.arange(0, d, 2, dtype=jnp.float32) / d *
                    math.log(10000.0))
    ang = pos[:, None] * freqs[None, :]
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)[None]
    return x + pe.astype(x.dtype)


def _stage_setup(params: Dict[str, Any], mesh):
    """Shared pipeline prologue: pp size, stage layout validation, and
    the (pp, layers_per_stage, ...) stage-param reshape — one place so
    the GPipe and 1F1B schedules can't desynchronize."""
    blocks = params["blocks"]
    n_layers = blocks["qkv"].shape[0]
    pp = mesh.shape.get(mesh_lib.PP, 1) if mesh is not None else 1
    if n_layers % pp:
        raise ValueError(f"{n_layers} layers not divisible by pp={pp}")
    layers_per_stage = n_layers // pp
    stage_params = None
    if pp > 1:
        stage_params = jax.tree_util.tree_map(
            lambda a: a.reshape((pp, layers_per_stage) + a.shape[1:]),
            blocks)
    return pp, layers_per_stage, stage_params


def forward(params: Dict[str, Any], tokens: jnp.ndarray, mesh,
            n_heads: int, num_microbatches: int = 4,
            attention: str = "auto", window: int = 0) -> jnp.ndarray:
    """tokens (b, s) int32 -> logits (b, s, V); blocks pipelined over
    ``pp``, embedding and tied head outside the pipeline."""
    pp, layers_per_stage, stage_params = _stage_setup(params, mesh)
    blocks = params["blocks"]
    embed = params["embed"]
    x = _embed_in(embed, tokens)

    if pp > 1:
        x = pp_lib.pipeline_apply(
            _stage_fn_for(n_heads, layers_per_stage, attention,
                          window),
            stage_params, x,
            mesh, num_microbatches=num_microbatches)
    else:
        for i in range(blocks["qkv"].shape[0]):
            x = _block(jax.tree_util.tree_map(lambda a, i=i: a[i], blocks),
                       x, n_heads, attention, window)
    return x @ embed.T  # tied head


def next_token_loss(params, tokens, mesh, n_heads: int,
                    num_microbatches: int = 4,
                    attention: str = "auto", window: int = 0):
    logits = forward(params, tokens, mesh, n_heads,
                     num_microbatches=num_microbatches,
                     attention=attention, window=window)
    tgt = tokens[:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    per_tok = optax.softmax_cross_entropy_with_integer_labels(lg, tgt)
    mask = (tgt != 0).astype(jnp.float32)
    return jnp.sum(per_tok * mask) / jnp.maximum(jnp.sum(mask), 1e-9)


def _head_loss(embed: jnp.ndarray, out: jnp.ndarray,
               y_mb: jnp.ndarray) -> jnp.ndarray:
    """Tied-head next-token loss for one microbatch (mean over its
    unpadded tokens). 1F1B's total loss is the mean over microbatches
    — identical to the full-batch mean when microbatches carry equal
    mask counts (no padding), the standard practice tradeoff."""
    logits = out @ embed.T
    tgt = y_mb[:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    per_tok = optax.softmax_cross_entropy_with_integer_labels(lg, tgt)
    mask = (tgt != 0).astype(jnp.float32)
    return jnp.sum(per_tok * mask) / jnp.maximum(jnp.sum(mask), 1e-9)


def value_and_grad_1f1b(params, tokens: jnp.ndarray, mesh, n_heads: int,
                        num_microbatches: int = 4,
                        attention: str = "auto", window: int = 0):
    """Hand-assembled train pass on the 1F1B schedule
    (parallel/pipeline.py): the pipelined middle returns its stage
    grads plus dx; the embedding's gradient combines the tied head's
    contribution with the lookup scatter — no outer autodiff through
    the pipeline loop."""
    pp, layers_per_stage, stage_params = _stage_setup(params, mesh)
    if pp <= 1:
        raise ValueError("1F1B needs a pp axis of size >= 2")
    embed = params["embed"]
    n_layers = params["blocks"]["qkv"].shape[0]
    x = _embed_in(embed, tokens)
    loss, dstage, dembed_head, dx = pp_lib.pipeline_value_and_grad_1f1b(
        _stage_fn_for(n_heads, layers_per_stage, attention, window),
        _head_loss,
        stage_params, embed, x, tokens, mesh,
        num_microbatches=num_microbatches)
    dblocks = jax.tree_util.tree_map(
        lambda g: g.reshape((n_layers,) + g.shape[2:]), dstage)
    d = embed.shape[-1]
    dembed = dembed_head + jnp.zeros_like(embed, jnp.float32).at[
        tokens.reshape(-1)].add(dx.reshape(-1, d))
    return loss, {"embed": dembed.astype(embed.dtype), "blocks": dblocks}


def fit(params, tokens: np.ndarray, mesh, n_heads: int, steps: int = 4,
        batch_size: Optional[int] = None, learning_rate: float = 1e-3,
        num_microbatches: int = 4, schedule: str = "gpipe",
        attention: str = "auto", window: int = 0,
        ) -> Tuple[Dict[str, Any], List[float]]:
    """Minimal jitted training loop (dryrun / test harness — the full
    REST-facing engine path uses LanguageModel; this validates the PP
    compute path, forward AND backward, end to end).

    ``schedule``: ``"gpipe"`` (autodiff through the fill/drain scan)
    or ``"1f1b"`` (hand-scheduled one-forward-one-backward with
    bounded activation stash)."""
    optimizer = optax.adam(learning_rate)
    opt_state = optimizer.init(params)
    bs = batch_size or tokens.shape[0]

    @jax.jit
    def step(p, o, batch):
        if schedule == "1f1b":
            loss, grads = value_and_grad_1f1b(p, batch, mesh, n_heads,
                                              num_microbatches,
                                              attention=attention,
                                              window=window)
        else:
            def loss_of(t):
                return next_token_loss(t, batch, mesh, n_heads,
                                       num_microbatches,
                                       attention=attention,
                                       window=window)

            loss, grads = jax.value_and_grad(loss_of)(p)
        updates, o = optimizer.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    losses: List[float] = []
    for i in range(steps):
        start = (i * bs) % max(1, len(tokens) - bs + 1)
        batch = jnp.asarray(tokens[start:start + bs])
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    return params, losses
