"""Decoder-only transformer family — the framework's flagship
long-context architecture.

The reference has no attention or transformer anywhere (SURVEY §5
"long-context" row: sequence models run as opaque user TF code through
the generic executor, binary_execution.py:177-189). This module is the
net-new TPU-first model family the parallelism library was built for:

- param naming matches ``parallel.sharding.TRANSFORMER_RULES`` exactly
  (``embed/embedding``, ``q_proj|k_proj|v_proj|o_proj/kernel``,
  ``gate|up_proj|down_proj/kernel``, ``experts/w_gate|w_up|w_down``,
  ``lm_head/kernel``), so TP/FSDP/EP sharding is a table lookup;
- attention is pluggable per config: ``dot`` (XLA-fused reference),
  ``flash`` (Pallas kernel, shard_map'd over heads so TP keeps the
  kernel local), ``ring`` (sequence-parallel KV rotation over ``sp``),
  ``ulysses`` (all-to-all head scatter over ``sp``);
- rotary position embeddings + RMSNorm + gated-SiLU MLP — the modern
  decoder block, all MXU-shaped matmuls;
- optional mixture-of-experts MLP (``n_experts > 0``) through
  ``parallel.moe``: gated experts behind a softmax router, the experts
  HELD here (``experts_held``, ``expert_offset``) as one grouped
  product with no dropped token; the older capacity schedule over
  ``ep``;
- ``head_dim`` and per-head QK-norm as settings, and a second training
  objective, block diffusion (docs/DIFFUSION.md).

``LanguageModel`` wraps the flax module in the same keras-shaped
method surface as :class:`~learningorchestra_tpu.models.neural.
NeuralModel` (fit/evaluate/predict + generate), because those method
names and kwargs are the reference's REST contract
(``method: "fit"``, binary_executor_image/server.py:23-71).
"""

from __future__ import annotations

import functools
import json
import math
import os
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from learningorchestra_tpu.observability import trace as obs_trace
from learningorchestra_tpu.ops import attention as attn_ops
from learningorchestra_tpu.ops import ssd as ssd_ops
from learningorchestra_tpu.parallel import moe as moe_lib
from learningorchestra_tpu.parallel import ring as ring_lib
from learningorchestra_tpu.parallel import sharding as sharding_lib
from learningorchestra_tpu.parallel import ulysses as ulysses_lib
from learningorchestra_tpu.runtime import data as data_lib
from learningorchestra_tpu.runtime import engine as engine_lib
from learningorchestra_tpu.runtime import mesh as mesh_lib

ATTENTION_IMPLS = ("dot", "flash", "ring", "ulysses")
LAYER_TYPES = ("attention", "mamba")
# parameters the engine leaves in float32 under a bf16 step: a Mamba-2
# mixer's decay exponents come from them (docs/STATE_SPACE.md)
FLOAT32_LEAVES = ("A_log", "dt_bias")


# ----------------------------------------------------------------------
# rotary position embeddings
# ----------------------------------------------------------------------
def rope_tables(seq_len: int, head_dim: int, base: float = 10000.0,
                offset: int = 0) -> Tuple[jax.Array, jax.Array]:
    half = head_dim // 2
    freqs = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    pos = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)
    ang = pos[:, None] * freqs[None, :]                    # (s, half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (b, s, h, d) with d even; rotate pairs (x1, x2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[None, :, None, :].astype(x.dtype)
    s = sin[None, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


# ----------------------------------------------------------------------
# flax modules
# ----------------------------------------------------------------------
class _Experts(nn.Module):
    """Bare param holder so the gated experts' weights live at
    ``.../experts/*`` where the EP sharding rules expect them."""
    n_experts: int      # the experts HELD here
    d_model: int
    d_ff: int

    @nn.compact
    def __call__(self):
        def stacked(name, fan_in, fan_out):
            return self.param(
                name, nn.initializers.normal(1.0 / math.sqrt(fan_in)),
                (self.n_experts, fan_in, fan_out))

        return {"w_gate": stacked("w_gate", self.d_model, self.d_ff),
                "w_up": stacked("w_up", self.d_model, self.d_ff),
                "w_down": stacked("w_down", self.d_ff, self.d_model)}


class _LoRADense(nn.Module):
    """Dense with an additive low-rank adapter: y = xW + (xA)B·(α/r).

    A is init'd like a normal layer, B at zero, so step 0 reproduces
    the base model exactly. The base ``kernel`` keeps the plain
    nn.Dense param name/shape, so existing artifacts load into the
    LoRA variant unchanged (the adapters init fresh) and sharding
    rules keyed on the module path still match."""

    features: int
    rank: int
    alpha: float

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features))
        y = x @ kernel
        a = self.param("lora_a", nn.initializers.lecun_normal(),
                       (x.shape[-1], self.rank))
        b = self.param("lora_b", nn.initializers.zeros,
                       (self.rank, self.features))
        scale = jnp.asarray(self.alpha / self.rank, x.dtype)
        return y + (x @ a.astype(x.dtype)) @ b.astype(x.dtype) * scale


def _make_dense(name: str, features: int, lora_rank: int,
                lora_alpha: float):
    if lora_rank > 0:
        return _LoRADense(features, lora_rank, lora_alpha, name=name)
    return nn.Dense(features, use_bias=False, name=name)


class _Attention(nn.Module):
    """Multi-head attention with optional grouped-query KV heads.

    ``n_kv_heads < n_heads`` is GQA (``=1`` is MQA): K/V are projected
    to fewer heads and each KV head serves a GROUP of query heads. On
    TPU the win is HBM, not FLOPs — the KV cache (the whole memory
    story of long-context decode) shrinks by ``n_heads/n_kv_heads``,
    and the decode step reads proportionally less HBM per token. The
    decode path computes grouped attention directly (no head repeat);
    the train/prefill path repeats KV up to ``n_heads`` before
    :func:`_dispatch_attention` so every impl (dot/flash/ring/ulysses)
    sees uniform heads — XLA fuses the repeat into the consuming
    matmul, so training costs the same as full-head attention."""

    n_heads: int
    head_dim: int
    impl: str
    causal: bool
    mesh: Any = None
    n_kv_heads: int = 0      # 0 -> n_heads (standard MHA)
    # one (d, 3*proj) matmul instead of three (d, proj) ones: at small
    # d_model the MXU is under-tiled in the output dim, so widening N
    # 3x raises utilization (not measured on today's code).
    # MHA only — under GQA the q/k/v widths differ and column-sharding
    # the concatenation would split across block boundaries.
    fused_qkv: bool = False
    # LoRA adapters on the attention projections (rank 0 = off)
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # sliding-window (banded causal) attention; 0 = unlimited
    window: int = 0
    # RoPE frequency base; raise (e.g. 500000) to stretch usable
    # context (NTK-style scaling)
    rope_base: float = 10000.0
    # RMS norm of q and k over the head's width, with a learned scale
    # of head_dim each, before RoPE (the Qwen3 lineage)
    qk_norm: bool = False
    # block diffusion (docs/DIFFUSION.md): > 0 and the row is
    # [noisy ; clean], 2L positions, under ops.attention's bd mask
    bd_block: int = 0
    # no rotary or any other position term (``position_embedding_type:
    # "nope"``), and the softmax's scale as a number (0: 1/sqrt(head_dim));
    # both on the training and prefill path only (LanguageModel refuses
    # to decode a model that sets them)
    rope: bool = True
    scale: float = 0.0
    eps: float = 1e-6        # of the QK-norms

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def _cache_vars(self, b: int, cache_len: int, dtype):
        shape = (b, cache_len, self.kv_heads, self.head_dim)
        ck = self.variable("cache", "k", jnp.zeros, shape, dtype)
        cv = self.variable("cache", "v", jnp.zeros, shape, dtype)
        return ck, cv

    @nn.compact
    def __call__(self, x, train: bool, decode_pos=None, cache_len: int = 0,
                 pad_offset=None, kv_len=None, block_tables=None,
                 page_len: int = 0, kv_pages: int = 0,
                 kv_quant: bool = False, verify_limit=None):
        d_model = x.shape[-1]
        kv = self.kv_heads
        if self.n_heads % kv:
            raise ValueError(
                f"n_kv_heads={kv} must divide n_heads={self.n_heads}")
        group = self.n_heads // kv
        proj = self.n_heads * self.head_dim
        dense = lambda name, feats: _make_dense(  # noqa: E731
            name, feats, self.lora_rank, self.lora_alpha)
        b, s, _ = x.shape
        shape4 = (b, s, self.n_heads, self.head_dim)
        kv_shape4 = (b, s, kv, self.head_dim)
        if self.fused_qkv and kv == self.n_heads:
            qkv = dense("qkv_proj", 3 * proj)(x)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q, k, v = (q.reshape(shape4), k.reshape(shape4),
                       v.reshape(shape4))
        else:
            q = dense("q_proj", proj)(x).reshape(shape4)
            k = dense("k_proj", kv * self.head_dim)(x).reshape(kv_shape4)
            v = dense("v_proj", kv * self.head_dim)(x).reshape(kv_shape4)
        if self.qk_norm:
            q = nn.RMSNorm(epsilon=self.eps, name="q_norm")(q)
            k = nn.RMSNorm(epsilon=self.eps, name="k_norm")(k)

        if decode_pos is not None and jnp.ndim(decode_pos) == 0 \
                and pad_offset is None:
            # single-token step at absolute position decode_pos: rope
            # from the scalar position, attend over the KV cache
            half = self.head_dim // 2
            freqs = 1.0 / (self.rope_base ** (
                jnp.arange(half, dtype=jnp.float32) / half))
            ang = decode_pos.astype(jnp.float32) * freqs       # (half,)
            cos, sin = jnp.cos(ang)[None, :], jnp.sin(ang)[None, :]
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
            ck, cv = self._cache_vars(b, cache_len, x.dtype)
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k.astype(x.dtype), (0, decode_pos, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v.astype(x.dtype), (0, decode_pos, 0, 0))
            # grouped scores: each KV head serves its `group` query
            # heads directly — the cache is never expanded to n_heads
            qg = q.astype(jnp.float32).reshape(
                b, s, kv, group, self.head_dim)
            scores = jnp.einsum(
                "bqhgd,bkhd->bqhgk", qg,
                ck.value.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ) / math.sqrt(self.head_dim)
            visible = jnp.arange(cache_len) <= decode_pos
            if self.window > 0:
                visible = jnp.logical_and(
                    visible,
                    jnp.arange(cache_len) > decode_pos - self.window)
            scores = jnp.where(visible[None, None, None, None, :], scores,
                               ring_lib.NEG_INF)
            p = jax.nn.softmax(scores, axis=-1)
            o = jnp.einsum("bqhgk,bkhd->bqhgd", p,
                           cv.value.astype(jnp.float32)
                           ).reshape(shape4).astype(x.dtype)
        elif decode_pos is not None:
            # per-row decode step: continuous-batched serving (every
            # slot sits at its OWN cache position) or a left-padded
            # batch decoding one shared column. The math is the scalar
            # branch's, elementwise per row — rope angle, cache write,
            # grouped scores, visibility mask — so a slot's output
            # bits match a solo batch-1 decode of the same request
            # (docs/SERVING.md bit-identity contract).
            pos = decode_pos if jnp.ndim(decode_pos) else \
                jnp.full((b,), decode_pos, jnp.int32)
            rel = pos if pad_offset is None else pos - pad_offset
            half = self.head_dim // 2
            freqs = 1.0 / (self.rope_base ** (
                jnp.arange(half, dtype=jnp.float32) / half))
            if s > 1:
                # speculative-verify step: s consecutive positions per
                # row (last accepted token + k drafts), query j at
                # absolute position pos + j. Per-position rope, the
                # sequential append order and the per-position masked
                # reduction all match s single-token steps bit-for-bit
                # (ops/attention.py paged_verify_attention), which is
                # what lets greedy speculative decode inherit the
                # bit-identity contract (docs/SERVING.md).
                if block_tables is None:
                    raise ValueError(
                        "multi-position decode (speculative verify) "
                        "requires the paged KV path (block_tables)")
                rel2 = (rel[:, None]
                        + jnp.arange(s)[None, :]).astype(jnp.float32)
                angv = rel2[:, :, None] * freqs[None, None, :]
                cosv = jnp.cos(angv)[:, :, None, :]  # (b, s, 1, half)
                sinv = jnp.sin(angv)[:, :, None, :]

                def rotv(t):
                    t1, t2 = jnp.split(t, 2, axis=-1)
                    c, si = cosv.astype(t.dtype), sinv.astype(t.dtype)
                    return jnp.concatenate(
                        [t1 * c - t2 * si, t1 * si + t2 * c], axis=-1)

                q, k = rotv(q), rotv(k)
                pool_shape = (kv_pages, page_len, kv, self.head_dim)
                if kv_quant:
                    ck = self.variable("cache", "k", jnp.zeros,
                                       pool_shape, jnp.int8)
                    cv = self.variable("cache", "v", jnp.zeros,
                                       pool_shape, jnp.int8)
                    cks = self.variable("cache", "k_scale", jnp.zeros,
                                        (kv_pages, kv), jnp.float32)
                    cvs = self.variable("cache", "v_scale", jnp.zeros,
                                        (kv_pages, kv), jnp.float32)
                    ck.value, cks.value = \
                        attn_ops.quantized_paged_append_tokens(
                            ck.value, cks.value, k, block_tables,
                            pos, page_len, limit=verify_limit)
                    cv.value, cvs.value = \
                        attn_ops.quantized_paged_append_tokens(
                            cv.value, cvs.value, v, block_tables,
                            pos, page_len, limit=verify_limit)
                    o = attn_ops.quantized_paged_verify_attention(
                        q, ck.value, cks.value, cv.value, cvs.value,
                        block_tables, pos, pad_offset=pad_offset,
                        window=self.window).reshape(shape4)
                else:
                    ck = self.variable("cache", "k", jnp.zeros,
                                       pool_shape, x.dtype)
                    cv = self.variable("cache", "v", jnp.zeros,
                                       pool_shape, x.dtype)
                    ck.value = attn_ops.paged_append_tokens(
                        ck.value, k, block_tables, pos, page_len,
                        limit=verify_limit)
                    cv.value = attn_ops.paged_append_tokens(
                        cv.value, v, block_tables, pos, page_len,
                        limit=verify_limit)
                    o = attn_ops.paged_verify_attention(
                        q, ck.value, cv.value, block_tables, pos,
                        pad_offset=pad_offset,
                        window=self.window).reshape(shape4)
                o = o.reshape(b, s, proj)
                return dense("o_proj", d_model)(o)
            ang = rel.astype(jnp.float32)[:, None] * freqs[None, :]
            cos = jnp.cos(ang)[:, None, None, :]       # (b, 1, 1, half)
            sin = jnp.sin(ang)[:, None, None, :]

            def rot(t):
                t1, t2 = jnp.split(t, 2, axis=-1)
                c, si = cos.astype(t.dtype), sin.astype(t.dtype)
                return jnp.concatenate(
                    [t1 * c - t2 * si, t1 * si + t2 * c], axis=-1)

            q, k = rot(q), rot(k)
            if block_tables is not None:
                # paged serving decode: the cache variable is the
                # SHARED page pool, not a per-slot rectangle. Rope,
                # the written K/V values, the grouped reduction and
                # the visibility mask are all the slot branch's —
                # only the storage addressing differs — so a paged
                # stream's output bits still match a solo decode
                # (docs/SERVING.md bit-identity contract).
                pool_shape = (kv_pages, page_len, kv, self.head_dim)
                if kv_quant:
                    # int8 pool + per-page-per-head float32 scale pool
                    # (docs/SERVING.md "Quantized serving"): append
                    # requantizes the touched page against its live
                    # rows; decode fuses dequant into the bounded
                    # gather, so no bf16 pool copy ever materializes
                    ck = self.variable("cache", "k", jnp.zeros,
                                       pool_shape, jnp.int8)
                    cv = self.variable("cache", "v", jnp.zeros,
                                       pool_shape, jnp.int8)
                    cks = self.variable("cache", "k_scale", jnp.zeros,
                                        (kv_pages, kv), jnp.float32)
                    cvs = self.variable("cache", "v_scale", jnp.zeros,
                                        (kv_pages, kv), jnp.float32)
                    ck.value, cks.value = \
                        attn_ops.quantized_paged_append_token(
                            ck.value, cks.value, k[:, 0], block_tables,
                            pos, page_len)
                    cv.value, cvs.value = \
                        attn_ops.quantized_paged_append_token(
                            cv.value, cvs.value, v[:, 0], block_tables,
                            pos, page_len)
                    o = attn_ops.quantized_paged_decode_attention(
                        q, ck.value, cks.value, cv.value, cvs.value,
                        block_tables, pos, pad_offset=pad_offset,
                        window=self.window).reshape(shape4)
                else:
                    ck = self.variable("cache", "k", jnp.zeros,
                                       pool_shape, x.dtype)
                    cv = self.variable("cache", "v", jnp.zeros,
                                       pool_shape, x.dtype)
                    ck.value = attn_ops.paged_append_token(
                        ck.value, k[:, 0], block_tables, pos, page_len)
                    cv.value = attn_ops.paged_append_token(
                        cv.value, v[:, 0], block_tables, pos, page_len)
                    o = attn_ops.paged_decode_attention(
                        q, ck.value, cv.value, block_tables, pos,
                        pad_offset=pad_offset,
                        window=self.window).reshape(shape4)
            else:
                ck, cv = self._cache_vars(b, cache_len, x.dtype)
                rows = jnp.arange(b)
                ck.value = ck.value.at[rows, pos].set(
                    k[:, 0].astype(x.dtype))
                cv.value = cv.value.at[rows, pos].set(
                    v[:, 0].astype(x.dtype))
                o = attn_ops.decode_attention(
                    q, ck.value, cv.value, pos, pad_offset=pad_offset,
                    window=self.window).reshape(shape4)
        else:
            if not self.rope:
                if pad_offset is not None or decode_pos is not None:
                    raise NotImplementedError(
                        "attention without RoPE runs on the training path")
            elif self.bd_block:
                # both halves of [noisy ; clean] sit at positions 0..L-1
                cos, sin = rope_tables(s // 2, self.head_dim,
                                       base=self.rope_base)
                cos, sin = jnp.tile(cos, (2, 1)), jnp.tile(sin, (2, 1))
                q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            elif pad_offset is None:
                cos, sin = rope_tables(s, self.head_dim,
                                       base=self.rope_base)
                q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            else:
                # left-padded batch prefill: each row's rope position
                # is its content-relative index (negative over the pad
                # columns — masked below, never read)
                half = self.head_dim // 2
                freqs = 1.0 / (self.rope_base ** (
                    jnp.arange(half, dtype=jnp.float32) / half))
                rel = (jnp.arange(s)[None, :]
                       - pad_offset[:, None]).astype(jnp.float32)
                ang = rel[:, :, None] * freqs[None, None, :]
                cos = jnp.cos(ang)[:, :, None, :]   # (b, s, 1, half)
                sin = jnp.sin(ang)[:, :, None, :]

                def rot(t):
                    t1, t2 = jnp.split(t, 2, axis=-1)
                    c, si = cos.astype(t.dtype), sin.astype(t.dtype)
                    return jnp.concatenate(
                        [t1 * c - t2 * si, t1 * si + t2 * c], axis=-1)

                q, k = rot(q), rot(k)
            kv_valid = None
            if pad_offset is not None:
                kv_valid = jnp.arange(s)[None, :] >= pad_offset[:, None]
            elif kv_len is not None:
                # right-padded serving prefill: rows past a request's
                # true length hold garbage keys — masked here; the
                # decode loop overwrites their cache rows column by
                # column before they ever become visible
                kv_valid = jnp.arange(s)[None, :] < kv_len[:, None]
            if cache_len:
                # prefill: stash the prompt's K/V so decode steps can
                # continue from position s without recomputing them
                ck, cv = self._cache_vars(b, cache_len, x.dtype)
                ck.value = ck.value.at[:, :s].set(k.astype(x.dtype))
                cv.value = cv.value.at[:, :s].set(v.astype(x.dtype))
            o = _dispatch_attention(q, k, v, impl=self.impl,
                                    causal=self.causal, mesh=self.mesh,
                                    window=self.window,
                                    kv_valid=kv_valid,
                                    bd_block=self.bd_block,
                                    scale=self.scale or None)
        o = o.reshape(b, s, proj)
        return dense("o_proj", d_model)(o)


def _dispatch_attention(q, k, v, *, impl: str, causal: bool, mesh=None,
                        window: int = 0, kv_valid=None, bd_block: int = 0,
                        scale: Optional[float] = None):
    """q: (b, s, h, d); k/v may carry FEWER (kv) heads under GQA.
    The single-chip flash path consumes them natively (the kernel
    folds the query group — K/V never materialize at h heads); every
    other impl repeats K/V up to h first, which XLA fuses into the
    consuming matmul on the dot path. ``window`` composes with every
    impl: ring hops apply the exact banded mask at static cross-shard
    offsets (hops wholly below the band skip), Ulysses windows its
    local full-sequence attention. ``kv_valid`` (``(b, s)`` bool,
    padded-batch prefill) always routes to the dense reference path —
    the sharded/flash kernels take no per-row mask, a documented cost
    of unequal-length batches (docs/SERVING.md). ``bd_block`` > 0 is a
    block-diffusion row [noisy ; clean] (docs/DIFFUSION.md): the flash
    kernels under that mask, or its dense masked softmax on ``dot``;
    no window, no sequence parallelism. ``scale`` (None: 1/sqrt(d))
    is the softmax's, on the dot and flash paths."""
    mesh = mesh or mesh_lib.current_mesh()
    b, s, h, _ = q.shape
    kvh = k.shape[2]
    group = h // kvh
    extra = {} if scale is None else {"scale": float(scale)}
    if scale is not None and (bd_block or impl in ("ring", "ulysses")):
        raise ValueError("a given attention scale runs on the dot and "
                         "flash paths of the next-token objective")
    if bd_block:
        if window or kv_valid is not None or impl in ("ring", "ulysses"):
            raise ValueError(
                "block diffusion runs on the dot and flash paths, with "
                "no window and no padding mask")
        flash = functools.partial(attn_ops.flash_bd_attention,
                                  block_length=bd_block)
    else:
        flash = functools.partial(attn_ops.flash_attention,
                                  causal=causal, window=window, **extra)

    def repeated():
        if group == 1:
            return k, v
        return (jnp.repeat(k, group, axis=2),
                jnp.repeat(v, group, axis=2))
    if kv_valid is not None:
        kr, vr = repeated()
        return ring_lib.full_attention_reference(
            q, kr, vr, causal=causal, window=window, kv_valid=kv_valid,
            **extra)
    data_size = mesh_lib.data_parallel_size(mesh)
    sp = mesh.shape.get(mesh_lib.SP, 1)
    tp = mesh.shape.get(mesh_lib.TP, 1)
    # shard_map needs every mapped dim to divide its mesh axis; the
    # 1-sample param-init trace (and odd user shapes) fall back to the
    # fused full-softmax path, which is numerically identical
    divisible = b % data_size == 0 and s % sp == 0

    if impl == "ring" and sp > 1 and divisible:
        kr, vr = repeated()
        return ring_lib.ring_attention_sharded(q, kr, vr, mesh,
                                               causal=causal,
                                               window=window)
    if impl == "ulysses" and sp > 1 and divisible and h % sp == 0:
        # GQA-native when kv heads divide sp: the head scatter moves
        # kv-width K/V (group-fold less all_to_all traffic) and the
        # local flash kernel consumes the group directly
        kr, vr = (k, v) if kvh % sp == 0 else repeated()
        return ulysses_lib.ulysses_attention_sharded(q, kr, vr, mesh,
                                                     causal=causal,
                                                     window=window)
    if impl == "flash":
        sharded = tp > 1 or data_size > 1
        if not sharded:
            # GQA-native: unrepeated K/V straight into the kernel
            return flash(q, k, v)
        if b % data_size == 0 and h % tp == 0:
            if kvh % tp:
                # kv heads don't divide tp: repeat up to full heads so
                # the contiguous head shards stay well-formed
                k, v = repeated()
            # else: shard the kv-width K/V directly — contiguous head
            # sharding aligns each device's q-head chunk with its
            # kv-head chunk (h/tp == group * kvh/tp), so the per-shard
            # kernel stays GQA-native and K/V HBM still scales with kv
            # pallas_call is opaque to GSPMD — shard_map it so each
            # device runs the kernel on its local (batch, heads) tile
            # and TP never gathers heads
            data = mesh_lib.data_axes(mesh)
            spec = P(data if data else None, None,
                     mesh_lib.TP if tp > 1 else None, None)
            # check_vma=False: pallas_call emits ShapeDtypeStructs with
            # no varying-mesh-axes info, which the vma checker rejects
            fn = mesh_lib.shard_map(
                flash, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=spec, check_vma=False)
            return fn(q, k, v)
    # "dot" and all fallbacks (no sp axis, non-divisible shapes)
    if bd_block:
        return attn_ops.bd_attention_reference(q, k, v,
                                               block_length=bd_block)
    kr, vr = repeated()
    return ring_lib.full_attention_reference(q, kr, vr, causal=causal,
                                             window=window, **extra)


class _MLP(nn.Module):
    d_ff: int
    fused_gate_up: bool = False  # one (d, 2*d_ff) matmul (see fused_qkv)

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]
        if self.fused_gate_up:
            gu = nn.Dense(2 * self.d_ff, use_bias=False,
                          name="gate_up")(x)
            gate, up = jnp.split(gu, 2, axis=-1)
        else:
            gate = nn.Dense(self.d_ff, use_bias=False, name="gate")(x)
            up = nn.Dense(self.d_ff, use_bias=False, name="up_proj")(x)
        h = nn.silu(gate) * up
        return nn.Dense(d_model, use_bias=False, name="down_proj")(h)


class _MoE(nn.Module):
    """Gated experts behind a router over ``n_experts``; this module
    HOLDS ``experts_held`` of them (0: all), from ``expert_offset`` on,
    and gives their part of the layer (parallel/moe.py). Returns
    ``(out, aux, counts)``: ``counts`` (held,) int32, the routed copies
    each held expert received."""
    n_experts: int
    d_ff: int
    k: int = 2
    mesh: Any = None
    experts_held: int = 0
    expert_offset: int = 0

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]
        gate = self.param("gate",
                          nn.initializers.normal(1.0 / math.sqrt(d_model)),
                          (d_model, self.n_experts))
        experts = _Experts(self.experts_held or self.n_experts, d_model,
                           self.d_ff, name="experts")()
        params = {"gate": gate, "experts": experts}
        mesh = self.mesh or mesh_lib.current_mesh()
        ep_mesh = mesh if (mesh_lib.EP in mesh.axis_names and
                           mesh.shape[mesh_lib.EP] > 1) else None
        return moe_lib.moe_layer(params, x, k=self.k, mesh=ep_mesh,
                                 expert_offset=self.expert_offset)


def _check_layer_types(layer_types, n_layers: int) -> None:
    if layer_types is not None and (
            len(layer_types) != int(n_layers)
            or set(layer_types) - set(LAYER_TYPES)):
        raise ValueError(
            f"layer_types must name one of {LAYER_TYPES} for each of the "
            f"{n_layers} layers, got {layer_types!r}")


def _scaled(x, factor: float):
    """``x * factor`` with the factor in float32 and the product rounded
    once: a factor cast to bf16 first is another number (0.22 becomes
    0.2197, every residual write 0.12% short; PERF.md, PR 32)."""
    if factor == 1.0:
        return x
    return (x.astype(jnp.float32) * factor).astype(x.dtype)


def _residual(x, h, multiplier: float):
    """``x + multiplier * h``; at 1.0 the sum the blocks always took."""
    return x + _scaled(h, multiplier)


class _Mamba2(nn.Module):
    """A Mamba-2 mixer (docs/STATE_SPACE.md): one projection to the gate
    ``z``, the conv's channels ``[x | B | C]`` and a step ``dt`` a head;
    a causal depthwise conv and SiLU over ``[x | B | C]``; the state-space
    scan (``ops/ssd.py``) over ``n_heads`` heads of ``head_dim`` with a
    state of ``head_dim x d_state`` each and ``B``, ``C`` shared by the
    heads (one group); ``RMSNorm(y * silu(z))`` over all channels with a
    learned scale; the projection back. Returns ``(out, stats)``:
    ``stats (2,)`` float32, the RMS of the states held at the rows' end
    and the mean decay ``a_t``, for the counters."""
    n_heads: int
    head_dim: int
    d_state: int
    d_conv: int = 4
    chunk: int = 256
    eps: float = 1e-6

    @nn.compact
    def __call__(self, u):
        b, s, d_model = u.shape
        heads, hd, n = self.n_heads, self.head_dim, self.d_state
        d_inner = heads * hd
        conv_dim = d_inner + 2 * n
        f32 = jnp.float32
        with jax.named_scope("ssm/in_proj"):
            zxbcdt = nn.Dense(d_inner + conv_dim + heads, use_bias=False,
                              name="in_proj")(u)
        z = zxbcdt[..., :d_inner]
        xbc = zxbcdt[..., d_inner:d_inner + conv_dim]
        dt = zxbcdt[..., d_inner + conv_dim:]
        with jax.named_scope("ssm/conv"):
            # conv_kernel[k] weighs position t - (d_conv - 1) + k
            w = self.param("conv_kernel", nn.initializers.normal(
                1.0 / math.sqrt(self.d_conv)), (self.d_conv, conv_dim))
            bias = self.param("conv_bias", nn.initializers.zeros,
                              (conv_dim,))
            padded = jnp.pad(xbc.astype(f32),
                             ((0, 0), (self.d_conv - 1, 0), (0, 0)))
            acc = bias.astype(f32)
            for k in range(self.d_conv):
                acc = acc + padded[:, k:k + s] * w[k].astype(f32)
            xbc = nn.silu(acc).astype(u.dtype)
        x = xbc[..., :d_inner].reshape(b, s, heads, hd)
        B = xbc[..., d_inner:d_inner + n]
        C = xbc[..., d_inner + n:]

        def log_uniform(lo, hi, inverse_softplus=False):
            def init(key, shape, dtype=f32):
                v = jnp.exp(jax.random.uniform(
                    key, shape, f32, math.log(lo), math.log(hi)))
                if inverse_softplus:
                    v = v + jnp.log(-jnp.expm1(-v))
                return v.astype(dtype)
            return init

        # the Mamba-2 convention: dt in 0.001..0.1 after the softplus,
        # exp(A_log) in 1..16
        dt_bias = self.param("dt_bias", log_uniform(1e-3, 1e-1, True),
                             (heads,))
        a_log = self.param(
            "A_log", lambda key, shape, dtype=f32: jnp.log(
                jax.random.uniform(key, shape, f32, 1.0, 16.0)
            ).astype(dtype), (heads,))
        skip = self.param("D", nn.initializers.ones, (heads,))
        with jax.named_scope("ssm/scan"):
            dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
            A = -jnp.exp(a_log.astype(f32))
            y, state = ssd_ops.ssd(x, dt, A, B, C, skip, self.chunk)
            stats = jax.lax.stop_gradient(jnp.stack([
                jnp.sqrt(jnp.mean(jnp.square(state))),
                jnp.mean(jnp.exp(dt * A))]))
        with jax.named_scope("ssm/gate_norm"):
            y = y.reshape(b, s, d_inner) * nn.silu(z)
            y = nn.RMSNorm(epsilon=self.eps, name="norm")(y)
        with jax.named_scope("ssm/out_proj"):
            out = nn.Dense(d_model, use_bias=False, name="out_proj")(y)
        return out, stats


class _Block(nn.Module):
    n_heads: int
    head_dim: int
    d_ff: int
    attention: str
    causal: bool
    n_experts: int
    moe_k: int
    dropout: float
    mesh: Any = None
    n_kv_heads: int = 0
    fused_proj: bool = False
    lora_rank: int = 0
    lora_alpha: float = 16.0
    window: int = 0
    rope_base: float = 10000.0
    experts_held: int = 0
    expert_offset: int = 0
    qk_norm: bool = False
    bd_block: int = 0
    # the layer's mixer, "attention" or "mamba" (TransformerLM's
    # ``layer_types``), and what a Mamba-2 mixer is sized by:
    # (heads, head_dim, d_state, d_conv, chunk)
    mixer: str = "attention"
    ssm: Tuple[int, ...] = ()
    eps: float = 1e-6
    rope: bool = True
    attention_scale: float = 0.0
    residual_multiplier: float = 1.0

    @nn.compact
    def __call__(self, x, train: bool, decode_pos=None, cache_len: int = 0,
                 pad_offset=None, kv_len=None, block_tables=None,
                 page_len: int = 0, kv_pages: int = 0,
                 kv_quant: bool = False, verify_limit=None):
        """Returns ``(x, aux, counts, stats)``; ``counts`` is the expert
        layer's (held,) copies, of length 0 under a dense MLP; ``stats``
        a Mamba-2 mixer's (``_Mamba2``), None under attention."""
        stats = None
        if self.mixer == "mamba":
            if decode_pos is not None or cache_len:
                raise NotImplementedError(
                    "a Mamba-2 layer has no decode path yet: its "
                    "recurrent state is not carried between calls")
            h = nn.RMSNorm(epsilon=self.eps, name="ssm_norm")(x)
            h, stats = _Mamba2(*self.ssm, eps=self.eps, name="ssm")(h)
        else:
            h = nn.RMSNorm(epsilon=self.eps, name="attn_norm")(x)
            h = _Attention(self.n_heads, self.head_dim, self.attention,
                           self.causal, self.mesh,
                           n_kv_heads=self.n_kv_heads,
                           fused_qkv=self.fused_proj,
                           lora_rank=self.lora_rank,
                           lora_alpha=self.lora_alpha,
                           window=self.window,
                           rope_base=self.rope_base, qk_norm=self.qk_norm,
                           bd_block=self.bd_block, rope=self.rope,
                           scale=self.attention_scale, eps=self.eps,
                           name="attn")(
                h, train, decode_pos=decode_pos, cache_len=cache_len,
                pad_offset=pad_offset, kv_len=kv_len,
                block_tables=block_tables, page_len=page_len,
                kv_pages=kv_pages, kv_quant=kv_quant,
                verify_limit=verify_limit)
        if self.dropout and train:
            h = nn.Dropout(self.dropout, deterministic=False)(h)
        x = _residual(x, h, self.residual_multiplier)
        h = nn.RMSNorm(epsilon=self.eps, name="mlp_norm")(x)
        aux = jnp.zeros((), jnp.float32)
        counts = jnp.zeros((0,), jnp.int32)
        if self.n_experts > 0:
            h, aux, counts = _MoE(self.n_experts, self.d_ff, self.moe_k,
                                  self.mesh, self.experts_held,
                                  self.expert_offset, name="moe")(h)
        else:
            h = _MLP(self.d_ff, fused_gate_up=self.fused_proj,
                     name="mlp")(h)
        if self.dropout and train:
            h = nn.Dropout(self.dropout, deterministic=False)(h)
        return (_residual(x, h, self.residual_multiplier), aux, counts,
                stats)


class FusedHeadOut(NamedTuple):
    """Training output of a ``fused_head_chunk`` TransformerLM: the
    final hidden states plus the lm_head kernel, so the loss can run
    the vocab projection + cross-entropy in token chunks and the
    (tokens, vocab) logits tensor never materializes in HBM: at 8,190
    tokens by 92,544 rows that tensor would be 3.0 GB in float32,
    where a chunk's is 0.38 GB. The chunked head is 66.6 ms of a 203
    ms step on one v5e chip (PERF.md section 5, my chip run, PR 25);
    the full-logits path has not been run at that size."""
    hidden: Any     # (b, s, d) final-norm output
    kernel: Any     # (d, vocab) lm_head weight
    aux: Any        # MoE load-balance scalar
    # (layers, held) int32, the copies each held expert received; of
    # no column under dense MLPs
    moe_counts: Any = None
    # (layers,) int32, the tiles of the layer's row buffer that hold
    # those copies (parallel/moe.py:tiles_used); None under dense MLPs
    moe_tiles: Any = None
    # block diffusion: the step's noise, {"masked": (b, L) bool,
    # "t": (b,)}, put here by LanguageModel._apply_fn for the loss
    noise: Any = None
    # {"l<i>": (2,) float32} of the Mamba-2 layers: the RMS of the
    # states held at the rows' end and the mean decay (``_Mamba2``)
    ssm_stats: Any = None


class _LMHead(nn.Module):
    """The vocab projection as its own submodule (param tree stays
    ``lm_head/kernel``, identical to the previous nn.Dense) so the
    fused-loss path can hand the kernel to the loss instead of
    computing full logits."""
    vocab_size: int

    @nn.compact
    def __call__(self, h, return_kernel: bool = False):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (h.shape[-1], self.vocab_size))
        if return_kernel:
            return kernel
        return h @ kernel.astype(h.dtype)


class TransformerLM(nn.Module):
    """Decoder-only LM: tokens (b, s) int32 -> (logits (b, s, V), aux).

    ``aux`` is the summed MoE load-balance loss (zero for dense MLP).
    With ``fused_head_chunk > 0`` the TRAIN forward returns
    :class:`FusedHeadOut` instead of logits; eval/decode always
    produce full logits.
    """
    vocab_size: int
    d_model: int = 256
    n_layers: int = 4
    n_heads: int = 4
    n_kv_heads: int = 0      # 0 -> n_heads; < n_heads is GQA, 1 is MQA
    d_ff: int = 0            # 0 -> 4 * d_model
    attention: str = "dot"
    causal: bool = True
    n_experts: int = 0
    moe_k: int = 2
    dropout: float = 0.0
    mesh: Any = None
    fused_head_chunk: int = 0
    # fuse q/k/v into one (d, 3*proj) matmul and gate/up into one
    # (d, 2*d_ff) matmul — wider MXU output tiles at small d_model
    # (the measured d=512 roofline gap). The param-tree layout depends
    # ONLY on this config (never on the ambient mesh, so artifacts
    # stay portable across mesh shapes): under GQA the attention
    # self-gates back to separate q/k/v (unequal widths) while the
    # MLP still fuses, and under TP the sharding rules REPLICATE the
    # fused kernels (a column shard would cross block boundaries)
    # instead of changing the tree.
    fused_proj: bool = False
    # LoRA: rank-r adapters on the attention projections; the base
    # kernels keep their plain names/shapes so a pre-trained artifact
    # loads into the LoRA variant unchanged (adapters init fresh)
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # sliding-window attention (banded causal, Mistral-style): query p
    # attends [p-W+1, p]; the flash kernels iterate a banded tile
    # grid so compute AND K/V DMA scale ~O(s*W). Composes with every
    # impl incl. ring/Ulysses sequence parallelism.
    sliding_window: int = 0
    # RoPE frequency base (NTK-style context stretching)
    rope_base: float = 10000.0
    # per-layer rematerialization under training: "none" saves all
    # activations, "dots" saves matmul outputs only (the standard TPU
    # memory/FLOPs trade), "full" recomputes everything in backward
    remat: str = "none"
    # width of a head; 0 -> d_model // n_heads
    head_dim: int = 0
    qk_norm: bool = False
    # of n_experts routed over, the experts held here (0: all) and the
    # first of them (parallel/moe.py)
    experts_held: int = 0
    expert_offset: int = 0
    # block diffusion: > 0 and ``tokens`` is [noisy ; clean], 2L
    # positions (docs/DIFFUSION.md); the output is FusedHeadOut
    bd_block: int = 0
    # the per-layer spec: one of LAYER_TYPES a layer (None: attention
    # everywhere), and a Mamba-2 mixer's sizes (docs/STATE_SPACE.md)
    layer_types: Optional[Tuple[str, ...]] = None
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 256
    rms_norm_eps: float = 1e-6
    rope: bool = True              # False: no position term at all
    attention_scale: float = 0.0   # 0: 1/sqrt(head_dim)
    # x = embed * embedding_multiplier; x += residual_multiplier * f(x);
    # logits = h @ W / logits_scaling (the granite family's settings)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # the head is the embedding transposed: one table, no lm_head
    tie_embeddings: bool = False

    @nn.compact
    def __call__(self, tokens, train: bool = False, decode_pos=None,
                 cache_len: int = 0, pad_offset=None, kv_len=None,
                 block_tables=None, page_len: int = 0,
                 kv_pages: int = 0, kv_quant: bool = False,
                 verify_limit=None):
        if self.attention not in ATTENTION_IMPLS:
            raise ValueError(f"unknown attention impl: {self.attention!r}")
        d_ff = self.d_ff or 4 * self.d_model
        head_dim = self.head_dim or self.d_model // self.n_heads
        mesh = self.mesh or mesh_lib.current_mesh()
        fuse = self.fused_proj
        if self.bd_block and not self.fused_head_chunk:
            raise ValueError("the block-diffusion loss runs through the "
                             "chunked head: fused_head_chunk must be > 0")

        _check_layer_types(self.layer_types, self.n_layers)
        types = self.layer_types or ("attention",) * self.n_layers
        ssm = (self.ssm_heads, self.ssm_head_dim, self.ssm_state,
               self.ssm_conv, self.ssm_chunk)
        eps = self.rms_norm_eps
        embed = nn.Embed(self.vocab_size, self.d_model, name="embed")
        with jax.named_scope("embed"):
            x = _scaled(embed(tokens), self.embedding_multiplier)
        if decode_pos is None:
            x = sharding_lib.constrain(
                x, mesh, mesh_lib.data_axes(mesh) or None,
                mesh_lib.SP if self.attention in ("ring", "ulysses")
                else None,
                None)
        block_cls = _Block
        if self.remat != "none" and train and decode_pos is None:
            policies = {
                "dots": jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable,
                "full": jax.checkpoint_policies.nothing_saveable,
            }
            if self.remat not in policies:
                raise ValueError(
                    f"unknown remat policy {self.remat!r} "
                    f"(none|dots|full)")
            # args: (self, x, train, decode_pos, cache_len, ...,
            # block_tables, page_len, kv_pages, kv_quant) — the
            # non-array flags are static (the paged-decode args are
            # always None/0/False here: remat only wraps train)
            # prevent_cse=True: outside nn.scan, XLA's CSE can undo
            # the recomputation and keep activations live (the flax
            # docs' reason it defaults True under jit)
            block_cls = nn.remat(_Block, policy=policies[self.remat],
                                 prevent_cse=True,
                                 static_argnums=(2, 3, 4, 7, 8, 9, 10))
        aux_total = jnp.zeros((), jnp.float32)
        counts = []
        ssm_stats = {}
        for i in range(self.n_layers):
            x, aux, layer_counts, stats = block_cls(
                self.n_heads, head_dim, d_ff,
                self.attention, self.causal,
                self.n_experts, self.moe_k,
                self.dropout, self.mesh,
                self.n_kv_heads, fuse,
                self.lora_rank, self.lora_alpha,
                self.sliding_window, self.rope_base,
                self.experts_held, self.expert_offset,
                self.qk_norm, self.bd_block,
                mixer=types[i], ssm=ssm if types[i] == "mamba" else (),
                eps=eps, rope=self.rope,
                attention_scale=self.attention_scale,
                residual_multiplier=self.residual_multiplier,
                name=f"layer_{i}")(
                x, train, decode_pos, cache_len, pad_offset, kv_len,
                block_tables, page_len, kv_pages, kv_quant,
                verify_limit)
            aux_total = aux_total + aux
            counts.append(layer_counts)
            if stats is not None:
                ssm_stats[f"l{i}"] = stats
        x = nn.RMSNorm(epsilon=eps, name="final_norm")(x)
        # h @ W / c as (h / c) @ W: the chunked head takes it so
        x = _scaled(x, 1.0 / self.logits_scaling)
        if self.tie_embeddings:
            def head(h, return_kernel: bool = False):
                kernel = embed.embedding.T
                return kernel if return_kernel \
                    else h @ kernel.astype(h.dtype)
        else:
            head = _LMHead(self.vocab_size, name="lm_head")
        if self.fused_head_chunk and decode_pos is None and \
                (train or self.bd_block):
            tiles = None
            if self.n_experts > 0:
                tiles = jnp.stack([moe_lib.tiles_used(
                    c, x.shape[0] * x.shape[1], self.moe_k, self.n_experts)
                    for c in counts])
            return FusedHeadOut(hidden=x,
                                kernel=head(x, return_kernel=True),
                                aux=aux_total,
                                moe_counts=jnp.stack(counts),
                                moe_tiles=tiles,
                                ssm_stats=ssm_stats or None)
        return head(x), aux_total


# ----------------------------------------------------------------------
# losses over (outputs=(logits, aux), batch, weights)
# ----------------------------------------------------------------------
def _token_targets(batch, weights):
    tokens = batch["x"].astype(jnp.int32)
    tgt = tokens[:, 1:]
    tok_mask = (tgt != 0).astype(jnp.float32)
    if weights is not None:
        tok_mask = tok_mask * weights.astype(jnp.float32)[:, None]
    return tgt, tok_mask


def _head_chunk_sums(h_c, t_c, m_c, kernel):
    """One chunk's logits and what the loss reads from them: the
    log-sum-exp, the masked cross-entropy sum and the masked count of
    argmax hits."""
    with jax.named_scope("logits"):
        # bf16 inputs, f32 accumulate — the MXU-native layout
        lg = jnp.einsum("cd,dv->cv", h_c, kernel,
                        preferred_element_type=jnp.float32)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    correct = jnp.take_along_axis(lg, t_c[:, None], axis=1)[:, 0]
    ok = (jnp.argmax(lg, axis=-1) == t_c).astype(jnp.float32)
    return lg, lse, jnp.sum((lse - correct) * m_c), jnp.sum(ok * m_c)


@jax.custom_vjp
def _head_chunk_scan(hs, tg, mk, kernel):
    """``(loss_sum, ok_sum)`` over token chunks ``hs (n, c, d)``,
    targets ``tg (n, c)`` and mask ``mk (n, c)`` against the lm_head
    ``kernel (d, vocab)``. This primal runs where no gradient is
    asked; under one, :func:`_head_chunk_scan_fwd` takes its place."""
    def body(carry, xs):
        _, _, loss_c, ok_c = _head_chunk_sums(*xs, kernel)
        return (carry[0] + loss_c, carry[1] + ok_c), None

    zero = jnp.zeros((), jnp.float32)
    sums, _ = jax.lax.scan(body, (zero, zero), (hs, tg, mk))
    return sums


def _head_chunk_scan_fwd(hs, tg, mk, kernel):
    """The same scan, taking each chunk's gradients while its logits
    exist: ``g = (softmax(lg) - onehot(t)) * m``, ``dh = g x kernel^T``
    (stacked) and ``dw += h^T x g`` (carried in the kernel's dtype, as
    the transposed scan carried it). The products take what the
    transpose of the logits' product would: ``g`` in float32 at the
    default precision, float32 accumulation. ``dh`` stays float32
    until the cotangent has scaled it: values already on the bf16 grid,
    times a scale just off a power of two (1/8190 tokens), all round
    back to the grid point that is 1/8192 of them (PERF.md, PR 25).
    Nothing of width ``vocab`` per token outlives its chunk."""
    def body(carry, xs):
        h_c, t_c, m_c = xs
        loss_sum, ok_sum, dw = carry
        lg, lse, loss_c, ok_c = _head_chunk_sums(h_c, t_c, m_c, kernel)
        g = (jnp.exp(lg - lse[:, None])
             - jax.nn.one_hot(t_c, lg.shape[-1])) * m_c[:, None]
        with jax.named_scope("dh"):
            dh_c = jax.lax.dot_general(
                g, kernel, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        with jax.named_scope("dw"):
            dw = dw + jax.lax.dot_general(
                h_c, g, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(dw.dtype)
        return (loss_sum + loss_c, ok_sum + ok_c, dw), dh_c

    zero = jnp.zeros((), jnp.float32)
    (loss_sum, ok_sum, dw), dh = jax.lax.scan(
        body, (zero, zero, jnp.zeros_like(kernel)), (hs, tg, mk))
    return (loss_sum, ok_sum), (dh, dw)


def _head_chunk_scan_bwd(res, cts):
    """Scale the gradients the forward took by ``loss_sum``'s
    cotangent (``ok_sum`` carries none; targets and mask get none).
    The kernel arrives in the hidden states' dtype, so ``dw``'s dtype
    is also the one ``dh`` is rounded to, once, after the scaling."""
    dh, dw = res
    ct = cts[0].astype(jnp.float32)
    return ((ct * dh).astype(dw.dtype), None, None,
            (ct * dw).astype(dw.dtype))


_head_chunk_scan.defvjp(_head_chunk_scan_fwd, _head_chunk_scan_bwd)


def bd_noise(key, shape: Tuple[int, int], t_low: float = 0.1):
    """The step's block-diffusion noise for ``shape = (rows, L)``
    (docs/DIFFUSION.md): row r draws ``t[r]``, uniform on [t_low, 1),
    from ``fold_in(fold_in(key, 1), r)`` and its L uniforms ``u[r, :]``
    from ``fold_in(fold_in(key, 2), r)``; position p is masked where
    ``u[r, p] < t[r]``. ``key`` is the step's own key, which the engine
    folds from the fit's seed and the global step; a row's noise does
    not depend on how many rows the batch has (padding rows, shards)."""
    with jax.named_scope("bd_noise"):
        rows = jnp.arange(shape[0])
        k_t, k_u = jax.random.fold_in(key, 1), jax.random.fold_in(key, 2)
        t = jax.vmap(lambda r: jax.random.uniform(
            jax.random.fold_in(k_t, r), (), jnp.float32, t_low, 1.0))(rows)
        u = jax.vmap(lambda r: jax.random.uniform(
            jax.random.fold_in(k_u, r), shape[1:], jnp.float32))(rows)
        return t, u < t[:, None]


def _moe_counters(out: FusedHeadOut) -> Dict[str, Any]:
    """The expert layers' router load as epoch-record counters: per
    layer, the routed copies that landed on held experts, the busiest
    held expert's copies, and the tiles of the row buffer those copies
    fill, which is where the passes over the buffer end (each a mean
    over the epoch's steps: a sum beside a count of 1 a step)."""
    counts = out.moe_counts
    if counts is None or counts.shape[-1] == 0:
        return {}
    one = jnp.ones((), jnp.float32)
    counters = {}
    for i in range(counts.shape[0]):
        c = counts[i].astype(jnp.float32)
        counters[f"moeHeldCopies_l{i}"] = (jnp.sum(c), one)
        counters[f"moeBusiestCopies_l{i}"] = (jnp.max(c), one)
        counters[f"moeTilesUsed_l{i}"] = (
            out.moe_tiles[i].astype(jnp.float32), one)
    return counters


def _ssm_counters(out: FusedHeadOut) -> Dict[str, Any]:
    """The Mamba-2 layers' readings as epoch-record counters, a mean
    over the epoch's steps: ``ssmStateRms_l<i>`` (the RMS of the states
    the layer holds at the rows' end) and ``ssmDecayMean_l<i>`` (the
    mean of ``a_t`` over positions and heads)."""
    one = jnp.ones((), jnp.float32)
    counters = {}
    for layer, stats in (out.ssm_stats or {}).items():
        counters[f"ssmStateRms_{layer}"] = (stats[0], one)
        counters[f"ssmDecayMean_{layer}"] = (stats[1], one)
    return counters


def _head_targets(out: FusedHeadOut, batch, weights):
    """(hidden (b, n, d), targets (b, n), weights (b, n), denominator,
    counters) of the chunked head, by objective. Next token: position
    p predicts token p+1, weight 1 where that is no padding, over the
    sum of the weights (denominator ``None``). Block diffusion
    (``out.noise``): the noisy half's position p predicts ``x0[p]``
    where it was masked, weight ``1/t`` of its row, over all the row's
    tokens that are no padding."""
    tokens = batch["x"].astype(jnp.int32)
    if out.noise is None:
        tgt, tok_mask = _token_targets(batch, weights)
        return out.hidden[:, :-1], tgt, tok_mask, None, {}
    real = (tokens != 0).astype(jnp.float32)
    if weights is not None:
        real = real * weights.astype(jnp.float32)[:, None]
    masked = out.noise["masked"].astype(jnp.float32) * real
    w = masked / out.noise["t"][:, None]
    counters = {"maskedPositions": (jnp.sum(masked),
                                    jnp.ones((), jnp.float32))}
    return (out.hidden[:, :tokens.shape[1]], tokens, w, jnp.sum(real),
            counters)


@jax.named_scope("head_loss")
def _fused_head_loss(out: FusedHeadOut, batch, weights, chunk: int,
                     aux_coef: float):
    """Chunked vocab-projection + softmax cross-entropy: scans token
    chunks of the final hidden states through the lm_head matmul, so
    peak logits memory is (chunk, vocab) instead of (b*s, vocab) and
    the full logits tensor never round-trips HBM between forward and
    loss. Under a gradient the scan is one pass
    (:func:`_head_chunk_scan_fwd`): three products a chunk
    (``head_loss/logits``, ``/dh``, ``/dw``), no backward loop and no
    recomputed logits. Accuracy is computed inside the same scan and
    emitted as a loss metric, so the engine does not re-run the
    projection for it. On one v5e chip, a chunk of 1024 tokens at
    d=2048 against 92,544 rows in bf16 (PERF.md section 5, my chip
    run, PR 25): logits 2.02 ms, argmax 0.50, sum of exponentials
    0.50, ``dh`` 2.49, ``dw`` 2.79, 8.3 ms in all where the
    checkpointed scan it replaced took 10.8; one product is 1.97 ms
    at the chip's peak."""
    hs, tgt, tok_mask, count, counters = _head_targets(out, batch, weights)
    b, sm1, d = hs.shape
    t_total = b * sm1
    chunk = max(1, min(chunk, t_total))  # no padding blowup on tiny shapes
    hs = hs.reshape(t_total, d)
    tg = tgt.reshape(t_total)
    mk = tok_mask.reshape(t_total)
    n_chunks = -(-t_total // chunk)
    pad = n_chunks * chunk - t_total
    if pad:
        hs = jnp.pad(hs, ((0, pad), (0, 0)))
        tg = jnp.pad(tg, (0, pad))
        mk = jnp.pad(mk, (0, pad))
    loss_sum, ok_sum = _head_chunk_scan(
        hs.reshape(n_chunks, chunk, d), tg.reshape(n_chunks, chunk),
        mk.reshape(n_chunks, chunk), out.kernel.astype(hs.dtype))
    total = jnp.maximum(jnp.sum(mk), 1e-9)
    loss = loss_sum / (total if count is None
                       else jnp.maximum(count, 1e-9)) \
        + aux_coef * out.aux.astype(jnp.float32)
    # accuracy over the weighted targets (under block diffusion: the
    # masked positions, each by its row's 1/t)
    counters["accuracy"] = (ok_sum, total)
    counters.update(_moe_counters(out))
    counters.update(_ssm_counters(out))
    return loss, counters


@jax.named_scope("head_loss")
def _fused_head_loss_sharded(out: FusedHeadOut, batch, weights,
                             chunk: int, aux_coef: float, mesh):
    """Sequence-parallel twin of :func:`_fused_head_loss`: under
    ring/Ulysses the hidden states are sharded over ``sp`` (and batch
    over dp/fsdp), which is exactly where the (tokens, vocab) logits
    hurt most — a 32k-token, 32k-vocab step would materialize 4 GB of
    f32 logits per batch row. The projection + CE runs INSIDE
    ``shard_map``: each shard scans its local token chunks; with
    tensor parallelism the lm_head columns stay sharded and the
    softmax reduces over ``tp`` (Megatron-style parallel CE: pmax of
    the local maxima, psum of the local exp-sums, psum of the local
    one-hot correct logit). Loss/accuracy sums then psum over the
    row-sharding axes, so the result is replicated and exact."""
    tokens = batch["x"].astype(jnp.int32)
    b, s = tokens.shape
    # global shift OUTSIDE shard_map (a one-position halo the compiler
    # handles); the appended 0 column self-masks via tgt != 0
    tgt = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((b, 1), jnp.int32)], axis=1)
    tok_mask = (tgt != 0).astype(jnp.float32)
    if weights is not None:
        tok_mask = tok_mask * weights.astype(jnp.float32)[:, None]

    data = mesh_lib.data_axes(mesh)
    tp = mesh.shape.get(mesh_lib.TP, 1)
    row_axes = tuple(a for a in (*data, mesh_lib.SP)
                     if mesh.shape.get(a, 1) > 1)
    h_spec = P(data if data else None, mesh_lib.SP, None)
    t_spec = P(data if data else None, mesh_lib.SP)
    k_spec = P(None, mesh_lib.TP if tp > 1 else None)
    kernel = out.kernel.astype(out.hidden.dtype)

    def local_loss(h, tg, mk, W):
        d = h.shape[-1]
        v_loc = W.shape[-1]
        t_total = h.shape[0] * h.shape[1]
        c = max(1, min(chunk, t_total))
        n_chunks = -(-t_total // c)
        pad = n_chunks * c - t_total
        hs = h.reshape(t_total, d)
        tgl = tg.reshape(t_total)
        mkl = mk.reshape(t_total)
        if pad:
            hs = jnp.pad(hs, ((0, pad), (0, 0)))
            tgl = jnp.pad(tgl, (0, pad))
            mkl = jnp.pad(mkl, (0, pad))
        if tp > 1:
            v_off = jax.lax.axis_index(mesh_lib.TP) * v_loc
        else:
            v_off = 0

        def body(carry, xs):
            h_c, t_c, m_c = xs
            lg = jnp.einsum("cd,dv->cv", h_c, W,
                            preferred_element_type=jnp.float32)
            lmax = jnp.max(lg, axis=-1)
            # the max subtraction is a stability constant — keep it
            # out of the grad graph; cross-tp reduction goes through
            # all_gather (pmax has no differentiation rule, which the
            # checkpointed scan's linearization requires even for
            # zero-tangent values)
            if tp > 1:
                gmax = jnp.max(jax.lax.all_gather(
                    lmax, mesh_lib.TP), axis=0)
            else:
                gmax = lmax
            gmax = jax.lax.stop_gradient(gmax)
            se = jnp.sum(jnp.exp(lg - gmax[:, None]), axis=-1)
            if tp > 1:
                se = jax.lax.psum(se, mesh_lib.TP)
            lse = gmax + jnp.log(se)
            loc = t_c - v_off
            in_range = (loc >= 0) & (loc < v_loc)
            corr = jnp.take_along_axis(
                lg, jnp.clip(loc, 0, v_loc - 1)[:, None], axis=1)[:, 0]
            corr = jnp.where(in_range, corr, 0.0)
            if tp > 1:
                corr = jax.lax.psum(corr, mesh_lib.TP)
            lg_sg = jax.lax.stop_gradient(lg)  # accuracy carries no grad
            amax_v = jnp.max(lg_sg, axis=-1)
            amax_i = jnp.argmax(lg_sg, axis=-1) + v_off
            if tp > 1:
                vs = jax.lax.all_gather(amax_v, mesh_lib.TP)  # (tp, c)
                is_ = jax.lax.all_gather(amax_i, mesh_lib.TP)
                win = jnp.argmax(vs, axis=0)
                amax_i = jnp.take_along_axis(
                    is_, win[None, :], axis=0)[0]
            ok = (amax_i == t_c).astype(jnp.float32)
            loss_sum, ok_sum, n_sum = carry
            return (loss_sum + jnp.sum((lse - corr) * m_c),
                    ok_sum + jnp.sum(ok * m_c),
                    n_sum + jnp.sum(m_c)), None

        zeros = (jnp.zeros((), jnp.float32),) * 3
        (loss_sum, ok_sum, n_sum), _ = jax.lax.scan(
            jax.checkpoint(body), zeros,
            (hs.reshape(n_chunks, c, d), tgl.reshape(n_chunks, c),
             mkl.reshape(n_chunks, c)))
        if row_axes:
            loss_sum = jax.lax.psum(loss_sum, row_axes)
            ok_sum = jax.lax.psum(ok_sum, row_axes)
            n_sum = jax.lax.psum(n_sum, row_axes)
        return loss_sum, ok_sum, n_sum

    loss_sum, ok_sum, n_sum = mesh_lib.shard_map(
        local_loss, mesh=mesh,
        in_specs=(h_spec, t_spec, t_spec, k_spec),
        out_specs=(P(), P(), P()), check_vma=False)(
        out.hidden, tgt, tok_mask, kernel)
    total = jnp.maximum(n_sum, 1e-9)
    loss = loss_sum / total + aux_coef * out.aux.astype(jnp.float32)
    return loss, {"accuracy": (ok_sum, total)}


def next_token_loss(aux_coef: float = 0.01, head_chunk: int = 1024,
                    mesh=None):
    """Causal LM loss: predict token t+1 from prefix <= t; padding
    tokens (id 0) and padded tail samples are masked out. On
    :class:`FusedHeadOut` training outputs the projection + CE runs
    chunked (``head_chunk`` tokens at a time) and the return value is
    ``(loss, {"accuracy": (sum, count)})`` — the engine merges
    loss-emitted metrics. With a sequence-parallel mesh the chunked
    scan runs inside ``shard_map`` (see
    :func:`_fused_head_loss_sharded`). A :class:`FusedHeadOut` that
    carries ``noise`` is a block-diffusion step: the same chunked head
    over the noisy half, masked positions weighted ``1/t``
    (:func:`_head_targets`)."""
    import optax

    def loss_fn(outputs, batch, weights):
        if isinstance(outputs, FusedHeadOut):
            m = mesh or mesh_lib.current_mesh()
            b, s = batch["x"].shape[:2]
            sp = m.shape.get(mesh_lib.SP, 1)
            tp = m.shape.get(mesh_lib.TP, 1)
            vocab = outputs.kernel.shape[-1]
            data_size = max(int(np.prod(
                [m.shape[a] for a in mesh_lib.data_axes(m)] or [1])), 1)
            # shard_map needs divisible mapped dims (incl. the vocab
            # columns under tp); odd shapes fall back to the flat
            # path (GSPMD gathers — correct, bigger)
            if outputs.noise is None and sp > 1 \
                    and b % data_size == 0 and s % sp == 0 \
                    and vocab % tp == 0:
                return _fused_head_loss_sharded(
                    outputs, batch, weights, head_chunk, aux_coef, m)
            return _fused_head_loss(outputs, batch, weights,
                                    head_chunk, aux_coef)
        logits, aux = outputs
        tgt, tok_mask = _token_targets(batch, weights)
        lg = logits[:, :-1].astype(jnp.float32)
        per_tok = optax.softmax_cross_entropy_with_integer_labels(lg, tgt)
        total = jnp.maximum(jnp.sum(tok_mask), 1e-9)
        loss = jnp.sum(per_tok * tok_mask) / total
        return loss + aux_coef * aux.astype(jnp.float32)

    return loss_fn


def token_accuracy(outputs, batch, weights):
    if isinstance(outputs, FusedHeadOut):
        # the fused loss emits accuracy itself; recomputing it here
        # would cost a second full vocab projection
        raise RuntimeError(
            "token_accuracy on FusedHeadOut — use the accuracy the "
            "fused loss emits (the engine skips same-named metric fns)")
    logits, _ = outputs
    tokens = batch["x"].astype(jnp.int32)
    tgt = tokens[:, 1:]
    pred = jnp.argmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    tok_mask = (tgt != 0).astype(jnp.float32)
    if weights is not None:
        tok_mask = tok_mask * weights.astype(jnp.float32)[:, None]
    correct = (pred == tgt).astype(jnp.float32) * tok_mask
    return jnp.sum(correct), jnp.sum(tok_mask)


# ----------------------------------------------------------------------
# keras-shaped wrapper (the stored lineage-root instance)
# ----------------------------------------------------------------------
class TransformerEncoder(nn.Module):
    """Non-causal (bidirectional) transformer encoder for sequence
    classification: embed → blocks(causal=False) → final RMSNorm →
    pad-masked mean pool → class head. Shares every block/param
    convention with :class:`TransformerLM`, so the TP/FSDP sharding
    rules and the attention impl table (dot/flash/ring/ulysses) apply
    unchanged; token id 0 is the pad and is excluded from the pool."""

    vocab_size: int
    n_classes: int
    d_model: int = 256
    n_layers: int = 4
    n_heads: int = 4
    n_kv_heads: int = 0
    d_ff: int = 0
    attention: str = "dot"
    dropout: float = 0.0
    mesh: Any = None

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        if self.attention not in ATTENTION_IMPLS:
            raise ValueError(
                f"unknown attention impl: {self.attention!r}")
        d_ff = self.d_ff or 4 * self.d_model
        head_dim = self.d_model // self.n_heads
        x = nn.Embed(self.vocab_size, self.d_model, name="embed")(tokens)
        mesh = self.mesh or mesh_lib.current_mesh()
        x = sharding_lib.constrain(
            x, mesh, mesh_lib.data_axes(mesh) or None,
            mesh_lib.SP if self.attention in ("ring", "ulysses")
            else None,
            None)
        for i in range(self.n_layers):
            x, _, _, _ = _Block(self.n_heads, head_dim, d_ff,
                             self.attention, False, 0, 2,
                             self.dropout, self.mesh, self.n_kv_heads,
                             name=f"layer_{i}")(x, train)
        x = nn.RMSNorm(name="final_norm")(x)
        mask = (tokens != 0).astype(jnp.float32)[..., None]
        pooled = jnp.sum(x * mask, axis=1) / jnp.maximum(
            jnp.sum(mask, axis=1), 1e-9)
        return nn.Dense(self.n_classes, use_bias=True,
                        name="cls_head")(pooled)


class TextClassifier:
    """Keras-shaped sequence classifier over the transformer encoder
    (the modern counterpart to the reference's IMDb-LSTM config):
    ``fit/evaluate/predict`` through the same GSPMD engine as every
    other model, reachable by module path through ``POST /model``."""

    _CONFIG_KEYS = ("vocab_size", "n_classes", "d_model", "n_layers",
                    "n_heads", "n_kv_heads", "d_ff", "max_len",
                    "attention", "dropout")

    def __init__(self, vocab_size: int, n_classes: int,
                 d_model: int = 256, n_layers: int = 4,
                 n_heads: int = 4, n_kv_heads: int = 0, d_ff: int = 0,
                 max_len: int = 512, attention: str = "dot",
                 dropout: float = 0.0, name: str = "text_classifier"):
        self.name = name
        self.vocab_size = int(vocab_size)
        self.n_classes = int(n_classes)
        self.d_model = int(d_model)
        self.n_layers = int(n_layers)
        self.n_heads = int(n_heads)
        self.n_kv_heads = int(n_kv_heads)
        self.d_ff = int(d_ff)
        self.max_len = int(max_len)
        if attention not in ATTENTION_IMPLS + ("auto",):
            raise ValueError(f"unknown attention impl: {attention!r}")
        self.attention = attention
        if self.n_kv_heads < 0 or (
                self.n_kv_heads and self.n_heads % self.n_kv_heads):
            raise ValueError(
                f"n_kv_heads={self.n_kv_heads} must be a positive "
                f"divisor of n_heads={self.n_heads} (or 0 for MHA)")
        self.dropout = float(dropout)
        self.optimizer_spec: Dict[str, Any] = {"kind": "adamw",
                                               "learning_rate": 3e-4}
        self.params: Any = None
        self.history: List[Dict[str, Any]] = []
        self.seed = 0
        self._engine: Optional[engine_lib.Engine] = None
        self._state = None
        self._mesh_override = None
        self._accum = engine_lib.default_grad_accum()

    def _require_built(self) -> None:
        if self.params is None:
            raise RuntimeError(
                "model has no parameters yet — call fit() first "
                "(or load a trained artifact)")

    def _resolved_attention(self, seq_len: Optional[int] = None) -> str:
        if self.attention != "auto":
            return self.attention
        # same crossover as the LM, resolved from the ACTUAL batch
        # width when known — a max_len=2048 classifier fed 128-token
        # batches should take the dot path, not flash below the
        # crossover
        if jax.default_backend() == "tpu":
            return "flash" if (seq_len or self.max_len) >= 1024 else "dot"
        return "dot"

    def _mesh(self):
        return self._mesh_override or mesh_lib.current_mesh()

    def set_mesh(self, mesh) -> None:
        self._mesh_override = mesh
        self._engine = None
        self._state = None

    def compile(self, optimizer: Any = "adamw", **_: Any) -> None:
        if isinstance(optimizer, str):
            self.optimizer_spec = {"kind": optimizer}
        elif isinstance(optimizer, dict):
            self.optimizer_spec = dict(optimizer)
        else:
            raise TypeError(f"unsupported optimizer: {optimizer!r}")
        self._engine = None

    @property
    def module(self) -> TransformerEncoder:
        return self._module()

    def _module(self, seq_len: Optional[int] = None) -> TransformerEncoder:
        return TransformerEncoder(
            vocab_size=self.vocab_size, n_classes=self.n_classes,
            d_model=self.d_model, n_layers=self.n_layers,
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            d_ff=self.d_ff, attention=self._resolved_attention(seq_len),
            dropout=self.dropout, mesh=self._mesh_override)

    def _apply_fn(self, params, model_state, batch, train, rng):
        rngs = {"dropout": rng} if (train and rng is not None and
                                    self.dropout) else None
        # the attention impl resolves from the traced batch width, so
        # an "auto" classifier takes flash only at-or-above the
        # measured crossover regardless of its configured max_len
        module = self._module(int(batch["x"].shape[1]))
        out = module.apply({"params": params}, batch["x"],
                           train=train, rngs=rngs)
        return out, model_state

    def _get_engine(self) -> engine_lib.Engine:
        if self._engine is None:
            from learningorchestra_tpu.config import get_config
            from learningorchestra_tpu.models.neural import (
                build_optimizer)
            dtype = jnp.bfloat16 \
                if get_config().compute_dtype == "bfloat16" \
                else jnp.float32
            mesh = self._mesh()
            self._engine = engine_lib.Engine(
                apply_fn=self._apply_fn,
                loss_fn=engine_lib.sparse_softmax_loss,
                optimizer=build_optimizer(self.optimizer_spec),
                mesh=mesh,
                metrics={"accuracy": engine_lib.accuracy_metric},
                compute_dtype=dtype,
                param_rules=sharding_lib.TRANSFORMER_RULES,
                batch_sharding=jax.sharding.NamedSharding(
                    mesh, sharding_lib.batch_spec(
                        mesh, seq_axis=self.attention in
                        ("ring", "ulysses"))),
                grad_accum=self._accum)
        return self._engine

    def _coerce(self, x) -> np.ndarray:
        if hasattr(x, "to_numpy"):
            x = data_lib.dataframe_to_arrays(x)["x"]
        x = np.atleast_2d(np.asarray(x)).astype(np.int32)
        if x.shape[1] > self.max_len:
            x = x[:, :self.max_len]
        return x

    def _batcher(self, x, y=None, batch_size=None, shuffle=False):
        from learningorchestra_tpu.config import get_config
        arrays = {"x": self._coerce(x)}
        if y is not None:
            arrays["y"] = np.asarray(y).astype(np.int32).reshape(-1)
        return data_lib.ArrayBatcher(
            arrays, batch_size or get_config().default_batch_size,
            shuffle=shuffle, seed=self.seed,
            dp_multiple=mesh_lib.data_parallel_size(self._mesh()))

    def _build_params(self, sample_x) -> None:
        sample = np.asarray(sample_x)
        variables = self._module(int(sample.shape[1])).init(
            jax.random.PRNGKey(self.seed),
            jnp.asarray(sample[:1]), train=False)
        self.params = variables["params"]

    def fit(self, x=None, y=None, batch_size: Optional[int] = None,
            epochs: int = 1, shuffle: bool = True, checkpointer=None,
            log_fn=None, grad_accum: Optional[int] = None, **_: Any):
        from learningorchestra_tpu.models.neural import History

        self._accum, changed = engine_lib.resolve_grad_accum(
            grad_accum, self._accum)
        if changed:
            self._engine = None
        batcher = self._batcher(x, y, batch_size, shuffle=shuffle)
        if self.params is None:
            self._build_params(batcher.array("x"))
        eng = self._get_engine()
        state = eng.init_state(self.params)
        state, history = eng.fit(state, batcher, epochs=epochs,
                                 seed=self.seed,
                                 checkpointer=checkpointer,
                                 log_fn=log_fn)
        self._state = state
        self.params = engine_lib.to_host(state.params)
        self.history.extend(history)
        return History(history)

    def evaluate(self, x=None, y=None,
                 batch_size: Optional[int] = None,
                 **_: Any) -> Dict[str, float]:
        self._require_built()
        eng = self._get_engine()
        state = self._state or eng.init_state(self.params)
        return eng.evaluate(state, self._batcher(x, y, batch_size))

    def predict(self, x=None, batch_size: Optional[int] = None,
                **_: Any) -> np.ndarray:
        """Class probabilities (n, n_classes)."""
        self._require_built()
        eng = self._get_engine()
        state = self._state or eng.init_state(self.params)
        logits = eng.predict(state, self._batcher(x, None, batch_size))
        return np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))

    def num_params(self) -> int:
        if self.params is None:
            return 0
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params))

    # artifact-store native protocol --------------------------------
    def __lo_save__(self, path: str) -> None:
        from learningorchestra_tpu.runtime import checkpoint as ckpt

        config = {k: getattr(self, k) for k in self._CONFIG_KEYS}
        config.update(name=self.name, optimizer_spec=self.optimizer_spec,
                      seed=self.seed, history=self.history,
                      built=self.params is not None)
        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump(config, f)
        if self.params is not None:
            ckpt.save_pytree({"params": self.params},
                             os.path.join(path, "weights.msgpack"))

    @classmethod
    def __lo_load__(cls, path: str) -> "TextClassifier":
        from learningorchestra_tpu.runtime import checkpoint as ckpt

        with open(os.path.join(path, "config.json")) as f:
            config = json.load(f)
        model = cls(**{k: config[k] for k in cls._CONFIG_KEYS
                       if k in config},
                    name=config["name"])
        model.optimizer_spec = config["optimizer_spec"]
        model.seed = config["seed"]
        model.history = config["history"]
        if config["built"]:
            sample = np.zeros((1, 8), np.int32)
            weights = os.path.join(path, "weights.msgpack")
            with obs_trace.span("paramInit"):
                model._build_params(sample)
            with obs_trace.span("weightsRead",
                                bytes=os.path.getsize(weights)):
                restored = ckpt.load_pytree(weights,
                                            {"params": model.params})
            model.params = restored["params"]
        return model


def _lora_optimizer(base):
    """Freeze everything except ``lora_*`` leaves: optax.multi_transform
    routes adapter params through the real optimizer and pins the base
    weights with set_to_zero — so optimizer state (adam mu/nu) exists
    ONLY for the adapters, the actual memory win of LoRA."""
    import optax

    def labels(params):
        def label(path, _):
            leaf = getattr(path[-1], "key", str(path[-1]))
            return "lora" if str(leaf).startswith("lora_") else "frozen"

        return jax.tree_util.tree_map_with_path(label, params)

    return optax.multi_transform(
        {"lora": base, "frozen": optax.set_to_zero()}, labels)


# ----------------------------------------------------------------------
# Quantized serving weights (docs/SERVING.md "Quantized serving").
#
# Serving is read-only over a pinned copy of the params, so the
# fp32/bf16 MASTER tree stays untouched for training/LoRA — only the
# serving pin narrows. A quantized leaf is replaced by a dict
# {"qvalue": int8/fp8, "qscale": f32 per-output-channel,
#  "qlike": 0-d array carrying the original dtype}; dequant runs as
# the first op INSIDE the jitted serve step/prefill, so XLA fuses the
# convert+scale into the consuming matmul operand and no full-width
# copy of the weights persists in HBM. Unquantized trees pass through
# both functions structurally unchanged, which is what keeps bf16
# sessions bit-identical to the pre-quantization serving plane.
# ----------------------------------------------------------------------

_WEIGHT_QUANT_LEAVES = ("kernel", "embedding")
_FP8_MAX = 448.0  # float8_e4m3fn finite max


def quantize_serving_params(params, dtype: str):
    """Quantize the matmul weights of a param tree for serving.

    ``dtype`` is ``"bf16"`` (no-op — the tree is returned as-is),
    ``"int8"`` (symmetric per-output-channel, scale = amax/127) or
    ``"fp8"`` (float8_e4m3fn, scale = amax/448; raises
    :class:`ValueError` when the installed jax lacks fp8 dtypes so
    the platform gate fails loudly at session create, not mid-step).
    Only ``kernel``/``embedding`` leaves with ndim >= 2 narrow; norms,
    biases and LoRA adapters (tiny, precision-sensitive) ride along
    unchanged."""
    if dtype in (None, "", "bf16"):
        return params
    if dtype == "fp8" and not hasattr(jnp, "float8_e4m3fn"):
        raise ValueError(
            "fp8 serving weights need jax.numpy.float8_e4m3fn, which "
            "this jax build does not provide — use int8 or bf16")
    if dtype not in ("int8", "fp8"):
        raise ValueError(
            f"unknown serving weight dtype {dtype!r} (bf16|int8|fp8)")

    def quant_leaf(a):
        f = jnp.asarray(a).astype(jnp.float32)
        axes = tuple(range(f.ndim - 1))
        amax = jnp.max(jnp.abs(f), axis=axes)
        if dtype == "int8":
            scale = jnp.maximum(amax / 127.0, attn_ops._QUANT_EPS)
            q = jnp.clip(jnp.round(f / scale), -127,
                         127).astype(jnp.int8)
        else:
            scale = jnp.maximum(amax / _FP8_MAX, attn_ops._QUANT_EPS)
            q = (f / scale).astype(jnp.float8_e4m3fn)
        return {"qvalue": q, "qscale": scale,
                "qlike": jnp.zeros((), jnp.asarray(a).dtype)}

    def walk(node):
        if isinstance(node, dict) or hasattr(node, "items"):
            return {k: (quant_leaf(v)
                        if k in _WEIGHT_QUANT_LEAVES
                        and jnp.ndim(v) >= 2 else walk(v))
                    for k, v in node.items()}
        return node

    return walk(params)


def dequantize_serving_params(tree):
    """Inverse of :func:`quantize_serving_params` — expand quantized
    leaf dicts back to their original dtype. Called INSIDE the jitted
    serve fns (fused dequant); a tree with no quantized leaves passes
    through with identical leaves, so the bf16 path compiles to the
    exact pre-quantization program."""
    if isinstance(tree, dict) or hasattr(tree, "items"):
        if "qvalue" in tree and "qscale" in tree:
            deq = tree["qvalue"].astype(jnp.float32) * tree["qscale"]
            return deq.astype(tree["qlike"].dtype)
        return {k: dequantize_serving_params(v)
                for k, v in tree.items()}
    return tree


class LanguageModel:
    """Trainable LM artifact with the reference's method-call surface.

    ``attention="auto"`` picks the Pallas flash kernel on TPU and the
    XLA-fused dot implementation elsewhere.
    """

    _CONFIG_KEYS = ("vocab_size", "d_model", "n_layers", "n_heads",
                    "n_kv_heads", "d_ff", "max_len", "attention",
                    "n_experts", "moe_k",
                    "dropout", "aux_coef", "head_chunk", "remat",
                    "fused_proj", "lora_rank", "lora_alpha",
                    "sliding_window", "rope_base", "head_dim", "qk_norm",
                    "experts_held", "expert_offset", "objective",
                    "block_length", "mask_token_id",
                    "layer_types", "ssm_heads", "ssm_head_dim",
                    "ssm_state", "ssm_conv", "ssm_chunk", "rms_norm_eps",
                    "position_embedding", "attention_scale",
                    "embedding_multiplier", "residual_multiplier",
                    "logits_scaling", "tie_embeddings")
    OBJECTIVES = ("next_token", "block_diffusion")
    POSITION_EMBEDDINGS = ("rope", "nope")

    def __init__(self, vocab_size: int, d_model: int = 256,
                 n_layers: int = 4, n_heads: int = 4,
                 n_kv_heads: int = 0, d_ff: int = 0,
                 max_len: int = 512, attention: str = "auto",
                 n_experts: int = 0, moe_k: int = 2, dropout: float = 0.0,
                 aux_coef: float = 0.01, head_chunk: Optional[int] = None,
                 remat: Optional[str] = None, fused_proj: bool = False,
                 lora_rank: int = 0, lora_alpha: float = 16.0,
                 sliding_window: int = 0, rope_base: float = 10000.0,
                 head_dim: int = 0, qk_norm: bool = False,
                 experts_held: int = 0, expert_offset: int = 0,
                 objective: str = "next_token", block_length: int = 4,
                 mask_token_id: Optional[int] = None,
                 layer_types: Optional[Any] = None, ssm_heads: int = 0,
                 ssm_head_dim: int = 64, ssm_state: int = 128,
                 ssm_conv: int = 4, ssm_chunk: int = 256,
                 rms_norm_eps: float = 1e-6,
                 position_embedding: str = "rope",
                 attention_scale: float = 0.0,
                 embedding_multiplier: float = 1.0,
                 residual_multiplier: float = 1.0,
                 logits_scaling: float = 1.0,
                 tie_embeddings: bool = False,
                 name: str = "language_model"):
        self.name = name
        # the per-layer spec (docs/STATE_SPACE.md): one of LAYER_TYPES a
        # layer, None for attention everywhere; a tuple, so that it
        # hashes (the engine's cache key) and saves as a list
        self.layer_types = None if layer_types is None \
            else tuple(str(t) for t in layer_types)
        _check_layer_types(self.layer_types, n_layers)
        self.ssm_heads, self.ssm_head_dim = int(ssm_heads), int(ssm_head_dim)
        self.ssm_state, self.ssm_conv = int(ssm_state), int(ssm_conv)
        self.ssm_chunk = int(ssm_chunk)
        if self.has_mamba and (
                min(self.ssm_heads, self.ssm_head_dim, self.ssm_state,
                    self.ssm_conv, self.ssm_chunk) < 1
                or n_experts or objective != "next_token"
                or attention in ("ring", "ulysses") or lora_rank):
            raise ValueError(
                "a model with Mamba-2 layers needs ssm_heads, ssm_head_dim, "
                "ssm_state, ssm_conv and ssm_chunk >= 1, a dense MLP, the "
                "next-token objective, no sequence parallelism and no LoRA")
        self.rms_norm_eps = float(rms_norm_eps)
        if position_embedding not in self.POSITION_EMBEDDINGS:
            raise ValueError(f"position_embedding must be one of "
                             f"{self.POSITION_EMBEDDINGS}, got "
                             f"{position_embedding!r}")
        self.position_embedding = position_embedding
        self.attention_scale = float(attention_scale)
        self.embedding_multiplier = float(embedding_multiplier)
        self.residual_multiplier = float(residual_multiplier)
        self.logits_scaling = float(logits_scaling)
        self.tie_embeddings = bool(tie_embeddings)
        if self.attention_scale < 0 or self.logits_scaling <= 0 \
                or self.rms_norm_eps <= 0:
            raise ValueError("attention_scale must be >= 0 (0: 1/sqrt("
                             "head_dim)), logits_scaling and rms_norm_eps "
                             "> 0")
        self.head_dim = int(head_dim)
        self.qk_norm = bool(qk_norm)
        if self.head_dim < 0 or self.head_dim % 2:
            raise ValueError(f"head_dim must be even and >= 0 (0: d_model "
                             f"// n_heads), got {head_dim}")
        # of the n_experts the router spans, this model holds
        # experts_held (0: all), from expert_offset on: one expert-
        # parallel rank's share of every expert layer
        self.experts_held = int(experts_held)
        self.expert_offset = int(expert_offset)
        held = self.experts_held or int(n_experts)
        if min(self.experts_held, self.expert_offset) < 0 or \
                self.expert_offset + held > int(n_experts):
            raise ValueError(
                f"experts_held={experts_held} from expert_offset="
                f"{expert_offset} are not among n_experts={n_experts}")
        if objective not in self.OBJECTIVES:
            raise ValueError(f"objective must be one of {self.OBJECTIVES}, "
                             f"got {objective!r}")
        # "block_diffusion" trains by masked diffusion over blocks of
        # block_length tokens (docs/DIFFUSION.md); mask_token_id is the
        # id a masked position shows (None: the vocabulary's last,
        # resolved here and saved as a number)
        self.objective = objective
        self.block_length = int(block_length)
        if self.block_length < 1:
            raise ValueError(f"block_length must be >= 1, got "
                             f"{block_length}")
        self.mask_token_id = int(vocab_size) - 1 if mask_token_id is None \
            else int(mask_token_id)
        if objective == "block_diffusion" and (
                attention in ("ring", "ulysses") or sliding_window
                or int(max_len) % self.block_length
                or not 0 <= self.mask_token_id < int(vocab_size)):
            raise ValueError(
                "objective='block_diffusion' runs on the dot and flash "
                "paths with no sliding window, rows of whole blocks "
                f"(max_len={max_len}, block_length={block_length}) and a "
                f"mask_token_id inside the vocabulary")
        self.head_chunk = head_chunk
        self.fused_proj = bool(fused_proj)
        self.lora_rank = int(lora_rank)
        self.lora_alpha = float(lora_alpha)
        if self.lora_rank < 0:
            raise ValueError(f"lora_rank must be >= 0, got {lora_rank}")
        self.rope_base = float(rope_base)
        if self.rope_base <= 1.0:
            raise ValueError(
                f"rope_base must be > 1, got {rope_base}")
        self.sliding_window = int(sliding_window)
        if self.sliding_window < 0:
            raise ValueError(
                f"sliding_window must be >= 0, got {sliding_window}")
        # LO_TLM_REMAT env overrides; default "none" (measure before
        # paying recompute FLOPs — not measured on today's code)
        self.remat = remat
        self.vocab_size = int(vocab_size)
        self.d_model = int(d_model)
        self.n_layers = int(n_layers)
        self.n_heads = int(n_heads)
        self.n_kv_heads = int(n_kv_heads)
        if self.n_kv_heads < 0 or (
                self.n_kv_heads and self.n_heads % self.n_kv_heads):
            raise ValueError(
                f"n_kv_heads={self.n_kv_heads} must be a positive "
                f"divisor of n_heads={self.n_heads} (or 0 for MHA)")
        self.d_ff = int(d_ff)
        self.max_len = int(max_len)
        self.attention = attention
        self.n_experts = int(n_experts)
        self.moe_k = int(moe_k)
        self.dropout = float(dropout)
        self.aux_coef = float(aux_coef)
        self.optimizer_spec: Dict[str, Any] = {"kind": "adamw",
                                               "learning_rate": 3e-4}
        self.params: Any = None
        self.history: List[Dict[str, Any]] = []
        self.seed = 0
        self._engine: Optional[engine_lib.Engine] = None
        self._state = None
        self._mesh_override = None
        self._accum = engine_lib.default_grad_accum()
        self._drop_decode_caches()

    @property
    def has_mamba(self) -> bool:
        return "mamba" in (self.layer_types or ())

    def set_mesh(self, mesh) -> None:
        """Pin this model to a mesh (e.g. a sweep trial's sub-slice of
        the default mesh) instead of the process-wide default."""
        self._mesh_override = mesh
        self._engine = None
        # device state from a previous fit is laid out on the old mesh;
        # host params survive, state must rebuild on the new mesh
        self._state = None
        self._drop_decode_caches()

    def _drop_decode_caches(self) -> None:
        """Generation/beam compiles close over the mesh-resolved
        module — anything that changes the mesh or the param layout
        must drop them or a stale compile serves the old config."""
        self._gen_cache_fns = {}
        self._beam_cache_fns = {}
        self._serve_cache_fns = {}
        self._serve_paged_fns = {}
        self._serve_spec_fns = {}

    def _mesh(self):
        return self._mesh_override or mesh_lib.current_mesh()

    # ------------------------------------------------------------------
    def _resolved_attention(self, seq_len: Optional[int] = None) -> str:
        if self.attention != "auto":
            return self.attention
        # flash from seq 1024 on the chip, resolved on the ACTUAL
        # sequence length when known; the dot path materializes the
        # (bh, s, s) scores, so long contexts need the kernel. The
        # crossover itself is not measured on today's code.
        if jax.default_backend() == "tpu":
            return "flash" if (seq_len or self.max_len) >= 1024 else "dot"
        return "dot"

    def _head_chunk(self) -> int:
        """Fused-head chunk size (0 = full logits). Auto rule: fuse
        when the vocab is large enough that the (tokens, vocab) f32
        logits tensor dominates the step's HBM traffic. Neither the
        threshold of 8192 rows nor the chunk of 1024 tokens has been
        swept on the chip: at 92,544 rows and 1024 tokens a chunk the
        fused head is 33% of the step (PERF.md section 5, my chip run,
        PR 25). Under sequence-parallel
        attention the loss runs its shard_map twin
        (:func:`_fused_head_loss_sharded`), keeping the sequence dim
        sharded. ``LO_LM_HEAD_CHUNK`` overrides (0 disables, N sets
        tokens per chunk)."""
        env = os.environ.get("LO_LM_HEAD_CHUNK")
        if env is not None:
            return max(0, int(env))
        if self.head_chunk is not None and (
                self.head_chunk or self.objective == "next_token"):
            return max(0, int(self.head_chunk))
        # the block-diffusion loss exists only through the chunked head
        fused = self.vocab_size >= 8192 or \
            self.objective == "block_diffusion"
        return 1024 if fused else 0

    def _param_rules(self, mesh):
        """TP sharding rules, head-granular: a projection whose HEAD
        count doesn't divide tp replicates, even when the raw column
        count happens to divide — column-sharding across a head
        boundary is numerically fine under GSPMD but defeats the
        head-parallel attention plan (extra resharding at the
        attention einsum). Checked separately for q/o (n_heads) and
        k/v (n_kv_heads), which differ under GQA/MQA."""
        rules = tuple(sharding_lib.TRANSFORMER_RULES)
        kv = self.n_kv_heads or self.n_heads
        tp_size = mesh.shape.get(mesh_lib.TP, 1)
        if tp_size > 1 and kv % tp_size:
            rules = ((r".*(k_proj|v_proj)/kernel$", P()),) + rules
        if tp_size > 1 and self.n_heads % tp_size:
            rules = ((r".*(q_proj|o_proj)/kernel$", P()),) + rules
        if tp_size > 1:
            # fused projections: a column shard of the [q|k|v] (or
            # [gate|up]) concatenation crosses block boundaries, so
            # replicate — the param tree never changes with the mesh
            # (artifact portability); FSDP may still storage-shard
            rules = ((r".*(qkv_proj|gate_up)/kernel$", P()),) + rules
        return rules

    def _resolved_remat(self) -> str:
        value = os.environ.get("LO_TLM_REMAT") or self.remat or "none"
        if value not in ("none", "dots", "full"):
            # fail at construction/resolution, not deep inside the
            # first training trace — eval paths never hit the module's
            # own check
            raise ValueError(
                f"unknown remat policy {value!r} (none|dots|full)")
        return value

    def _resolved_fused_proj(self) -> bool:
        env = os.environ.get("LO_TLM_FUSED_PROJ")
        if not env:  # unset or empty -> constructor value
            return self.fused_proj
        value = env.strip().lower()
        if value in ("1", "true", "yes"):
            return True
        if value in ("0", "false", "no"):
            return False
        # fail at resolution, not by silently measuring the wrong
        # path (the _resolved_remat convention)
        raise ValueError(
            f"LO_TLM_FUSED_PROJ={env!r} (want 1/true/yes or "
            f"0/false/no)")

    def _module_for(self, seq_len: Optional[int] = None,
                    bd: bool = False) -> TransformerLM:
        """``bd``: the module of a block-diffusion step, whose rows are
        [noisy ; clean] (``seq_len`` is then L, the row of tokens)."""
        return TransformerLM(
            vocab_size=self.vocab_size, d_model=self.d_model,
            n_layers=self.n_layers, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, d_ff=self.d_ff,
            attention=self._resolved_attention(seq_len), causal=True,
            n_experts=self.n_experts, moe_k=self.moe_k,
            dropout=self.dropout, mesh=self._mesh_override,
            fused_head_chunk=self._head_chunk(),
            remat=self._resolved_remat(),
            fused_proj=self._resolved_fused_proj(),
            lora_rank=self.lora_rank, lora_alpha=self.lora_alpha,
            sliding_window=self.sliding_window,
            rope_base=self.rope_base, head_dim=self.head_dim,
            qk_norm=self.qk_norm, experts_held=self.experts_held,
            expert_offset=self.expert_offset,
            bd_block=self.block_length if bd else 0,
            layer_types=self.layer_types, ssm_heads=self.ssm_heads,
            ssm_head_dim=self.ssm_head_dim, ssm_state=self.ssm_state,
            ssm_conv=self.ssm_conv, ssm_chunk=self.ssm_chunk,
            rms_norm_eps=self.rms_norm_eps,
            rope=self.position_embedding == "rope",
            attention_scale=self.attention_scale,
            embedding_multiplier=self.embedding_multiplier,
            residual_multiplier=self.residual_multiplier,
            logits_scaling=self.logits_scaling,
            tie_embeddings=self.tie_embeddings)

    @property
    def module(self) -> TransformerLM:
        return self._module_for(None)

    def compile(self, optimizer: Any = "adamw", loss: Any = None,
                metrics: Any = None, **_: Any) -> None:
        if isinstance(optimizer, str):
            self.optimizer_spec = {"kind": optimizer}
        elif isinstance(optimizer, dict):
            self.optimizer_spec = dict(optimizer)
        elif hasattr(optimizer, "spec"):
            self.optimizer_spec = dict(optimizer.spec)
        else:
            raise TypeError(f"unsupported optimizer: {optimizer!r}")
        self._engine = None

    # ------------------------------------------------------------------
    def _apply_fn(self, params, model_state, batch, train, rng):
        rngs = {"dropout": rng} if (train and rng is not None and
                                    self.dropout) else None
        # batch["x"].shape is static under jit, so "auto" attention
        # resolves against the real window length at trace time
        seq = int(batch["x"].shape[1])
        if self.objective == "block_diffusion":
            return self._apply_bd(params, batch["x"], train, rng,
                                  rngs), model_state
        module = self._module_for(seq)
        out = module.apply({"params": params}, batch["x"],
                           train=train, rngs=rngs)
        return out, model_state

    def _apply_bd(self, params, x0, train, rng, rngs) -> FusedHeadOut:
        """One block-diffusion pass (docs/DIFFUSION.md): the step's
        noise from the step's key, the model once over [xt ; x0], and
        the noise handed on to the loss. Evaluation has no step: its
        noise is that of ``PRNGKey(seed)``."""
        seq = x0.shape[1]
        key = jax.random.PRNGKey(self.seed) if rng is None else rng
        t, masked = bd_noise(key, x0.shape)
        x0 = x0.astype(jnp.int32)
        xt = jnp.where(masked, jnp.int32(self.mask_token_id), x0)
        out = self._module_for(seq, bd=True).apply(
            {"params": params}, jnp.concatenate([xt, x0], axis=1),
            train=train, rngs=rngs)
        return out._replace(noise={"masked": masked, "t": t})

    def _build_params(self, sample_x: np.ndarray) -> None:
        rng = jax.random.PRNGKey(self.seed)
        variables = self.module.init(rng, jnp.asarray(sample_x[:1]),
                                     train=False)
        self.params = dict(variables)["params"]

    def _engine_cache_key(self):
        """Identity of the traced program (``NeuralModel``'s rule): the
        constructor's settings, the optimizer's, the evaluation noise's
        seed and every ``LO_*`` environment setting (remat, fused
        projections, head chunk and kernel tiles are resolved from them
        while the step is traced). A second job of the same model, a
        new instance loaded from the same artifact, then runs the first
        job's jitted steps: no trace, no lowering, no read of the
        compile cache (PERF.md section 6, PR 26: three traces of 14 to
        21 s each on the chip's host for the expert model)."""
        try:
            key = ("lm", type(self).__qualname__,
                   tuple((k, getattr(self, k)) for k in self._CONFIG_KEYS),
                   int(self.seed),
                   tuple(sorted(self.optimizer_spec.items())),
                   self._mesh_override,
                   tuple(sorted((k, v) for k, v in os.environ.items()
                                if k.startswith("LO_"))))
            hash(key)
            return key
        except TypeError:  # an unhashable setting: no sharing
            return None

    def _get_engine(self) -> engine_lib.Engine:
        if self._engine is None:
            from learningorchestra_tpu.config import get_config
            from learningorchestra_tpu.models.neural import build_optimizer

            dtype = jnp.bfloat16 \
                if get_config().compute_dtype == "bfloat16" else jnp.float32
            mesh = self._mesh()
            seq_axis = self._resolved_attention() in ("ring", "ulysses")
            def flops_floor(batch):
                # analytic train-step lower bound (6 flops per matmul
                # param per token + the causal-attention quad term):
                # pallas_call is a custom call XLA's cost analysis
                # counts as ZERO flops, so the flash path would
                # otherwise report a deflated MFU. The embedding table
                # is excluded — its lookup is a gather, not a matmul
                # (lm_head is a separate, counted matrix).
                b, s = batch["x"].shape[:2]
                matmul_params = self.num_params()
                if not self.tie_embeddings:  # a tied table IS the head
                    matmul_params -= self.vocab_size * self.d_model
                proj = self.n_heads * (self.head_dim
                                       or self.d_model // self.n_heads)
                attn_layers = self.n_layers if self.layer_types is None \
                    else self.layer_types.count("attention")
                attn = 6.0 * attn_layers * b * s * s * proj
                if self.n_experts:
                    # a token meets moe_k of n_experts: of the held
                    # experts' matrices, that share
                    held = self.experts_held or self.n_experts
                    expert = 3 * self.d_model * self.d_ff * self.n_layers
                    matmul_params -= expert * held * (
                        1.0 - self.moe_k / self.n_experts)
                if self.objective == "block_diffusion":
                    # 2L positions through the layers, the head over
                    # L; each position sees L keys and a block
                    head = self.vocab_size * self.d_model
                    return (6.0 * max(matmul_params - head, 0) * b * 2 * s
                            + 6.0 * head * b * s + 2 * attn)
                return 6.0 * max(matmul_params, 0) * b * s + attn

            optimizer = build_optimizer(self.optimizer_spec)
            if self.lora_rank > 0:
                optimizer = _lora_optimizer(optimizer)
            self._engine = engine_lib.Engine(
                apply_fn=self._apply_fn,
                loss_fn=next_token_loss(
                    self.aux_coef,
                    head_chunk=self._head_chunk() or 1024,
                    mesh=mesh),
                optimizer=optimizer,
                mesh=mesh,
                metrics={"accuracy": token_accuracy},
                compute_dtype=dtype,
                param_rules=self._param_rules(mesh),
                batch_sharding=jax.sharding.NamedSharding(
                    mesh, sharding_lib.batch_spec(mesh, seq_axis=seq_axis)),
                predict_transform=lambda outputs: outputs[0],
                flops_floor_fn=flops_floor,
                grad_accum=self._accum,
                counter_prefixes=("moe", "masked", "ssm"),
                float32_leaves=FLOAT32_LEAVES if self.has_mamba else (),
                cache_key=self._engine_cache_key())
        return self._engine

    def _set_grad_accum(self, grad_accum: Optional[int]) -> None:
        """Fit-time microbatch override (env default LO_GRAD_ACCUM) —
        an effective change rebuilds the engine."""
        self._accum, changed = engine_lib.resolve_grad_accum(
            grad_accum, self._accum)
        if changed:
            self._engine = None

    # ------------------------------------------------------------------
    def _coerce_tokens(self, x) -> np.ndarray:
        if hasattr(x, "to_numpy"):
            x = data_lib.dataframe_to_arrays(x)["x"]
        x = np.asarray(x)
        if x.ndim == 1:  # flat corpus -> non-overlapping windows
            seq = min(self.max_len, max(2, len(x) // 2))
            n = len(x) // seq
            x = x[:n * seq].reshape(n, seq)
        if x.shape[1] > self.max_len:
            x = x[:, :self.max_len]
        return x.astype(np.int32)

    def _batcher(self, x, batch_size: Optional[int],
                 shuffle: bool = False) -> data_lib.ArrayBatcher:
        from learningorchestra_tpu.config import get_config

        mesh = self._mesh()
        return data_lib.ArrayBatcher(
            {"x": self._coerce_tokens(x)},
            batch_size or get_config().default_batch_size,
            shuffle=shuffle, seed=self.seed,
            dp_multiple=mesh_lib.data_parallel_size(mesh))

    def fit(self, x=None, y=None, batch_size: Optional[int] = None,
            epochs: int = 1, shuffle: bool = True, checkpointer=None,
            log_fn=None, grad_accum: Optional[int] = None,
            validation_split: float = 0.0, **_: Any):
        from learningorchestra_tpu.models.neural import History

        self._set_grad_accum(grad_accum)
        val_x = None
        if validation_split:
            # keras-parity tail split (sequences, no labels: held-out
            # windows scored on next-token loss/accuracy); range
            # validation shared with NeuralModel
            from learningorchestra_tpu.models.neural import (
                validation_tail_count)
            x = self._coerce_tokens(x)
            n_val = validation_tail_count(len(x), validation_split)
            val_x = x[-n_val:]
            x = x[:-n_val]
        batcher = self._batcher(x, batch_size, shuffle=shuffle)
        if self.params is None:
            self._build_params(batcher.array("x"))
        eng = self._get_engine()
        state = eng.init_state(self.params)
        state, history = eng.fit(state, batcher, epochs=epochs,
                                 seed=self.seed, checkpointer=checkpointer,
                                 log_fn=log_fn)
        if val_x is not None:
            val = eng.evaluate(state, self._batcher(val_x, batch_size))
            if not history:
                history.append({})
            for k, v in val.items():
                history[-1][f"val_{k}"] = v
        self._state = state
        self.params = engine_lib.to_host(state.params)
        self.history.extend(history)
        return History(history)

    def evaluate(self, x=None, y=None, batch_size: Optional[int] = None,
                 **_: Any) -> Dict[str, float]:
        self._require_built()
        eng = self._get_engine()
        state = self._state or eng.init_state(self.params)
        return eng.evaluate(state, self._batcher(x, batch_size))

    def predict(self, x=None, batch_size: Optional[int] = None,
                **_: Any) -> np.ndarray:
        """Next-token logits (n, seq, vocab)."""
        self._require_next_token("predict")
        self._require_built()
        eng = self._get_engine()
        state = self._state or eng.init_state(self.params)
        return eng.predict(state, self._batcher(x, batch_size))

    def generate(self, prompt, max_new_tokens: int = 32,
                 temperature: float = 0.0, seed: int = 0,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 num_beams: int = 1) -> np.ndarray:
        """Greedy / temperature sampling with an incremental KV cache:
        the prompt runs ONCE (prefill fills every layer's K/V cache),
        then the whole continuation decodes inside ONE jitted
        ``lax.fori_loop`` of single-position forwards attending over
        the cache — O(L) per token instead of the O(L²) full
        re-forward, and one host round trip for the entire
        continuation. prompt: (b, s) token ids.

        ``top_k`` keeps only the k highest-logit tokens and ``top_p``
        keeps the smallest nucleus whose probability mass reaches p;
        both apply only when ``temperature > 0`` (greedy decoding
        ignores them) and compose (k-filter first, then nucleus).

        Prompts longer than ``max_len`` keep their last ``max_len - 1``
        tokens (sliding-window truncation). Token id 0 is reserved as
        padding by ``next_token_loss`` and is masked out of sampling.

        Unequal-length prompts are accepted (list of lists): rows are
        left-padded with id 0 so the last prompt tokens align, and the
        attention mask hides pad columns — each row's continuation is
        the same tokens a solo ``generate([row])`` call would produce
        (greedy; sampled runs draw per-position keys from the shared
        buffer layout). The returned array keeps the leading pad zeros
        so rows stay rectangular; slice ``row[pad:]`` to recover the
        solo-shaped sequence.
        """
        self._require_autoregressive("generate")
        self._require_built()
        if num_beams > 1:
            if temperature > 0:
                raise ValueError(
                    "beam search is deterministic — use temperature=0 "
                    "(sampling and beams don't compose)")
            if top_k is not None or top_p is not None:
                raise ValueError(
                    "beam search is deterministic — top_k/top_p "
                    "sampling filters don't compose with num_beams>1")
            if num_beams >= self.vocab_size:
                # token 0 is pad-masked, so vocab-1 real candidates
                raise ValueError(
                    f"num_beams={num_beams} exceeds the "
                    f"{self.vocab_size - 1} non-pad vocabulary "
                    f"candidates")
            return self._beam_search(prompt, max_new_tokens,
                                     int(num_beams))
        if temperature <= 0:
            # greedy argmax never reads the filters — normalize so
            # generate(.., top_k=50) shares the greedy compile
            top_k = top_p = None
        if top_k is not None:
            top_k = int(top_k)
            if top_k < 1:
                raise ValueError(f"top_k must be >= 1, got {top_k}")
            if top_k >= self.vocab_size:
                top_k = None  # keeps everything — same compile as None
        if top_p is not None:
            top_p = float(top_p)
            if not 0.0 < top_p <= 1.0:
                raise ValueError(f"top_p must be in (0, 1], got {top_p}")
            if top_p == 1.0:
                top_p = None  # keeps everything — same compile as None
        prompt, b, s, total, pad = self._prep_prompt(prompt,
                                                     max_new_tokens)
        if total <= s:
            # nothing to generate — prefill would clamp buf[:, s] onto
            # the last prompt column and corrupt it
            return prompt
        buf = np.zeros((b, total), np.int32)
        buf[:, :s] = prompt
        buf = jnp.asarray(buf)
        prefill, decode = self._gen_fns(
            b, s, total, float(temperature), top_k, top_p,
            padded=pad is not None)
        params = self.params
        key = jax.random.PRNGKey(seed)
        key, sub = jax.random.split(key)
        if pad is None:
            buf, cache = prefill(params, buf, sub)
            if total > s + 1:
                key, sub = jax.random.split(key)
                buf, cache = decode(params, cache, buf, sub)
        else:
            # unequal-length prompts: left-pad aligned the rows' last
            # tokens, so the whole batch prefills and decodes in
            # lockstep — pad rows are hidden by the attention mask
            pad_j = jnp.asarray(pad)
            buf, cache = prefill(params, buf, sub, pad_j)
            if total > s + 1:
                key, sub = jax.random.split(key)
                buf, cache = decode(params, cache, buf, sub, pad_j)
        return np.asarray(buf)

    def _prep_prompt(self, prompt, max_new_tokens: int):
        """Shared generate/beam preprocessing: 2-D int32 prompt,
        sliding-window truncation of prompts at/over max_len, and the
        clamped total length. A list of UNEQUAL-length prompts is
        left-padded (with the reserved pad id 0) so every row's last
        prompt token lands in the same column and the batch decodes in
        lockstep; the returned ``pad`` (``(b,)`` int32, None for
        rectangular input) carries each row's pad width into the
        attention masks."""
        pad = None
        if isinstance(prompt, (list, tuple)) and len(prompt) > 1 and \
                all(hasattr(p, "__len__") for p in prompt) and \
                len({len(p) for p in prompt}) > 1:
            s = max(len(p) for p in prompt)
            rows = np.zeros((len(prompt), s), np.int32)
            pad = np.zeros(len(prompt), np.int32)
            for i, p in enumerate(prompt):
                arr = np.asarray(p, dtype=np.int32).reshape(-1)
                pad[i] = s - arr.shape[0]
                rows[i, pad[i]:] = arr
            prompt = rows
        prompt = np.atleast_2d(np.asarray(prompt)).astype(np.int32)
        b, s = prompt.shape
        if s >= self.max_len:
            keep = self.max_len - 1
            prompt = prompt[:, -keep:]
            if pad is not None:
                pad = np.minimum(pad - (s - keep), keep).clip(0) \
                    .astype(np.int32)
            s = prompt.shape[1]
        total = min(self.max_len, s + max_new_tokens)
        return prompt, b, s, total, pad

    # ------------------------------------------------------------------
    # beam search
    # ------------------------------------------------------------------
    def _beam_search(self, prompt, max_new_tokens: int,
                     num_beams: int) -> np.ndarray:
        """Deterministic beam search over the KV cache: prefill runs
        once per sample, the cache tiles to ``b·beams`` rows, and each
        jitted ``fori_loop`` step scores every (beam, token) candidate
        (summed log-probs), keeps the top ``num_beams``, and REORDERS
        buf+cache by each survivor's parent beam (a batch-axis gather
        inside the loop). All beams share one fixed length, so raw
        summed log-prob is the ranking (no length penalty needed);
        returns the best beam per sample, shape (b, s+new)."""
        prompt, b, s, total, pad = self._prep_prompt(prompt,
                                                     max_new_tokens)
        if pad is not None:
            raise ValueError(
                "beam search requires equal-length prompts (pass one "
                "prompt at a time, or use num_beams=1 which "
                "left-pads)")
        if total <= s:
            return prompt
        fns = self._beam_cache_fns
        sig = (b, s, total, num_beams, self._resolved_attention(s))
        if sig not in fns:
            fns[sig] = self._build_beam_fns(b, s, total, num_beams)
        run = fns[sig]
        return np.asarray(run(self.params, jnp.asarray(prompt)))

    def _build_beam_fns(self, b: int, s: int, total: int, n: int):
        module = self._module_for(s)
        V = self.vocab_size

        def logp_of(logits):
            lg = logits.astype(jnp.float32)
            lg = lg.at[..., 0].set(ring_lib.NEG_INF)  # pad token
            return jax.nn.log_softmax(lg, axis=-1)

        @jax.jit
        def run(params, prompt):
            buf0 = jnp.zeros((b, total), jnp.int32).at[:, :s].set(prompt)
            (logits, _), mut = module.apply(
                {"params": params}, prompt, train=False,
                cache_len=total, mutable=["cache"])
            first = logp_of(logits[:, -1])                  # (b, V)
            scores, toks = jax.lax.top_k(first, n)          # (b, n)
            buf = jnp.repeat(buf0[:, None, :], n, axis=1)   # (b, n, T)
            buf = buf.at[:, :, s].set(toks)
            cache = jax.tree_util.tree_map(
                lambda c: jnp.repeat(c, n, axis=0), mut["cache"])

            def body(pos, carry):
                buf, cache, scores = carry
                tok = jax.lax.dynamic_slice(
                    buf, (0, 0, pos - 1), (b, n, 1)).reshape(b * n, 1)
                (lg, _), mut = module.apply(
                    {"params": params, "cache": cache}, tok,
                    train=False, decode_pos=pos - 1, cache_len=total,
                    mutable=["cache"])
                logp = logp_of(lg[:, 0]).reshape(b, n, V)
                cand = scores[..., None] + logp             # (b, n, V)
                scores, flat = jax.lax.top_k(
                    cand.reshape(b, n * V), n)              # (b, n)
                parent = flat // V
                token = (flat % V).astype(jnp.int32)
                buf = jnp.take_along_axis(
                    buf, parent[..., None], axis=1)
                buf = jax.lax.dynamic_update_slice(
                    buf, token[..., None], (0, 0, pos))
                rows = (jnp.arange(b)[:, None] * n
                        + parent).reshape(-1)               # (b*n,)
                cache = jax.tree_util.tree_map(
                    lambda c: jnp.take(c, rows, axis=0), mut["cache"])
                return buf, cache, scores

            buf, cache, scores = jax.lax.fori_loop(
                s + 1, total, body, (buf, cache, scores))
            best = jnp.argmax(scores, axis=1)
            return jnp.take_along_axis(
                buf, best[:, None, None], axis=1)[:, 0]

        return run

    @staticmethod
    def _filter_logits(last, temperature: float,
                       top_k: Optional[int] = None,
                       top_p: Optional[float] = None):
        """The sampling transform of :meth:`_sample` up to (but not
        including) the draw: pad mask, temperature, top-k, top-p.
        Factored out so speculative acceptance (serve_fns_spec) can
        score draft tokens against the EXACT distribution _sample
        draws from — ``softmax(_filter_logits(...))`` for
        ``temperature > 0``, ``argmax`` for greedy."""
        # id 0 is the padding/loss-mask token — never emit it
        last = last.astype(jnp.float32).at[..., 0].set(ring_lib.NEG_INF)
        if temperature <= 0:
            return last
        logits = last / temperature
        if top_k is not None and top_k < logits.shape[-1]:
            kth = jnp.sort(logits, axis=-1)[..., -top_k, None]
            logits = jnp.where(logits < kth, ring_lib.NEG_INF, logits)
        if top_p is not None and top_p < 1.0:
            order = jnp.argsort(-logits, axis=-1)
            ranked = jnp.take_along_axis(logits, order, axis=-1)
            probs = jax.nn.softmax(ranked, axis=-1)
            # keep tokens whose EXCLUSIVE prefix mass is < p, so the
            # token that crosses the threshold stays in the nucleus
            keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p
            ranked = jnp.where(keep, ranked, ring_lib.NEG_INF)
            inv = jnp.argsort(order, axis=-1)
            logits = jnp.take_along_axis(ranked, inv, axis=-1)
        return logits

    @staticmethod
    def _sample(last, temperature: float, key,
                top_k: Optional[int] = None,
                top_p: Optional[float] = None):
        logits = LanguageModel._filter_logits(last, temperature,
                                              top_k, top_p)
        if temperature <= 0:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(key, logits, axis=-1)

    def _gen_fns(self, b: int, s: int, total: int, temperature: float,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 padded: bool = False):
        """Jitted (prefill, decode) per (batch, prompt_len, total,
        temperature) — params/cache are arguments, not closures, so
        weights stay device-resident and repeated generate() calls
        reuse the compile. ``decode`` runs the WHOLE continuation in
        one fori_loop program (buf and cache donated into it, updated
        in place across iterations — no per-token host round trip).
        ``padded=True`` compiles the left-padded variant: prefill and
        decode take a per-row ``pad`` width and mask pad rows out of
        attention (unequal-length prompt batches)."""
        fns = self._gen_cache_fns
        # resolve flash-vs-dot from the PREFILL length, not max_len: a
        # max_len>=2048 model generating from a short prompt attends
        # over only s tokens, below the measured flash crossover
        sig = (b, s, total, temperature, top_k, top_p,
               self._resolved_attention(s), padded)
        if sig in fns:
            return fns[sig]
        module = self._module_for(s)

        if padded:
            @jax.jit
            def prefill(params, buf, key, pad):
                (logits, _), mut = module.apply(
                    {"params": params}, buf[:, :s], train=False,
                    cache_len=total, pad_offset=pad,
                    mutable=["cache"])
                nxt = self._sample(logits[:, -1], temperature, key,
                                   top_k, top_p)
                buf = buf.at[:, s].set(nxt.astype(jnp.int32))
                return buf, mut["cache"]

            @functools.partial(jax.jit, donate_argnums=(1, 2))
            def decode(params, cache, buf, key, pad):
                def body(pos, carry):
                    buf, cache = carry
                    tok = jax.lax.dynamic_slice(buf, (0, pos - 1),
                                                (b, 1))
                    (logits, _), mut = module.apply(
                        {"params": params, "cache": cache}, tok,
                        train=False, decode_pos=pos - 1,
                        cache_len=total, pad_offset=pad,
                        mutable=["cache"])
                    nxt = self._sample(logits[:, 0], temperature,
                                       jax.random.fold_in(key, pos),
                                       top_k, top_p)
                    buf = jax.lax.dynamic_update_slice(
                        buf, nxt[:, None].astype(jnp.int32), (0, pos))
                    return buf, mut["cache"]

                return jax.lax.fori_loop(s + 1, total, body,
                                         (buf, cache))

            fns[sig] = (prefill, decode)
            return fns[sig]

        @jax.jit
        def prefill(params, buf, key):
            (logits, _), mut = module.apply(
                {"params": params}, buf[:, :s], train=False,
                cache_len=total, mutable=["cache"])
            nxt = self._sample(logits[:, -1], temperature, key,
                               top_k, top_p)
            buf = buf.at[:, s].set(nxt.astype(jnp.int32))
            return buf, mut["cache"]

        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def decode(params, cache, buf, key):
            # the WHOLE decode loop runs as one device program
            # (lax.fori_loop carrying buf+cache) — one host round trip
            # for the entire continuation instead of one per token
            def body(pos, carry):
                buf, cache = carry
                tok = jax.lax.dynamic_slice(buf, (0, pos - 1), (b, 1))
                (logits, _), mut = module.apply(
                    {"params": params, "cache": cache}, tok, train=False,
                    decode_pos=pos - 1, cache_len=total,
                    mutable=["cache"])
                nxt = self._sample(logits[:, 0], temperature,
                                   jax.random.fold_in(key, pos),
                                   top_k, top_p)
                buf = jax.lax.dynamic_update_slice(
                    buf, nxt[:, None].astype(jnp.int32), (0, pos))
                return buf, mut["cache"]

            return jax.lax.fori_loop(s + 1, total, body, (buf, cache))

        fns[sig] = (prefill, decode)
        return fns[sig]

    # ------------------------------------------------------------------
    # resident serving (services/serving.py)
    # ------------------------------------------------------------------
    def serve_fns(self, slots: int, cache_len: int, temperature: float,
                  top_k: Optional[int] = None,
                  top_p: Optional[float] = None):
        """Jitted continuous-batching kernel set for a serving session
        (docs/SERVING.md): ``(step, prefill_for, join)``.

        - ``step(params, cache, tok (slots,1), col (slots,), keys
          (slots,2))`` advances EVERY slot one token: each row attends
          its own cache prefix at its own position ``col[i]`` and
          samples with its own fold_in(key_i, col_i+1) — exactly the
          key/position schedule a solo ``generate()`` row follows, so
          a slot's token stream is bit-identical to decoding that
          request alone. Idle slots compute garbage (finite — their
          mask sees a valid self position) that the caller discards.
        - ``prefill_for(s)`` returns the jitted batch-1 prompt prefill
          for prompt length ``s`` (cached per length): fills a
          (1, cache_len) layer cache and samples the first token.
        - ``join(cache, pcache, slot)`` scatters a prefill cache into
          the session cache at ``slot`` (traced index — one compile
          covers every slot, so slot reuse never recompiles).
        """
        self._require_autoregressive("serving")
        fns = self._serve_cache_fns
        sig = (slots, cache_len, temperature, top_k, top_p)
        if sig not in fns:
            fns[sig] = self._build_serve_fns(slots, cache_len,
                                             temperature, top_k, top_p)
        return fns[sig]

    def _build_serve_fns(self, slots: int, cache_len: int,
                         temperature: float, top_k: Optional[int],
                         top_p: Optional[float]):
        module = self._module_for(1)
        sample = self._sample

        @functools.partial(jax.jit, donate_argnums=(1,))
        def step(params, cache, tok, col, keys):
            params = dequantize_serving_params(params)
            (logits, _), mut = module.apply(
                {"params": params, "cache": cache}, tok, train=False,
                decode_pos=col, cache_len=cache_len,
                mutable=["cache"])
            # per-row key schedule: fold_in(row_key, buffer_position)
            # where the position being WRITTEN is col+1 — matching the
            # solo decode loop's fold_in(key, pos) at pos = col + 1
            ks = jax.vmap(jax.random.fold_in)(keys, col + 1)
            nxt = jax.vmap(
                lambda lg, k: sample(lg[None], temperature, k,
                                     top_k, top_p)[0])(logits[:, 0], ks)
            return nxt.astype(jnp.int32), mut["cache"]

        prefill_cache: Dict[int, Any] = {}

        def prefill_for(s: int):
            if s in prefill_cache:
                return prefill_cache[s]
            pmod = self._module_for(s)

            @jax.jit
            def prefill(params, tokens, key):
                params = dequantize_serving_params(params)
                (logits, _), mut = pmod.apply(
                    {"params": params}, tokens, train=False,
                    cache_len=cache_len, mutable=["cache"])
                nxt = sample(logits[:, -1], temperature, key,
                             top_k, top_p)
                return nxt.astype(jnp.int32), mut["cache"]

            prefill_cache[s] = prefill
            return prefill

        @jax.jit
        def join(cache, pcache, slot):
            return jax.tree_util.tree_map(
                lambda sc, pc: sc.at[slot].set(pc[0]), cache, pcache)

        return step, prefill_for, join

    def serve_cache(self, slots: int, cache_len: int):
        """Zero-initialized per-layer KV cache for a serving session
        (the shape ``init`` would produce for a (slots, ·) decode)."""
        module = self._module_for(1)
        shapes = jax.eval_shape(
            lambda: module.init(
                jax.random.PRNGKey(0),
                jnp.zeros((slots, 1), jnp.int32), train=False,
                decode_pos=jnp.zeros((slots,), jnp.int32),
                cache_len=cache_len)["cache"])
        return jax.tree_util.tree_map(
            lambda sd: jnp.zeros(sd.shape, sd.dtype), shapes)

    def serve_fns_paged(self, slots: int, cache_len: int,
                        page_len: int, n_pages: int,
                        temperature: float,
                        top_k: Optional[int] = None,
                        top_p: Optional[float] = None,
                        kv_dtype: str = "bf16"):
        """Paged-KV variant of :meth:`serve_fns` (docs/SERVING.md
        "Paged KV"): the per-layer cache is one SHARED
        ``(n_pages, page_len, kv, d)`` pool and each stream owns an
        ordered page list (its block-table row) instead of a
        ``cache_len`` rectangle. Returns
        ``(step, prefill_for, join_paged, copy_page, sample_first)``:

        - ``step(params, pool, tok, col, block_tables, keys)`` — one
          continuous-batch decode step over the pool. The gather
          width is ``block_tables.shape[1]``: the session slices the
          table to the live-length bucket on the host, so one compile
          per bucket and short streams never gather long-stream
          pages. Rope/mask/sampling schedule is byte-for-byte the
          slot step's (bit-identity contract).
        - ``prefill_for(s)`` — per-length batch-1 prefill returning
          ``(next_token, last_logits, pcache)``; ``last_logits``
          feeds the prefix cache so an exact-prompt hit can resample
          a first token without recomputing the prefill.
        - ``join_paged(pool, pcache, page_ids, start_row)`` — write
          prefill KV rows ``[start_row, ·)`` directly into
          ``page_ids`` (one compile per page count; shared prefix
          pages are excluded and never rewritten).
        - ``copy_page(pool, src, dst)`` — clone one page (a prefix
          hit's partially-filled tail page is copy-on-write: the new
          stream appends into its own copy).
        - ``sample_first(logits, key)`` — the prefill's sampling
          epilogue alone, for prefix hits that skipped the prefill.

        ``kv_dtype="int8"`` switches the pool to int8 values + a
        per-page-per-head scale pool ("Quantized serving"): the same
        five functions over half the pool bytes, with dequant fused
        into the gather/step.
        """
        self._require_autoregressive("serving")
        fns = self._serve_paged_fns
        sig = (slots, cache_len, page_len, n_pages, temperature,
               top_k, top_p, kv_dtype)
        if sig not in fns:
            fns[sig] = self._build_serve_fns_paged(
                slots, cache_len, page_len, n_pages, temperature,
                top_k, top_p, kv_dtype)
        return fns[sig]

    def _build_serve_fns_paged(self, slots: int, cache_len: int,
                               page_len: int, n_pages: int,
                               temperature: float,
                               top_k: Optional[int],
                               top_p: Optional[float],
                               kv_dtype: str = "bf16"):
        module = self._module_for(1)
        sample = self._sample
        kv_quant = kv_dtype == "int8"

        @functools.partial(jax.jit, donate_argnums=(1,))
        def step(params, pool, tok, col, block_tables, keys):
            params = dequantize_serving_params(params)
            (logits, _), mut = module.apply(
                {"params": params, "cache": pool}, tok, train=False,
                decode_pos=col, cache_len=cache_len,
                block_tables=block_tables, page_len=page_len,
                kv_pages=n_pages, kv_quant=kv_quant,
                mutable=["cache"])
            # same per-row fold_in(key, col + 1) schedule as the slot
            # step — the whole bit-identity story rides on it
            ks = jax.vmap(jax.random.fold_in)(keys, col + 1)
            nxt = jax.vmap(
                lambda lg, k: sample(lg[None], temperature, k,
                                     top_k, top_p)[0])(logits[:, 0], ks)
            return nxt.astype(jnp.int32), mut["cache"]

        prefill_cache: Dict[int, Any] = {}

        def prefill_for(s: int):
            if s in prefill_cache:
                return prefill_cache[s]
            pmod = self._module_for(s)

            @jax.jit
            def prefill(params, tokens, key):
                params = dequantize_serving_params(params)
                (logits, _), mut = pmod.apply(
                    {"params": params}, tokens, train=False,
                    cache_len=cache_len, mutable=["cache"])
                nxt = sample(logits[:, -1], temperature, key,
                             top_k, top_p)
                return (nxt.astype(jnp.int32), logits[:, -1],
                        mut["cache"])

            prefill_cache[s] = prefill
            return prefill

        # both donate the pool like step() does: without donation
        # every prefill join / tail clone materializes a second full
        # copy of the page pool in HBM (transient 2x footprint per
        # layer tree), which would break equal-HBM sizing at large
        # pool sizes
        if kv_quant:
            # the pool tree carries k_scale/v_scale leaves the plain
            # prefill cache lacks, so tree_map's structure match fails;
            # walk the dicts by hand and quantize at the k/v level
            @functools.partial(jax.jit, donate_argnums=(0,))
            def join_paged(pool, pcache, page_ids, start_row):
                def walk(pl, pc):
                    if isinstance(pl, dict) or hasattr(pl, "items"):
                        if "k_scale" in pl:
                            kq, ks = \
                                attn_ops.quantized_paged_prefill_write(
                                    pl["k"], pl["k_scale"], pc["k"][0],
                                    page_ids, start_row)
                            vq, vs = \
                                attn_ops.quantized_paged_prefill_write(
                                    pl["v"], pl["v_scale"], pc["v"][0],
                                    page_ids, start_row)
                            return {"k": kq, "k_scale": ks,
                                    "v": vq, "v_scale": vs}
                        return {k: walk(pl[k], pc[k]) for k in pl}
                    return pl

                return walk(pool, pcache)
        else:
            @functools.partial(jax.jit, donate_argnums=(0,))
            def join_paged(pool, pcache, page_ids, start_row):
                return jax.tree_util.tree_map(
                    lambda pl, pc: attn_ops.paged_prefill_write(
                        pl, pc[0], page_ids, start_row), pool, pcache)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def copy_page(pool, src, dst):
            return jax.tree_util.tree_map(
                lambda pl: pl.at[dst].set(pl[src]), pool)

        @jax.jit
        def sample_first(logits, key):
            # identical floats to the prefill's own epilogue: the
            # cached logits ARE the prefill's logits[:, -1] row
            return sample(logits[None], temperature, key,
                          top_k, top_p)[0].astype(jnp.int32)

        return step, prefill_for, join_paged, copy_page, sample_first

    def serve_cache_paged(self, n_pages: int, page_len: int,
                          kv_dtype: str = "bf16"):
        """Zero-initialized shared KV page pool:
        ``{layer: {k/v: (n_pages, page_len, kv_heads, head_dim)}}`` —
        ONE allocation every stream's block table indexes into. Under
        ``kv_dtype="int8"`` the k/v leaves are int8 and per-layer
        ``k_scale``/``v_scale`` ``(n_pages, kv_heads)`` float32 leaves
        ride along (zero scales dequantize to exact zeros, matching
        the zero pool)."""
        module = self._module_for(1)
        shapes = jax.eval_shape(
            lambda: module.init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, 1), jnp.int32), train=False,
                decode_pos=jnp.zeros((1,), jnp.int32),
                cache_len=page_len * n_pages,
                block_tables=jnp.zeros((1, 1), jnp.int32),
                page_len=page_len, kv_pages=n_pages,
                kv_quant=kv_dtype == "int8")["cache"])
        return jax.tree_util.tree_map(
            lambda sd: jnp.zeros(sd.shape, sd.dtype), shapes)

    def serve_fns_spec(self, slots: int, cache_len: int,
                       page_len: int, n_pages: int, spec_k: int,
                       temperature: float,
                       top_k: Optional[int] = None,
                       top_p: Optional[float] = None,
                       kv_dtype: str = "bf16"):
        """Speculative-decoding verify step for a paged serving
        session (docs/SERVING.md "Disaggregated serving & speculative
        decoding"): ONE jitted dispatch that scores the last accepted
        token plus ``spec_k`` draft tokens, accepts a prefix of the
        drafts by exact rejection sampling against this (target)
        model's sampling distribution, and emits the correction/bonus
        token — up to ``spec_k + 1`` tokens per step.

        ``verify(params, pool, tok (slots,1), drafts (slots,k),
        col (slots,), keys (slots,2), block_tables, limit (slots,))``
        returns ``(emitted (slots, k+1) int32, n_acc (slots,) int32,
        pool)``; a slot's valid emissions are
        ``emitted[:n_acc + 1]``, continuing its stream at positions
        ``col+1 .. col+n_acc+1``.

        Exactness: the drafts are the draft model's GREEDY picks — a
        one-hot proposal q — so the standard accept probability
        ``min(1, p/q)`` reduces to ``p(draft)`` under the target's
        :meth:`_filter_logits` distribution, and the rejection
        residual ``max(p - q, 0)`` normalized is exactly p with the
        draft token excluded: every emitted position is distributed
        exactly as a solo :meth:`_sample` draw. For greedy sessions
        (``temperature <= 0``) accept degenerates to
        ``draft == argmax(target)`` and the emitted stream is
        BIT-IDENTICAL to solo decode: the verify forward reproduces
        sequential single-token steps float-for-float
        (ops/attention.py paged_verify_attention) and argmax needs no
        randomness. Per-position keys follow the solo schedule —
        position ``pos`` folds ``fold_in(row_key, pos)``, split once
        into (accept-uniform, residual) keys for sampled sessions.

        Rejected drafts leave stale KV rows beyond the new ``col``;
        the visibility mask hides them and the next window overwrites
        them — no rollback. ``limit`` is each stream's last funded
        position: past-limit appends land in trash page 0, so a
        window overrunning a stream's pages can never corrupt a
        neighbor (the host discards the overrun emissions).
        """
        self._require_autoregressive("serving")
        fns = self._serve_spec_fns
        sig = ("verify", slots, cache_len, page_len, n_pages, spec_k,
               temperature, top_k, top_p, kv_dtype)
        if sig in fns:
            return fns[sig]
        module = self._module_for(1)
        filter_fn = self._filter_logits
        kv_quant = kv_dtype == "int8"

        @functools.partial(jax.jit, donate_argnums=(1,))
        def verify(params, pool, tok, drafts, col, keys,
                   block_tables, limit):
            params = dequantize_serving_params(params)
            toks = jnp.concatenate([tok, drafts], axis=1)
            (logits, _), mut = module.apply(
                {"params": params, "cache": pool}, toks, train=False,
                decode_pos=col, cache_len=cache_len,
                block_tables=block_tables, page_len=page_len,
                kv_pages=n_pages, kv_quant=kv_quant,
                verify_limit=limit, mutable=["cache"])
            # logits[:, i] scores position col + i + 1 — the position
            # draft i (or the correction after a rejection) lands at
            filt = filter_fn(logits, temperature, top_k, top_p)
            rows = jnp.arange(toks.shape[0])
            if temperature <= 0:
                choice = jnp.argmax(filt, axis=-1).astype(jnp.int32)
                accept = drafts == choice[:, :spec_k]
                corr = choice
            else:
                probs = jax.nn.softmax(filt, axis=-1)
                acc_cols, corr_cols = [], []
                for i in range(spec_k):
                    kp = jax.vmap(jax.random.fold_in)(keys,
                                                      col + i + 1)
                    kur = jax.vmap(jax.random.split)(kp)
                    u = jax.vmap(
                        lambda k: jax.random.uniform(k))(kur[:, 0])
                    p_d = probs[rows, i, drafts[:, i]]
                    acc_cols.append(u < p_d)
                    resid = filt[:, i].at[rows, drafts[:, i]].set(
                        ring_lib.NEG_INF)
                    corr_cols.append(jax.vmap(
                        lambda lg, k: jax.random.categorical(k, lg))(
                        resid, kur[:, 1]))
                # bonus position (every draft accepted): a plain
                # categorical under the solo key schedule for
                # position col + spec_k + 1
                kp = jax.vmap(jax.random.fold_in)(keys,
                                                  col + spec_k + 1)
                corr_cols.append(jax.vmap(
                    lambda lg, k: jax.random.categorical(k, lg))(
                    filt[:, spec_k], kp))
                accept = jnp.stack(acc_cols, axis=1)
                corr = jnp.stack(corr_cols, axis=1).astype(jnp.int32)
            n_acc = jnp.sum(jnp.cumprod(
                accept.astype(jnp.int32), axis=1), axis=1)
            padded = jnp.concatenate(
                [drafts, jnp.zeros((drafts.shape[0], 1), jnp.int32)],
                axis=1)
            idx = jnp.arange(spec_k + 1)[None, :]
            emitted = jnp.where(
                idx < n_acc[:, None], padded,
                jnp.where(idx == n_acc[:, None], corr, 0))
            return (emitted.astype(jnp.int32),
                    n_acc.astype(jnp.int32), mut["cache"])

        fns[sig] = verify
        return fns[sig]

    def serve_fns_draft(self, slots: int, cache_len: int,
                        spec_k: int):
        """Draft-side propose step for speculative decoding: ONE
        jitted scan that greedily extends every slot by ``spec_k``
        tokens over the draft model's own slot KV cache (prompt KV
        arrives via :meth:`serve_fns`'s prefill/join, so the draft
        shares the target's admission path). The scan runs
        ``spec_k + 1`` forwards: the last feeds draft k purely to
        append its KV row, so the NEXT window's propose attends a
        complete prefix whatever the acceptance count was. Greedy
        proposals make the proposal distribution one-hot, which is
        what keeps acceptance sampling exact (see serve_fns_spec)."""
        self._require_autoregressive("serving")
        fns = self._serve_spec_fns
        sig = ("draft", slots, cache_len, spec_k)
        if sig in fns:
            return fns[sig]
        module = self._module_for(1)
        sample = self._sample

        @functools.partial(jax.jit, donate_argnums=(1,))
        def propose(params, cache, tok, col):
            params = dequantize_serving_params(params)

            def body(carry, _):
                cache, tok, col = carry
                (logits, _), mut = module.apply(
                    {"params": params, "cache": cache}, tok,
                    train=False, decode_pos=col, cache_len=cache_len,
                    mutable=["cache"])
                nxt = sample(logits[:, 0], 0.0, None).astype(jnp.int32)
                return (mut["cache"], nxt[:, None], col + 1), nxt

            (cache, _, _), drafts = jax.lax.scan(
                body, (cache, tok, col), None, length=spec_k + 1)
            return jnp.transpose(drafts[:spec_k]), cache

        fns[sig] = propose
        return fns[sig]

    def _require_next_token(self, what: str) -> None:
        """Generation by blocks (several denoising passes a block, a
        step that yields a block and not a token) is not built yet
        (ROADMAP): a block-diffusion model trains and evaluates."""
        if self.objective != "next_token":
            raise NotImplementedError(
                f"{what} of an objective={self.objective!r} model: "
                f"block-wise denoising generation is not implemented")

    def _require_autoregressive(self, what: str) -> None:
        """The guard of every path that decodes through a cache:
        ``_require_next_token``, and neither is decoding through a
        Mamba-2 layer built (its recurrent state would live beside the
        KV cache, ROADMAP R5) nor the decode paths' share of the
        settings that came with it: such a model trains, evaluates and
        predicts (whole rows, no cache)."""
        self._require_next_token(what)
        undecodable = [k for k, default in (
            ("position_embedding", "rope"), ("attention_scale", 0.0),
            ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
            ("logits_scaling", 1.0)) if getattr(self, k) != default]
        if self.has_mamba or undecodable:
            raise NotImplementedError(
                f"{what} of a model with "
                f"{'Mamba-2 layers' if self.has_mamba else undecodable}: "
                f"the decode paths carry neither a recurrent state nor "
                f"these settings yet (docs/STATE_SPACE.md); fit, evaluate "
                f"and predict work")

    def _require_built(self) -> None:
        if self.params is None:
            raise RuntimeError(
                "model has no parameters yet — call fit() first "
                "(or load a trained artifact)")

    def enable_lora(self, rank: int, alpha: float = 16.0) -> None:
        """Attach fresh rank-``rank`` adapters to a trained model: the
        base kernels keep their values (B inits at zero, so step-0
        predictions are unchanged) and subsequent fit() updates ONLY
        the adapters (frozen-base optimizer). Reachable through the
        reference's call-method-on-stored-object train contract."""
        if self.lora_rank > 0:
            raise RuntimeError(
                f"model already has LoRA adapters (rank "
                f"{self.lora_rank}); merge_lora() first")
        if int(rank) <= 0:
            raise ValueError(f"rank must be >= 1, got {rank}")
        self._require_built()
        self.lora_rank = int(rank)
        self.lora_alpha = float(alpha)
        sample = jnp.zeros((1, min(8, self.max_len)), jnp.int32)
        fresh = self._module_for(None).init(
            jax.random.PRNGKey(self.seed), sample)["params"]

        def graft(fresh_node, old_node, path=""):
            if isinstance(fresh_node, dict):
                old = old_node if isinstance(old_node, dict) else {}
                return {k: graft(v, old.get(k), f"{path}/{k}")
                        for k, v in fresh_node.items()}
            if old_node is not None:
                return old_node
            # ONLY adapters may init fresh — any other missing leaf
            # means the trained tree's layout doesn't match this
            # config (e.g. a fused_proj env toggle) and silently
            # re-initializing it would discard trained weights
            if path.rsplit("/", 1)[-1].startswith("lora_"):
                return fresh_node
            raise ValueError(
                f"enable_lora: trained params have no leaf at "
                f"{path!r} — the model config resolves to a "
                f"different param layout (fused_proj/attention "
                f"mismatch?); refusing to re-initialize a base "
                f"weight")

        self.params = graft(fresh, engine_lib.to_host(self.params))
        self._engine = None
        self._state = None
        self._drop_decode_caches()

    def merge_lora(self) -> None:
        """Fold the adapters into the base kernels (W += A·B·α/r) and
        drop them: the model becomes a plain artifact, numerically
        identical to the adapted one, loadable anywhere without LoRA
        config."""
        if self.lora_rank <= 0:
            raise RuntimeError("model has no LoRA adapters to merge")
        self._require_built()
        scale = self.lora_alpha / self.lora_rank

        def walk(node):
            if isinstance(node, dict):
                if "lora_a" in node and "kernel" in node:
                    merged = node["kernel"] + np.asarray(
                        node["lora_a"]) @ np.asarray(
                        node["lora_b"]) * scale
                    return {"kernel": merged}
                return {k: walk(v) for k, v in node.items()}
            return node

        self.params = walk(engine_lib.to_host(self.params))
        self.lora_rank = 0
        self._engine = None
        self._state = None
        self._drop_decode_caches()

    def num_params(self) -> int:
        if self.params is None:
            return 0
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params))

    # ------------------------------------------------------------------
    # artifact-store native protocol (catalog/artifacts.py)
    # ------------------------------------------------------------------
    def __lo_save__(self, path: str) -> None:
        from learningorchestra_tpu.runtime import checkpoint as ckpt

        config = {k: getattr(self, k) for k in self._CONFIG_KEYS}
        config.update(name=self.name, optimizer_spec=self.optimizer_spec,
                      seed=self.seed, history=self.history,
                      built=self.params is not None)
        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump(config, f)
        if self.params is not None:
            ckpt.save_pytree({"params": self.params},
                             os.path.join(path, "weights.msgpack"))

    @classmethod
    def __lo_load__(cls, path: str) -> "LanguageModel":
        from learningorchestra_tpu.runtime import checkpoint as ckpt

        with open(os.path.join(path, "config.json")) as f:
            config = json.load(f)
        # .get-style filter: configs saved before a key existed fall
        # back to the constructor default (e.g. head_chunk)
        model = cls(**{k: config[k] for k in cls._CONFIG_KEYS
                       if k in config},
                    name=config["name"])
        model.optimizer_spec = config["optimizer_spec"]
        model.seed = config["seed"]
        model.history = config["history"]
        if config["built"]:
            sample = np.zeros((1, 8), np.int32)
            weights = os.path.join(path, "weights.msgpack")
            with obs_trace.span("paramInit"):
                # the tree's structure alone: every leaf is read from
                # the file, so nothing is initialised to be overwritten
                template = jax.eval_shape(
                    lambda: model.module.init(
                        jax.random.PRNGKey(model.seed),
                        jnp.asarray(sample), train=False))["params"]
            with obs_trace.span("weightsRead",
                                bytes=os.path.getsize(weights)):
                restored = ckpt.load_pytree(weights,
                                            {"params": dict(template)})
            model.params = restored["params"]
        return model
