"""Ring attention: sequence/context parallelism over the ``sp`` axis.

Long-context attention where the sequence is sharded across devices:
each device keeps its Q block resident and the K/V blocks rotate
around the ring (``ppermute`` over ICI neighbours) while an online-
softmax accumulator (running max + log-sum-exp) keeps the math exact —
the composition of blockwise softmax corrections equals full softmax.
Compute on each hop is a dense (seq_local × seq_local) attention block
that XLA maps onto the MXU, and the rotation overlaps with it in the
usual XLA async-collective schedule.

The reference has no attention at all (SURVEY §5 long-context row);
this module is one of the net-new first-class components. Used inside
``shard_map`` (see :func:`ring_attention_sharded` for the pjit-level
wrapper).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from learningorchestra_tpu.runtime import mesh as mesh_lib

NEG_INF = -1e30


def _block_attn(q, k, v, scale, mask):
    """One (q_block × kv_block) attention tile.

    q: (b, sq, h, d)  k/v: (b, sk, h, d)  mask: (sq, sk) or None.
    Returns (numerator (b, sq, h, d), row_max (b, sq, h),
    row_sumexp (b, sq, h)) of THIS tile only.
    """
    scores = jnp.einsum("bqhd,bkhd->bqhk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask[None, :, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)
    p = jnp.exp(scores - m[..., None])
    if mask is not None:
        # rows with no visible keys: exp(NEG_INF - NEG_INF) = 1 junk
        any_visible = jnp.any(mask, axis=-1)  # (sq,)
        p = jnp.where(any_visible[None, :, None, None], p, 0.0)
        m = jnp.where(any_visible[None, :, None], m, NEG_INF)
    num = jnp.einsum("bqhk,bkhd->bqhd", p,
                     v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return num, m, jnp.sum(p, axis=-1)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = mesh_lib.SP,
                   causal: bool = False,
                   scale: Optional[float] = None,
                   window: int = 0) -> jax.Array:
    """Exact attention over a sequence sharded on ``axis_name``.

    Call INSIDE ``shard_map``; q/k/v are the local sequence shards
    shaped (batch, seq_local, heads, head_dim). Returns the local
    output shard, same shape as ``q``, in ``q``'s dtype.
    """
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if window and not causal:
        raise ValueError("window requires causal=True")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32)

    q_pos = my_idx * sq + jnp.arange(sq)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, hop):
        o, m, l, k_blk, v_blk = carry
        # after `hop` rotations we hold the block that started on
        # device (my_idx - hop) mod n
        kv_idx = (my_idx - hop) % n

        def attend(o, m, l):
            mask = None
            if causal:
                k_pos = kv_idx * sk + jnp.arange(sk)
                mask = q_pos[:, None] >= k_pos[None, :]
                if window > 0:
                    mask = mask & (k_pos[None, :]
                                   > q_pos[:, None] - window)
            num, bm, bl = _block_attn(qf, k_blk.astype(jnp.float32),
                                      v_blk, scale, mask)
            new_m = jnp.maximum(m, bm)
            old_c = jnp.exp(m - new_m)
            blk_c = jnp.exp(bm - new_m)
            o = o * old_c[..., None] + num * blk_c[..., None]
            l = l * old_c + bl * blk_c
            return o, new_m, l

        if causal:
            # skip K/V blocks strictly in this shard's future (every
            # key position > every local query position): the block is
            # fully masked, so attending would compute then discard it.
            # Each device branches on its own index — halves total
            # causal FLOPs around the ring. A sliding window also
            # skips blocks wholly BELOW the band (too far in the
            # past), so only ~(W/sk + 1) hops attend at all.
            fully_masked = kv_idx * sk > my_idx * sq + sq - 1
            if window > 0:
                below = (kv_idx * sk + sk - 1
                         < my_idx * sq - window + 1)
                fully_masked = jnp.logical_or(fully_masked, below)
            o, m, l = lax.cond(fully_masked,
                               lambda o, m, l: (o, m, l), attend, o, m, l)
        else:
            o, m, l = attend(o, m, l)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (o, m, l, k_blk, v_blk), None

    # carries derived from qf so shard_map marks them device-varying
    # (plain zeros are "unvarying" and fail the scan vma check)
    o0 = qf * 0.0
    m0 = qf[..., 0] * 0.0 + NEG_INF
    l0 = qf[..., 0] * 0.0
    (o, _, l, _, _), _ = lax.scan(step, (o0, m0, l0, k, v),
                                  jnp.arange(n))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def ring_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         axis_name: str = mesh_lib.SP,
                         causal: bool = False,
                         scale: Optional[float] = None,
                         interpret: Optional[bool] = None,
                         window: int = 0) -> jax.Array:
    """Ring attention with the PALLAS FLASH KERNEL as the per-hop
    block (call inside ``shard_map``; same contract as
    :func:`ring_attention`).

    The dense ring materializes a (b, sq_local, h, sk_local) score
    tile per hop; here each hop is a fused flash call — intra-shard
    memory stays O(block), so local shards can themselves be long.
    Hop results merge EXACTLY via log-sum-exp weights (the kernel
    returns lse; its custom VJP carries the merge gradient through
    ``delta - dlse``). With equal shard sizes every causal hop is one
    of three static shapes: fully-past (unmasked flash), diagonal
    (aligned causal flash), or fully-future (skipped) — no
    offset-mask kernel variant is needed.
    """
    from learningorchestra_tpu.ops import attention as attn_ops

    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if sk != sq:
        raise ValueError("ring_flash_attention needs equal shards "
                         f"(sq={sq}, sk={sk})")
    if window and not causal:
        raise ValueError("window requires causal=True")
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    def flash_hop(k_blk, v_blk, hop_causal: bool, offset: int = 0,
                  win: int = 0):
        o, lse = attn_ops.flash_attention_with_lse(
            q, k_blk, v_blk, causal=hop_causal, scale=scale,
            interpret=interpret, window=win, kv_offset=offset)
        return o.astype(jnp.float32), lse

    def skip_hop(kb, vb):
        # lse = -inf: zero weight in the log-sum-exp merge
        return (jnp.zeros((b, sq, h, d), jnp.float32),
                jnp.full((b, sq, h), NEG_INF))

    def step(carry, hop):
        o_acc, lse_acc, k_blk, v_blk = carry
        kv_idx = (my_idx - hop) % n

        if causal and window > 0:
            # one branch per past-hop distance: the kernel applies the
            # exact banded mask at static offset -dist*sk, and hops
            # wholly below the band (dist*sk >= W + sq - 1) are
            # statically skipped — a W << total_seq ring attends only
            # ~(W/sk + 1) hops
            dist = my_idx - kv_idx
            case = jnp.where(dist >= 0, dist, n)
            branches = []
            for d_ in range(n):
                if d_ * sk >= window + sq - 1:
                    branches.append(skip_hop)
                else:
                    branches.append(functools.partial(
                        flash_hop, hop_causal=True, offset=-d_ * sk,
                        win=window))
            branches.append(skip_hop)  # future
            o_hop, lse_hop = lax.switch(case, branches, k_blk, v_blk)
        elif causal:
            # 0 = fully past (unmasked), 1 = diagonal (aligned
            # causal), 2 = fully future (skip — zero weight)
            case = jnp.where(kv_idx < my_idx, 0,
                             jnp.where(kv_idx == my_idx, 1, 2))
            o_hop, lse_hop = lax.switch(
                case,
                [lambda kb, vb: flash_hop(kb, vb, False),
                 lambda kb, vb: flash_hop(kb, vb, True),
                 skip_hop],
                k_blk, v_blk)
        else:
            o_hop, lse_hop = flash_hop(k_blk, v_blk, False)

        new_lse = jnp.logaddexp(lse_acc, lse_hop)
        w_acc = jnp.exp(lse_acc - new_lse)
        w_hop = jnp.exp(lse_hop - new_lse)
        o_acc = o_acc * w_acc[..., None] + o_hop * w_hop[..., None]
        k_blk = lax.ppermute(k_blk, axis_name, _ring_perm(n))
        v_blk = lax.ppermute(v_blk, axis_name, _ring_perm(n))
        return (o_acc, new_lse, k_blk, v_blk), None

    o0 = q.astype(jnp.float32) * 0.0
    lse0 = q[..., 0].astype(jnp.float32) * 0.0 + NEG_INF
    (o, _, _, _), _ = lax.scan(step, (o0, lse0, k, v), jnp.arange(n))
    return o.astype(q.dtype)


def _ring_perm(n) -> list:
    return [(i, (i + 1) % int(n)) for i in range(int(n))]


def ring_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                           mesh: Mesh, causal: bool = False,
                           scale: Optional[float] = None,
                           block_impl: str = "auto",
                           window: int = 0) -> jax.Array:
    """pjit-level entry: global (b, seq, h, d) arrays, sequence sharded
    over ``sp``, batch over the data axes.

    ``block_impl``: ``"dense"`` (XLA einsum tiles), ``"flash"``
    (Pallas kernel per hop), or ``"auto"`` (dense on the CPU backend
    only — interpret-mode pallas is for tests, not speed; every other
    backend compiles the kernel, and a compile failure propagates)."""
    if mesh_lib.SP not in mesh.axis_names:
        raise ValueError("mesh has no 'sp' axis")
    if block_impl == "auto":
        block_impl = "dense" if jax.default_backend() == "cpu" else "flash"
    data = mesh_lib.data_axes(mesh)
    spec = P(data if data else None, mesh_lib.SP, None, None)
    inner = (ring_flash_attention if block_impl == "flash"
             else ring_attention)
    # pallas_call emits ShapeDtypeStructs with no varying-mesh-axes
    # info, which the vma checker rejects (same as the tp flash path)
    extra = {"check_vma": False} if block_impl == "flash" else {}
    fn = mesh_lib.shard_map(
        functools.partial(inner, axis_name=mesh_lib.SP,
                          causal=causal, scale=scale, window=window),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, **extra)
    return fn(q, k, v)


def full_attention_reference(q, k, v, causal: bool = False,
                             scale: Optional[float] = None,
                             window: int = 0,
                             kv_valid=None) -> jax.Array:
    """Plain full-softmax attention (the oracle ring_attention must
    match; also the single-device fallback). ``window=W`` with
    ``causal`` restricts query p to keys in [p-W+1, p] (sliding
    window). ``kv_valid`` (bool, ``(b, sk)``) additionally masks
    per-batch-row key positions — padded prompt slots in batched
    prefill (left-pad generate, serving bucket prefill). NEG_INF
    scores underflow to exact zero under softmax, so a masked key
    never perturbs the unmasked rows' bits."""
    d = q.shape[-1]
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if window and not causal:
        raise ValueError("window requires causal=True")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bqhk", q.astype(jnp.float32),
                        k.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = scores.shape[1], scores.shape[3]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        if window > 0:
            mask = mask & (jnp.arange(sk)[None, :] >
                           jnp.arange(sq)[:, None] - window)
        scores = jnp.where(mask[None, :, None, :], scores, NEG_INF)
    if kv_valid is not None:
        scores = jnp.where(kv_valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bqhk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
