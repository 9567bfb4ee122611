"""Fused flash attention (Pallas TPU kernels, forward AND backward).

Forward: one ``pallas_call`` over a ``(batch*heads, q_blocks,
kv_blocks)`` grid — the Q tile stays resident in VMEM while K/V tiles
stream past it, an online-softmax accumulator (running max +
log-sum-exp) keeps the math exact, and scores never round-trip to HBM.
The MXU sees two matmuls per tile (``q·kᵀ`` and ``p·v``), both with
``preferred_element_type=float32``.

Backward: custom VJP with two hand-scheduled Pallas kernels using the
standard flash recurrence (score tiles recomputed from the saved
log-sum-exp; the (seq × seq) matrix is never materialised):

- dQ kernel — Q/dO tiles resident, K/V stream past; 3 MXU matmuls per
  tile (``q·kᵀ``, ``do·vᵀ``, ``ds·k``), dQ accumulates in VMEM.
- dK/dV kernel — K/V tiles resident, Q/dO stream past; 4 MXU matmuls
  per tile, dK/dV accumulate in VMEM.

``delta = Σ do·o`` is a cheap XLA fusion outside the kernels. Causal
runs skip fully-masked tiles in all three kernels (grid-level
``pl.when``), halving causal FLOPs.

Names on the device: the three kernels are ``flash_fwd``,
``flash_bwd_dq`` and ``flash_bwd_dkv`` (``name=``; the TPU compiler
takes it into the instruction name, ``%flash_fwd.1 = … custom-call``),
which is how a profiler trace and ``benchmark/layer_metrics`` tell them
apart. The decode paths below are plain XLA under the scopes
``decode_attn``, ``paged_decode_attn`` and
``quantized_paged_decode_attn`` (``op_name`` metadata).

The reference framework has no attention op at all (SURVEY §5
"long-context" row — sequence models run inside user TF code through
the generic executor, binary_execution.py:177-189); flash attention is
one of the net-new TPU-first components. On CPU (tests, the 8-virtual-
device mesh) the same kernels run in interpreter mode.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _auto_interpret() -> bool:
    # interpret ONLY on the CPU backend (tests, rehearsals); every
    # other backend compiles the kernel and a compile failure
    # propagates — nothing catches it and gives way to dot/dense
    return jax.default_backend() == "cpu"


# Default tile edge for block_q/block_k when the caller doesn't pick
# one. Measured on a real v5e chip (seq 4096, d 64, fwd+bwd): 128-wide
# tiles leave the kernel grid-overhead-bound at 65 ms vs the 36 ms XLA
# fused-dot oracle, while 512-wide tiles amortize the per-step
# bookkeeping and overtake it at 21 ms (1024: 18.6 ms, but coarse
# tiles blunt the causal block-skip and cost 4x the VMEM for ~12%
# more, so 512 is the cap; override per-call or via LO_FLASH_BLOCK).
def _auto_block(seq: int) -> int:
    raw = os.environ.get("LO_FLASH_BLOCK", "512")
    try:
        cap = int(raw)
    except ValueError:
        raise ValueError(f"LO_FLASH_BLOCK must be an integer, got {raw!r}")
    if cap < 8 or cap % 8:
        raise ValueError(
            f"LO_FLASH_BLOCK must be a multiple of 8 and >= 8 "
            f"(TPU sublane tiling), got {cap}")
    block = cap
    # shrink while the tile would pad the sequence by more than ~12%:
    # e.g. seq 640 under a 512 tile pads to 1024 (2.5x the MXU work
    # of the exact 128-tile grid); 128 tiles pad it not at all
    while block > 128 and _round_up(seq, block) > seq * 1.125:
        block //= 2
    return block


def _band_lo(i, block_q: int, block_k: int, window: int,
             offset: int = 0):
    """First in-band kv tile for q tile ``i`` (0 when unwindowed).
    ``offset`` is the static global-position shift of the k axis
    relative to q (cross-shard ring hops): col_global = c + offset."""
    if window <= 0:
        return 0
    return jnp.maximum(0, (i * block_q - window + 1 - offset)
                       // block_k)


def _band_width(nk: int, block_q: int, block_k: int,
                window: int) -> int:
    """Grid width (in kv tiles) that covers any q tile's band."""
    if window <= 0:
        return nk
    span = block_q + window - 1
    return min(nk, (span - 2) // block_k + 2)


def _kv_index_map(block_q: int, block_k: int, window: int,
                  causal: bool, nk: int, nq_head: int,
                  offset: int = 0):
    """BlockSpec index map for the streamed K/V tiles: maps grid step
    j to kv tile clip(lo+j, 0, hi). Out-of-band steps repeat the
    boundary tile index — Mosaic's pipeline only issues a copy when
    the block index CHANGES between steps, so the clamp turns the
    causal upper triangle (and both sides of a sliding-window band)
    into zero-copy revisits instead of dead DMA. Under grouped-query
    folding the q-tile position within its head is i % nq_head."""

    def index(b, i, j):
        ih = i % nq_head
        j_eff = _band_lo(ih, block_q, block_k, window, offset) + j
        hi = nk - 1
        if causal:
            # floored: a positive offset (future-shifted keys) could
            # push the causal bound below 0 — the DMA index must stay
            # in bounds even for tiles the run predicate discards
            hi = jnp.maximum(
                jnp.minimum(
                    hi,
                    (ih * block_q + block_q - 1 - offset) // block_k),
                0)
        return (b, jnp.clip(j_eff, 0, hi), 0)

    return index


def _qband_lo(j, block_q: int, block_k: int, causal: bool,
              offset: int = 0):
    """First q tile whose rows can see kv tile ``j`` (causal)."""
    if not causal:
        return 0
    return jnp.maximum(0, (j * block_k + offset) // block_q)


def _qband_width(nq: int, block_q: int, block_k: int,
                 window: int) -> int:
    """Grid width (in q tiles) covering any kv tile's visible rows
    when windowed (causal-only bands run to the end, width nq)."""
    if window <= 0:
        return nq
    span = block_k + window - 1
    return min(nq, (span - 2) // block_q + 2)


def _q_index_map(block_q: int, block_k: int, window: int,
                 causal: bool, nq: int, band_ni: int,
                 offset: int = 0):
    """Streamed-Q BlockSpec index map for the dK/dV kernel: grid step
    i = (head, within-band) -> folded q tile
    head·nq + clip(lo+within, 0, hi); out-of-band steps revisit."""

    def index(b, j, i):
        head = i // band_ni
        within = i % band_ni
        i_eff = _qband_lo(j, block_q, block_k, causal, offset) + within
        hi = nq - 1
        if window > 0:
            # a negative ring offset can push the whole band before
            # row 0 (hi < 0) — floor it so the clip never emits a
            # negative block index (the run predicate discards the
            # tile's data, but the DMA itself must stay in bounds)
            hi = jnp.maximum(
                jnp.minimum(
                    hi,
                    (j * block_k + block_k - 1 + offset + window - 1)
                    // block_q),
                0)
        return (b, head * nq + jnp.clip(i_eff, 0, hi), 0)

    return index


def _bd_visible(row, col, strict, block: int):
    """Block-diffusion visibility of CLEAN key ``col`` to query ``row``
    (both positions within the row of tokens): the causal diagonal
    rounded up to blocks of ``block``, ``blk(col) <= blk(row)``, and
    for a noisy query (``strict``, a scalar of the tile) strictly
    below it, ``blk(col) < blk(row)``. The tile-level skip stays the
    causal one: with tile edges that are multiples of ``block`` no
    tile above the diagonal holds a visible key."""
    row_start = row - lax.rem(row, block)
    return col < row_start + jnp.where(strict, 0, block)


def _resolve_blocks(block_q: Optional[int], block_k: Optional[int],
                    sq: int, sk: int) -> Tuple[int, int]:
    return (int(block_q) if block_q else _auto_block(sq),
            int(block_k) if block_k else _auto_block(sk))


# ----------------------------------------------------------------------
# forward kernel
# ----------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref,
                *, scale: float, causal: bool, kv_len: int,
                block_q: int, block_k: int, window: int = 0,
                nk_total: int = 0, nq_head: int = 0,
                offset: int = 0, bd_block: int = 0, bd_group: int = 0):
    # grouped-query folding: the q-row axis stacks `group` query heads
    # per kv head, so the tile's POSITION within its head is
    # i % nq_head (== i when ungrouped) — all causal/window math uses
    # that, while the storage index stays i. `offset` statically
    # shifts k positions (cross-shard ring hops): col = c + offset.
    i = pl.program_id(1)
    ih = i % nq_head
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # banded iteration: grid step j covers the kv tile lo+j, where lo
    # is the first in-band tile for this q tile (window) — the kv
    # BlockSpec index map clamps with the same formula, so
    # out-of-band steps revisit a fetched block (no DMA) and are
    # predicated off here
    j_eff = _band_lo(ih, block_q, block_k, window, offset) + j
    run = True
    if causal:
        run = (j_eff * block_k + offset
               <= ih * block_q + block_q - 1)
    if window > 0:
        run = jnp.logical_and(run, j_eff <= nk_total - 1)

    @pl.when(run)
    def _tile():
        q = q_ref[0]                       # (block_q, d)
        k = k_ref[0]                       # (block_k, d)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)

        col = j_eff * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = col < kv_len
        if causal or window > 0:
            row = ih * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
        if bd_block:
            valid = jnp.logical_and(
                valid, _bd_visible(row, col, i // nq_head < bd_group,
                                   bd_block))
        elif causal:
            valid = jnp.logical_and(valid, row >= col + offset)
        if window > 0:
            valid = jnp.logical_and(valid,
                                    col + offset > row - window)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[:, :1]                              # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # guard: a fully-masked row has s = m_new = NEG_INF and
        # exp(0) = 1 junk — zero it explicitly
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)                    # (bq, 1)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new * jnp.ones_like(m_ref)

    @pl.when(j == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        m = m_ref[:, :1]
        # a row with NO visible keys (possible on windowed/offset
        # hops) must carry lse = -inf so ring log-sum-exp merges give
        # it ZERO weight — 0.0 would weigh it exp(0) = 1
        lse = jnp.where(l > 0, m + jnp.log(safe_l), NEG_INF)  # (bq, 1)
        # lse output carries a 128-lane trailing dim (Mosaic requires
        # the last two block dims tile to (8, 128)); value broadcast
        # across lanes, wrapper reads lane 0
        lse_ref[0] = lse * jnp.ones_like(lse_ref[0])


def _fwd_pallas(q, k, v, *, scale: float, causal: bool,
                block_q: int, block_k: int, interpret: bool,
                window: int = 0, group: int = 1, seq_q: int = 0,
                offset: int = 0, bd_block: int = 0, bd_group: int = 0
                ) -> Tuple[jax.Array, jax.Array]:
    """q: (b·kv, group·sq_p, d) pre-padded/folded (``_fold_q``);
    k/v: (b·kv, sk, d). Returns (o, lse) in the folded layout.
    ``seq_q`` is the per-head padded q length (sq_p)."""
    bh, sq_fold, d = q.shape
    sq_p = seq_q or sq_fold
    sk = k.shape[1]
    block_k = min(block_k, _round_up(sk, 8))
    sk_p = _round_up(sk, block_k)
    d_p = _round_up(d, 128)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, d_p - d)))
    k = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, d_p - d)))
    v = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, d_p - d)))

    nk = sk_p // block_k
    nj = _band_width(nk, block_q, block_k, window)
    nq_head = sq_p // block_q
    grid = (bh, group * nq_head, nj)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, kv_len=sk,
        block_q=block_q, block_k=block_k, window=window, nk_total=nk,
        nq_head=nq_head, offset=offset, bd_block=bd_block,
        bd_group=bd_group)
    kv_map = _kv_index_map(block_q, block_k, window, causal, nk,
                           nq_head, offset)
    lanes = 128
    scratch = [
        pltpu.VMEM((block_q, d_p), jnp.float32),
        pltpu.VMEM((block_q, lanes), jnp.float32),
        pltpu.VMEM((block_q, lanes), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d_p), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d_p), kv_map),
            pl.BlockSpec((1, block_k, d_p), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d_p), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, lanes), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, group * sq_p, d_p), q.dtype),
            jax.ShapeDtypeStruct((bh, group * sq_p, lanes),
                                 jnp.float32),
        ],
        scratch_shapes=scratch,
        # bh and the Q-tile axis own disjoint outputs/accumulator
        # streaks -> Mosaic may split them across megacore; the KV
        # stream axis accumulates and must stay sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bd_fwd" if bd_block else "flash_fwd",
    )(q, k, v)
    return o[..., :d], lse[..., 0]


# ----------------------------------------------------------------------
# backward kernels (flash recurrence, hand-scheduled)
# ----------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc_ref,
                   *, scale: float, causal: bool, kv_len: int,
                   block_q: int, block_k: int, window: int = 0,
                   nk_total: int = 0, nq_head: int = 0,
                   offset: int = 0, bd_block: int = 0, bd_group: int = 0):
    """Grid (bh, q_blocks, kv_band): Q/dO resident, K/V stream the
    band (same clamped-index revisit scheme as the forward; grouped
    folding puts `group` query heads on the q axis — see
    _fwd_kernel)."""
    i = pl.program_id(1)
    ih = i % nq_head
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    j_eff = _band_lo(ih, block_q, block_k, window, offset) + j
    run = True
    if causal:
        run = (j_eff * block_k + offset
               <= ih * block_q + block_q - 1)
    if window > 0:
        run = jnp.logical_and(run, j_eff <= nk_total - 1)

    @pl.when(run)
    def _tile():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]                            # (bq, 1)
        delta = delta_ref[0][:, :1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (bq, bk)
        col = j_eff * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = col < kv_len
        if causal or window > 0:
            row = ih * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
        if bd_block:
            valid = jnp.logical_and(
                valid, _bd_visible(row, col, i // nq_head < bd_group,
                                   bd_block))
        elif causal:
            valid = jnp.logical_and(valid, row >= col + offset)
        if window > 0:
            valid = jnp.logical_and(valid,
                                    col + offset > row - window)
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, bk)
        ds = p * (dp - delta) * scale
        dq_acc_ref[...] += jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                    *, scale: float, causal: bool, kv_len: int,
                    block_q: int, block_k: int, window: int = 0,
                    nq_total: int = 0, band_ni: int = 0,
                    offset: int = 0, bd_block: int = 0, bd_group: int = 0):
    """Grid (bh·kv, kv_blocks, group·q_band): K/V resident, Q/dO
    stream the band of q tiles whose rows can see this kv tile
    (causal: from the diagonal down; window: at most W-1 rows past
    it), once per grouped query head — dK/dV accumulate over the
    whole group. Same clamped-index revisit scheme as the forward."""
    j = pl.program_id(1)
    i = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    within = i % band_ni
    i_eff = _qband_lo(j, block_q, block_k, causal, offset) + within
    run = i_eff <= nq_total - 1
    if causal:
        run = jnp.logical_and(
            run,
            j * block_k + offset <= i_eff * block_q + block_q - 1)
    if window > 0:
        run = jnp.logical_and(
            run,
            i_eff * block_q
            <= j * block_k + block_k - 1 + offset + window - 1)

    @pl.when(run)
    def _tile():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (bq, bk)
        col = j * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = col < kv_len
        if causal or window > 0:
            row = i_eff * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
        if bd_block:
            valid = jnp.logical_and(
                valid, _bd_visible(row, col, i // band_ni < bd_group,
                                   bd_block))
        elif causal:
            valid = jnp.logical_and(valid, row >= col + offset)
        if window > 0:
            valid = jnp.logical_and(valid,
                                    col + offset > row - window)
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)         # (bq, bk)
        dv_acc_ref[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bk, d)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                       # (bq, bk)
        dk_acc_ref[...] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bk, d)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, o, lse, do, *, scale: float, causal: bool,
                block_q: int, block_k: int, interpret: bool,
                dlse=None, window: int = 0, group: int = 1,
                seq_q: int = 0, offset: int = 0, bd_block: int = 0,
                bd_group: int = 0):
    """Folded layout (see ``_fwd_pallas``): q/o/do (b·kv, g·sq_p, d),
    lse (b·kv, g·sq_p), k/v (b·kv, sk, d). Returns (dq, dk, dv) in
    the same folded layout. ``seq_q`` is the per-head padded q length.

    ``dlse``, when given, is the upstream gradient on the
    log-sum-exp output (ring-flash merges consume lse, so it carries
    real gradient there). Math: dL/ds_ij gains the term
    ``dlse_i · ∂lse_i/∂s_ij = dlse_i · p_ij``, so
    ``ds = p·(dp - delta + dlse)`` — exactly the existing kernels with
    ``delta - dlse`` fed in place of ``delta``. No kernel change.
    """
    bh, sq_fold, d = q.shape
    sq_p = seq_q or sq_fold
    sk = k.shape[1]
    block_k = min(block_k, _round_up(sk, 8))
    sk_p = _round_up(sk, block_k)
    d_p = _round_up(d, 128)
    lanes = 128
    nq_head = sq_p // block_q

    # delta = rowsum(do * o): one XLA fusion, no kernel needed. Padded
    # rows carry q = do = 0, so their p·(dp - delta) contributions to
    # dk/dv vanish without an explicit row mask.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                             # (bh, g·sq_p)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    q = jnp.pad(q, ((0, 0), (0, 0), (0, d_p - d)))
    k = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, d_p - d)))
    v = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, d_p - d)))
    do = jnp.pad(do, ((0, 0), (0, 0), (0, d_p - d)))
    lse_l = lse[..., None] * jnp.ones((1, 1, lanes), jnp.float32)
    delta_l = delta[..., None] * jnp.ones((1, 1, lanes), jnp.float32)

    nk = sk_p // block_k
    nj = _band_width(nk, block_q, block_k, window)
    q_spec_i = pl.BlockSpec((1, block_q, d_p), lambda b, i, j: (b, i, 0))
    kv_spec_j = pl.BlockSpec((1, block_k, d_p),
                             _kv_index_map(block_q, block_k, window,
                                           causal, nk, nq_head,
                                           offset))
    row_spec_i = pl.BlockSpec((1, block_q, lanes),
                              lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          kv_len=sk, block_q=block_q, block_k=block_k,
                          window=window, nk_total=nk, nq_head=nq_head,
                          offset=offset, bd_block=bd_block,
                          bd_group=bd_group),
        grid=(bh, group * nq_head, nj),
        in_specs=[q_spec_i, kv_spec_j, kv_spec_j, q_spec_i, row_spec_i,
                  row_spec_i],
        out_specs=q_spec_i,
        out_shape=jax.ShapeDtypeStruct((bh, group * sq_p, d_p),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, d_p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bd_bwd_dq" if bd_block else "flash_bwd_dq",
    )(q, k, v, do, lse_l, delta_l)

    # second kernel: K/V resident, Q streams — grid dims (b, j, i)
    band_ni = _qband_width(nq_head, block_q, block_k, window)
    q_map = _q_index_map(block_q, block_k, window, causal, nq_head,
                         band_ni, offset)
    q_spec_g2 = pl.BlockSpec((1, block_q, d_p), q_map)
    kv_spec_g2 = pl.BlockSpec((1, block_k, d_p), lambda b, j, i: (b, j, 0))
    row_spec_g2 = pl.BlockSpec((1, block_q, lanes), q_map)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          kv_len=sk, block_q=block_q, block_k=block_k,
                          window=window, nq_total=nq_head,
                          band_ni=band_ni, offset=offset,
                          bd_block=bd_block, bd_group=bd_group),
        grid=(bh, sk_p // block_k, group * band_ni),
        in_specs=[q_spec_g2, kv_spec_g2, kv_spec_g2, q_spec_g2,
                  row_spec_g2, row_spec_g2],
        out_specs=[kv_spec_g2, kv_spec_g2],
        out_shape=[jax.ShapeDtypeStruct((bh, sk_p, d_p), jnp.float32),
                   jax.ShapeDtypeStruct((bh, sk_p, d_p), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_k, d_p), jnp.float32),
                        pltpu.VMEM((block_k, d_p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bd_bwd_dkv" if bd_block else "flash_bwd_dkv",
    )(q, k, v, do, lse_l, delta_l)
    return (dq[..., :d], dk[:, :sk, :d], dv[:, :sk, :d])


# ----------------------------------------------------------------------
# grouped fold helpers + custom-vjp wrapper
# ----------------------------------------------------------------------
def _fold_q(x, kvh: int, group: int, sq_p: int):
    """(b, sq, h, d) -> (b*kv, group*sq_p, d): head-major fold with
    per-head row padding, so each query head's rows are a contiguous
    run of whole q tiles and K/V stream ONCE per kv head."""
    b, sq, h, d = x.shape
    x = jnp.pad(x, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    x = x.transpose(0, 2, 1, 3).reshape(b, kvh, group, sq_p, d)
    return x.reshape(b * kvh, group * sq_p, d)


def _unfold_q(x, b: int, kvh: int, group: int, sq_p: int, sq: int):
    d = x.shape[-1]
    x = x.reshape(b, kvh * group, sq_p, d)
    return x.transpose(0, 2, 1, 3)[:, :sq]


def _merge_kv(x):
    """(b, sk, kv, d) -> (b*kv, sk, d)."""
    b, sk, kvh, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * kvh, sk, d)


def _split_kv(x, b: int, kvh: int):
    bkv, sk, d = x.shape
    return x.reshape(b, kvh, sk, d).transpose(0, 2, 1, 3)


def _flash_plan(q, k, block_q: int):
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    group = h // kvh
    bq = min(block_q, _round_up(sq, 8))
    sq_p = _round_up(sq, bq)
    return b, sq, h, d, kvh, group, bq, sq_p


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret,
           window=0):
    """q: (b, sq, h, d); k/v: (b, sk, kv, d) with kv | h. Grouped
    query heads fold into the q-row axis, so K/V never materialize at
    h heads (the GQA point: HBM traffic scales with kv, not h)."""
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                        interpret, window)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               window=0):
    b, sq, h, d, kvh, group, bq, sq_p = _flash_plan(q, k, block_q)
    qf = _fold_q(q, kvh, group, sq_p)
    kf, vf = _merge_kv(k), _merge_kv(v)
    o, lse = _fwd_pallas(qf, kf, vf, scale=scale, causal=causal,
                         block_q=bq, block_k=block_k,
                         interpret=interpret, window=window,
                         group=group, seq_q=sq_p)
    out = _unfold_q(o, b, kvh, group, sq_p, sq)
    return out, (qf, kf, vf, o, lse, (b, sq, kvh, group, bq, sq_p))


def _flash_bwd(causal, scale, block_q, block_k, interpret, window,
               res, g):
    qf, kf, vf, o, lse, meta = res
    b, sq, kvh, group, bq, sq_p = meta
    gf = _fold_q(g, kvh, group, sq_p)
    dq, dk, dv = _bwd_pallas(qf, kf, vf, o, lse, gf, scale=scale,
                             causal=causal, block_q=bq,
                             block_k=block_k, interpret=interpret,
                             window=window, group=group, seq_q=sq_p)
    dq4 = _unfold_q(dq, b, kvh, group, sq_p, sq).astype(qf.dtype)
    dk4 = _split_kv(dk, b, kvh).astype(kf.dtype)
    dv4 = _split_kv(dv, b, kvh).astype(vf.dtype)
    return dq4, dk4, dv4


_flash.defvjp(_flash_fwd, _flash_bwd)


def _pad_rows(x, sq_p: int):
    return jnp.pad(x, ((0, 0), (0, sq_p - x.shape[1])) +
                   ((0, 0),) * (x.ndim - 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8,
                                                    9))
def _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret,
               window=0, offset=0):
    """Like ``_flash`` but merged-head 3D (bh, s, d) and also returns
    the log-sum-exp rows — the merge quantity sequence-parallel (ring)
    composition needs. lse carries real gradient through the merge
    weights, handled in the vjp via the ``delta - dlse`` identity
    (see _bwd_pallas). Ungrouped (ring repeats KV to full heads
    before sharding)."""
    out, _ = _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k,
                            interpret, window, offset)
    return out


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                   window=0, offset=0):
    bh, sq, d = q.shape
    bq = min(block_q, _round_up(sq, 8))
    sq_p = _round_up(sq, bq)
    qp = _pad_rows(q, sq_p)
    o, lse = _fwd_pallas(qp, k, v, scale=scale, causal=causal,
                         block_q=bq, block_k=block_k,
                         interpret=interpret, seq_q=sq_p,
                         window=window, offset=offset)
    return (o[:, :sq], lse[:, :sq]), (qp, k, v, o, lse, sq, sq_p, bq)


def _flash_lse_bwd(causal, scale, block_q, block_k, interpret, window,
                   offset, res, g):
    qp, k, v, o, lse, sq, sq_p, bq = res
    do, dlse = g
    dq, dk, dv = _bwd_pallas(qp, k, v, o, lse, _pad_rows(do, sq_p),
                             scale=scale, causal=causal, block_q=bq,
                             block_k=block_k, interpret=interpret,
                             dlse=_pad_rows(dlse, sq_p), seq_q=sq_p,
                             window=window, offset=offset)
    return (dq[:, :sq].astype(qp.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# ----------------------------------------------------------------------
# block diffusion: [noisy ; clean] queries over the clean keys
# ----------------------------------------------------------------------
def _fold_q_bd(x, kvh: int, group: int, sq_p: int):
    """(b, 2L, h, d) -> (b*kv, 2*group*sq_p, d): as ``_fold_q`` with
    the two halves of the doubled row as a further, outer part of the
    group, the noisy half's ``group`` heads first. The kernels tell a
    tile's half by ``i // nq_head < group``."""
    b, s2, h, d = x.shape
    half = s2 // 2
    x = x.reshape(b, 2, half, kvh, group, d)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, sq_p - half)) + ((0, 0),) * 3)
    x = x.transpose(0, 3, 1, 4, 2, 5)
    return x.reshape(b * kvh, 2 * group * sq_p, d)


def _unfold_q_bd(x, b: int, kvh: int, group: int, sq_p: int, half: int):
    """Inverse of ``_fold_q_bd`` for (.., d) outputs and (..,) rows."""
    tail = x.shape[2:]
    x = x.reshape((b, kvh, 2, group, sq_p) + tail)
    x = jnp.moveaxis(x, (2, 4, 1, 3), (1, 2, 3, 4))[:, :, :half]
    return x.reshape((b, 2 * half, kvh * group) + tail)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bd(q, k, v, scale, block_q, block_k, interpret, bd_block):
    """q: (b, 2L, h, d), the noisy half first; k, v: (b, L, kv, d),
    the CLEAN keys. Returns (out (b, 2L, h, d), lse (b, 2L, h)): lse
    carries gradient (the noisy half's merge with its own block)."""
    out, _ = _flash_bd_fwd(q, k, v, scale, block_q, block_k, interpret,
                           bd_block)
    return out


def _flash_bd_fwd(q, k, v, scale, block_q, block_k, interpret, bd_block):
    b, s2, h, d = q.shape
    half = s2 // 2
    kvh = k.shape[2]
    group = h // kvh
    bq = min(block_q, _round_up(half, 8))
    sq_p = _round_up(half, bq)
    qf = _fold_q_bd(q, kvh, group, sq_p)
    kf, vf = _merge_kv(k), _merge_kv(v)
    o, lse = _fwd_pallas(qf, kf, vf, scale=scale, causal=True,
                         block_q=bq, block_k=block_k,
                         interpret=interpret, group=2 * group,
                         seq_q=sq_p, bd_block=bd_block, bd_group=group)
    meta = (b, half, kvh, group, bq, sq_p)
    return ((_unfold_q_bd(o, b, kvh, group, sq_p, half),
             _unfold_q_bd(lse, b, kvh, group, sq_p, half)),
            (qf, kf, vf, o, lse, meta))


def _flash_bd_bwd(scale, block_q, block_k, interpret, bd_block, res, g):
    qf, kf, vf, o, lse, meta = res
    b, half, kvh, group, bq, sq_p = meta
    do, dlse = g
    dq, dk, dv = _bwd_pallas(
        qf, kf, vf, o, lse, _fold_q_bd(do, kvh, group, sq_p),
        scale=scale, causal=True, block_q=bq, block_k=block_k,
        interpret=interpret,
        dlse=_fold_q_bd(dlse[..., None], kvh, group, sq_p)[..., 0],
        group=2 * group, seq_q=sq_p, bd_block=bd_block, bd_group=group)
    return (_unfold_q_bd(dq, b, kvh, group, sq_p, half).astype(qf.dtype),
            _split_kv(dk, b, kvh).astype(kf.dtype),
            _split_kv(dv, b, kvh).astype(vf.dtype))


_flash_bd.defvjp(_flash_bd_fwd, _flash_bd_bwd)


def _bd_block_diagonal(q, k, v, block: int, scale: float):
    """The noisy half's attention to its OWN block of noisy keys:
    q (b, L, h, d), k/v (b, L, kv, d) -> (out float32 (b, L, h, d),
    lse (b, L, h)). ``block`` keys a query: plain XLA."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    nb = sq // block
    qb = q.reshape(b, nb, block, kvh, h // kvh, d)
    kb = k.reshape(b, nb, block, kvh, d)
    vb = v.reshape(b, nb, block, kvh, d)
    s = jnp.einsum("bnqcgd,bnkcd->bncgqk", qb, kb,
                   preferred_element_type=jnp.float32) * scale
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bncgqk,bnkcd->bnqcgd", p, vb.astype(jnp.float32))
    return (o.reshape(b, sq, h, d),
            jnp.moveaxis(lse, 4, 2).reshape(b, sq, h))


def _check_bd(q, k, block_length: int):
    s2, h, kvh = q.shape[1], q.shape[2], k.shape[2]
    if h % kvh:
        raise ValueError(f"q has {h} heads but k/v have {kvh}")
    if block_length < 1 or s2 % 2 or (s2 // 2) % block_length:
        raise ValueError(
            f"block diffusion wants [noisy ; clean] rows of 2L positions "
            f"with block_length | L; got {s2} positions, block_length "
            f"{block_length}")
    return s2 // 2


def flash_bd_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       block_length: int,
                       block_q: Optional[int] = None,
                       block_k: Optional[int] = None,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Attention of a block-diffusion training row (docs/DIFFUSION.md).

    ``q`` (b, 2L, h, d) and ``k``/``v`` (b, 2L, kv, d) hold the noisy
    copy ``xt`` of a row in positions 0..L-1 and the clean row ``x0``
    in L..2L-1. With ``blk(p) = p // block_length`` of the position
    within the row: a noisy query sees the noisy keys of its own block
    and the clean keys of the blocks before it; a clean query sees the
    clean keys of its own block and of those before; no query of the
    clean half sees a noisy key.

    One kernel call takes both halves' queries over the clean keys (the
    kernels ``flash_bd_fwd``, ``flash_bd_bwd_dq``, ``flash_bd_bwd_dkv``:
    the flash kernels under this mask, tiles above the diagonal skipped
    as under the causal one); the noisy half's own block, ``block_length``
    keys a query, is plain XLA, and the two parts merge by their
    log-sum-exps. GQA-native like :func:`flash_attention`."""
    half = _check_bd(q, k, block_length)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = _auto_interpret()
    if block_q is None and block_k is None and half >= 4096 \
            and _auto_block(half) == 512:
        # rows of 4096 and more, 8 query heads folded on a KV head:
        # tiles of 1024 take 31.5 ms forward and backward where 512
        # take 35.3 (one layer of the sdar cell, PERF.md PR 26); the
        # diagonal's blunter skip costs less than the grid's steps
        block_q = block_k = 1024
    block_q, block_k = _resolve_blocks(block_q, block_k, half, half)
    if block_q % block_length or block_k % block_length:
        raise ValueError(
            f"tile edges ({block_q}, {block_k}) must be multiples of "
            f"block_length {block_length}")
    o, lse = _flash_bd(q, k[:, half:], v[:, half:], float(scale), block_q,
                       block_k, bool(interpret), int(block_length))
    with jax.named_scope("bd_diag"):
        o_d, lse_d = _bd_block_diagonal(q[:, :half], k[:, :half],
                                        v[:, :half], int(block_length),
                                        float(scale))
        # a query of block 0 sees no clean key: lse = NEG_INF there,
        # and its own block is the whole of its output
        lse_c = lse[:, :half]
        top = jnp.maximum(lse_c, lse_d)
        w_c = jnp.exp(lse_c - top)[..., None]
        w_d = jnp.exp(lse_d - top)[..., None]
        noisy = (w_c * o[:, :half].astype(jnp.float32) + w_d * o_d) \
            / (w_c + w_d)
    return jnp.concatenate([noisy.astype(o.dtype), o[:, half:]], axis=1)


def bd_visible_mask(half: int, block_length: int) -> jax.Array:
    """(2L, 2L) bool: which key each query of a [noisy ; clean] row
    sees (the rule in :func:`flash_bd_attention`)."""
    pos = jnp.arange(2 * half)
    noisy = pos < half
    blk = (pos % half) // block_length
    qn, kn = noisy[:, None], noisy[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return jnp.where(qn, jnp.where(kn, qb == kb, kb < qb),
                     jnp.logical_and(~kn, kb <= qb))


def bd_attention_reference(q, k, v, *, block_length: int) -> jax.Array:
    """Dense masked softmax of the same rule: the ``dot`` path (CPU,
    short rows) and the kernels' oracle. Holds (2L, 2L) scores."""
    half = _check_bd(q, k, block_length)
    group = q.shape[2] // k.shape[2]
    scale = 1.0 / (q.shape[-1] ** 0.5)
    kr = jnp.repeat(k, group, axis=2) if group > 1 else k
    vr = jnp.repeat(v, group, axis=2) if group > 1 else v
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(bd_visible_mask(half, block_length)[None, None], s,
                  NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32))
    return o.astype(q.dtype)


# ----------------------------------------------------------------------
# public API
# ----------------------------------------------------------------------
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: int = 0) -> jax.Array:
    """Fused attention over (batch, seq, heads, head_dim) arrays.

    Layout matches :mod:`learningorchestra_tpu.parallel.ring` so the
    transformer can swap between single-chip flash and ring/Ulysses SP
    without reshuffling. Differentiable (custom VJP).

    GQA-native: ``k``/``v`` may carry FEWER heads than ``q``
    (``kv | h``) — the query-head group folds into the kernel's q-row
    axis, so K/V stream once per KV head and never materialize at
    ``h`` heads in HBM (the grouped-attention memory win survives the
    kernel boundary).

    ``window=W`` (requires ``causal=True``) is sliding-window
    attention: query p attends keys in ``[p-W+1, p]``. The kv grid
    axis is BANDED: it spans only ~(block+W)/block tiles per q tile,
    with clamped index maps so boundary revisits issue no DMA — both
    compute AND copy traffic scale ~O(s·W) instead of O(s²)
    (Mistral-style SWA). Plain causal runs get the same clamp on the
    upper triangle, halving their K/V copy traffic.
    """
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    if h % kvh:
        raise ValueError(
            f"q has {h} heads but k/v have {kvh} — kv heads must "
            f"divide query heads (GQA)")
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if window and not causal:
        raise ValueError("window requires causal=True (banded causal "
                         "attention)")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = _auto_interpret()
    block_q, block_k = _resolve_blocks(block_q, block_k,
                                       sq, k.shape[1])
    return _flash(q, k, v, causal, float(scale), block_q, block_k,
                  bool(interpret), int(window))


def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                             *, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: Optional[bool] = None,
                             window: int = 0, kv_offset: int = 0,
                             ) -> Tuple[jax.Array, jax.Array]:
    """(out (b, sq, h, d), lse (b, sq, h)) — the blockwise form ring
    attention composes across devices (parallel/ring.py): hop outputs
    merge exactly via log-sum-exp weights. Differentiable in both
    outputs (lse gradient flows through the merge)."""
    b, sq, h, d = q.shape
    if k.shape[2] != h:
        raise ValueError(
            f"flash_attention_with_lse needs equal head counts "
            f"(q has {h}, k/v have {k.shape[2]}) — repeat K/V to "
            f"full heads first; grouped GQA is flash_attention only")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = _auto_interpret()
    block_q, block_k = _resolve_blocks(block_q, block_k,
                                       sq, k.shape[1])

    def merge_heads(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    o, lse = _flash_lse(merge_heads(q), merge_heads(k), merge_heads(v),
                        causal, float(scale), block_q,
                        block_k, bool(interpret), int(window),
                        int(kv_offset))
    o = o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    lse = lse.reshape(b, h, sq).transpose(0, 2, 1)
    return o, lse


def reference_attention(q, k, v, causal: bool = False,
                        scale: Optional[float] = None) -> jax.Array:
    """Unfused full-softmax oracle (same layout/contract)."""
    from learningorchestra_tpu.parallel.ring import full_attention_reference

    return full_attention_reference(q, k, v, causal=causal, scale=scale)


# ---------------------------------------------------------------------------
# Single-token decode attention (the serving plane's hot op).
#
# These are deliberately NOT pallas kernels: the serving bit-identity
# contract (docs/SERVING.md) requires the continuous batcher to
# reproduce the solo decode loop's float32 reduction order exactly,
# so the math below mirrors models/transformer.py's decode branch
# einsum-for-einsum. A fused single-token kernel saves little anyway —
# q is one row, the op is bandwidth-bound on the KV cache read.


@jax.named_scope("decode_attn")
def decode_attention(q: jax.Array, k_cache: jax.Array,
                     v_cache: jax.Array, col: jax.Array, *,
                     pad_offset: Optional[jax.Array] = None,
                     window: int = 0,
                     scale: Optional[float] = None) -> jax.Array:
    """One-token GQA attention against a per-row cache position.

    ``q`` is ``(b, 1, n_heads, d)``, ``k_cache``/``v_cache`` are
    ``(b, L, kv_heads, d)``, ``col`` is ``(b,)`` — each batch row
    attends its own prefix ``[pad_offset[i], col[i]]`` of the cache
    (continuous batching: rows sit at different decode positions).
    ``pad_offset`` (``(b,)``, optional) hides left-pad rows;
    ``window > 0`` restricts to the last ``window`` positions. Masked
    scores take ``NEG_INF`` whose softmax term underflows to exact
    zero, so a row's output bits match a solo batch-1 decode."""
    b, s, h, d = q.shape
    kv = k_cache.shape[2]
    group = h // kv
    qg = q.astype(jnp.float32).reshape(b, s, kv, group, d)
    scores = jnp.einsum(
        "bqhgd,bkhd->bqhgk", qg, k_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    # DIVIDE by sqrt(d) (not multiply by the reciprocal): x/s and
    # x*(1/s) round differently, and the solo decode branch in
    # models/transformer.py divides — the bit-identity contract hangs
    # on matching it exactly
    scores = scores * scale if scale is not None \
        else scores / (d ** 0.5)
    length = k_cache.shape[1]
    positions = jnp.arange(length)
    visible = positions[None, :] <= col[:, None]
    if pad_offset is not None:
        visible = visible & (positions[None, :] >= pad_offset[:, None])
    if window > 0:
        visible = visible & (positions[None, :] >
                             (col - window)[:, None])
    scores = jnp.where(visible[:, None, None, None, :], scores,
                       NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", p,
                   v_cache.astype(jnp.float32))
    return o.reshape(b, s, h, d).astype(q.dtype)


@jax.named_scope("paged_decode_attn")
def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array,
                           block_tables: jax.Array,
                           col: jax.Array, *,
                           pad_offset: Optional[jax.Array] = None,
                           window: int = 0,
                           scale: Optional[float] = None,
                           max_pages: int = 0) -> jax.Array:
    """:func:`decode_attention` over a paged KV pool (vLLM layout).

    ``k_pool``/``v_pool`` are ``(pages, page_len, kv_heads, d)``;
    ``block_tables`` (``(b, n_pages)`` int) maps each request's
    logical cache to physical pages, so a request joining a serving
    slot reuses whatever pages are free — no recompile, no copy of
    other requests' state. Pages are gathered into the contiguous
    ``(b, n_pages * page_len, kv, d)`` layout and fed through the
    SAME reduction as :func:`decode_attention`: masked positions
    contribute exact zeros, so padding the key axis with garbage
    pages never changes the live positions' float sums and the
    gathered path stays bit-identical to the contiguous one
    (``tests/test_ops.py::test_paged_decode_*`` bit-parity suite;
    end-to-end vs ``generate`` in tests/test_serving.py).

    ``max_pages > 0`` statically clamps the gather to the first
    ``max_pages`` table columns: every masked-softmax term past the
    highest live ``col`` is an exact zero, so the caller (the paged
    serving session) can bucket the gather width to the longest live
    stream and short streams stop paying long-stream HBM reads.
    Under ``jit`` the clamp must be a static Python int (it picks the
    compiled gather shape)."""
    if max_pages and max_pages < block_tables.shape[1]:
        block_tables = block_tables[:, :max_pages]
    b = block_tables.shape[0]
    n_pages = block_tables.shape[1]
    page_len, kv, d = k_pool.shape[1], k_pool.shape[2], k_pool.shape[3]
    k = jnp.take(k_pool, block_tables, axis=0).reshape(
        b, n_pages * page_len, kv, d)
    v = jnp.take(v_pool, block_tables, axis=0).reshape(
        b, n_pages * page_len, kv, d)
    return decode_attention(q, k, v, col, pad_offset=pad_offset,
                            window=window, scale=scale)


def checked_pool_cast(pool: jax.Array, values: jax.Array) -> jax.Array:
    """Cast ``values`` to ``pool.dtype`` — REFUSING the cast when the
    pool is an integer (quantized) pool and the values are raw floats.
    A bare ``.astype(int8)`` silently truncates bf16 activations to
    garbage with no scaling; every raw pool write funnels through here
    so that mistake raises instead of corrupting a token stream. The
    quantized write path (:func:`quantized_paged_append_token` /
    :func:`quantized_paged_prefill_write`) scales first and never hits
    this guard."""
    if jnp.issubdtype(pool.dtype, jnp.integer) and \
            jnp.issubdtype(values.dtype, jnp.inexact):
        raise TypeError(
            f"raw write of {values.dtype} values into a quantized "
            f"{pool.dtype} KV pool — use the quantized_* ops, which "
            f"scale per page/head before narrowing")
    return values.astype(pool.dtype)


def paged_append_token(pool: jax.Array, new: jax.Array,
                       block_tables: jax.Array,
                       pos: jax.Array, page_len: int) -> jax.Array:
    """Scatter one decode step's K (or V) rows into their pages.

    ``pool`` is ``(pages, page_len, kv, d)``, ``new`` is
    ``(b, kv, d)`` (this step's projected key/value per stream),
    ``pos`` is ``(b,)`` absolute cache positions. Row ``i`` lands at
    ``pool[block_tables[i, pos[i] // page_len], pos[i] % page_len]``
    — the paged analog of the slot cache's ``at[rows, pos].set``."""
    rows = jnp.arange(new.shape[0])
    page = block_tables[rows, pos // page_len]
    return pool.at[page, pos % page_len].set(
        checked_pool_cast(pool, new))


def paged_prefill_write(pool: jax.Array, kv_rows: jax.Array,
                        page_ids: jax.Array,
                        start_row: jax.Array) -> jax.Array:
    """Write a prefill's prompt KV directly into pages.

    ``kv_rows`` is the ``(L, kv, d)`` contiguous K (or V) a prompt
    prefill produced; rows ``[start_row, start_row + n*page_len)``
    are reshaped into ``n = page_ids.shape[0]`` page chunks and
    scattered to ``pool[page_ids]``. ``start_row`` (a multiple of
    ``page_len``) is traced, so one compile per PAGE COUNT covers
    every prefix-cache split point — shared prefix pages are simply
    not in ``page_ids`` and never rewritten while other streams read
    them."""
    n = page_ids.shape[0]
    page_len = pool.shape[1]
    chunk = jax.lax.dynamic_slice_in_dim(
        kv_rows, start_row, n * page_len, axis=0)
    chunk = chunk.reshape((n, page_len) + kv_rows.shape[1:])
    return pool.at[page_ids].set(checked_pool_cast(pool, chunk))


# ---------------------------------------------------------------------------
# Quantized paged KV (int8 pages + per-page-per-head scales).
#
# Decode is bandwidth-bound on the pool read, so halving pool bytes
# roughly doubles resident streams at fixed HBM and tokens/s/chip
# (docs/SERVING.md "Quantized serving"). Layout: the int8 pool keeps
# the bf16 pool's (pages, page_len, kv, d) shape; a parallel scale
# pool (pages, kv) float32 holds one symmetric scale per page per KV
# head — coarse enough to be ~0.4% of pool bytes, fine enough that a
# loud head in one page never clips a quiet head. Dequant is fused
# into the bounded paged gather: only the gathered (b, width*page_len)
# working set is ever materialized in float, never a pool-sized bf16
# copy. The dequantized rows then flow through the SAME
# decode_attention reduction as the exact path, so quantization error
# is confined to the value rounding itself (bounded by the round-trip
# property test in tests/test_ops.py) and measured end-to-end by the
# serving drift gate.

_QUANT_EPS = 1e-8


def quantize_kv_pages(pages: jax.Array,
                      eps: float = _QUANT_EPS) -> Tuple[jax.Array,
                                                        jax.Array]:
    """Symmetric int8 quantization of a stack of KV pages.

    ``pages`` is ``(n, page_len, kv, d)`` float; returns
    ``(q (n, page_len, kv, d) int8, scales (n, kv) float32)`` with
    ``scale = max(amax / 127, eps)`` over each page's ``(page_len, d)``
    plane per KV head. The eps clamp keeps all-zero pages (fresh
    allocations, masked rows) from dividing by zero — they round-trip
    to exact zeros."""
    amax = jnp.max(jnp.abs(pages.astype(jnp.float32)), axis=(1, 3))
    scales = jnp.maximum(amax / 127.0, eps)
    scaled = pages.astype(jnp.float32) / scales[:, None, :, None]
    q = jnp.clip(jnp.round(scaled), -127, 127).astype(jnp.int8)
    return q, scales


def dequantize_kv_pages(q: jax.Array, scales: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_kv_pages`: ``(n, page_len, kv, d)``
    int8 + ``(n, kv)`` scales -> float32 pages."""
    return q.astype(jnp.float32) * scales[:, None, :, None]


def quantized_paged_prefill_write(pool: jax.Array, scales: jax.Array,
                                  kv_rows: jax.Array,
                                  page_ids: jax.Array,
                                  start_row: jax.Array,
                                  ) -> Tuple[jax.Array, jax.Array]:
    """:func:`paged_prefill_write` into an int8 pool: quantize the
    page chunks, scatter values to ``pool[page_ids]`` and their scales
    to ``scales[page_ids]``. Rows of ``kv_rows`` past the prompt
    length are exact zeros (the prefill cache is zero-initialized), so
    a partial last page's scale reflects only the live rows."""
    n = page_ids.shape[0]
    page_len = pool.shape[1]
    chunk = jax.lax.dynamic_slice_in_dim(
        kv_rows, start_row, n * page_len, axis=0)
    chunk = chunk.reshape((n, page_len) + kv_rows.shape[1:])
    q, s = quantize_kv_pages(chunk)
    return pool.at[page_ids].set(q), scales.at[page_ids].set(s)


def quantized_paged_append_token(pool: jax.Array, scales: jax.Array,
                                 new: jax.Array,
                                 block_tables: jax.Array,
                                 pos: jax.Array, page_len: int,
                                 ) -> Tuple[jax.Array, jax.Array]:
    """:func:`paged_append_token` into an int8 pool, requantizing the
    touched page in place.

    Each stream's current page is gathered, dequantized, masked to its
    LIVE rows (``row < pos % page_len`` — a freshly allocated page may
    carry a previous stream's stale int8 garbage, and masking kills it
    without any host-side page reset), the new row is inserted, and
    the page is requantized against the live maximum. While the scale
    is unchanged the old int8 values round-trip exactly (they are
    integer multiples of the scale); when the new row grows the amax
    the page re-rounds once against the larger scale — the same
    bounded per-value error as the original quantization. Duplicate
    trash-page-0 scatters (retired streams all point at page 0) pick
    an arbitrary winner, which is fine: page 0 is never read
    unmasked."""
    rows = jnp.arange(new.shape[0])
    page = block_tables[rows, pos // page_len]
    slot = pos % page_len
    cur = dequantize_kv_pages(pool[page], scales[page])  # (b,pl,kv,d)
    live = jnp.arange(page_len)[None, :, None, None] < \
        slot[:, None, None, None]
    cur = jnp.where(live, cur, 0.0)
    cur = jax.vmap(lambda p, i, r: p.at[i].set(r))(
        cur, slot, new.astype(jnp.float32))
    q, s = quantize_kv_pages(cur)
    return pool.at[page].set(q), scales.at[page].set(s)


# ---------------------------------------------------------------------------
# Speculative-decode verify: k+1 positions per paged step.
#
# The draft model proposes k tokens; the target model scores all k+1
# known positions (last accepted token + k drafts) in ONE dispatch.
# Bit-identity is preserved by construction: the appends below are the
# SAME per-token scatter the sequential path issues (in the same
# order), and each query position runs the SAME decode_attention
# reduction at its own ``col + j`` over the gathered pages — positions
# beyond a query's col are masked to NEG_INF exactly as a not-yet-
# written cache row would be, so query j's float sums cannot see
# drafts j+1..k. Rejected drafts need no KV rollback for the same
# reason: their rows sit beyond the new col, masked until the next
# window overwrites them.


def paged_append_tokens(pool: jax.Array, new: jax.Array,
                        block_tables: jax.Array, pos: jax.Array,
                        page_len: int,
                        limit: Optional[jax.Array] = None) -> jax.Array:
    """Scatter ``s`` consecutive decode positions' K (or V) rows.

    ``new`` is ``(b, s, kv, d)``; row ``i``'s position ``j`` lands
    where a sequential :func:`paged_append_token` at ``pos[i] + j``
    would put it. ``limit`` (``(b,)``, optional) is each stream's last
    fundable position: writes past it are routed to trash page 0
    (never read unmasked), so a speculative window near the end of a
    stream's funded pages can neither scribble on another stream's
    pages nor fall off its block-table row."""
    rows = jnp.arange(new.shape[0])
    width = block_tables.shape[1]
    for j in range(new.shape[1]):
        p = pos + j
        page = block_tables[rows, jnp.clip(p // page_len, 0, width - 1)]
        if limit is not None:
            page = jnp.where(p <= limit, page, 0)
        pool = pool.at[page, p % page_len].set(
            checked_pool_cast(pool, new[:, j]))
    return pool


def paged_verify_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array,
                           block_tables: jax.Array,
                           col: jax.Array, *,
                           pad_offset: Optional[jax.Array] = None,
                           window: int = 0,
                           scale: Optional[float] = None,
                           max_pages: int = 0) -> jax.Array:
    """:func:`paged_decode_attention` for ``s`` query positions at
    once: ``q`` is ``(b, s, n_heads, d)`` and query ``j`` attends
    ``[0, col + j]``. Pages are gathered ONCE and each position runs
    the exact single-token reduction, so position ``j``'s output bits
    match a sequential single-token step at ``col + j`` — the
    speculative verify step inherits the serving bit-identity
    contract instead of re-proving it."""
    if max_pages and max_pages < block_tables.shape[1]:
        block_tables = block_tables[:, :max_pages]
    b, s = q.shape[0], q.shape[1]
    n_pages = block_tables.shape[1]
    page_len, kv, d = k_pool.shape[1], k_pool.shape[2], k_pool.shape[3]
    k = jnp.take(k_pool, block_tables, axis=0).reshape(
        b, n_pages * page_len, kv, d)
    v = jnp.take(v_pool, block_tables, axis=0).reshape(
        b, n_pages * page_len, kv, d)
    outs = [decode_attention(q[:, j:j + 1], k, v, col + j,
                             pad_offset=pad_offset, window=window,
                             scale=scale)
            for j in range(s)]
    return jnp.concatenate(outs, axis=1)


def quantized_paged_append_tokens(pool: jax.Array, scales: jax.Array,
                                  new: jax.Array,
                                  block_tables: jax.Array,
                                  pos: jax.Array, page_len: int,
                                  limit: Optional[jax.Array] = None,
                                  ) -> Tuple[jax.Array, jax.Array]:
    """:func:`paged_append_tokens` into an int8 pool: the ``s`` rows
    are appended SEQUENTIALLY through
    :func:`quantized_paged_append_token` (each append requantizes its
    page against the live rows, exactly as the one-token path would
    have), with past-``limit`` writes routed to trash page 0."""
    rows = jnp.arange(new.shape[0])
    width = block_tables.shape[1]
    for j in range(new.shape[1]):
        p = pos + j
        bt = block_tables.at[
            rows, jnp.clip(p // page_len, 0, width - 1)].get()
        if limit is not None:
            bt = jnp.where(p <= limit, bt, 0)
        # one-column table: quantized_paged_append_token indexes it
        # with p // page_len — rebuild a table whose hit column IS the
        # resolved page so the shared helper stays untouched
        pool, scales = quantized_paged_append_token(
            pool, scales, new[:, j],
            jnp.broadcast_to(bt[:, None], (bt.shape[0], 1)),
            p % page_len, page_len)
    return pool, scales


def quantized_paged_verify_attention(q: jax.Array, k_pool: jax.Array,
                                     k_scales: jax.Array,
                                     v_pool: jax.Array,
                                     v_scales: jax.Array,
                                     block_tables: jax.Array,
                                     col: jax.Array, *,
                                     pad_offset: Optional[jax.Array]
                                     = None,
                                     window: int = 0,
                                     scale: Optional[float] = None,
                                     max_pages: int = 0) -> jax.Array:
    """:func:`paged_verify_attention` over int8 pools — one fused
    dequant gather shared by all ``s`` query positions."""
    if max_pages and max_pages < block_tables.shape[1]:
        block_tables = block_tables[:, :max_pages]
    b, s = q.shape[0], q.shape[1]
    n_pages = block_tables.shape[1]
    page_len, kv, d = (k_pool.shape[1], k_pool.shape[2],
                       k_pool.shape[3])

    def gather(pool, pool_scales):
        pages = jnp.take(pool, block_tables, axis=0)
        sc = jnp.take(pool_scales, block_tables, axis=0)
        deq = pages.astype(jnp.float32) * sc[:, :, None, :, None]
        return deq.reshape(b, n_pages * page_len, kv, d)

    k = gather(k_pool, k_scales)
    v = gather(v_pool, v_scales)
    outs = [decode_attention(q[:, j:j + 1], k, v, col + j,
                             pad_offset=pad_offset, window=window,
                             scale=scale)
            for j in range(s)]
    return jnp.concatenate(outs, axis=1)


@jax.named_scope("quantized_paged_decode_attn")
def quantized_paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                                     k_scales: jax.Array,
                                     v_pool: jax.Array,
                                     v_scales: jax.Array,
                                     block_tables: jax.Array,
                                     col: jax.Array, *,
                                     pad_offset: Optional[jax.Array]
                                     = None,
                                     window: int = 0,
                                     scale: Optional[float] = None,
                                     max_pages: int = 0) -> jax.Array:
    """:func:`paged_decode_attention` over int8 pools with dequant
    fused into the bounded gather: pages and their scales are gathered
    together, multiplied out into the ``(b, width * page_len, kv, d)``
    float32 working set, and fed through the exact
    :func:`decode_attention` reduction. HBM traffic is the int8 pool
    read (+0.4% scales) — half the bf16 path's — and no pool-sized
    float copy ever exists."""
    if max_pages and max_pages < block_tables.shape[1]:
        block_tables = block_tables[:, :max_pages]
    b = block_tables.shape[0]
    n_pages = block_tables.shape[1]
    page_len, kv, d = (k_pool.shape[1], k_pool.shape[2],
                       k_pool.shape[3])

    def gather(pool, pool_scales):
        pages = jnp.take(pool, block_tables, axis=0)
        s = jnp.take(pool_scales, block_tables, axis=0)
        deq = pages.astype(jnp.float32) * s[:, :, None, :, None]
        return deq.reshape(b, n_pages * page_len, kv, d)

    return decode_attention(q, gather(k_pool, k_scales),
                            gather(v_pool, v_scales), col,
                            pad_offset=pad_offset, window=window,
                            scale=scale)
