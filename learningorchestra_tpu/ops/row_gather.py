"""Each token's sum of its copies' rows in an expert layer's row buffer
(a Pallas TPU kernel).

``rows_summed(buffer, runs(pos, group, groups, rows), weights)``:
``out[t] = sum over j of weights[t, j] * buffer[pos[t, j]]``, float32,
where copy ``(t, j)`` of group ``group[t, j]`` lies in row ``pos[t, j]``
of the buffer (``groups`` and a row past the buffer for a copy that is
not there). The buffer is ``parallel/moe.py:grouped_layout``'s: sorted
by group and, within a group, by token, so the copies of one group from
a tile of tokens lie in consecutive rows, a run.

XLA's gather reads every one of the ``T x k`` slots, held or not, and
writes each as a row before a second pass sums them (about 5 ms at
131,072 slots of 2,048 on a v5e, whatever the load); its scatter-add
costs a fixed time for every row that lands (PERF.md section 5). This
kernel reads the rows that hold copies. For a tile of 128 tokens it
copies each group's run into VMEM, rounded out to whole tiles of 8
rows (a DMA cannot start inside one), then multiplies a one-hot of the
tile's slots against each 128 rows it copied: the MXU adds each row
onto its token, weighted, with float32 sums. Its time follows the
copies held here, not the slots. :func:`runs` is the plan, computed
once for the layer's combine and the dispatch's backward, with no
scatter and no lookup a slot (a compare of each slot with each group).

The kernel is named ``moe_rows_sum`` on the device. On the CPU backend
it runs in interpret mode, as the other kernels do.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from learningorchestra_tpu.ops.attention import _auto_interpret

# rows of the copied runs a one-hot product takes
_CHUNK = 128
# a slot that holds no copy here: no row of the copied runs
_NOWHERE = 2 ** 30
_VMEM_LIMIT = 64 * 1024 * 1024
# rows a DMA of the buffer starts and ends on: a tile's sublanes in HBM
_GRANULE = 8


def _kernel(n_ref, table_ref, srow_ref, w_ref, buf_hbm, out_ref, copied,
            sem, *, groups: int, k: int, weighted: bool):
    """``table_ref``: the tile's table, each group's first row (rounded
    down to a tile), tiles to copy and place among the copied rows, at
    ``0``, ``groups`` and ``2 * groups``."""
    i = pl.program_id(0)

    def block(e, b):
        src = pl.multiple_of(table_ref[e] + b * _GRANULE, _GRANULE)
        dst = pl.multiple_of(table_ref[2 * groups + e] + b * _GRANULE,
                             _GRANULE)
        return pltpu.make_async_copy(buf_hbm.at[pl.ds(src, _GRANULE)],
                                     copied.at[pl.ds(dst, _GRANULE)],
                                     sem.at[0])

    @pl.loop(0, groups)
    def _fetch(e):
        @pl.loop(0, table_ref[groups + e])
        def _start(b):
            block(e, b).start()

    n = n_ref[i]

    @pl.loop(0, n // _GRANULE)
    def _wait(b):
        block(0, 0).wait()

    exact = copied.dtype == jnp.float32

    def chunk(c, acc):
        base = pl.multiple_of(c * _CHUNK, _CHUNK)
        row = base + jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, 1), 0)
        onehot = jnp.zeros((_CHUNK, out_ref.shape[0]), jnp.float32)
        named = jnp.zeros((_CHUNK, 1), jnp.float32)
        for j in range(k):
            here = srow_ref[j:j + 1, :] == row
            onehot = onehot + jnp.where(
                here, w_ref[j:j + 1, :] if weighted else 1.0, 0.0)
            named = jnp.maximum(named, jnp.max(
                here.astype(jnp.float32), axis=1, keepdims=True))
        # a copied row no slot names (the rounding out to whole tiles,
        # or past ``n``) is no number to the product
        rows = jnp.where(named > 0, copied[pl.ds(base, _CHUNK), :], 0)
        dims = (((0,), (0,)), ((), ()))
        if exact:
            return acc + jax.lax.dot_general(
                onehot, rows, dims, precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        # the weights in two bf16 parts, so the rows are weighted by
        # their 16 leading bits (a one-hot of ones is exact in one)
        hi = onehot.astype(rows.dtype)
        acc = acc + jax.lax.dot_general(hi, rows, dims,
                                        preferred_element_type=jnp.float32)
        if weighted:
            lo = (onehot - hi.astype(jnp.float32)).astype(rows.dtype)
            acc = acc + jax.lax.dot_general(
                lo, rows, dims, preferred_element_type=jnp.float32)
        return acc

    out_ref[...] = jax.lax.fori_loop(
        0, pl.cdiv(n, _CHUNK), chunk,
        jnp.zeros(out_ref.shape, jnp.float32)).astype(out_ref.dtype)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["fetched", "table", "slot_row"],
                   meta_fields=["groups", "tokens"])
@dataclasses.dataclass(frozen=True)
class Runs:
    """Where each tile of ``bt`` tokens finds its copies: ``fetched``
    (nt,) the rows it copies into VMEM; ``table`` each group's first
    row (rounded down to a tile), tiles to copy and place among the
    copied rows, a tile's table at a time; ``slot_row`` (k, nt * bt)
    each slot's row among the copied rows (``_NOWHERE`` for none), of
    ``tokens`` tokens and ``groups`` groups."""
    fetched: jax.Array
    table: jax.Array
    slot_row: jax.Array
    groups: int
    tokens: int


def runs(pos: jax.Array, group: jax.Array, groups: int,
         n_rows: int) -> Runs:
    """The :class:`Runs` of copies ``(t, j)`` of group ``group[t, j]``
    in row ``pos[t, j]`` of a buffer of ``n_rows`` rows (``groups`` and
    a row past the buffer for a copy that is not there), each group's
    copies in consecutive rows in token order, as ``grouped_layout``
    puts them; ``pos``, ``group`` (T, k) int32. No scatter and no
    lookup a slot: compares of each slot with each group."""
    t, k = pos.shape
    g = _GRANULE
    if n_rows % g:
        raise ValueError(f"{n_rows} rows are no whole tiles of {g}")
    bt = _token_tile(t)
    t_pad = -(-t // bt) * bt
    nt = t_pad // bt
    if t_pad != t:
        pad = ((0, t_pad - t), (0, 0))
        pos = jnp.pad(pos, pad, constant_values=n_rows)
        group = jnp.pad(group, pad, constant_values=groups)
    slots_pos = pos.reshape(nt, bt * k)
    mine = group.reshape(nt, bt * k)[:, :, None] == jnp.arange(groups)
    copies = jnp.sum(mine, axis=1, dtype=jnp.int32)
    start = jnp.min(jnp.where(mine, slots_pos[:, :, None], n_rows), axis=1)
    first = start // g * g
    blocks = jnp.where(copies > 0, -(-(start + copies - first) // g), 0)
    fetched = blocks * g
    at = jnp.cumsum(fetched, axis=1) - fetched
    slot_row = jnp.where(
        (group < groups).reshape(nt, bt * k),
        slots_pos + jnp.sum(jnp.where(mine, (at - first)[:, None, :], 0),
                            axis=2), _NOWHERE)
    # one table a tile in SMEM, in whole tiles of 1,024 (XLA's layout
    # of an int32 vector)
    width = _table_width(groups)
    table = jnp.pad(jnp.concatenate([first, blocks, at], axis=1),
                    ((0, 0), (0, width - 3 * groups)))
    return Runs(
        fetched=jnp.sum(fetched, axis=1, dtype=jnp.int32),
        table=table.astype(jnp.int32).reshape(-1),
        slot_row=slot_row.astype(jnp.int32).reshape(nt, bt, k).transpose(
            2, 0, 1).reshape(k, t_pad),
        groups=groups, tokens=t)


def _token_tile(t: int) -> int:
    return 128 if t > 128 else -(-t // 8) * 8


def _table_width(groups: int) -> int:
    return -(-3 * groups // 1024) * 1024


def rows_summed(buffer: jax.Array, where: Runs,
                weights: Optional[jax.Array] = None) -> jax.Array:
    """``out (T, d)`` float32: ``out[t] = sum over j of weights[t, j] *
    buffer[pos[t, j]]`` (weights (T, k), 1 if none), zero for a copy
    that is not in the buffer; ``where`` is :func:`runs` of ``pos``."""
    n_rows, d = buffer.shape
    k, t_pad = where.slot_row.shape
    t = where.tokens
    bt = _token_tile(t)
    nt = t_pad // bt
    groups = where.groups
    width = _table_width(groups)
    w_t = jnp.ones((k, t_pad), jnp.float32) if weights is None else \
        jnp.pad(weights.astype(jnp.float32).T, ((0, 0), (0, t_pad - t)))
    capacity = bt * min(k, groups) + groups * 2 * (_GRANULE - 1)
    capacity = -(-capacity // _CHUNK) * _CHUNK
    by_tile = pl.BlockSpec((k, bt), lambda i, n: (0, i))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(nt,),
        in_specs=[pl.BlockSpec((width,), lambda i, n: (i,),
                               memory_space=pltpu.SMEM), by_tile, by_tile,
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((bt, d), lambda i, n: (i, 0)),
        scratch_shapes=[pltpu.VMEM((capacity, d), buffer.dtype),
                        pltpu.SemaphoreType.DMA((1,))])
    out = pl.pallas_call(
        functools.partial(_kernel, groups=groups, k=k,
                          weighted=weights is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_auto_interpret(),
        name="moe_rows_sum",
    )(where.fetched, where.table, where.slot_row, w_t, buffer)
    return out[:t] if t_pad != t else out
