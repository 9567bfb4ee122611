"""Grouped matrix product for expert layers (Pallas TPU kernels).

``grouped_matmul(x, w, tile_group, n_active)``: the rows of ``x (m, k)``
lie sorted by group in tiles of ``tile_m`` rows, each tile wholly of one
group (``parallel/moe.py:grouped_layout`` pads every group to whole
tiles), and row tile ``i`` is multiplied by ``w[tile_group[i]]`` of
``w (g, k, n)``. Only the first ``n_active`` tiles hold rows: the rest
are skipped and read zero (their input block is the last active tile
again, which Mosaic does not copy twice). No row is dropped and no
expert is padded to a capacity: the cost follows the rows that are
there.

Three kernels, named on the device as ``pallas_call(name=...)`` makes
them (``%moe_gmm_fwd.3 = ... custom-call``):

- ``moe_gmm_fwd``: ``y = x @ w[g]``, grid over row tiles. A group's
  ``(k, n)`` matrix is one block whose index changes only where the
  group does, so each matrix is read once (Mosaic copies a block when
  its index changes), and the product is compute-bound from some 200
  rows a group on.
- ``moe_gmm_dx``: the same with the matrix transposed, ``dx = dy @
  w[g]^T``.
- ``moe_gmm_dw``: ``dw[g] = sum over the group's tiles of x^T @ dy``,
  accumulated in the float32 output block, which stays in VMEM while
  consecutive tiles are of one group. Every group owns at least one
  tile (of padding, if it has no row), so every block is written.

On the CPU backend the kernels run in interpret mode, as the flash
kernels do.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from learningorchestra_tpu.ops.attention import _auto_interpret

# a (k, n) expert matrix is held whole and double-buffered (2 x 3 MB
# at 2048 x 768 in bf16) beside the row tiles: past the compiler's
# default scoped limit of 16 MB, well inside a v5e core's 128 MiB
_VMEM_LIMIT = 64 * 1024 * 1024


def _active(i, na_ref):
    """Row tile ``i``, or the last active one past them."""
    return jnp.minimum(i, na_ref[0] - 1)


def _fwd_kernel(tg_ref, na_ref, x_ref, w_ref, o_ref, *, transpose_rhs):
    i = pl.program_id(0)

    @pl.when(i < na_ref[0])
    def _tile():
        dims = (((1,), (1,)), ((), ())) if transpose_rhs \
            else (((1,), (0,)), ((), ()))
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], dims,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(i >= na_ref[0])
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)


def _gmm(x, w, tile_group, n_active, *, tile_m: int, transpose_rhs: bool,
         interpret: bool):
    m, kx = x.shape
    g, k, n = w.shape
    out_n = k if transpose_rhs else n
    assert kx == (n if transpose_rhs else k), (x.shape, w.shape)
    n_tiles = m // tile_m
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile_m, kx), lambda i, tg, na: (_active(i, na), 0)),
            pl.BlockSpec((1, k, n), lambda i, tg, na: (tg[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_m, out_n), lambda i, tg, na: (i, 0)))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, transpose_rhs=transpose_rhs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, out_n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm_dx" if transpose_rhs else "moe_gmm_fwd",
    )(tile_group, n_active, x, w)


def _dw_kernel(tg_ref, na_ref, x_ref, dy_ref, dw_ref):
    i = pl.program_id(1)
    first = jnp.logical_or(
        i == 0, tg_ref[i] != tg_ref[jnp.maximum(i - 1, 0)])

    @pl.when(first)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(i < na_ref[0])
    def _tile():
        dw_ref[0] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _col_tile(n: int) -> int:
    """Widest divisor of ``n`` that is a multiple of 128 and at most
    512 (the float32 output block is (k, tile) and double-buffered);
    ``n`` itself where there is none (small test shapes)."""
    for t in range(512, 127, -128):
        if n % t == 0:
            return t
    return n


def _tgmm(x, dy, tile_group, n_active, *, n_groups: int, tile_m: int,
          interpret: bool):
    m, k = x.shape
    n = dy.shape[1]
    tn = _col_tile(n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n // tn, m // tile_m),
        in_specs=[
            pl.BlockSpec((tile_m, k),
                         lambda j, i, tg, na: (_active(i, na), 0)),
            pl.BlockSpec((tile_m, tn),
                         lambda j, i, tg, na: (_active(i, na), j)),
        ],
        out_specs=pl.BlockSpec((1, k, tn),
                               lambda j, i, tg, na: (tg[i], 0, j)))
    return pl.pallas_call(
        _dw_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_groups, k, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm_dw",
    )(tile_group, n_active, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _grouped(x, w, tile_group, n_active, tile_m, interpret):
    return _gmm(x, w, tile_group, n_active, tile_m=tile_m,
                transpose_rhs=False, interpret=interpret)


def _grouped_fwd(x, w, tile_group, n_active, tile_m, interpret):
    y = _gmm(x, w, tile_group, n_active, tile_m=tile_m,
             transpose_rhs=False, interpret=interpret)
    return y, (x, w, tile_group, n_active)


def _grouped_bwd(tile_m, interpret, res, dy):
    x, w, tile_group, n_active = res
    dx = _gmm(dy, w, tile_group, n_active, tile_m=tile_m,
              transpose_rhs=True, interpret=interpret)
    dw = _tgmm(x, dy, tile_group, n_active, n_groups=w.shape[0],
               tile_m=tile_m, interpret=interpret)
    return dx, dw.astype(w.dtype), None, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(x: jax.Array, w: jax.Array, tile_group: jax.Array,
                   n_active: jax.Array, *, tile_m: int,
                   interpret: Optional[bool] = None) -> jax.Array:
    """``y[r] = x[r] @ w[tile_group[r // tile_m]]`` for the rows of the
    first ``n_active`` tiles, zero for the rest. ``x (m, k)`` with
    ``tile_m | m``; ``w (g, k, n)``; ``tile_group (m // tile_m,)`` int32,
    non-decreasing, every group at least once among the active tiles
    (past them: the last group again); ``n_active`` (1,) int32.
    Differentiable in ``x`` and ``w``."""
    if x.shape[0] % tile_m:
        raise ValueError(f"{x.shape[0]} rows are no whole tiles of {tile_m}")
    return _grouped(x, w.astype(x.dtype), tile_group.astype(jnp.int32),
                    n_active.astype(jnp.int32), int(tile_m),
                    _auto_interpret() if interpret is None
                    else bool(interpret))
