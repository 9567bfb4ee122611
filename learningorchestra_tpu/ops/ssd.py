"""State-space scan of a Mamba-2 mixer in its chunked (state-space
dual) form, forward and backward (docs/STATE_SPACE.md).

For one row and one head of width ``P`` with a state of ``P x N``::

    a_t = exp(A * dt_t)                     (A < 0, dt_t >= 0)
    S_t = a_t S_{t-1} + dt_t x_t B_t^T      (S_0 = 0 at the row's start)
    y_t = S_t C_t + D x_t

``ssd(x, dt, A, B, C, D, chunk)`` computes it over chunks of ``chunk``
positions. With ``u_j = dt_j x_j`` and ``cum_i`` the sum of ``A dt`` over
the chunk's positions up to ``i``, a chunk that starts from the state
``S`` gives::

    y_i = sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) u_j + exp(cum_i) S C_i
    S'  = exp(cum_last) S + sum_j exp(cum_last - cum_j) u_j B_j^T

the first term a masked ``chunk x chunk`` product (``(C B^T o L) u``,
``L`` the lower-triangular matrix of decays), the state carried from
chunk to chunk. The decay exponents, their sums and the state are
float32; the products take their operands in ``x``'s dtype (bf16 on the
chip) and accumulate in float32. ``B`` and ``C`` are shared by the heads
(one group).

On the TPU the core runs as two Pallas kernels, ``ssd_fwd`` and
``ssd_bwd`` (``pallas_call(name=...)``: ``%ssd_fwd.3 = ... custom-call``
in a device trace). A grid step is one chunk of ``_HEAD_BLOCK`` heads;
the chunk axis is the innermost and sequential, the state (backward: its
gradient) lives in VMEM scratch across it. ``C B^T``, ``L`` and their
gradients exist in VMEM only: nothing ``chunk x chunk`` is written to
HBM or kept for the backward, which is given the chunks' starting
states (float32, ``P x N`` a head and chunk) and builds the matrices
again. Elsewhere (``impl="jnp"``: the CPU path, and the kernels' oracle
in interpret mode) the same chunked algorithm runs as plain
``jax.numpy`` under ``lax.scan`` and JAX's own differentiation.
``impl="auto"`` is ``attention: "auto"``'s rule: the kernels on the TPU,
``jnp`` elsewhere.

The kernels read x, and the backward ``dy``, where the mixer holds them,
``(b, s, H P)``, a ``(chunk, hb P)`` block a grid step, and write y and
``dx`` there. Heads narrower than a lane tile share a slab of 128 lanes
(heads of 64: pairs), so every load and store is on whole tiles; a
head's products run over the slab and the other heads' lanes are left
out of its result. The forward forms ``u = dt x`` (rounded to x's dtype
as an operand) and adds the skip ``D x`` in float32 before y's one
rounding; the backward writes ``dx = dt du + D dy``, the rows ``x . du``
(``dt``'s gradient through ``u``) beside ``d cum``, and per-chunk sums
of ``dy x`` (``D``'s).

What stays outside the kernels, in XLA, is small: the sums of ``A dt``
within a chunk and ``dt`` as ``(chunk, heads)`` rows (the kernels take a
head's values as columns, one transpose a block), ``D`` spread over its
head's lanes, the sums of the kernels' per-block partials, and the
gradients of these (so ``dt`` and ``A`` get theirs through the cumulative
sum from JAX's rules).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from learningorchestra_tpu.ops.attention import _auto_interpret

NEG = -1e30
# heads of one grid step: C B^T is taken once for them, and their
# (heads, chunk) rows of exponents fill whole float32 tiles
_HEAD_BLOCK = 8
_VMEM_LIMIT = 64 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))    # a @ b^T
_TN = (((0,), (0,)), ((), ()))    # a^T @ b
_NN = (((1,), (0,)), ((), ()))


def _dot(a, b, dims=_NN):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# ----------------------------------------------------------------------
# the chunked algorithm in plain jax.numpy
# ----------------------------------------------------------------------
def _core_jnp(u, cum, B, C):
    """``u (b, H, s, P)``, ``cum (b, nc, H, Q)``, ``B, C (b, s, N)`` ->
    ``(y (b, H, s, P), final state (b, H, P, N) float32)``."""
    b, H, s, P = u.shape
    nc, Q = cum.shape[1], cum.shape[3]
    N = B.shape[-1]
    mm = u.dtype
    uc = u.reshape(b, H, nc, Q, P).transpose(2, 0, 1, 3, 4)
    cc = cum.transpose(1, 0, 2, 3)                       # (nc, b, H, Q)
    Bc = B.reshape(b, nc, Q, N).transpose(1, 0, 2, 3)
    Cc = C.reshape(b, nc, Q, N).transpose(1, 0, 2, 3)
    lower = jnp.tril(jnp.ones((Q, Q), bool))

    def one_chunk(S, xs):
        u_c, c_c, B_c, C_c = xs
        G = jnp.einsum("bin,bjn->bij", C_c, B_c,
                       preferred_element_type=jnp.float32)
        diff = c_c[..., :, None] - c_c[..., None, :]     # (b, H, Q, Q)
        L = jnp.exp(jnp.where(lower, diff, NEG))
        M = (G[:, None] * L).astype(mm)
        y = jnp.einsum("bhij,bhjp->bhip", M, u_c,
                       preferred_element_type=jnp.float32)
        CS = jnp.einsum("bin,bhpn->bhip", C_c, S.astype(mm),
                        preferred_element_type=jnp.float32)
        y = y + jnp.exp(c_c)[..., None] * CS
        last = c_c[..., -1:]                             # (b, H, 1)
        uw = (u_c.astype(jnp.float32)
              * jnp.exp(last - c_c)[..., None]).astype(mm)
        S = jnp.exp(last)[..., None] * S + jnp.einsum(
            "bhjp,bjn->bhpn", uw, B_c, preferred_element_type=jnp.float32)
        return S, y.astype(mm)

    S0 = jnp.zeros((b, H, P, N), jnp.float32)
    S, ys = lax.scan(one_chunk, S0, (uc, cc, Bc, Cc))
    return ys.transpose(1, 2, 0, 3, 4).reshape(b, H, s, P), S


# ----------------------------------------------------------------------
# the kernels
# ----------------------------------------------------------------------
def _last(row, lane):
    """(1, 1): the row's last value."""
    return jnp.sum(jnp.where(lane == row.shape[1] - 1, row, 0.0), axis=1,
                   keepdims=True)


def _masks(q: int):
    ri = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    ci = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return ri >= ci, lax.broadcasted_iota(jnp.int32, (1, q), 1)


def _by_head(head, vals):
    """One value a head of a slab, each spread over its head's lanes
    (``head (1, w)``: the head of each lane) or rows (``(w, 1)``)."""
    out = vals[-1]
    for i in range(len(vals) - 2, -1, -1):
        out = jnp.where(head == i, vals[i], out)
    return out


class _Slab:
    """``k`` heads of ``p`` lanes side by side in a ``(chunk, k p)`` tile
    of x; a head's ``(p, N)`` state is rows ``i p`` to ``(i + 1) p`` of
    the slab's. ``dt``'s and the decays' values of a head are columns
    (positions down the sublanes) of the block's rows, transposed once."""

    def __init__(self, j, k, p, dts, cols, rows, lower, lane):
        self.heads = range(j * k, (j + 1) * k)
        self.lanes = slice(j * k * p, (j + 1) * k * p)
        self.p = p
        self.lane_head = lax.broadcasted_iota(jnp.int32, (1, k * p), 1) // p
        self.row_head = lax.broadcasted_iota(jnp.int32, (k * p, 1), 0) // p
        self.dt = _by_head(self.lane_head, [dts[:, h:h + 1]
                                            for h in self.heads])
        self.L, last, col = [], [], []
        for h in self.heads:
            col.append(cols[:, h:h + 1])
            row = rows[h:h + 1, :]
            self.L.append(jnp.exp(jnp.where(lower, col[-1] - row, NEG)))
            last.append(_last(row, lane))
        self.e_last = [jnp.exp(v) for v in last]
        # exp(cum_i) and exp(cum_last - cum_i), over the slab's lanes
        self.E = _by_head(self.lane_head, [jnp.exp(c) for c in col])
        self.W = _by_head(self.lane_head, [jnp.exp(v - c)
                                           for v, c in zip(last, col)])

    def lanes_of(self, i, t):
        return t if len(self.heads) == 1 else jnp.where(
            self.lane_head == i, t, jnp.zeros_like(t))

    def rows_of(self, i, t):
        """Head ``i``'s part of a ``(k p, n)`` value."""
        return t[i * self.p:(i + 1) * self.p]


def _slabs(hb, k, p, dt_ref, cum_ref, lower, lane):
    """The block's slabs in turn (a generator: one slab's decays are
    live at a time)."""
    rows = cum_ref[0, 0]                                  # (hb, Q)
    cols, dts = rows.T, dt_ref[0, 0].T                    # (Q, hb)
    for j in range(hb // k):
        yield _Slab(j, k, p, dts, cols, rows, lower, lane)


def _fwd_kernel(x_ref, dt_ref, cum_ref, d_ref, b_ref, c_ref, *refs,
                hb: int, k: int, p: int, save_states: bool):
    if save_states:
        y_ref, final_ref, states_ref, s_scr = refs
    else:
        y_ref, final_ref, s_scr = refs
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _start():
        s_scr[...] = jnp.zeros_like(s_scr)

    if save_states:
        states_ref[0, 0] = s_scr[...]
    Bm, Cm = b_ref[0], c_ref[0]                           # (Q, N)
    mm = Bm.dtype
    G = _dot(Cm, Bm, _NT)                                 # (Q, Q)
    lower, lane = _masks(G.shape[0])
    for sl in _slabs(hb, k, p, dt_ref, cum_ref, lower, lane):
        X = x_ref[0, :, sl.lanes].astype(jnp.float32)     # (Q, k p)
        U = (X * sl.dt).astype(mm)
        S = s_scr[sl.lanes]                               # (k p, N) f32
        y = _by_head(sl.lane_head, [_dot((G * L).astype(mm), U)
                                    for L in sl.L])
        y = y + sl.E * _dot(Cm, S.astype(mm), _NT)
        y_ref[0, :, sl.lanes] = (
            y + d_ref[:, sl.lanes] * X).astype(y_ref.dtype)
        uw = (U.astype(jnp.float32) * sl.W).astype(mm)
        s_scr[sl.lanes] = (_by_head(sl.row_head, sl.e_last) * S
                           + _dot(uw, Bm, _TN))

    @pl.when(c == pl.num_programs(2) - 1)
    def _end():
        final_ref[0] = s_scr[...]


def _bwd_kernel(x_ref, dt_ref, cum_ref, d_ref, b_ref, c_ref, dy_ref,
                states_ref, dx_ref, ddt_ref, dcum_ref, dd_ref, db_ref,
                dc_ref, ds_scr, *, hb: int, k: int, p: int):
    c = pl.program_id(2)          # the index maps run the chunks backwards

    @pl.when(c == 0)
    def _start():
        ds_scr[...] = jnp.zeros_like(ds_scr)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    Bm, Cm = b_ref[0], c_ref[0]
    mm = Bm.dtype
    f32 = jnp.float32
    G = _dot(Cm, Bm, _NT)
    q = G.shape[0]
    lower, lane = _masks(q)
    dG = jnp.zeros((q, q), f32)
    dB = jnp.zeros(Bm.shape, f32)
    dC = jnp.zeros(Cm.shape, f32)
    for sl in _slabs(hb, k, p, dt_ref, cum_ref, lower, lane):
        X = x_ref[0, :, sl.lanes].astype(f32)
        U = (X * sl.dt).astype(mm)
        Uf = U.astype(f32)
        dY = dy_ref[0, :, sl.lanes]                       # (Q, k p)
        dYf = dY.astype(f32)
        S, dS = states_ref[0, 0, sl.lanes], ds_scr[sl.lanes]
        Sb, dSb = S.astype(mm), dS.astype(mm)
        Ms = [(G * L).astype(mm) for L in sl.L]
        dU_state = sl.W * _dot(Bm, dSb, _NT)              # (Q, k p)
        dU = dU_state + _by_head(sl.lane_head,
                                 [_dot(M, dY, _TN) for M in Ms])
        Y = sl.E * _dot(Cm, Sb, _NT) + _by_head(sl.lane_head,
                                                [_dot(M, U) for M in Ms])
        for i, L in enumerate(sl.L):
            dG = dG + _dot(sl.lanes_of(i, dY), U, _NT) * L
        EdY = (sl.E * dYf).astype(mm)
        dC = dC + _dot(EdY, Sb)
        dB = dB + _dot((sl.W * Uf).astype(mm), dSb)
        # u = dt x and the skip D x, in the lanes where they were taken
        dx_ref[0, :, sl.lanes] = (
            sl.dt * dU + d_ref[:, sl.lanes] * dYf).astype(dx_ref.dtype)
        dd_ref[0, :, sl.lanes] += jnp.sum(dYf * X, axis=0, keepdims=True)
        # d cum_i = dy_i . y_i - du_i . u_i, at the chunk's last position
        # what the state's decay and its weights add, and d dt_i = x_i .
        # du_i: summed over a head's lanes, transposed to rows of positions
        d_cum = (dYf * Y - dU * Uf).T                     # (k p, Q)
        d_dt = (X * dU).T
        dSS = jnp.sum(dS * S, axis=1, keepdims=True)      # (k p, 1)
        dUU = jnp.sum(dU_state * Uf, axis=0, keepdims=True)   # (1, k p)
        for i, h in enumerate(sl.heads):
            d_last = (sl.e_last[i] * jnp.sum(sl.rows_of(i, dSS), axis=0,
                                             keepdims=True)
                      + jnp.sum(sl.lanes_of(i, dUU), axis=1,
                                keepdims=True))
            dcum_ref[0, 0, h:h + 1, :] = jnp.sum(
                sl.rows_of(i, d_cum), axis=0, keepdims=True) + jnp.where(
                    lane == q - 1, d_last, 0.0)
            ddt_ref[0, 0, h:h + 1, :] = jnp.sum(sl.rows_of(i, d_dt), axis=0,
                                                keepdims=True)
        ds_scr[sl.lanes] = (_by_head(sl.row_head, sl.e_last) * dS
                            + _dot(EdY, Cm, _TN))
    dGb = dG.astype(mm)
    dc_ref[0, 0] = dC + _dot(dGb, Bm)
    db_ref[0, 0] = dB + _dot(dGb, Cm, _TN)


def _head_block(heads: int, p: int) -> int:
    """Heads of a grid step: ``_HEAD_BLOCK`` where their lanes are whole
    lane tiles, else all of them (a block as wide as x)."""
    hb = _HEAD_BLOCK
    return hb if heads % hb == 0 and hb * p % 128 == 0 else heads


def _heads_per_slab(hb: int, p: int) -> int:
    """Heads that share a slab: as many as one lane tile holds, so that a
    slab starts on a tile's first lane (a head of 64: pairs, each head's
    products taken over both and the other's lanes left out)."""
    k = max(1, 128 // p) if 128 % p == 0 or p % 128 == 0 else hb
    return k if hb % k == 0 else hb


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _shapes(x, cum, B):
    b, s, hp = x.shape
    nc, H, Q = cum.shape[1], cum.shape[2], cum.shape[3]
    p = hp // H
    hb = _head_block(H, p)
    return b, s, hp, nc, H, Q, p, hb, B.shape[-1]


def _fwd_pallas(x, dt, cum, D, B, C, *, save_states: bool,
                interpret: bool):
    b, s, hp, nc, H, Q, p, hb, N = _shapes(x, cum, B)
    w = hb * p
    out_shape = [jax.ShapeDtypeStruct((b, s, hp), x.dtype),
                 jax.ShapeDtypeStruct((b, hp, N), jnp.float32)]
    out_specs = [pl.BlockSpec((1, Q, w), lambda i, g, c: (i, c, g)),
                 pl.BlockSpec((1, w, N), lambda i, g, c: (i, g, 0))]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct((b, nc, hp, N), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, w, N),
                                      lambda i, g, c: (i, c, g, 0)))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, k=_heads_per_slab(hb, p),
                          p=p, save_states=save_states),
        grid=(b, H // hb, nc),
        in_specs=[
            pl.BlockSpec((1, Q, w), lambda i, g, c: (i, c, g)),
            pl.BlockSpec((1, 1, hb, Q), lambda i, g, c: (i, c, g, 0)),
            pl.BlockSpec((1, 1, hb, Q), lambda i, g, c: (i, c, g, 0)),
            pl.BlockSpec((1, w), lambda i, g, c: (0, g)),
            pl.BlockSpec((1, Q, N), lambda i, g, c: (i, c, 0)),
            pl.BlockSpec((1, Q, N), lambda i, g, c: (i, c, 0)),
        ],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((w, N), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name="ssd_fwd",
    )(x, dt, cum, D, B, C)


def _bwd_pallas(x, dt, cum, D, B, C, dy, states, *, interpret: bool):
    b, s, hp, nc, H, Q, p, hb, N = _shapes(x, cum, B)
    w = hb * p
    groups = H // hb

    def back(c):
        return nc - 1 - c

    rows = pl.BlockSpec((1, 1, hb, Q), lambda i, g, c: (i, back(c), g, 0))
    flat = pl.BlockSpec((1, Q, w), lambda i, g, c: (i, back(c), g))
    shared = pl.BlockSpec((1, Q, N), lambda i, g, c: (i, back(c), 0))
    # B and C are shared by the heads: a head block's part each
    part = pl.BlockSpec((1, 1, Q, N), lambda i, g, c: (i, g, back(c), 0))
    dx, ddt, dcum, dD, dB, dC = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, k=_heads_per_slab(hb, p),
                          p=p),
        grid=(b, groups, nc),
        in_specs=[flat, rows, rows,
                  pl.BlockSpec((1, w), lambda i, g, c: (0, g)),
                  shared, shared, flat,
                  pl.BlockSpec((1, 1, w, N),
                               lambda i, g, c: (i, back(c), g, 0))],
        out_specs=[flat, rows, rows,
                   pl.BlockSpec((1, 1, w), lambda i, g, c: (i, 0, g)),
                   part, part],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(dt.shape, jnp.float32),
            jax.ShapeDtypeStruct(cum.shape, jnp.float32),
            jax.ShapeDtypeStruct((b, 1, hp), jnp.float32),
            jax.ShapeDtypeStruct((b, groups, s, N), jnp.float32),
            jax.ShapeDtypeStruct((b, groups, s, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((w, N), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name="ssd_bwd",
    )(x, dt, cum, D, B, C, dy, states)
    return (dx, ddt, dcum, jnp.sum(dD, axis=0),
            jnp.sum(dB, axis=1).astype(B.dtype),
            jnp.sum(dC, axis=1).astype(C.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _core_pallas(x, dt, cum, D, B, C, interpret):
    return _fwd_pallas(x, dt, cum, D, B, C, save_states=False,
                       interpret=interpret)


def _core_pallas_fwd(x, dt, cum, D, B, C, interpret):
    y, final, states = _fwd_pallas(x, dt, cum, D, B, C, save_states=True,
                                   interpret=interpret)
    return (y, final), (x, dt, cum, D, B, C, states)


def _core_pallas_bwd(interpret, res, cts):
    """The final state is a reading (a counter's), not a path of the
    loss: its cotangent is not followed."""
    x, dt, cum, D, B, C, states = res
    return _bwd_pallas(x, dt, cum, D, B, C, cts[0].astype(x.dtype), states,
                       interpret=interpret)


_core_pallas.defvjp(_core_pallas_fwd, _core_pallas_bwd)


# ----------------------------------------------------------------------
def resolve_impl(impl: str = "auto") -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl not in ("pallas", "jnp"):
        raise ValueError(f"ssd impl must be auto, pallas or jnp: {impl!r}")
    return impl


def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, D: jax.Array, chunk: int = 256, *,
        impl: str = "auto", interpret: Optional[bool] = None,
        ) -> Tuple[jax.Array, jax.Array]:
    """``x (b, s, H, P)``; ``dt (b, s, H)`` (after the softplus, >= 0);
    ``A (H,)`` (negative: minus the exponential of ``A_log``); ``B, C
    (b, s, N)``; ``D (H,)``. Returns ``(y (b, s, H, P)`` in ``x``'s
    dtype, ``state (b, H, P, N)`` float32: what each head holds after
    the row's last position``)``. The state carries no gradient. A row
    is padded to whole chunks with ``dt = 0``, which neither moves the
    state nor is read."""
    b, s, H, P = x.shape
    q = int(chunk)
    nc = -(-s // q)
    pad = nc * q - s
    dt = dt.astype(jnp.float32)
    xp, dtp, Bp, Cp = x, dt, B.astype(x.dtype), C.astype(x.dtype)
    if pad:
        xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dtp = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bp = jnp.pad(Bp, ((0, 0), (0, pad), (0, 0)))
        Cp = jnp.pad(Cp, ((0, 0), (0, pad), (0, 0)))
    # (b, nc, H, Q): the sum of A dt over a chunk's positions up to each
    cum = jnp.cumsum((dtp * A.astype(jnp.float32)).reshape(b, nc, q, H),
                     axis=2).transpose(0, 1, 3, 2)
    if resolve_impl(impl) == "pallas":
        if interpret is None:
            interpret = _auto_interpret()
        y, state = _core_pallas(
            xp.reshape(b, nc * q, H * P),
            dtp.reshape(b, nc, q, H).transpose(0, 1, 3, 2), cum,
            jnp.repeat(D.astype(jnp.float32), P)[None], Bp, Cp,
            bool(interpret))
        return (y.reshape(b, nc * q, H, P)[:, :s],
                lax.stop_gradient(state.reshape(b, H, P, -1)))
    u = (xp.astype(jnp.float32) * dtp[..., None]).astype(x.dtype)
    y, state = _core_jnp(u.transpose(0, 2, 1, 3), cum, Bp, Cp)
    y = y.transpose(0, 2, 1, 3)[:, :s]
    y = y + (D.astype(jnp.float32)[:, None]
             * x.astype(jnp.float32)).astype(x.dtype)
    return y, lax.stop_gradient(state)


def ssd_recurrence(x, dt, A, B, C, D):
    """The recurrence position by position, float32: the definition the
    chunked forms are tested against (tests; not a path of the
    program)."""
    f32 = jnp.float32
    x, dt, B, C = (a.astype(f32) for a in (x, dt, B, C))
    a = jnp.exp(dt * A.astype(f32))                      # (b, s, H)

    def step(S, xs):
        x_t, dt_t, a_t, B_t, C_t = xs
        S = a_t[..., None, None] * S + jnp.einsum(
            "bhp,bn->bhpn", x_t * dt_t[..., None], B_t)
        return S, jnp.einsum("bhpn,bn->bhp", S, C_t)

    b, s, H, P = x.shape
    S0 = jnp.zeros((b, H, P, B.shape[-1]), f32)
    swap = lambda t: jnp.swapaxes(t, 0, 1)  # noqa: E731
    S, ys = lax.scan(step, S0, (swap(x), swap(dt), swap(a), swap(B),
                                swap(C)))
    return swap(ys) + D.astype(f32)[:, None] * x, S
