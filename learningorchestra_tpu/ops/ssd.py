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

What stays outside the kernels, in XLA: ``u = dt x``, the sums of ``A
dt`` within a chunk, the skip ``D x`` and their gradients (so ``dt``,
``A`` and ``D`` get theirs from JAX's rules for a product and a
cumulative sum), and the layouts: heads before positions, so that a
head's chunk is one ``(chunk, P)`` tile.
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
def _decays(row, eye, lower):
    """From one head's ``(1, Q)`` row of exponents: the same values as
    a column ``(Q, 1)`` (the diagonal of its broadcast, summed along the
    lanes: exact) and ``L (Q, Q)``, ``exp(cum_i - cum_j)`` at and under
    the diagonal and nought above."""
    q = eye.shape[0]
    rows = jnp.broadcast_to(row, (q, q))
    col = jnp.sum(jnp.where(eye, rows, 0.0), axis=1, keepdims=True)
    return col, jnp.exp(jnp.where(lower, col - rows, NEG))


def _last(row, lane):
    """(1, 1): the row's last value."""
    return jnp.sum(jnp.where(lane == row.shape[1] - 1, row, 0.0), axis=1,
                   keepdims=True)


def _masks(q: int):
    ri = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    ci = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return ri == ci, ri >= ci, lax.broadcasted_iota(jnp.int32, (1, q), 1)


def _fwd_kernel(u_ref, cum_ref, b_ref, c_ref, *refs, hb: int,
                save_states: bool):
    if save_states:
        y_ref, final_ref, states_ref, s_scr = refs
    else:
        y_ref, final_ref, s_scr = refs
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _start():
        s_scr[...] = jnp.zeros_like(s_scr)

    Bm, Cm = b_ref[0], c_ref[0]                           # (Q, N)
    mm = Bm.dtype
    G = _dot(Cm, Bm, _NT)                                 # (Q, Q)
    eye, lower, lane = _masks(G.shape[0])
    for h in range(hb):
        row = cum_ref[0, 0, h:h + 1, :]
        col, L = _decays(row, eye, lower)
        M = (G * L).astype(mm)
        U = u_ref[0, h]                                   # (Q, P)
        S = s_scr[h]                                      # (P, N) f32
        if save_states:
            states_ref[0, h, 0] = S
        y = _dot(M, U) + jnp.exp(col) * _dot(Cm, S.astype(mm), _NT)
        y_ref[0, h] = y.astype(y_ref.dtype)
        last = _last(row, lane)
        uw = (U.astype(jnp.float32) * jnp.exp(last - col)).astype(mm)
        s_scr[h] = jnp.exp(last) * S + _dot(uw, Bm, _TN)

    @pl.when(c == pl.num_programs(2) - 1)
    def _end():
        final_ref[0] = s_scr[...]


def _bwd_kernel(u_ref, cum_ref, b_ref, c_ref, dy_ref, states_ref,
                du_ref, dcum_ref, db_ref, dc_ref, ds_scr, *, hb: int):
    c = pl.program_id(2)          # the index maps run the chunks backwards

    @pl.when(c == 0)
    def _start():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    Bm, Cm = b_ref[0], c_ref[0]
    mm = Bm.dtype
    G = _dot(Cm, Bm, _NT)
    q = G.shape[0]
    eye, lower, lane = _masks(q)
    dG = jnp.zeros((q, q), jnp.float32)
    dB = jnp.zeros(Bm.shape, jnp.float32)
    dC = jnp.zeros(Cm.shape, jnp.float32)
    for h in range(hb):
        row = cum_ref[0, 0, h:h + 1, :]
        col, L = _decays(row, eye, lower)
        M = (G * L).astype(mm)
        U, dY = u_ref[0, h], dy_ref[0, h]                 # (Q, P)
        Uf, dYf = U.astype(jnp.float32), dY.astype(jnp.float32)
        S, dS = states_ref[0, h, 0], ds_scr[h]            # (P, N) f32
        Sb, dSb = S.astype(mm), dS.astype(mm)
        last = _last(row, lane)
        E, W, e_last = jnp.exp(col), jnp.exp(last - col), jnp.exp(last)

        dG = dG + _dot(dY, U, _NT) * L
        dU_state = W * _dot(Bm, dSb, _NT)                 # (Q, P)
        dU = _dot(M, dY, _TN) + dU_state
        du_ref[0, h] = dU.astype(du_ref.dtype)
        Y = _dot(M, U) + E * _dot(Cm, Sb, _NT)
        EdY = (E * dYf).astype(mm)
        dC = dC + _dot(EdY, Sb)
        dB = dB + _dot((W * Uf).astype(mm), dSb)
        # d cum_i = dy_i . y_i - du_i . u_i, and at the chunk's last
        # position what the state's decay and its weights add
        d_col = (jnp.sum(dYf * Y, axis=1, keepdims=True)
                 - jnp.sum(dU * Uf, axis=1, keepdims=True))
        d_row = jnp.sum(jnp.where(eye, jnp.broadcast_to(d_col, (q, q)),
                                  0.0), axis=0, keepdims=True)
        d_last = (e_last * jnp.sum(jnp.sum(dS * S, axis=1, keepdims=True),
                                   axis=0, keepdims=True)
                  + jnp.sum(jnp.sum(dU_state * Uf, axis=1, keepdims=True),
                            axis=0, keepdims=True))
        dcum_ref[0, 0, h:h + 1, :] = d_row + jnp.where(
            lane == q - 1, d_last, 0.0)
        ds_scr[h] = e_last * dS + _dot(EdY, Cm, _TN)
    dGb = dG.astype(mm)
    dc_ref[0, 0] = dC + _dot(dGb, Bm)
    db_ref[0, 0] = dB + _dot(dGb, Cm, _TN)


def _head_block(heads: int) -> int:
    return _HEAD_BLOCK if heads % _HEAD_BLOCK == 0 else heads


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _fwd_pallas(u, cum, B, C, *, save_states: bool, interpret: bool):
    b, H, s, P = u.shape
    nc, Q = cum.shape[1], cum.shape[3]
    N = B.shape[-1]
    hb = _head_block(H)
    out_shape = [jax.ShapeDtypeStruct((b, H, s, P), u.dtype),
                 jax.ShapeDtypeStruct((b, H, P, N), jnp.float32)]
    out_specs = [pl.BlockSpec((1, hb, Q, P), lambda i, g, c: (i, g, c, 0)),
                 pl.BlockSpec((1, hb, P, N), lambda i, g, c: (i, g, 0, 0))]
    if save_states:
        out_shape.append(
            jax.ShapeDtypeStruct((b, H, nc, P, N), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (1, hb, 1, P, N), lambda i, g, c: (i, g, c, 0, 0)))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, save_states=save_states),
        grid=(b, H // hb, nc),
        in_specs=[
            pl.BlockSpec((1, hb, Q, P), lambda i, g, c: (i, g, c, 0)),
            pl.BlockSpec((1, 1, hb, Q), lambda i, g, c: (i, c, g, 0)),
            pl.BlockSpec((1, Q, N), lambda i, g, c: (i, c, 0)),
            pl.BlockSpec((1, Q, N), lambda i, g, c: (i, c, 0)),
        ],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb, P, N), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name="ssd_fwd",
    )(u, cum, B, C)


def _bwd_pallas(u, cum, B, C, dy, states, *, interpret: bool):
    b, H, s, P = u.shape
    nc, Q = cum.shape[1], cum.shape[3]
    N = B.shape[-1]
    hb = _head_block(H)
    groups = H // hb

    def back(c):
        return nc - 1 - c

    du, dcum, dB, dC = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb),
        grid=(b, groups, nc),
        in_specs=[
            pl.BlockSpec((1, hb, Q, P), lambda i, g, c: (i, g, back(c), 0)),
            pl.BlockSpec((1, 1, hb, Q), lambda i, g, c: (i, back(c), g, 0)),
            pl.BlockSpec((1, Q, N), lambda i, g, c: (i, back(c), 0)),
            pl.BlockSpec((1, Q, N), lambda i, g, c: (i, back(c), 0)),
            pl.BlockSpec((1, hb, Q, P), lambda i, g, c: (i, g, back(c), 0)),
            pl.BlockSpec((1, hb, 1, P, N),
                         lambda i, g, c: (i, g, back(c), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, Q, P), lambda i, g, c: (i, g, back(c), 0)),
            pl.BlockSpec((1, 1, hb, Q), lambda i, g, c: (i, back(c), g, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda i, g, c: (i, g, back(c), 0)),
            pl.BlockSpec((1, 1, Q, N), lambda i, g, c: (i, g, back(c), 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, H, s, P), u.dtype),
            jax.ShapeDtypeStruct(cum.shape, jnp.float32),
            # B and C are shared by the heads: a head block's part each
            jax.ShapeDtypeStruct((b, groups, s, N), jnp.float32),
            jax.ShapeDtypeStruct((b, groups, s, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, P, N), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name="ssd_bwd",
    )(u, cum, B, C, dy, states)
    return (du, dcum, jnp.sum(dB, axis=1).astype(B.dtype),
            jnp.sum(dC, axis=1).astype(C.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _core_pallas(u, cum, B, C, interpret):
    return _fwd_pallas(u, cum, B, C, save_states=False,
                       interpret=interpret)


def _core_pallas_fwd(u, cum, B, C, interpret):
    y, final, states = _fwd_pallas(u, cum, B, C, save_states=True,
                                   interpret=interpret)
    return (y, final), (u, cum, B, C, states)


def _core_pallas_bwd(interpret, res, cts):
    """The final state is a reading (a counter's), not a path of the
    loss: its cotangent is not followed."""
    u, cum, B, C, states = res
    return _bwd_pallas(u, cum, B, C, cts[0].astype(u.dtype), states,
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
    u = (xp.astype(jnp.float32) * dtp[..., None]).astype(x.dtype)
    u = u.transpose(0, 2, 1, 3)                          # (b, H, s, P)
    if resolve_impl(impl) == "pallas":
        if interpret is None:
            interpret = _auto_interpret()
        y, state = _core_pallas(u, cum, Bp, Cp, bool(interpret))
    else:
        y, state = _core_jnp(u, cum, Bp, Cp)
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
