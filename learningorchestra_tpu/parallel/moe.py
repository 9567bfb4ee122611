"""Mixture-of-experts: gated (SwiGLU) experts behind a softmax router.

``moe_layer`` routes every token over ALL ``n_experts`` (float32
softmax, top-k, renormalised over the k) and computes the part of the
result that the experts HELD here give: ``experts_held`` of them, from
``expert_offset`` on (all of them by default). What the absent experts
would add is left out: that is one expert-parallel rank's share of the
layer, and the shares of all ranks add up to the whole layer
(tests/test_parallel.py). No token is dropped: there is no capacity.

The held experts' products run as ONE grouped product
(``ops/grouped_matmul.py``): the routed copies are sorted by expert
(:func:`grouped_layout`), each expert's run padded to whole row tiles,
gathered, multiplied (gate, up, down) and gathered back weighted. The
row buffer is static and holds the worst case (every choice of every
token held here), so every copy is computed whatever the router does.
The layout puts every copy in the buffer's first ``n_active`` tiles,
and nothing that touches the buffer goes past them. The products skip
the tiles that hold no copy. The passes on the buffer's side are loops
over chunks of whole tiles whose trip count is the used prefix, forward
and backward (written by hand: a ``while_loop`` has no reverse mode):
the gather into the buffer (:func:`dispatch`), ``silu(gate) * up``
between the products (:func:`gated`) and the backward of the weighted
sum out of it (:func:`combine`). The rows past the last used chunk are
never written and never read, so these passes cost what the copies
cost, by a chunk at a time; when every choice of every token is held
they run over the whole buffer. What lands on the tokens is read, not
added (a scatter-add costs a fixed time for every row that lands, 0.3
us on a v5e: PERF.md section 5): the layout gives each copy its row
(``pos``); the combine and the dispatch's backward sum each token's
rows in ``ops/row_gather.py``'s kernel, which reads the rows that hold
copies and no others; the gradient of the router's weights reads a
number a row by ``pos``; the router's top-k puts its gradient back by
a compare (:func:`top_k`). No scatter, forward or backward. One path:
no second buffer, no ``cond``.

On a mesh whose ``ep`` axis is larger than 1 the older schedule stays
until a four-chip cell measures its replacement (ROADMAP S17): experts
sharded over ``ep``, every expert padded to a shared capacity
(``capacity_factor``), copies past it DROPPED, the exchange left to
GSPMD. Same router, same gated experts.

Parameters (stacked experts, shardable by sharding.TRANSFORMER_RULES):
  ``gate``             (d_model, n_experts)   the router, replicated
  ``experts/w_gate``   (experts_held, d_model, d_ff)
  ``experts/w_up``     (experts_held, d_model, d_ff)
  ``experts/w_down``   (experts_held, d_ff, d_model)
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from learningorchestra_tpu.ops import grouped_matmul as gmm_ops
from learningorchestra_tpu.ops import row_gather
from learningorchestra_tpu.parallel import sharding as sharding_lib
from learningorchestra_tpu.runtime import mesh as mesh_lib


def init_moe_params(rng, d_model: int, d_ff: int, n_experts: int,
                    experts_held: int = 0, dtype=jnp.float32,
                    ) -> Dict[str, Any]:
    held = experts_held or n_experts
    kr, kg, ku, kd = jax.random.split(rng, 4)
    scale_in = 1.0 / math.sqrt(d_model)
    scale_out = 1.0 / math.sqrt(d_ff)

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape) * scale).astype(dtype)

    return {
        "gate": normal(kr, (d_model, n_experts), scale_in),
        "experts": {
            "w_gate": normal(kg, (held, d_model, d_ff), scale_in),
            "w_up": normal(ku, (held, d_model, d_ff), scale_in),
            "w_down": normal(kd, (held, d_ff, d_model), scale_out),
        },
    }


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def top_k(probs: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """``lax.top_k``, whose backward puts each value's gradient on its
    expert by comparing ``idx`` with every expert (T x E x k compares):
    ``lax.top_k``'s own is a scatter-add of every choice."""
    vals, idx = jax.lax.top_k(probs, k)
    return vals, idx


def _top_k_fwd(probs, k):
    vals, idx = jax.lax.top_k(probs, k)
    return (vals, idx), (probs, idx)


def _top_k_bwd(k, res, cot):
    probs, idx = res
    dvals = cot[0]
    with jax.named_scope("moe/route"):
        experts = jnp.arange(probs.shape[-1], dtype=idx.dtype)
        dprobs = jnp.zeros_like(probs)
        for j in range(k):
            dprobs = dprobs + jnp.where(idx[..., j, None] == experts,
                                        dvals[..., j, None], 0)
    return (dprobs,)


top_k.defvjp(_top_k_fwd, _top_k_bwd)


def route(logits: jax.Array, k: int, scale: float = 1.0,
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The router's head: float32 softmax over all experts, the k
    largest, renormalised over the k and times ``scale`` (the routed
    scaling factor: 2.5 in the Laguna family), and the Switch
    load-balancing term (``E * mean(share of first choices * mean
    probability)``). Returns (idx (T, k), weights (T, k) float32,
    aux)."""
    e = logits.shape[-1]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vals, idx = top_k(probs, k)
    vals = vals / jnp.maximum(jnp.sum(vals, axis=-1, keepdims=True), 1e-9)
    if scale != 1.0:
        vals = vals * jnp.float32(scale)
    top1 = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.mean(jnp.mean(top1, axis=0) * jnp.mean(probs, axis=0))
    return idx, vals, aux


def held_counts(idx: jax.Array, held: int, offset: int) -> jax.Array:
    """(held,) int32: the routed copies each held expert received. A
    compare and a sum: ``bincount`` is a scatter of every copy (two of
    them made the layout 2.07 ms where the sort of all copies is 0.27,
    at 131,072 copies on a v5e: PERF.md section 5, PR 26)."""
    local = idx.reshape(-1, 1) - offset
    return jnp.sum(local == jnp.arange(held), axis=0, dtype=jnp.int32)


def held_group(idx: jax.Array, held: int, offset: int) -> jax.Array:
    """The held expert of each routed copy, ``held`` for one not held
    here (``idx``'s shape)."""
    local = idx - offset
    return jnp.where((local >= 0) & (local < held), local, held)


def tiles_needed(counts: jax.Array, tile_m: int) -> jax.Array:
    """Row tiles of each group: its copies in whole tiles, one at
    least (the grouped product writes every group's gradient block)."""
    return jnp.maximum(-(-counts // tile_m), 1)


def grouped_layout(idx: jax.Array, held: int, offset: int, rows: int,
                   tile_m: int, counts: Optional[jax.Array] = None):
    """Where each routed copy goes in a buffer of ``rows`` rows sorted
    by held expert, every expert's run starting on a tile edge.

    Returns ``(src, valid, tile_group, n_active, pos)``: for each row
    the flat index ``t * k + choice`` of the copy it holds and whether
    it holds one; the held expert of each row tile (non-decreasing;
    past the used tiles the last expert again); the number of used
    tiles, (1,) int32; for each copy (``pos`` (T, k) int32) the row
    that holds it, ``rows`` (past the buffer) for a copy not held here,
    so ``src[pos[t, j]] == t * k + j`` for every held copy. Copies keep
    token order within an expert. The caller sees to it that they fit
    (``tiles_needed`` summed, against ``rows // tile_m``)."""
    n_copies = idx.size
    key = held_group(idx, held, offset).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    if counts is None:
        counts = held_counts(idx, held, offset)
    starts = jnp.cumsum(counts) - counts
    tiles = tiles_needed(counts, tile_m)
    tile_ends = jnp.cumsum(tiles)
    tile_starts = tile_ends - tiles
    # a copy's row is its place in the sort (the inverse of ``order``,
    # a second sort: its scatter would be one) moved by its expert's
    # shift, found by a compare as ``held_counts`` counts
    shift = tile_starts * tile_m - starts
    mine = key[:, None] == jnp.arange(held)
    pos = jnp.where(key < held,
                    jnp.argsort(order).astype(jnp.int32)
                    + jnp.sum(jnp.where(mine, shift, 0), axis=1), rows)
    n_tiles = rows // tile_m
    tile = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_ends, tile, side="right"),
        held - 1).astype(jnp.int32)
    # what a tile's rows share is looked up a tile and spread over its
    # rows: a lookup a row is a millisecond at 135,168 rows on a v5e
    within = ((tile - tile_starts[tile_group]) * tile_m)[:, None] \
        + jnp.arange(tile_m, dtype=jnp.int32)
    valid = (within < counts[tile_group][:, None]) \
        & (tile < tile_ends[-1])[:, None]
    src = order[jnp.clip(starts[tile_group][:, None] + within, 0,
                         n_copies - 1)]
    return (src.reshape(rows), valid.reshape(rows), tile_group,
            tile_ends[-1:].astype(jnp.int32),
            pos.astype(jnp.int32).reshape(idx.shape))


def _auto_tile(copies_per_expert: float) -> int:
    """Rows a tile: 256 where an expert sees that many copies (the
    padding of a run's last tile is then an eighth of 1,024 copies),
    the largest power of two under the expected copies otherwise, 8 at
    least (the sublane tiling)."""
    tile = 8
    while tile < 256 and tile * 2 <= copies_per_expert:
        tile *= 2
    return tile


# rows of the buffer a trip of the loops covers (8 tiles of 256 rows):
# half a chunk of a pass holds no copy on average, and a trip has a
# cost of its own; 1,024 to 4,096 read within 6% of each other on a v5e
# (PERF.md section 6, PR 27)
_CHUNK_ROWS = 2048


def buffer_shape(t: int, k: int, n_experts: int, held: int,
                 ) -> Tuple[int, int, int]:
    """``(tile_m, rows, chunk)`` of the row buffer of ``t`` tokens: every
    choice of every token in whole tiles and each held expert's last
    tile (an empty expert's one), in whole chunks (a buffer under one
    chunk is one chunk)."""
    tile_m = _auto_tile(t * k / n_experts)
    rows = -(-t * min(k, held) // tile_m) * tile_m + held * tile_m
    chunk = min(rows, _CHUNK_ROWS)
    return tile_m, -(-rows // chunk) * chunk, chunk


def tiles_used(counts: jax.Array, t: int, k: int, n_experts: int,
               ) -> jax.Array:
    """() int32: the tiles of the row buffer that hold the copies of
    ``counts`` (held,), ``grouped_layout``'s ``n_active``: where the
    passes over the buffer end, by a chunk."""
    with jax.named_scope("moe/route"):
        return jnp.sum(tiles_needed(
            counts, _auto_tile(t * k / n_experts))).astype(jnp.int32)


def _over_used_chunks(body: Callable, init, n_active: jax.Array,
                      tile_m: int, chunk: int):
    """``carry = body(rows_at, carry)`` for each chunk of ``chunk`` rows
    that holds any of the first ``n_active`` tiles; ``rows_at(a)`` is
    the chunk's rows of a buffer-long ``a``, ``rows_at(a, new)`` is
    ``a`` with them replaced."""
    def trip(i, carry):
        def rows_at(a, new=None):
            if new is None:
                return jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk)
            return jax.lax.dynamic_update_slice_in_dim(
                a, new.astype(a.dtype), i * chunk, 0)

        return body(rows_at, carry)

    return jax.lax.fori_loop(0, -(-n_active[0] * tile_m // chunk), trip,
                             init)


def _take(table: jax.Array, index: jax.Array) -> jax.Array:
    """``table[index]``, zero where ``index`` is past the table."""
    return jnp.take(table, index, axis=0, mode="fill", fill_value=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 5, 6))
def dispatch(tokens: jax.Array, copy: jax.Array, runs: row_gather.Runs,
             k: int, n_active: jax.Array, tile_m: int, chunk: int,
             ) -> Tuple[jax.Array, jax.Array]:
    """The row buffer ``xs (rows, d)``: ``xs[r] = tokens[copy[r] // k]``
    (``copy[r]``: the routed copy ``token * k + choice`` the row holds,
    ``t * k`` and a row of zeros for none), for the rows of the used
    chunks; the rows past them are not written (zero). ``chunk``
    divides ``rows``. Given TWICE, once for each product that reads
    it: their two gradients then come back apart and are summed a chunk
    at a time, where one buffer's would be summed over all of it.
    Backward, each token sums its copies' rows (``runs``: where they
    lie, ``row_gather.runs`` of :func:`grouped_layout`'s ``pos``)."""
    def body(rows_at, xs):
        return rows_at(xs, _take(tokens, rows_at(copy) // k))

    xs = _over_used_chunks(
        body, jnp.zeros((copy.shape[0], tokens.shape[1]), tokens.dtype),
        n_active, tile_m, chunk)
    return xs, xs


def _dispatch_fwd(tokens, copy, runs, k, n_active, tile_m, chunk):
    return dispatch(tokens, copy, runs, k, n_active, tile_m, chunk), \
        (runs, n_active)


def _dispatch_bwd(k, tile_m, chunk, res, dxs):
    runs, n_active = res
    with jax.named_scope("moe/experts"):
        # the two gradients summed into the first, a used chunk at a
        # time; each token's copies then summed in float32 and rounded
        # once to the tokens' type
        def body(rows_at, d):
            return rows_at(d, rows_at(d) + rows_at(dxs[1]))

        d = _over_used_chunks(body, dxs[0], n_active, tile_m, chunk)
        dtokens = row_gather.rows_summed(d, runs).astype(dxs[0].dtype)
    return dtokens, None, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _gate(g: jax.Array, u: jax.Array) -> jax.Array:
    return jax.nn.silu(g) * u


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gated(g: jax.Array, u: jax.Array, n_active: jax.Array, tile_m: int,
          chunk: int) -> jax.Array:
    """``silu(g) * u`` for the rows of the used chunks, zero past
    them."""
    def body(rows_at, h):
        return rows_at(h, _gate(rows_at(g), rows_at(u)))

    return _over_used_chunks(body, jnp.zeros_like(g), n_active, tile_m,
                             chunk)


def _gated_fwd(g, u, n_active, tile_m, chunk):
    return gated(g, u, n_active, tile_m, chunk), (g, u, n_active)


def _gated_bwd(tile_m, chunk, res, dh):
    g, u, n_active = res
    with jax.named_scope("moe/experts"):
        # the gradients start as ``g`` and ``u`` themselves: a chunk of
        # them is read where its gradient is then written, so the loop
        # fills no buffer of its own; past the used chunks they are
        # what the products wrote there, zero
        def body(rows_at, carry):
            dg, du = carry
            dg_rows, du_rows = jax.vjp(_gate, rows_at(dg), rows_at(du))[1](
                rows_at(dh))
            return rows_at(dg, dg_rows), rows_at(du, du_rows)

        dg, du = _over_used_chunks(body, (g, u), n_active, tile_m, chunk)
    return dg, du, None


gated.defvjp(_gated_fwd, _gated_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def combine(ys: jax.Array, weights: jax.Array, copy: jax.Array,
            pos: jax.Array, runs: row_gather.Runs, n_active: jax.Array,
            tile_m: int, chunk: int) -> jax.Array:
    """``out (t, d)`` float32: ``out[t] = sum over j of weights[t, j] *
    ys[pos[t, j]]`` (``weights (t, k)``; a ``pos`` past the buffer reads
    zero), by ``runs`` (``row_gather.runs`` of ``pos``). Backward,
    ``copy`` (as :func:`dispatch`'s) gives each row of the used chunks
    its token's gradient."""
    return row_gather.rows_summed(ys, runs, weights)


def _combine_fwd(ys, weights, copy, pos, runs, n_active, tile_m, chunk):
    return combine(ys, weights, copy, pos, runs, n_active, tile_m, chunk), \
        (ys, weights, copy, pos, n_active)


def _combine_bwd(tile_m, chunk, res, dout):
    ys, weights, copy, pos, n_active = res
    k = weights.shape[1]
    flat = weights.reshape(-1)
    with jax.named_scope("moe/combine"):
        # the weights' gradient is a number a row, ``<dout[t], ys[r]>``,
        # read back by each copy's row
        def body(rows_at, carry):
            dys, dot = carry
            c = rows_at(copy)
            d = _take(dout, c // k)
            return (rows_at(dys, d * _take(flat, c)[:, None]),
                    rows_at(dot, jnp.sum(
                        d * rows_at(ys).astype(jnp.float32), axis=-1)))

        # ``dys`` does not start as ``ys`` the way ``gated``'s gradients
        # start as its inputs: the chunk's read (for ``dot``) and its
        # write are two fusions here, and in a whole step XLA then
        # copied the buffer twice a trip to keep them apart (PERF.md
        # section 6, PR 27)
        dys, dot = _over_used_chunks(
            body, (jnp.zeros_like(ys),
                   jnp.zeros(ys.shape[:1], jnp.float32)),
            n_active, tile_m, chunk)
        dweights = _take(dot, pos)
    return dys, dweights.astype(weights.dtype), None, None, None, None


combine.defvjp(_combine_fwd, _combine_bwd)


def _held_experts(experts: Dict[str, Any], tokens: jax.Array,
                  idx: jax.Array, weights: jax.Array, counts: jax.Array,
                  offset: int, rows: int, tile_m: int, chunk: int,
                  ) -> jax.Array:
    """The held experts' weighted outputs summed per token, float32
    (T, d), through a buffer of ``rows`` rows in chunks of ``chunk``."""
    t, k = idx.shape
    held = experts["w_gate"].shape[0]
    with jax.named_scope("moe/route"):
        src, valid, tile_group, n_active, pos = grouped_layout(
            idx, held, offset, rows, tile_m, counts)
        # a row that holds no copy reads past the copies: zeros
        copy = jnp.where(valid, src, t * k)
        runs = row_gather.runs(pos, held_group(idx, held, offset), held,
                               rows)
    passes = (n_active, tile_m, chunk)
    product = lambda a, w: gmm_ops.grouped_matmul(  # noqa: E731
        a, w, tile_group, n_active, tile_m=tile_m)
    with jax.named_scope("moe/experts"):
        xs_gate, xs_up = dispatch(tokens, copy, runs, k, *passes)
        h = gated(product(xs_gate, experts["w_gate"]),
                  product(xs_up, experts["w_up"]), *passes)
        ys = product(h, experts["w_down"])
    with jax.named_scope("moe/combine"):
        return combine(ys, weights, copy, pos, runs, *passes)


def sparse_route(gate_idx: jax.Array, gate_vals: jax.Array, e: int,
                 capacity: int,
                 ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sort/segment routing plan of the ``ep`` schedule.

    Returns ``(tok, slot, keep, w)``, each (T·k,), in expert-sorted
    order: ``tok`` is each kept copy's source token, ``slot`` its flat
    index into the (E·C, d) expert buffer, ``keep`` the capacity mask,
    ``w`` the gate weight. Stable choice-major sort: earlier choices
    win capacity, then token order."""
    t, k = gate_idx.shape
    flat_e = gate_idx.T.reshape(-1)           # (k·T,) choice-major
    flat_w = gate_vals.T.reshape(-1)
    flat_tok = jnp.tile(jnp.arange(t, dtype=jnp.int32), k)
    order = jnp.argsort(flat_e, stable=True)  # choice/token priority
    se = flat_e[order]
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(k * t, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    keep = pos < capacity
    slot = se * capacity + jnp.clip(pos, 0, capacity - 1)
    return flat_tok[order], slot, keep, flat_w[order]


def capacity_experts(experts: Dict[str, Any], tokens: jax.Array,
                     idx: jax.Array, weights: jax.Array, *,
                     capacity_factor: float = 1.25,
                     mesh: Optional[Mesh] = None) -> jax.Array:
    """The ``ep`` schedule: every expert padded to a shared capacity,
    copies past it dropped; with ``mesh`` the expert-stacked buffers
    are constrained to ``ep`` and GSPMD makes the exchange."""
    t, k = idx.shape
    d = tokens.shape[1]
    e = experts["w_gate"].shape[0]
    capacity = max(1, int(capacity_factor * k * t / e))
    tok, slot, keep, w = sparse_route(idx, weights, e, capacity)
    buf = jnp.zeros((e * capacity, d), tokens.dtype)
    expert_in = buf.at[slot].add(
        tokens[tok] * keep[:, None].astype(tokens.dtype)
    ).reshape(e, capacity, d)
    if mesh is not None:
        expert_in = sharding_lib.constrain(
            expert_in, mesh, mesh_lib.EP, None, None)

    def product(a, kernel, spec):
        return jnp.einsum(spec, a, kernel.astype(tokens.dtype),
                          preferred_element_type=jnp.float32)

    h = (jax.nn.silu(product(expert_in, experts["w_gate"], "ecd,edf->ecf"))
         * product(expert_in, experts["w_up"], "ecd,edf->ecf")
         ).astype(tokens.dtype)
    expert_out = product(h, experts["w_down"], "ecf,efd->ecd")
    if mesh is not None:
        expert_out = sharding_lib.constrain(
            expert_out.astype(tokens.dtype), mesh, mesh_lib.EP, None, None)
    copies = expert_out.astype(jnp.float32).reshape(e * capacity, d)[slot]
    copies = copies * (w * keep.astype(jnp.float32))[:, None]
    return jnp.zeros((t, d), jnp.float32).at[tok].add(copies)


def moe_layer(params: Dict[str, Any], x: jax.Array, *, k: int = 2,
              expert_offset: int = 0, mesh: Optional[Mesh] = None,
              capacity_factor: float = 1.25, routed_scale: float = 1.0,
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (..., d_model) -> (same shape, aux, counts).

    ``counts`` (held,) int32: the routed copies each held expert
    received this call (a counter: no gradient). ``mesh`` selects the
    ``ep`` schedule (module docstring), which holds every expert.
    ``routed_scale`` multiplies the renormalised weights (``route``)."""
    orig_shape = x.shape
    d = orig_shape[-1]
    tokens = x.reshape(-1, d)
    t = tokens.shape[0]
    experts = params["experts"]
    n_experts = params["gate"].shape[-1]
    held = experts["w_gate"].shape[0]
    if expert_offset < 0 or expert_offset + held > n_experts:
        raise ValueError(
            f"experts {expert_offset}..{expert_offset + held - 1} are not "
            f"among the router's {n_experts}")
    with jax.named_scope("moe/route"):
        logits = jnp.dot(tokens, params["gate"].astype(tokens.dtype),
                         preferred_element_type=jnp.float32)
        idx, weights, aux = route(logits, k, routed_scale)
        counts = jax.lax.stop_gradient(
            held_counts(idx, held, expert_offset))
    if mesh is not None:
        if held != n_experts:
            raise ValueError("the ep schedule holds every expert: "
                             f"{held} of {n_experts} held")
        out = capacity_experts(experts, tokens, idx, weights,
                               capacity_factor=capacity_factor, mesh=mesh)
        return out.reshape(orig_shape).astype(x.dtype), aux, counts

    tile_m, rows, chunk = buffer_shape(t, k, n_experts, held)
    out = _held_experts(experts, tokens, idx, weights, counts,
                        expert_offset, rows, tile_m, chunk)
    return out.reshape(orig_shape).astype(x.dtype), aux, counts
