"""Ulysses-style sequence parallelism: all-to-all head scatter.

The alternative SP strategy (SURVEY §2.4): instead of rotating KV
around a ring, re-shard with two ``all_to_all``s — gather the full
sequence while scattering heads, run ordinary full attention on
``heads / sp`` local heads, then reverse. Communication volume is
O(seq·hidden / sp) per all-to-all (cheaper than ring for moderate
sequences; ring wins when seq >> devices·heads or memory forbids
materializing full seq).

Used inside ``shard_map``; :func:`ulysses_attention_sharded` is the
pjit-level wrapper.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from learningorchestra_tpu.parallel import ring as ring_lib
from learningorchestra_tpu.runtime import mesh as mesh_lib


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str = mesh_lib.SP,
                      causal: bool = False, window: int = 0,
                      scale: Optional[float] = None,
                      attn_fn: Optional[Callable] = None) -> jax.Array:
    """Inside shard_map: q local shard (b, seq_local, heads, d); k/v
    may carry FEWER (kv) heads (GQA) — both head counts must divide
    the axis size, and the head scatter then moves kv-width K/V
    (n-fold less all_to_all traffic than repeating first). Returns
    the local output shard (b, seq_local, heads, d)."""
    n = lax.psum(1, axis_name)
    h, kvh = q.shape[2], k.shape[2]
    if h % n:
        raise ValueError(f"heads {h} not divisible by sp={n}")
    if kvh != h and (h % kvh or kvh % n):
        raise ValueError(
            f"GQA kv heads {kvh} must divide query heads {h} and be "
            f"divisible by sp={n} (repeat K/V to full heads "
            f"otherwise)")
    if attn_fn is None:
        if _flash_local():
            # local attention over the gathered sequence runs the
            # fused flash kernel — O(block) memory for the full-seq
            # score rows instead of a dense (s, s) tile per head;
            # grouped K/V consumed natively
            from learningorchestra_tpu.ops import attention as attn_ops

            attn_fn = functools.partial(attn_ops.flash_attention,
                                        causal=causal, scale=scale,
                                        window=window)
        else:
            def attn_fn(ql, kl, vl):
                if kl.shape[2] != ql.shape[2]:
                    g = ql.shape[2] // kl.shape[2]
                    kl = jnp.repeat(kl, g, axis=2)
                    vl = jnp.repeat(vl, g, axis=2)
                return ring_lib.full_attention_reference(
                    ql, kl, vl, causal=causal, window=window,
                    scale=scale)

    def scatter_heads(x):  # (b, s/n, h, d) -> (b, s, h/n, d)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def gather_heads(x):  # (b, s, h/n, d) -> (b, s/n, h, d)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    out = attn_fn(scatter_heads(q), scatter_heads(k), scatter_heads(v))
    return gather_heads(out)


def _flash_local() -> bool:
    """The local full-sequence attention runs the Pallas flash kernel
    on every backend but the CPU (same rule as the kernel's own
    interpret switch, ops/attention.py): the kernel compiles or the
    failure propagates — nothing gives way to the dense path."""
    return jax.default_backend() != "cpu"


def ulysses_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                              mesh: Mesh, causal: bool = False,
                              window: int = 0,
                              scale: Optional[float] = None) -> jax.Array:
    if mesh_lib.SP not in mesh.axis_names:
        raise ValueError("mesh has no 'sp' axis")
    data = mesh_lib.data_axes(mesh)
    spec = P(data if data else None, mesh_lib.SP, None, None)
    # pallas_call emits ShapeDtypeStructs with no varying-mesh-axes
    # info, which the vma checker rejects (same as ring's flash hops)
    extra = {"check_vma": False} if _flash_local() else {}
    fn = mesh_lib.shard_map(
        functools.partial(ulysses_attention, axis_name=mesh_lib.SP,
                          causal=causal, scale=scale, window=window),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, **extra)
    return fn(q, k, v)
