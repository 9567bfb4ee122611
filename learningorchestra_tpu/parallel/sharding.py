"""Parameter/activation sharding rules (DP / FSDP / TP).

The scaling-book recipe: pick a mesh, annotate shardings on params and
batch, let GSPMD insert the collectives. Rules here are (path-regex →
PartitionSpec) pairs matched against flax param paths like
``"decoder/layer_3/attn/q_proj/kernel"``; first match wins. FSDP is a
fallback rule that shards the largest divisible axis of any still-
replicated tensor over the ``fsdp`` axis (ZeRO-3-style, gathered by
XLA just-in-time per layer).
"""

from __future__ import annotations

import re
from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from learningorchestra_tpu.runtime import mesh as mesh_lib

Rule = Tuple[str, P]


# TP rules for the transformer family (models/transformer.py naming):
# column-parallel in-projections, row-parallel out-projections —
# activations stay sharded on heads between the two, so the only
# collective per block is one reduce-scatter/all-gather pair inserted
# by XLA.
TRANSFORMER_RULES: Sequence[Rule] = (
    # qkv_proj/gate_up are the fused-projection layouts; under tp > 1
    # the model's _param_rules prepends a replicate override for them
    # (a column shard would cross the concatenation's block
    # boundaries), so their TP entry here serves meshes without tp
    (r".*(q_proj|k_proj|v_proj|qkv_proj|wi|gate|gate_up|up_proj)"
     r"/kernel$",
     P(None, mesh_lib.TP)),
    (r".*(o_proj|wo|down_proj)/kernel$", P(mesh_lib.TP, None)),
    (r".*embed/embedding$", P(None, mesh_lib.TP)),
    (r".*lm_head/kernel$", P(None, mesh_lib.TP)),
    (r".*experts/(w_gate|w_up)$", P(mesh_lib.EP, None, mesh_lib.TP)),
    (r".*experts/w_down$", P(mesh_lib.EP, mesh_lib.TP, None)),
    (r".*(bias|scale)$", P()),
)


def _path_str(path) -> str:
    parts = []
    for key in path:
        name = getattr(key, "key", None) or getattr(key, "name", None) \
            or getattr(key, "idx", None)
        parts.append(str(name))
    return "/".join(parts)


def _axes_in_mesh(spec: P, mesh: Mesh) -> P:
    """Drop rule axes the mesh doesn't have (so one rule set serves
    every mesh shape; a missing axis just means replicated there)."""
    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in mesh.axis_names
                         and mesh.shape[a] > 1)
            return kept if kept else None
        return entry if entry in mesh.axis_names and \
            mesh.shape[entry] > 1 else None

    return P(*(keep(e) for e in spec))


def _fsdp_spec(shape: Tuple[int, ...], base: P, mesh: Mesh) -> P:
    """Extend ``base`` by sharding the largest unsharded divisible dim
    over the fsdp axis."""
    if mesh_lib.FSDP not in mesh.axis_names or \
            mesh.shape[mesh_lib.FSDP] <= 1:
        return base
    fsdp_size = mesh.shape[mesh_lib.FSDP]
    entries = list(base) + [None] * (len(shape) - len(base))
    candidates = [(dim, i) for i, (dim, e) in enumerate(zip(shape, entries))
                  if e is None and dim % fsdp_size == 0 and dim >= fsdp_size]
    if not candidates:
        return base
    _, idx = max(candidates)
    entries[idx] = mesh_lib.FSDP
    return P(*entries)


def _axes_size(entry, mesh: Mesh) -> int:
    """Total device count of a PartitionSpec entry (axis name or
    tuple of names)."""
    axes = entry if isinstance(entry, (tuple, list)) else (entry,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def _drop_non_divisible(base: P, shape: Tuple[int, ...],
                        mesh: Mesh) -> P:
    """Replicate (instead of erroring) any rule-sharded dim the mesh
    axis doesn't divide — e.g. an MQA k_proj whose single-head output
    column is narrower than the tp axis."""
    entries = []
    for i, entry in enumerate(base):
        if entry is not None and i < len(shape) and \
                shape[i] % _axes_size(entry, mesh):
            entry = None
        entries.append(entry)
    return P(*entries)


def spec_for(path: str, shape: Tuple[int, ...], mesh: Mesh,
             rules: Sequence[Rule] = TRANSFORMER_RULES,
             fsdp: bool = True) -> P:
    base = P()
    for pattern, spec in rules:
        if re.match(pattern, path):
            base = _drop_non_divisible(
                _axes_in_mesh(spec, mesh), shape, mesh)
            break
    return _fsdp_spec(shape, base, mesh) if fsdp else base


def param_shardings(params: Any, mesh: Mesh,
                    rules: Sequence[Rule] = TRANSFORMER_RULES,
                    fsdp: bool = True) -> Any:
    """NamedSharding pytree matching ``params`` (use as
    ``in_shardings``/``device_put`` target)."""
    def leaf_sharding(path, leaf):
        shape = getattr(leaf, "shape", ())
        spec = spec_for(_path_str(path), tuple(shape), mesh, rules, fsdp)
        if len(spec) > len(shape):  # rule wider than tensor: replicate
            spec = P()
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf_sharding, params)


def shard_params(params: Any, mesh: Mesh,
                 rules: Sequence[Rule] = TRANSFORMER_RULES,
                 fsdp: bool = True) -> Any:
    return jax.device_put(params, param_shardings(params, mesh, rules, fsdp))


def batch_spec(mesh: Mesh, seq_axis: bool = False) -> P:
    """Batch activations: batch dim over (dp, fsdp), optionally the
    sequence dim over sp."""
    data = mesh_lib.data_axes(mesh)
    first = data if data else None
    if seq_axis and mesh_lib.SP in mesh.axis_names and \
            mesh.shape[mesh_lib.SP] > 1:
        return P(first, mesh_lib.SP)
    return P(first)


def config_axis_spec(mesh: Mesh, n_configs: int) -> P:
    """PartitionSpec for the leading config axis of a fused sweep
    (docs/PERFORMANCE.md "Sweep fusion"): shard it over the data axes
    when the cohort size divides them — GSPMD then places each config's
    params/opt_state on its own device group, the same trick the batch
    axis uses — else replicate (small cohorts still win by sharing one
    compile)."""
    data = mesh_lib.data_axes(mesh)
    if not data:
        return P()
    size = 1
    for a in data:
        size *= mesh.shape[a]
    if size > 1 and n_configs % size == 0:
        return P(data)
    return P()


def fused_state_shardings(state: Any, mesh: Mesh, n_configs: int) -> Any:
    """NamedSharding pytree for config-stacked train state: every leaf
    whose leading dim is the config axis gets ``config_axis_spec``;
    scalars (the step counter, optimizer counts that vmap left
    unstacked) stay replicated."""
    spec = config_axis_spec(mesh, n_configs)

    def leaf_sharding(leaf):
        shape = getattr(leaf, "shape", ())
        if len(shape) >= 1 and shape[0] == n_configs:
            return NamedSharding(mesh, spec)
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(leaf_sharding, state)


def constrain(x, mesh: Mesh, *spec_entries) -> Any:
    """``with_sharding_constraint`` shorthand that tolerates axes
    missing from the mesh and dims the axis size doesn't divide (e.g.
    the 1-sample trace during param init)."""
    spec = _axes_in_mesh(P(*spec_entries), mesh)

    entries = [e if (e is not None and d % _axes_size(e, mesh) == 0)
               else None
               for e, d in zip(spec, x.shape)]
    spec = P(*entries)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
