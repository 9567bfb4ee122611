"""Device-mesh manager.

Axis-name conventions (scaling-book style), used consistently by the
parallelism library and every sharded engine:

==========  =====================================================
axis        meaning
==========  =====================================================
``dcn``     cross-slice data parallel (OUTERMOST axis; spans pod
            slices over the data-center network — only the gradient
            all-reduce crosses it, everything else stays in-slice)
``dp``      data parallel (batch dim; gradients all-reduced)
``fsdp``    fully-sharded data parallel (params sharded over it too)
``tp``      tensor parallel (weight matrices split; activations
            all-gathered / reduce-scattered by XLA)
``pp``      pipeline parallel (layer stages; shard_map + ppermute)
``sp``      sequence/context parallel (ring attention over seq dim)
``ep``      expert parallel (MoE experts)
==========  =====================================================

Multi-slice discipline (SURVEY §2.5; scaling-book): DCN bandwidth is
orders of magnitude below ICI, so ``dcn`` carries ONLY per-step
gradient all-reduces (weight-update cost, overlappable); params and
optimizer state replicate across slices and every tp/sp/ep/pp
collective stays inside a slice. ``build_mesh`` enforces dcn
outermost so device order maps slice boundaries to the dcn axis.

The reference has no device concept at all — its "cluster" is Docker
Swarm placement (SURVEY §2.4). Here the mesh is the cluster.
"""

from __future__ import annotations

import contextlib
import re
import threading
from typing import Dict, Iterator, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DCN, DP, FSDP, TP, PP, SP, EP = \
    "dcn", "dp", "fsdp", "tp", "pp", "sp", "ep"
KNOWN_AXES = (DCN, DP, FSDP, TP, PP, SP, EP)

# plain aliases of the installed JAX's own names, kept so callers need
# not change (always pass ``check_vma``)
shard_map = jax.shard_map
pcast = jax.lax.pcast
typeof = jax.typeof


def parse_mesh_spec(spec: str) -> Dict[str, int]:
    """Parse ``"dp=2,tp=4"`` into an ordered axis->size dict."""
    out: Dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        m = re.fullmatch(r"([a-z_]+)\s*=\s*(-?\d+)", part)
        if not m:
            raise ValueError(f"bad mesh spec element: {part!r}")
        out[m.group(1)] = int(m.group(2))
    if not out:
        raise ValueError(f"empty mesh spec: {spec!r}")
    return out


def build_mesh(spec: str = "auto",
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build the global mesh.

    ``"auto"`` = 1-D data-parallel over all devices. An explicit spec
    like ``"dp=2,tp=4"`` may leave one axis as ``-1`` to absorb the
    remaining devices.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    # AxisType.Auto = classic GSPMD propagation: we annotate inputs /
    # outputs, XLA infers internals and inserts collectives. (JAX
    # defaults to Explicit, which demands out_shardings on every
    # ambiguous gather/scatter — wrong trade-off for a framework that
    # runs arbitrary user models.)
    def make(shapes, names, devs):
        return jax.make_mesh(
            shapes, names, (jax.sharding.AxisType.Auto,) * len(names),
            devices=devs)

    if spec == "auto":
        return make((n,), (DP,), devices)
    sizes = parse_mesh_spec(spec)
    if DCN in sizes and next(iter(sizes)) != DCN:
        # slice-crossing traffic must map to the outermost axis, so
        # contiguous device blocks (slices, in a real multislice
        # topology) land on the inner in-slice axes
        raise ValueError(
            f"dcn must be the OUTERMOST (first) mesh axis: {spec!r}")
    unknown = [a for a, s in sizes.items() if s == -1]
    if len(unknown) > 1:
        raise ValueError("at most one -1 axis allowed")
    known = int(np.prod([s for s in sizes.values() if s != -1]))
    if unknown:
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        sizes[unknown[0]] = n // known
    total = int(np.prod(list(sizes.values())))
    if total > n:
        raise ValueError(
            f"mesh {sizes} needs {total} devices, have {n}")
    # a mesh smaller than the host's device count is legal (e.g. a
    # sub-slice lease, or dp=1 debugging on a multi-chip host)
    return make(tuple(sizes.values()), tuple(sizes.keys()),
                devices[:total])


_default_mesh: Optional[Mesh] = None


def get_default_mesh() -> Mesh:
    """Process-wide mesh built from config (cached; the mesh is the
    cluster, and there is one per process)."""
    global _default_mesh
    if _default_mesh is None:
        from learningorchestra_tpu.config import get_config
        _default_mesh = build_mesh(get_config().mesh_shape)
    return _default_mesh


def reset_default_mesh() -> None:
    global _default_mesh
    _default_mesh = None


def slice_mesh(devices: Sequence[jax.Device],
               spec: str = "auto") -> Mesh:
    """First-class sub-mesh over an explicit device subset.

    Axis names follow the same convention as :func:`build_mesh`
    (``"auto"`` = 1-D ``dp``), so two slices over the SAME devices
    compare equal — engine executable-cache keys that embed the mesh
    stay stable across repeat grants of an identical slice.
    """
    return build_mesh(spec, devices=list(devices))


def sub_meshes(mesh: Mesh, k: int) -> list:
    """Split ``mesh`` into ``k`` disjoint equal 1-D dp sub-meshes
    (trailing remainder devices are left unused). The scheduler's
    slice allocator and the builder's per-family spatial multiplexing
    both cut the mesh this way, so contiguous blocks map to the same
    slices everywhere."""
    devices = list(np.asarray(mesh.devices).flat)
    k = max(1, min(k, len(devices)))
    per = len(devices) // k
    return [slice_mesh(devices[i * per:(i + 1) * per])
            for i in range(k)]


# -- per-job mesh override ------------------------------------------------
# The slice scheduler grants a job a device subset; the job's thread
# sees it through this thread-local so model code deep in the stack
# (estimators, neural, sweep) trains on the granted slice without
# threading a mesh through every signature. Absent an override,
# current_mesh() is exactly get_default_mesh().
_mesh_override = threading.local()


def current_mesh() -> Mesh:
    """The mesh THIS thread should compute on: the granted slice when
    running under ``use_mesh`` (scheduler slice grants), else the
    process-wide default mesh."""
    mesh = getattr(_mesh_override, "mesh", None)
    return mesh if mesh is not None else get_default_mesh()


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]) -> Iterator[Optional[Mesh]]:
    """Scope ``current_mesh()`` to ``mesh`` on this thread (None is a
    no-op, keeping the default-mesh fast path allocation-free)."""
    if mesh is None:
        yield None
        return
    previous = getattr(_mesh_override, "mesh", None)
    _mesh_override.mesh = mesh
    try:
        yield mesh
    finally:
        _mesh_override.mesh = previous


def set_current_mesh(mesh: Optional[Mesh]) -> Optional[Mesh]:
    """Swap this thread's mesh override IN PLACE and return the
    previous one. Live migration (services/migration.py) uses this to
    re-point a job that is already inside a ``use_mesh`` scope at its
    NEW slice; the enclosing context manager's finally still restores
    whatever preceded the scope, so the swap never leaks past the
    lease."""
    previous = getattr(_mesh_override, "mesh", None)
    _mesh_override.mesh = mesh
    return previous


def mesh_for_slice(device_indices: Optional[Sequence[int]]) -> Mesh:
    """Materialize a scheduler grant (indices into the default mesh's
    flat device order) as a mesh. ``None`` or a full-cover grant
    returns the default-mesh OBJECT itself so cache keys and ``is``
    checks treat full-mesh jobs exactly as before slicing existed."""
    base = get_default_mesh()
    if device_indices is None:
        return base
    devices = list(np.asarray(base.devices).flat)
    indices = sorted(int(i) for i in device_indices)
    if len(indices) >= len(devices):
        return base
    return slice_mesh([devices[i] for i in indices])


def mesh_fraction(mesh: Mesh) -> float:
    """``mesh``'s share of the default mesh (per-slice arena budgets);
    1.0 when the default mesh is unavailable or smaller."""
    try:
        base = get_default_mesh()
        return min(1.0, float(mesh.size) / max(1, int(base.size)))
    except Exception:  # noqa: BLE001 — no default mesh formed yet
        return 1.0


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes the batch dimension is sharded over (dcn, dp and fsdp all
    shard data; dcn outermost so each slice holds a contiguous batch
    block and only gradients cross the slice boundary)."""
    return tuple(a for a in (DCN, DP, FSDP) if a in mesh.axis_names)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    axes = data_axes(mesh)
    return NamedSharding(mesh, P(axes if axes else None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_parallel_size(mesh: Mesh) -> int:
    size = 1
    for a in data_axes(mesh):
        size *= mesh.shape[a]
    return size


def pad_to_multiple(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple
