"""Device-memory feature arena: the HBM tier of the feature-plane
cache (docs/PERFORMANCE.md).

Every compute step used to pay the full host->device data path on
every fit — ``read_dataframe`` -> pandas -> numpy -> ``device_put`` —
even when the same dataset version had been staged seconds earlier by
another classifier or pipeline step (SparkNet's observation that
caching the training set in executor memory across iterations is the
dominant cluster-ML win, PAPERS.md). The arena keeps *sharded device
arrays* resident between jobs:

- entries are dicts of ``jax.Array`` keyed by an opaque content token
  (dataset versions + projection + dtype policy) plus the mesh and
  sharding they were staged under — a GSPMD global array only makes
  sense relative to its mesh;
- a byte budget (``LO_ARENA_BYTES``; default a quarter of one
  device's memory, 1 GiB when the backend doesn't report it) bounds
  residency with LRU eviction;
- readers *pin* entries while a fit consumes them. Eviction only
  unlinks an entry from the table; the arrays themselves stay alive
  until the last pin (Python reference) drops, so an in-flight fit
  can never observe a corrupted or freed batch. Pins are released in
  ``finally`` blocks, so cancelled / timed-out jobs
  (docs/LIFECYCLE.md) release them on the ``JobCancelled`` unwind;
- write-invalidation is driven by the catalog change feed through
  per-entry *tags* (collection names): ``invalidate(name)`` drops
  every entry staged from that collection.

The module never imports jax at top level: metrics endpoints and
config plumbing must be able to touch arena *stats* without
initializing an accelerator backend.
"""

from __future__ import annotations

import collections
import threading
from typing import Any, Callable, Dict, Iterable, Optional, Tuple
from learningorchestra_tpu.runtime import locks


def _ledger(op: str, key: Any, nbytes: int = 0,
            tags: Tuple[str, ...] = ()) -> None:
    """Mirror resident insert/drop into the X-ray HBM ledger (owner
    ``arena``). Advisory — the import is lazy and any failure is
    swallowed so the arena never depends on observability."""
    try:
        from learningorchestra_tpu.observability import xray

        if op == "register":
            xray.register("arena", key, nbytes,
                          name=tags[0] if tags else None)
        else:
            xray.release("arena", key)
    except Exception:  # noqa: BLE001
        pass


def _auto_budget() -> int:
    """A quarter of one device's reported memory. XLA:CPU reports no
    ``bytes_limit`` and gets a 1 GiB stand-in; an accelerator that
    reports none is an error — sizing an HBM tier from a guess would
    either waste the device or overcommit it."""
    import jax

    dev = jax.local_devices()[0]
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
    if limit > 0:
        return limit // 4
    if dev.platform != "cpu":
        raise RuntimeError(
            f"{dev.platform} device {dev.device_kind!r} reports no "
            f"bytes_limit in memory_stats(); set LO_ARENA_BYTES to "
            f"size the HBM arena explicitly")
    return 1 << 30


class ArenaEntry:
    """A pinned handle on one resident dict of device arrays. Use as a
    context manager (or call :meth:`release`) so the pin drops on ANY
    exit path, including ``JobCancelled``."""

    __slots__ = ("key", "arrays", "nbytes", "tags", "_arena", "_released")

    def __init__(self, key: Any, arrays: Dict[str, Any], nbytes: int,
                 tags: Tuple[str, ...], arena: Optional["DeviceArena"]):
        self.key = key
        self.arrays = arrays
        self.nbytes = nbytes
        self.tags = tags
        self._arena = arena
        self._released = False

    def release(self) -> None:
        if self._released:
            return
        self._released = True
        if self._arena is not None:
            self._arena._unpin(self.key)

    def __enter__(self) -> "ArenaEntry":
        return self

    def __exit__(self, *exc) -> None:
        self.release()


class _Resident:
    __slots__ = ("arrays", "nbytes", "tags", "pins", "group")

    def __init__(self, arrays, nbytes, tags, group=None):
        self.arrays = arrays
        self.nbytes = nbytes
        self.tags = tags
        self.pins = 0
        self.group = group


class DeviceArena:
    """Byte-budgeted LRU of staged device-array dicts with reader
    pins and tag-based invalidation. Thread-safe: builder classifier
    threads and concurrent jobs share one arena."""

    def __init__(self, byte_budget: Optional[int] = None):
        # None = resolve lazily from the device on first insertion
        # (stats() must stay accelerator-free); <= 0 = disabled.
        self._budget = byte_budget
        self._entries: "collections.OrderedDict[Any, _Resident]" = \
            collections.OrderedDict()
        self._bytes = 0
        # per-group residency (group = the mesh an entry was staged
        # under); a slice-scheduled fit budgets against its slice's
        # HBM fraction, not the whole arena
        self._group_bytes: Dict[Any, int] = {}
        self._lock = locks.make_lock("arena.entries")
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.invalidations = 0

    # -- core ----------------------------------------------------------
    def get_or_put(self, key: Any, build: Callable[[], Dict[str, Any]],
                   tags: Iterable[str] = (), group: Any = None,
                   group_fraction: float = 1.0) -> ArenaEntry:
        """Pinned entry for ``key``, building (and staging) it on miss.

        The build runs outside the lock; a concurrent miss on the same
        key may build twice, in which case the first insert wins and
        the loser's arrays are garbage-collected — duplicate staging
        is cheaper than serializing every fit behind one transfer.

        ``group`` partitions the budget: entries inserted under a
        group are additionally bounded by ``budget * group_fraction``
        with eviction scoped to that group — a fit running on a
        half-mesh slice budgets against half the arena instead of
        evicting full-mesh residents. ``group=None`` (the default)
        keeps the single global budget exactly as before.
        """
        tags = tuple(tags)
        with self._lock:
            res = self._entries.get(key)
            if res is not None:
                self._entries.move_to_end(key)
                res.pins += 1
                self.hits += 1
                return ArenaEntry(key, res.arrays, res.nbytes, res.tags,
                                  self)
            self.misses += 1
        arrays = build()
        nbytes = sum(int(getattr(a, "nbytes", 0)) for a in arrays.values())
        with self._lock:
            if self._budget is None:
                self._budget = _auto_budget()
            if self._budget <= 0 or nbytes > self._budget:
                # uncacheable: hand back an untracked pinned-by-nobody
                # entry; release() is a no-op
                return ArenaEntry(key, arrays, nbytes, tags, None)
            res = self._entries.get(key)
            if res is not None:  # lost the build race — reuse the winner
                self._entries.move_to_end(key)
                res.pins += 1
                return ArenaEntry(key, res.arrays, res.nbytes, res.tags,
                                  self)
            res = _Resident(arrays, nbytes, tags, group)
            res.pins = 1
            self._entries[key] = res
            self._bytes += nbytes
            _ledger("register", key, nbytes, tags)
            if group is not None:
                self._group_bytes[group] = \
                    self._group_bytes.get(group, 0) + nbytes
                limit = int(self._budget * max(0.0, min(1.0,
                                                        group_fraction)))
                self._evict_group_locked(group, limit)
            self._evict_locked()
            return ArenaEntry(key, arrays, nbytes, tags, self)

    def _unpin(self, key: Any) -> None:
        with self._lock:
            res = self._entries.get(key)
            if res is not None and res.pins > 0:
                res.pins -= 1

    def _drop_locked(self, key: Any) -> "_Resident":
        res = self._entries.pop(key)
        self._bytes -= res.nbytes
        _ledger("release", key)
        if res.group is not None:
            remaining = self._group_bytes.get(res.group, 0) - res.nbytes
            if remaining > 0:
                self._group_bytes[res.group] = remaining
            else:
                self._group_bytes.pop(res.group, None)
        return res

    def _evict_locked(self) -> None:
        """LRU-evict unpinned entries until under budget. Pinned
        entries are skipped — an over-budget arena full of in-flight
        readers degrades to 'no caching' rather than corrupting them;
        their bytes free when the pins drop and the next insert
        sweeps again."""
        if self._budget is None or self._budget <= 0:
            return
        while self._bytes > self._budget:
            victim = None
            for key, res in self._entries.items():  # oldest first
                if res.pins == 0:
                    victim = key
                    break
            if victim is None:
                return
            self._drop_locked(victim)
            self.evictions += 1

    def _evict_group_locked(self, group: Any, limit: int) -> None:
        """LRU-evict unpinned entries of ``group`` until its bytes fit
        ``limit`` — the slice-budget analogue of :meth:`_evict_locked`,
        scoped so one slice's staging pressure only recycles its own
        residents."""
        if limit <= 0:
            return
        while self._group_bytes.get(group, 0) > limit:
            victim = None
            for key, res in self._entries.items():  # oldest first
                if res.group == group and res.pins == 0:
                    victim = key
                    break
            if victim is None:
                return
            self._drop_locked(victim)
            self.evictions += 1

    # -- invalidation --------------------------------------------------
    def invalidate(self, collection: str) -> int:
        """Drop every entry tagged with ``collection`` (catalog change
        feed / version-mismatch hook). Pinned entries are dropped from
        the table too — their arrays survive for the in-flight reader,
        but no future reader can hit the stale version."""
        dropped = 0
        with self._lock:
            for key in [k for k, r in self._entries.items()
                        if collection in r.tags]:
                self._drop_locked(key)
                dropped += 1
            self.invalidations += dropped
        return dropped

    def clear(self) -> None:
        with self._lock:
            for key in self._entries:
                _ledger("release", key)
            self._entries.clear()
            self._bytes = 0
            self._group_bytes.clear()

    # -- observability -------------------------------------------------
    @property
    def bytes_in_use(self) -> int:
        with self._lock:
            return self._bytes

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "entries": len(self._entries),
                "bytesInUse": self._bytes,
                "byteBudget": self._budget,
                "pins": sum(r.pins for r in self._entries.values()),
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "invalidations": self.invalidations,
                "groups": len(self._group_bytes),
            }


# ----------------------------------------------------------------------
# process-wide default (the mesh is process-wide, so the arrays staged
# onto it are too); config swaps reset it like the default mesh
# ----------------------------------------------------------------------
_default_arena: Optional[DeviceArena] = None
_default_lock = locks.make_lock("arena.default")


def _configured_budget() -> Optional[int]:
    from learningorchestra_tpu.config import get_config

    raw = getattr(get_config(), "arena_bytes", -1)
    return None if raw < 0 else int(raw)  # None = auto-size lazily


def get_default_arena() -> DeviceArena:
    global _default_arena
    with _default_lock:
        if _default_arena is None:
            _default_arena = DeviceArena(_configured_budget())
        return _default_arena


def reset_default_arena() -> None:
    """Drop the process arena (config swap / test teardown): entries
    are keyed by mesh + dataset version, both invalid across a config
    change."""
    global _default_arena
    with _default_lock:
        _default_arena = None
