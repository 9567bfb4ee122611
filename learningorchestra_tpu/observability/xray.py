"""HBM attribution ledger + compiled-artifact X-ray
(docs/OBSERVABILITY.md "HBM attribution & X-ray").

The roofline layer says whether a program is compute- or
bandwidth-bound; this module says *where device memory actually
goes*. Three instruments, all advisory (nothing here may ever raise
into or stall the job it observes):

- a **live ledger**: every allocation site that pins device bytes —
  arena residents, engine train state, fused stacked params, serving
  param pins + KV slot caches, async-checkpoint host snapshots —
  registers owner-tagged byte counts and releases them on drop.
  ``unattributed = bytes_in_use − Σledger`` surfaces XLA temporaries
  and leaks (the SLO watchdog pages on sustained growth);
- a **compiled-artifact registry**: per cached executable, XLA's
  ``memory_analysis()`` (argument/output/temp/code bytes) and
  ``cost_analysis()`` captured next to the engine's flops cache, so
  ``GET /observability/compile/{name}`` explains a job's HBM budget
  per compiled step;
- **retrace and transfer sentinels**: a per-program-key signature
  tracker that counts warm-key recompiles (recording the differing
  abstract signature), and an opt-in ``jax.transfer_guard``-based
  hot-loop guard (``LO_TRANSFER_GUARD=log|fail``) that turns implicit
  host↔device transfers into events + a prometheus counter.

``LO_XRAY=0`` turns registration into a no-op (releases stay active
so a mid-process flip can never leak ledger entries); like perf.py
the switch is read per call because CI smoke flips it in-process.
"""

from __future__ import annotations

import collections
import os
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple
from learningorchestra_tpu.runtime import locks

# canonical owner tags; anything else still ledgers, these are what
# the docs table and the xray-smoke CI stage assert on
OWNERS = ("arena", "train-state", "serving-params", "kv-cache",
          "snapshot")

_MAX_COMPILES = 128      # per-name compiled-artifact reports (LRU)
_MAX_EVENTS = 64         # retained retrace / transfer events
_MAX_ENTRIES_LISTED = 256  # ledger rows returned per report

_lock = locks.make_lock("xray.ledger")
# (owner, key) -> {"bytes": int, "owner": str, "name": str|None, ...}
_ledger: "collections.OrderedDict[Tuple[str, Any], Dict[str, Any]]" = \
    collections.OrderedDict()
_compiles: "collections.OrderedDict[str, Dict[str, Any]]" = \
    collections.OrderedDict()
# program key (shape-free) -> {"signature": ..., "name": ...}
_signatures: Dict[Any, Dict[str, Any]] = {}
_retraces_total = 0
_transfers_total = 0
_retrace_events: "collections.deque" = collections.deque(
    maxlen=_MAX_EVENTS)
_transfer_events: "collections.deque" = collections.deque(
    maxlen=_MAX_EVENTS)


def enabled() -> bool:
    """Master switch for ledger registration + compile capture
    (``LO_XRAY``, default on). Read on every call, so a process
    may flip it while it runs."""
    return os.environ.get("LO_XRAY", "1") not in ("0", "false", "no")


# ----------------------------------------------------------------------
# live HBM ledger
# ----------------------------------------------------------------------
def register(owner: str, key: Any, nbytes: int,
             name: Optional[str] = None, **meta: Any) -> None:
    """Upsert one owner-tagged allocation. ``key`` must be hashable
    and stable until :func:`release` — allocation sites pass the same
    identity they free with (arena keys, ``id(session)`` tuples,
    per-step snapshot ids). Re-registering a live key replaces its
    byte count (state replacement, migration re-placement)."""
    if not enabled():
        return
    try:
        entry: Dict[str, Any] = {"owner": str(owner),
                                 "bytes": int(nbytes),
                                 "ts": time.time()}
        if name:
            entry["name"] = str(name)
        for k, v in meta.items():
            if isinstance(v, (str, int, float, bool)) or v is None:
                entry[k] = v
        with _lock:
            _ledger[(str(owner), key)] = entry
            _ledger.move_to_end((str(owner), key))
    except Exception:  # noqa: BLE001 — observability is advisory
        pass


def release(owner: str, key: Any) -> None:
    """Drop one ledger entry. Always active (even under ``LO_XRAY=0``)
    so flipping the switch mid-process can never strand bytes in the
    ledger; unknown keys are ignored."""
    try:
        with _lock:
            _ledger.pop((str(owner), key), None)
    except Exception:  # noqa: BLE001
        pass


def by_owner() -> Dict[str, int]:
    """Attributed bytes summed per owner tag. Every known owner is
    present (zero-filled) so the ``lo_hbm_attributed_bytes{owner=}``
    label set stays stable across scrapes — a vanishing series reads
    as a scrape failure on a dashboard, not as a release."""
    with _lock:
        out: Dict[str, int] = {o: 0 for o in OWNERS}
        for entry in _ledger.values():
            out[entry["owner"]] = out.get(entry["owner"], 0) \
                + entry["bytes"]
        return out


def attributed_bytes() -> int:
    with _lock:
        return sum(e["bytes"] for e in _ledger.values())


def device_bytes_in_use() -> Tuple[Optional[int], str]:
    """``(bytes, source)`` for the whole local process: the sum of
    every device's ``memory_stats()['bytes_in_use']`` where the
    backend reports it (source ``memoryStats``), else the nbytes sum
    of ``jax.live_arrays()`` (source ``liveArrays`` — XLA:CPU reports
    no allocator stats), else ``(None, "unavailable")``."""
    try:
        import jax

        total, reported = 0, False
        for dev in jax.local_devices():
            stats = dev.memory_stats() or {}
            if "bytes_in_use" in stats:
                total += int(stats["bytes_in_use"])
                reported = True
        if reported:
            return total, "memoryStats"
        total = sum(int(getattr(a, "nbytes", 0))
                    for a in jax.live_arrays())
        return total, "liveArrays"
    except Exception:  # noqa: BLE001 — no backend, no number
        return None, "unavailable"


def memory_report(name: Optional[str] = None) -> Dict[str, Any]:
    """The attribution report behind ``GET /observability/memory``:
    per-owner totals, bounded per-entry rows, bytes-in-use vs the
    ledger (``unattributedBytes`` = XLA temps, fragmentation, leaks)
    and the sentinel counters. With ``name``, rows and totals are
    filtered to entries tagged with that job/session/model name (the
    process-wide unattributed remainder is omitted — it is not
    meaningful for a slice of the ledger)."""
    with _lock:
        rows = [dict(e, key=_key_str(k))
                for (o, k), e in _ledger.items()
                if name is None or e.get("name") == name]
        retraces, transfers = _retraces_total, _transfers_total
    rows = rows[-_MAX_ENTRIES_LISTED:]
    # bare report: zero-fill every known owner (stable dashboard
    # columns); a named slice lists only the owners it actually has
    owners: Dict[str, int] = (
        {} if name is not None else {o: 0 for o in OWNERS})
    for e in rows:
        owners[e["owner"]] = owners.get(e["owner"], 0) + e["bytes"]
    attributed = sum(owners.values())
    out: Dict[str, Any] = {
        "enabled": enabled(),
        "owners": owners,
        "attributedBytes": attributed,
        "entries": rows,
        "retracesTotal": retraces,
        "implicitTransfersTotal": transfers,
    }
    if name is not None:
        out["name"] = name
        return out
    # host-resident entries (async-ckpt snapshots carry host=True)
    # attribute real bytes but not DEVICE bytes — they stay out of
    # the in-use subtraction or they would fake negative XLA temps
    device_attr = sum(e["bytes"] for e in rows if not e.get("host"))
    out["attributedDeviceBytes"] = device_attr
    in_use, source = device_bytes_in_use()
    out["bytesInUse"] = in_use
    out["bytesSource"] = source
    if in_use is not None:
        out["unattributedBytes"] = max(0, in_use - device_attr)
    return out


def ring_sample() -> Tuple[Optional[int], Optional[int]]:
    """``(attributedBytes, unattributedBytes)`` for the monitor's
    per-tick rings — the cheap subset of :func:`memory_report` (the
    leak-detector SLO differences the unattributed series)."""
    try:
        with _lock:
            attributed = sum(e["bytes"] for e in _ledger.values())
            device_attr = sum(e["bytes"] for e in _ledger.values()
                              if not e.get("host"))
        in_use, _source = device_bytes_in_use()
        if in_use is None:
            return attributed, None
        return attributed, max(0, in_use - device_attr)
    except Exception:  # noqa: BLE001
        return None, None


def _key_str(key: Any) -> str:
    s = str(key)
    return s if len(s) <= 160 else s[:157] + "..."


# ----------------------------------------------------------------------
# compiled-artifact registry
# ----------------------------------------------------------------------
def record_compile(name: str, program: str,
                   report: Dict[str, Any]) -> None:
    """Attach one compiled program's X-ray (memory_analysis +
    cost_analysis extract, engine._xray_compile) to ``name``'s
    report. Programs accumulate under the name (a fit has a train
    step, an eval step, ...); names age out LRU."""
    if not enabled():
        return
    try:
        entry = dict(report)
        entry["updatedAt"] = time.time()
        with _lock:
            rec = _compiles.get(name)
            if rec is None:
                rec = {"name": name, "programs": {}}
            rec["programs"][str(program)] = entry
            _compiles[name] = rec
            _compiles.move_to_end(name)
            while len(_compiles) > _MAX_COMPILES:
                _compiles.popitem(last=False)
    except Exception:  # noqa: BLE001
        pass


def compile_report(name: str) -> Optional[Dict[str, Any]]:
    with _lock:
        rec = _compiles.get(name)
        if rec is None:
            return None
        return {"name": rec["name"],
                "programs": {k: dict(v)
                             for k, v in rec["programs"].items()}}


def known_compiles() -> List[str]:
    with _lock:
        return list(_compiles.keys())


def extract_memory_analysis(compiled: Any) -> Dict[str, Any]:
    """The named int fields of XLA's ``CompiledMemoryStats`` —
    NEVER the whole object (it drags a serialized HLO proto along)."""
    out: Dict[str, Any] = {}
    try:
        stats = compiled.memory_analysis()
        for attr, key in (
                ("argument_size_in_bytes", "argumentBytes"),
                ("output_size_in_bytes", "outputBytes"),
                ("temp_size_in_bytes", "tempBytes"),
                ("alias_size_in_bytes", "aliasBytes"),
                ("generated_code_size_in_bytes", "codeBytes")):
            v = getattr(stats, attr, None)
            if isinstance(v, int):
                out[key] = v
        if out:
            # alias bytes are donated-in/out overlap, already counted
            # in arguments — the live-per-step footprint excludes them
            out["peakBytesEstimate"] = (
                out.get("argumentBytes", 0) + out.get("outputBytes", 0)
                + out.get("tempBytes", 0) - out.get("aliasBytes", 0))
    except Exception:  # noqa: BLE001
        pass
    return out


def extract_cost_analysis(source: Any) -> Dict[str, Any]:
    """flops / bytes-accessed out of ``cost_analysis()``, which is a
    dict on Lowered and a list-of-dicts on Compiled depending on
    jaxlib version — normalize to one flat dict of floats."""
    out: Dict[str, Any] = {}
    try:
        cost = source.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        if isinstance(cost, dict):
            for src, key in (("flops", "flops"),
                             ("bytes accessed", "bytesAccessed")):
                v = cost.get(src)
                if isinstance(v, (int, float)):
                    out[key] = float(v)
    except Exception:  # noqa: BLE001
        pass
    return out


# ----------------------------------------------------------------------
# retrace sentinel
# ----------------------------------------------------------------------
def note_signature(program: Any, signature: Any,
                   name: Optional[str] = None) -> bool:
    """Record ``program``'s abstract signature (shapes/dtypes of its
    traced inputs). Returns True — and counts a retrace, keeping the
    differing signatures — when a previously-seen program recompiles
    under a new signature: the warm-cache-miss the engine's
    ``compiledSteps`` stat can only count, not explain."""
    global _retraces_total
    try:
        sig = str(signature)
        with _lock:
            prev = _signatures.get(program)
            _signatures[program] = {"signature": sig, "name": name}
            if prev is None or prev["signature"] == sig:
                return False
            _retraces_total += 1
            event = {"ts": time.time(), "program": _key_str(program),
                     "name": name, "prevSignature": prev["signature"],
                     "newSignature": sig}
            _retrace_events.append(event)
    except Exception:  # noqa: BLE001
        return False
    _emit("retrace", name or _key_str(program), **{
        k: v for k, v in event.items() if k not in ("ts", "name")})
    return True


def retrace_events() -> List[Dict[str, Any]]:
    with _lock:
        return [dict(e) for e in _retrace_events]


# ----------------------------------------------------------------------
# transfer sentinel
# ----------------------------------------------------------------------
_TRANSFER_RE = re.compile(
    r"Disallowed ([\w-]+) transfer:?\s*(.*)", re.DOTALL)


def transfer_guard_mode() -> str:
    """``LO_TRANSFER_GUARD``: "" (off, the default), ``log`` (count +
    event + proceed) or ``fail`` (count + event + raise)."""
    try:
        from learningorchestra_tpu.config import get_config

        mode = str(getattr(get_config(), "transfer_guard", "") or "")
    except Exception:  # noqa: BLE001
        mode = os.environ.get("LO_TRANSFER_GUARD", "")
    mode = mode.strip().lower()
    return mode if mode in ("log", "fail") else ""


def guarded_call(fn: Callable, *args: Any,
                 name: Optional[str] = None, **kwargs: Any) -> Any:
    """Run one hot-loop dispatch under the transfer sentinel.

    Off (the default) this is a plain call. Armed, the call runs
    under ``jax.transfer_guard("disallow")``: jax raises on any
    implicit host↔device transfer with the offending abstract value
    in the message. The sentinel parses that signature, counts it
    (``lo_implicit_transfers_total``) and emits an ``LO_EVENT_LOG``
    event; ``fail`` re-raises (CI mode), ``log`` retries the call
    outside the guard — safe even with donated arguments, because a
    guard-blocked dispatch never consumes its input buffers."""
    mode = transfer_guard_mode()
    if not mode:
        return fn(*args, **kwargs)
    import jax

    try:
        with jax.transfer_guard("disallow"):
            return fn(*args, **kwargs)
    except Exception as exc:  # noqa: BLE001 — only transfer-guard
        # errors are ours; anything else propagates untouched
        match = _TRANSFER_RE.search(str(exc))
        if match is None:
            raise
        note_transfer(match.group(1), match.group(2).strip()[:200],
                      name=name)
        if mode == "fail":
            raise
    return fn(*args, **kwargs)


def note_transfer(direction: str, signature: str,
                  name: Optional[str] = None) -> None:
    """Count one implicit transfer and keep its signature."""
    global _transfers_total
    try:
        event = {"ts": time.time(), "direction": str(direction),
                 "signature": str(signature), "name": name}
        with _lock:
            _transfers_total += 1
            _transfer_events.append(event)
    except Exception:  # noqa: BLE001
        return
    _emit("implicitTransfer", name or "transfer", **{
        k: v for k, v in event.items() if k not in ("ts", "name")})


def transfer_events() -> List[Dict[str, Any]]:
    with _lock:
        return [dict(e) for e in _transfer_events]


# ----------------------------------------------------------------------
# counters / reset
# ----------------------------------------------------------------------
def counters() -> Dict[str, int]:
    """The sentinel counters behind ``lo_retraces_total`` and
    ``lo_implicit_transfers_total``."""
    with _lock:
        return {"retraces": _retraces_total,
                "implicitTransfers": _transfers_total}


def _emit(kind: str, name: str, **fields: Any) -> None:
    try:
        from learningorchestra_tpu.observability import export

        export.log_event(kind, name, **fields)
    except Exception:  # noqa: BLE001
        pass


def reset() -> None:
    """Test/teardown hook: drop ledger, compile reports, signatures
    and counters."""
    global _retraces_total, _transfers_total
    with _lock:
        _ledger.clear()
        _compiles.clear()
        _signatures.clear()
        _retraces_total = 0
        _transfers_total = 0
        _retrace_events.clear()
        _transfer_events.clear()
