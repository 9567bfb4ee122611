"""Roofline performance observability (docs/OBSERVABILITY.md).

The single source of truth for what the hardware CAN do and what each
program ACHIEVED against it:

- a platform registry of per-chip dense bf16 peak FLOP/s and peak HBM
  bandwidth (public spec-sheet numbers, substring-matched against
  jax's ``device_kind``; ``None`` on the CPU backend where a roofline
  is not meaningful, an error for an accelerator no row matches;
  ``LO_PEAK_TFLOPS_PER_CHIP`` / ``LO_PEAK_HBM_GBPS`` override — e.g.
  to pin a roofline on the CPU backend in tests);
- :func:`roofline` — achieved TFLOP/s/chip, achieved GB/s/chip,
  arithmetic intensity and a compute-/bandwidth-bound classification
  against the ridge point, from the per-step flops and
  ``bytes accessed`` the engine extracts out of XLA's
  ``cost_analysis()``;
- a bounded per-job report registry fed by the engine once per
  steady-state window and read by ``GET /observability/perf/{name}``
  plus the ``lo_mfu`` / ``lo_tflops_per_chip`` /
  ``lo_hbm_bw_util_frac`` gauges on ``/metrics``.

``LO_PERF=0`` disables the extended block and the registry (the
legacy ``tflopsPerSecPerChip``/``mfu`` history fields stay). Like the
rest of this package nothing here stalls the job it observes, with
ONE deliberate failure: an accelerator whose ``device_kind`` matches
no table row raises :class:`UnknownDeviceKind` rather than letting MFU
silently vanish from every report.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Dict, List, Optional
from learningorchestra_tpu.runtime import locks

# per-chip dense bf16 peak FLOP/s, public spec-sheet numbers; substring
# matched against jax's device_kind (moved from runtime/engine.py)
PEAK_FLOPS_BF16 = (
    ("v6", 918e12),          # Trillium
    ("v5p", 459e12),
    ("v5 lite", 197e12),     # v5e reports "TPU v5 lite"
    ("v5e", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

# per-chip peak HBM bandwidth, bytes/s (same matching rule)
PEAK_HBM_BYTES = (
    ("v6", 1640e9),          # Trillium
    ("v5p", 2765e9),
    ("v5 lite", 819e9),
    ("v5e", 819e9),
    ("v5", 2765e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
)

_MAX_JOBS = 128

_lock = locks.make_lock("perf.registry")
_reports: "collections.OrderedDict[str, Dict[str, Any]]" = \
    collections.OrderedDict()


def enabled() -> bool:
    """Master switch for the extended roofline block + registry
    (``LO_PERF``, default on). Read per call — it is one dict lookup
    per epoch window, and the perf-report CI smoke flips it inside a
    single process."""
    return os.environ.get("LO_PERF", "1") not in ("0", "false", "no")


def _device() -> Any:
    import jax

    return jax.devices()[0]


class UnknownDeviceKind(RuntimeError):
    """An accelerator whose ``device_kind`` matches no row of the peak
    tables: a roofline against a guessed peak is worse than none."""


def _match(table, kind: str):
    """The first ``(row key, peak)`` whose key is in ``kind``."""
    for key, value in table:
        if key in kind.lower():
            return key, value
    raise UnknownDeviceKind(
        f"no peak for device_kind {kind!r} in observability/perf.py "
        f"(rows: {[k for k, _ in table]}); add the chip's public "
        f"spec-sheet row")


def _peak(table, env_var: str, unit: float) -> Optional[float]:
    """``env_var`` override, else the table row for this accelerator's
    ``device_kind``; None on the CPU backend (no roofline there), an
    :class:`UnknownDeviceKind` error for an accelerator no row
    matches."""
    env = os.environ.get(env_var)
    if env:
        try:
            return float(env) * unit
        except ValueError:
            pass
    dev = _device()
    if dev.platform == "cpu":
        return None
    return _match(table, dev.device_kind)[1]


def peak_flops_per_chip() -> Optional[float]:
    """Dense bf16 peak of the current accelerator (None on the CPU
    backend: MFU is only meaningful against a hardware roofline).
    ``LO_PEAK_TFLOPS_PER_CHIP`` overrides the table."""
    return _peak(PEAK_FLOPS_BF16, "LO_PEAK_TFLOPS_PER_CHIP", 1e12)


def peak_hbm_bytes_per_chip() -> Optional[float]:
    """Peak HBM bandwidth (bytes/s) of the current accelerator, None
    on the CPU backend. ``LO_PEAK_HBM_GBPS`` overrides the table."""
    return _peak(PEAK_HBM_BYTES, "LO_PEAK_HBM_GBPS", 1e9)


def platform_summary() -> Dict[str, Any]:
    """The roofline this process measures against: platform, the
    ``device_kind`` the chip reports, the peaks of the table row it
    matched and the ridge point (flops/byte above which a program is
    compute-bound)."""
    dev = _device()
    peak_f = peak_flops_per_chip()
    peak_b = peak_hbm_bytes_per_chip()
    out: Dict[str, Any] = {
        "platform": dev.platform,
        "deviceKind": dev.device_kind,
        "peakTflopsPerChip": (round(peak_f / 1e12, 2)
                              if peak_f else None),
        "peakHbmGbPerSec": (round(peak_b / 1e9, 1) if peak_b else None),
    }
    if os.environ.get("LO_PEAK_TFLOPS_PER_CHIP"):
        out["peakRow"] = "LO_PEAK_TFLOPS_PER_CHIP"
    elif dev.platform != "cpu":
        out["peakRow"] = _match(PEAK_FLOPS_BF16, dev.device_kind)[0]
    if peak_f and peak_b:
        out["ridgeFlopsPerByte"] = round(peak_f / peak_b, 2)
    return out


def roofline(flops_per_step: float, bytes_per_step: float, steps: int,
             dt: float, n_chips: int) -> Dict[str, Any]:
    """Roofline position of ``steps`` steady-state steps over ``dt``
    seconds on ``n_chips`` chips.

    Always emits ``tflopsPerSecPerChip`` (+ ``mfu`` when a peak is
    known) — the legacy history fields. With ``bytes_per_step`` (XLA's
    ``bytes accessed``) and :func:`enabled`, adds achieved
    ``gbPerSecPerChip``, ``arithmeticIntensity`` (flops/byte),
    ``hbmBwUtil`` and the ``boundBy`` classification against the
    ridge point. On the CPU backend with no override every
    peak-relative field is simply absent — never a division by a
    made-up number."""
    out: Dict[str, Any] = {}
    if not flops_per_step or steps <= 0 or dt <= 0 or n_chips <= 0:
        return out
    achieved_flops = flops_per_step * steps / dt / n_chips
    out["tflopsPerSecPerChip"] = round(achieved_flops / 1e12, 4)
    peak_f = peak_flops_per_chip()
    if peak_f:
        out["mfu"] = round(achieved_flops / peak_f, 4)
    if not enabled() or not bytes_per_step:
        return out
    achieved_bytes = bytes_per_step * steps / dt / n_chips
    out["gbPerSecPerChip"] = round(achieved_bytes / 1e9, 3)
    intensity = flops_per_step / bytes_per_step
    out["arithmeticIntensity"] = round(intensity, 3)
    peak_b = peak_hbm_bytes_per_chip()
    if peak_b:
        out["hbmBwUtil"] = round(min(achieved_bytes / peak_b, 1.0), 4)
    if peak_f and peak_b:
        # below the ridge the memory system, not the MXU, caps the
        # program (decode famously lives here — ops/attention.py)
        out["boundBy"] = ("compute" if intensity >= peak_f / peak_b
                          else "bandwidth")
    return out


# ----------------------------------------------------------------------
# per-job report registry (train jobs; serving reports come live from
# ServingManager stats)
def record_job(job: str, report: Dict[str, Any]) -> None:
    """Upsert ``job``'s latest roofline window (bounded LRU, like the
    timeline rings). No-op when LO_PERF=0."""
    if not enabled():
        return
    entry = dict(report)
    entry["updatedAt"] = time.time()
    with _lock:
        _reports[job] = entry
        _reports.move_to_end(job)
        while len(_reports) > _MAX_JOBS:
            _reports.popitem(last=False)


def job_report(job: str) -> Optional[Dict[str, Any]]:
    with _lock:
        report = _reports.get(job)
        return dict(report) if report else None


def known_jobs() -> List[str]:
    with _lock:
        return list(_reports.keys())


def latest(limit: int = 32) -> Dict[str, Dict[str, Any]]:
    """The most recently updated reports (newest last), for the
    ``/metrics`` gauges — bounded so the exposition stays scrape-sized
    even after hundreds of jobs."""
    with _lock:
        names = list(_reports.keys())[-max(0, int(limit)):]
        return {n: dict(_reports[n]) for n in names}


def reset() -> None:
    with _lock:
        _reports.clear()
