"""From a profiler trace (``.xplane.pb``) to numbers.

Device planes are those named ``/device:TPU:<n>``. On each, the line
``XLA Ops`` holds one event per operation that ran and ``XLA Modules``
one per program execution. From them:

- ``busy_s``: the union of the intervals in which an operation ran,
  averaged over the device planes; ``window_s``: the traced window as
  the device saw it, from the first device event's start to the last
  one's end. Host threads and the profiler's own start and stop are not
  part of it: a program that the trace cuts at either end is recorded
  from where the cut falls, so nothing at the edges counts as idle.
- ``ops``: device seconds by operation name; ``modules``: the
  durations of each program's executions.
- ``module_gaps``: the idle stretch between one program's end and the
  next one's start, by ``"<previous> -> <next>"``: what the host was
  doing in a gap is preparing the program that follows it.
  ``same_program_gaps``: from one run of a program to its next run.
- Operation events are named by their whole HLO line; ``short_name``
  keeps the result's name and the opcode. ``while`` and other containers
  are left out of ``ops``: their time is their children's.

``reduce_events`` takes plain tuples, so it is checked on the recorded
sample beside this file without a profiler.
"""

from __future__ import annotations

import gzip
import json
import re
from typing import Any, Dict, Iterable, List, Tuple

Event = Tuple[str, float, float]          # name, start_s, duration_s

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")
CONTAINERS = ("while", "conditional", "call")


def short_name(op_event_name: str) -> str:
    """An op event is named by its whole HLO line; keep the result's
    name and the opcode: ``%attn.164 custom-call``."""
    lhs, sep, rest = op_event_name.partition(" = ")
    if not sep:
        return op_event_name[:80]
    m = _OPCODE.search(" " + rest)
    return f"{lhs.strip()} {m.group(1)}" if m else lhs.strip()[:80]


def read_planes(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{device plane: {"ops": [...], "modules": [...]}} in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: Dict[str, Any] = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        entry = {"ops": [], "modules": []}
        for line in plane.lines:
            key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
            for e in line.events if key else ():
                start, dur = e.start_ns * 1e-9, e.duration_ns * 1e-9
                name = short_name(e.name) if key == "ops" else e.name
                entry[key].append((name, start, dur))
        if entry["ops"] or entry["modules"]:
            planes[plane.name] = entry
    return planes


def union_seconds(events: Iterable[Event]) -> float:
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    total, end = 0.0, float("-inf")
    for a, b in spans:
        if a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def program_name(module_event_name: str) -> str:
    """``jit_epoch_fn(1234567)`` -> ``epoch_fn``."""
    name = re.sub(r"\(.*\)$", "", module_event_name).strip()
    return name[4:] if name.startswith("jit_") else name


def reduce_events(planes: Dict[str, Any]) -> Dict[str, Any]:
    planes = {k: v for k, v in planes.items() if k.startswith(DEVICE_PLANE)}
    if not planes:
        return {}
    busy, lo, hi = [], float("inf"), float("-inf")
    ops: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    modules: Dict[str, List[float]] = {}
    gaps: Dict[str, List[float]] = {}
    same: Dict[str, List[float]] = {}
    for entry in planes.values():
        leaf = entry["ops"] or entry["modules"]
        busy.append(union_seconds(leaf))
        for name, start, dur in leaf:
            lo, hi = min(lo, start), max(hi, start + dur)
        for name, _, dur in entry["ops"]:
            if name.rpartition(" ")[2] in CONTAINERS:
                continue  # its time is its children's
            ops[name] = ops.get(name, 0.0) + dur
            counts[name] = counts.get(name, 0) + 1
        runs = sorted(entry["modules"], key=lambda e: e[1])
        last_end: Dict[str, float] = {}
        for name, start, dur in runs:
            prog = program_name(name)
            modules.setdefault(prog, []).append(dur)
            if prog in last_end and start > last_end[prog]:
                same.setdefault(prog, []).append(start - last_end[prog])
            last_end[prog] = start + dur
        for (a, a0, ad), (b, b0, _) in zip(runs, runs[1:]):
            gap = b0 - (a0 + ad)
            if gap > 0:
                gaps.setdefault(
                    f"{program_name(a)} -> {program_name(b)}", []
                ).append(gap)
    n = len(planes)
    out = {
        "busy_s": sum(busy) / n,
        "window_s": hi - lo if hi > lo else 0.0,
        "planes": n,
        "ops": {k: v / n for k, v in ops.items()},
        "op_counts": counts,
        "modules": modules,
        "module_gaps": gaps,
        "same_program_gaps": same,
    }
    out["device_ops"] = [[k, v] for k, v in sorted(
        out["ops"].items(), key=lambda kv: -kv[1])]
    out["idle_gaps"] = [[k, sum(v) / n] for k, v in sorted(
        gaps.items(), key=lambda kv: -sum(kv[1]))]
    return out


def reduce_file(path: str) -> Dict[str, Any]:
    return reduce_events(read_planes(path))


def save_sample(planes, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(planes, f)


def load_sample(path: str) -> Dict[str, Dict[str, List[Event]]]:
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return {p: {k: [tuple(e) for e in v] for k, v in entry.items()}
            for p, entry in raw.items()}


if __name__ == "__main__":
    # look at one trace by hand: planes, lines, and what took the time
    import sys

    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(sys.argv[1]).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            total: Dict[str, float] = {}
            for e in events:
                total[e.name] = total.get(e.name, 0.0) + e.duration_ns * 1e-9
            print("  LINE", line.name, len(events))
            for name, sec in sorted(total.items(),
                                    key=lambda kv: -kv[1])[:25]:
                print(f"     {sec:10.4f}s  {name[:140]}")
