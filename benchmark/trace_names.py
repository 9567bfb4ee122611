"""What the program names in a profiler trace (``.xplane.pb``): its live
spans on the host plane and the ``jax.named_scope`` of each device op.

    python3 benchmark/run.py --workload <cell> --seed N --seconds 15 \
        --trace 1 --keep-trace DIR
    python3 benchmark/trace_names.py DIR --steps 4

``trace_reduce.py`` reads the device planes through
``jax.profiler.ProfileData``, which shows no event's METADATA stats:
the ``tf_op`` stat, where an op's scopes are, lives there.
:func:`read_space` reads the raw protobuf (the classes come with the
installed TensorFlow) into the planes ``trace_reduce.read_planes``
gives, with two additions that ``trace_reduce.reduce_events`` passes
over (it reads ``ops`` and ``modules`` of the device planes):

- on each device plane, ``op_names``: the ``tf_op`` of each entry of
  ``ops``, in the same order;
- under :data:`HOST_PLANE`, ``spans``: ``(name, start_s, duration_s,
  thread)`` of each host event that carries a ``span`` argument. A live
  span of the program holds a ``TraceAnnotation`` marked with its id
  (``observability/trace.py``); jax's own events carry none.

Both planes are on the trace's one clock. :func:`reduce_names` then
gives:

- ``idle_by_span``: the device's idle seconds between two program
  executions, by the innermost program span open over each part of
  them on the threads that dispatch (those holding a ``dispatch``
  span); what no span covers is ``"none"``. A span open when the
  capture starts or stops is not in it, so only the stretches between
  the first ``epoch`` span's start and the last one's end count.
- ``epoch_gaps``: the same split for each stretch between two
  executions of the epoch program (``by_span``), the seconds under
  each span at any depth (``under``), and the programs that ran in it.
- ``program_spans``: for each program, the innermost span open on the
  dispatching threads where each execution started: what launched it.
- ``wait_lag_ms``: for each ``deviceWait``, its end less the end of the
  epoch program's execution nearest to it: the check of the clock (the
  host's wake-up where the planes agree; a skew between them shows here
  first).

:func:`per_step_ms` sums the device time of the ops inside whole
executions of the epoch program by kernel-name prefix, then by the
first matching scope, over the steps they ran. The per-layer readings
of the expert layer, the head loss and the conv are cases of it.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import scope_shares, trace_reduce  # noqa: E402

HOST_PLANE = "/host:CPU"
SPAN_ARG = "span"
NONE = "none"
_EDGE = 1e-9    # an op's edge on its program's, to the clock's rounding

Span = Tuple[str, float, float, int]   # name, start_s, duration_s, thread


def _stat_names(plane) -> Dict[int, str]:
    return {mid: m.name for mid, m in plane.stat_metadata.items()}


def _tf_op(meta, names: Dict[int, str]) -> str:
    for stat in meta.stats:
        if names.get(stat.metadata_id) == "tf_op":
            return stat.str_value or (names.get(stat.ref_value, "")
                                      if stat.ref_value else "")
    return ""


def read_space(path: str) -> Optional[Dict[str, Dict[str, List]]]:
    """The planes of one ``.xplane.pb`` (module docstring), in seconds,
    or None, said on standard error, where the protobuf classes cannot
    be imported."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError as e:
        print(f"trace_names: cannot read {path}: {e}", file=sys.stderr)
        return None

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    planes: Dict[str, Dict[str, List]] = {}
    for plane in space.planes:
        names = _stat_names(plane)
        meta = plane.event_metadata
        if plane.name.startswith(trace_reduce.DEVICE_PLANE):
            entry = {"ops": [], "modules": [], "op_names": []}
            for line in plane.lines:
                key = {trace_reduce.OPS_LINE: "ops",
                       trace_reduce.MODULES_LINE: "modules"}.get(line.name)
                for e in line.events if key else ():
                    m = meta[e.metadata_id]
                    start = line.timestamp_ns * 1e-9 + e.offset_ps * 1e-12
                    dur = e.duration_ps * 1e-12
                    if key == "ops":
                        entry["ops"].append(
                            (trace_reduce.short_name(m.name), start, dur))
                        entry["op_names"].append(_tf_op(m, names))
                    else:
                        entry["modules"].append((m.name, start, dur))
            if entry["ops"] or entry["modules"]:
                planes[plane.name] = entry
        elif plane.name == HOST_PLANE:
            spans = []
            for line in plane.lines:
                for e in line.events:
                    if any(names.get(s.metadata_id) == SPAN_ARG
                           for s in e.stats):
                        spans.append((meta[e.metadata_id].name,
                                      line.timestamp_ns * 1e-9
                                      + e.offset_ps * 1e-12,
                                      e.duration_ps * 1e-12, line.id))
            planes[HOST_PLANE] = {"spans": spans}
    return planes


def read_ops(path: str) -> List[scope_shares.Op]:
    """``scope_shares.read_ops``'s rows, ``(result name, opcode,
    op_name, seconds)`` of every device op but the containers, from
    :func:`read_space` (empty where it cannot read)."""
    out: List[scope_shares.Op] = []
    for plane, entry in (read_space(path) or {}).items():
        if plane.startswith(trace_reduce.DEVICE_PLANE):
            out.extend(_scoped(entry["ops"], entry["op_names"]))
    return out


def _scoped(ops, op_names) -> List[scope_shares.Op]:
    rows = []
    for (name, _, dur), op_name in zip(ops, op_names):
        lhs, _, opcode = name.partition(" ")
        if opcode not in trace_reduce.CONTAINERS:
            rows.append((lhs, opcode, op_name, dur))
    return rows


# ----------------------------------------------------------------------
def _dispatching(spans: Sequence[Span]) -> List[Span]:
    threads = {s[3] for s in spans if s[0] == "dispatch"}
    return [s for s in spans if s[3] in threads]


def _split(a: float, b: float, spans: Sequence[Span],
           ) -> Tuple[Dict[str, float], Dict[str, float]]:
    """([a, b] by innermost open span, by every span open over it)."""
    inside = [s for s in spans if s[1] < b and s[1] + s[2] > a]
    cuts = sorted({a, b, *(t for s in inside for t in (s[1], s[1] + s[2])
                          if a < t < b)})
    innermost: Dict[str, float] = {}
    under: Dict[str, float] = {}
    for x, y in zip(cuts, cuts[1:]):
        open_ = _open(0.5 * (x + y), inside)
        top = _innermost(open_)
        innermost[top] = innermost.get(top, 0.0) + (y - x)
        for name in {s[0] for s in open_}:
            under[name] = under.get(name, 0.0) + (y - x)
    return innermost, under


def _open(t: float, spans: Sequence[Span]) -> List[Span]:
    return [s for s in spans if s[1] <= t < s[1] + s[2]]


def _innermost(open_: Sequence[Span]) -> str:
    # nested spans of one thread: the innermost started last
    return max(open_, key=lambda s: (s[1], -s[2]))[0] if open_ else NONE


def _add(into: Dict[str, float], part: Dict[str, float], k: float = 1.0):
    for name, sec in part.items():
        into[name] = into.get(name, 0.0) + k * sec


def reduce_names(planes: Dict[str, Any], program: str = "epoch_fn",
                 ) -> Dict[str, Any]:
    """The host spans' reductions (module docstring); {} without device
    planes or ``epoch`` spans."""
    spans = _dispatching((planes.get(HOST_PLANE) or {}).get("spans", []))
    devices = [v for k, v in planes.items()
               if k.startswith(trace_reduce.DEVICE_PLANE)]
    epochs = [s for s in spans if s[0] == "epoch"]
    if not devices or not epochs:
        return {}
    # the capture holds a span that opened and closed inside it: the
    # host is read whole from the first epoch's start to the last's end
    lo = min(s[1] for s in epochs)
    hi = max(s[1] + s[2] for s in epochs)
    n = len(devices)
    idle: Dict[str, float] = {}
    epoch_gaps: List[Dict[str, Any]] = []
    launched: Dict[str, Dict[str, int]] = {}
    lags: List[float] = []
    waits = [s[1] + s[2] for s in spans if s[0] == "deviceWait"]
    for entry in devices:
        runs = sorted(entry["modules"], key=lambda e: e[1])
        gap: Optional[Dict[str, Any]] = None
        for i, (name, start, dur) in enumerate(runs):
            prog = trace_reduce.program_name(name)
            by = launched.setdefault(prog, {})
            span = _innermost(_open(start, spans))
            by[span] = by.get(span, 0) + 1
            prev_end = runs[i - 1][1] + runs[i - 1][2] if i else start
            if start > prev_end and not lo <= prev_end < start <= hi:
                gap = None      # the capture's edge cuts this stretch
            elif start > prev_end:
                inner, under = _split(prev_end, start, spans)
                _add(idle, inner, 1.0 / n)
                if gap is not None:
                    _add(gap["by_span"], inner)
                    _add(gap["under"], under)
                    gap["idle_s"] += start - prev_end
            if prog == program:
                if gap is not None:
                    epoch_gaps.append(gap)
                gap = {"idle_s": 0.0, "by_span": {}, "under": {},
                       "between": []}
            elif gap is not None:
                gap["between"].append(prog)
        ends = [s + d for nm, s, d in runs
                if trace_reduce.program_name(nm) == program]
        for w in waits:
            if ends:
                lags.append(1e3 * (w - min(ends, key=lambda e: abs(w - e))))
    return {"idle_by_span": idle, "epoch_gaps": epoch_gaps,
            "program_spans": launched, "wait_lag_ms": lags}


def whole_executions(planes: Dict[str, Any], program: str = "epoch_fn",
                     ) -> List[Tuple[float, float]]:
    """(start, end) of each execution of ``program`` that the trace
    holds whole: neither its plane's first program event (the trace's
    start may cut it) nor its last (its stop may)."""
    out = []
    for key, entry in planes.items():
        if not key.startswith(trace_reduce.DEVICE_PLANE):
            continue
        runs = sorted(entry["modules"], key=lambda e: e[1])[1:-1]
        out += [(s, s + d) for nm, s, d in runs
                if trace_reduce.program_name(nm) == program]
    return out


def per_step_ms(planes: Dict[str, Any], steps: int,
                scopes: Sequence[str], kernels: Sequence[str] = (),
                program: str = "epoch_fn") -> Optional[Dict[str, Any]]:
    """``scope_shares.shares`` of the ops inside whole executions of
    ``program`` (``steps`` steps each), in milliseconds a step, with
    ``executions``; None where the trace holds no whole execution or
    no op carries a scope (a trace read without the metadata)."""
    whole = whole_executions(planes, program)
    rows: List[scope_shares.Op] = []
    named = False
    for key, entry in planes.items():
        if not key.startswith(trace_reduce.DEVICE_PLANE):
            continue
        names = entry.get("op_names") or []
        named = named or any(names)
        inside = [(op, nm) for op, nm in zip(entry["ops"], names)
                  if any(a - _EDGE <= op[1] and op[1] + op[2] <= b + _EDGE
                         for a, b in whole)]
        rows += _scoped([op for op, _ in inside], [nm for _, nm in inside])
    if not whole or not named:
        return None
    k = 1e3 / (steps * len(whole))
    got = scope_shares.shares(rows, scopes, kernels)
    out: Dict[str, Any] = {"executions": len(whole),
                           "total": k * got["total_s"]}
    for group in ("kernels", "scopes", "rest"):
        out[group] = {name: k * e["s"] for name, e in got[group].items()}
    return out


# the per-layer readings by scope, each a case of the one sum:
# (kernel prefixes, scopes)
READINGS = {
    "head_loss_ms": ((), ("head_loss",)),
    "moe_layer_ms": (("moe_gmm_", "moe_rows_"), ("moe/",)),
    "ssm_conv_ms": ((), ("ssm/conv",)),
}


def reading_ms(planes: Dict[str, Any], steps: int, name: str,
               program: str = "epoch_fn") -> Optional[float]:
    """Device milliseconds a step of one of :data:`READINGS`, or None
    where the trace holds none of it."""
    kernels, scopes = READINGS[name]
    got = per_step_ms(planes, steps, scopes, kernels, program)
    if got is None:
        return None
    ms = sum(got["kernels"].values()) + sum(got["scopes"].values())
    return ms or None


def idle_under_ms(names: Dict[str, Any], span: str) -> Optional[float]:
    """The lower median over the gaps between epoch programs of the
    device's idle milliseconds under ``span`` at any depth (the lower
    median, as ``epoch_gap_ms.train`` takes its gaps)."""
    gaps = names.get("epoch_gaps") or []
    if not gaps:
        return None
    return 1e3 * statistics.median_low(g["under"].get(span, 0.0)
                                       for g in gaps)


# first match wins: the named phases, then the Flax module paths
DEFAULT_SCOPES = ("moe/", "head_loss", "ssm/conv", "ssm/in_proj",
                  "ssm/scan", "ssm/gate_norm", "ssm/out_proj", "attn/rope",
                  "attn/gate", "bd_noise", "bd_diag", "optimizer", "embed",
                  "attn/", "mlp/")
DEFAULT_KERNELS = ("flash_", "moe_gmm_", "moe_rows_", "ssd_")


def summary(planes: Dict[str, Any], steps: int,
            program: str = "epoch_fn") -> Dict[str, Any]:
    """Everything above, for one trace read by hand."""
    names = reduce_names(planes, program)
    idle = names.get("idle_by_span") or {}
    lags = names.get("wait_lag_ms") or []
    out: Dict[str, Any] = {
        "idle_by_span_ms": {k: 1e3 * v for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])},
        "idle_named_share": (1.0 - idle.get(NONE, 0.0) / sum(idle.values())
                             if sum(idle.values()) else None),
        "epoch_gaps": names.get("epoch_gaps"),
        "program_spans": names.get("program_spans"),
        "wait_lag_ms_median": statistics.median(lags) if lags else None,
        "idle_in_epoch_end_ms": idle_under_ms(names, "epochEnd"),
        "per_step_ms": per_step_ms(planes, steps, DEFAULT_SCOPES,
                                   DEFAULT_KERNELS, program),
    }
    for name in READINGS:
        out[name] = reading_ms(planes, steps, name, program)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="an .xplane.pb or a directory above one")
    ap.add_argument("--steps", type=int, required=True,
                    help="steps an execution of the epoch program runs")
    args = ap.parse_args(argv)
    found = [args.trace] if os.path.isfile(args.trace) else sorted(
        glob.glob(os.path.join(args.trace, "**", "*.xplane.pb"),
                  recursive=True))
    if not found:
        print(f"no .xplane.pb at {args.trace}", file=sys.stderr)
        return 1
    planes = read_space(found[-1])
    if planes is None:
        return 1
    print(json.dumps(summary(planes, args.steps), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
