"""Not part of a run: the device time of a kept trace by named kernel
and by the scope in the ops' ``op_name``.

    python3 benchmark/run.py --workload <cell> --seed N --seconds 15 \
        --trace 1 --keep-trace DIR
    python3 benchmark/scope_shares.py DIR \
        --scopes moe/route,moe/experts,moe/combine,bd_diag,head_loss \
        --kernels flash_bd_,moe_gmm_

``trace_reduce.py`` names an op by its HLO line, which carries no scope:
the ``jax.named_scope`` an op was traced under is in the ``tf_op`` stat
of the event's METADATA, which ``jax.profiler.ProfileData`` does not
show. This reads the raw ``.xplane.pb`` (the protobuf classes come with
the installed TensorFlow) and prints one JSON object: the device
seconds and op count of each kernel (a result named with one of
``--kernels``' prefixes, its number stripped), of each scope (the first
of ``--scopes`` found in the op's ``op_name``; kernels are not counted
again) and of ``rest`` by opcode. PERF.md section 5's table of the
block-diffusion cell is this output over the steps traced.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, Iterable, List, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402

Op = Tuple[str, str, str, float]   # result name, opcode, op_name, seconds


def read_ops(path: str) -> List[Op]:
    """The ``XLA Ops`` events of every device plane of one
    ``.xplane.pb``; containers (``while``) left out, as
    ``trace_reduce`` leaves them."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ops: List[Op] = []
    for plane in space.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        stat_names = plane.stat_metadata
        described = {}
        for mid, meta in plane.event_metadata.items():
            op_name = ""
            for stat in meta.stats:
                if stat_names[stat.metadata_id].name == "tf_op":
                    op_name = stat.str_value or (
                        stat_names[stat.ref_value].name
                        if stat.ref_value else "")
            lhs, _, opcode = trace_reduce.short_name(meta.name).partition(" ")
            described[mid] = (lhs, opcode, op_name)
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for event in line.events:
                lhs, opcode, op_name = described[event.metadata_id]
                if opcode not in trace_reduce.CONTAINERS:
                    ops.append((lhs, opcode, op_name,
                                event.duration_ps * 1e-12))
    return ops


def shares(ops: Iterable[Op], scopes: Sequence[str],
           kernels: Sequence[str]) -> Dict[str, Any]:
    """{"total_s", "kernels", "scopes", "rest"}: each a
    ``{name: {"s", "n"}}`` (``rest`` by opcode)."""
    out: Dict[str, Any] = {"total_s": 0.0, "kernels": {}, "scopes": {},
                           "rest": {}}

    def add(group: str, name: str, seconds: float) -> None:
        entry = out[group].setdefault(name, {"s": 0.0, "n": 0})
        entry["s"] += seconds
        entry["n"] += 1

    for lhs, opcode, op_name, seconds in ops:
        out["total_s"] += seconds
        name = re.sub(r"[.\d]+$", "", lhs.lstrip("%"))
        if any(name.startswith(k) for k in kernels):
            add("kernels", name, seconds)
            continue
        scope = next((s for s in scopes if s in op_name), None)
        if scope is not None:
            add("scopes", scope, seconds)
        else:
            add("rest", opcode or name, seconds)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--scopes", default="moe/route,moe/experts,moe/combine,"
                    "bd_noise,bd_diag,head_loss,optimizer,embed")
    ap.add_argument("--kernels", default="flash_,moe_gmm_")
    args = ap.parse_args(argv)
    found = sorted(glob.glob(os.path.join(args.trace_dir, "**",
                                          "*.xplane.pb"), recursive=True))
    if not found:
        print(f"no .xplane.pb under {args.trace_dir}", file=sys.stderr)
        return 1
    split = lambda s: [x for x in s.split(",") if x]  # noqa: E731
    print(json.dumps(shares(read_ops(found[-1]), split(args.scopes),
                            split(args.kernels)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
