"""Not part of a run: the readings the limits of ``correct`` are set
from. For each seed, the cell is run in a process of its own (this
process never touches JAX, so each child has the chip to itself) with a
short window, and its ``compared`` numbers are the PROGRAM's readings.
For the first ``--controls`` seeds a second child puts the reference in
the program's place, computed in the next lower precision (the
control) and with each fault planted (half of the batch left out; a
step that returns its state unchanged), and reads the same numbers.

    python3 benchmark/readings.py --workload internlm2-l4.train-seq4k \
        --seeds 101,102,103 --controls 3 --seconds 5 --out chiprun_out/r.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def controls_child(workload: str, seed: int, rehearse: str,
                   skip=()) -> None:
    """Training cells: reference, control and faults, same numbers."""
    from benchmark import harness
    from benchmark.reference import decoder

    _, cell, config, traffic = harness.find_cell(workload)
    lm = dict(config["language_model"])
    p = dict(traffic)
    if rehearse:
        lm = dict(harness.load_json("rehearsal.json")[rehearse][
            "language_model"])
        p.update(traffic.get("rehearsal") or {})
    else:
        harness.Device.require(int(cell["chips"]), False)
    driver = harness.load_module("drivers", traffic["driver"])
    steps, batch, seq = p["steps_per_epoch"], p["batch_size"], p["seq"]
    epochs = int(p["check_epochs"])
    data = driver.token_rows(seed, steps * batch, seq, lm["vocab_size"])
    batches = np.concatenate([data.reshape(steps, batch, seq)] * epochs)
    eps = float(config["rms_norm_eps"])
    ref = decoder.follow_steps(seed, lm, eps, batches, p["optimizer"])
    out = {"seed": seed, "kind": "controls"}
    variants = {"control_fp8": {"precision": "fp8"},
                "fault_half_batch": {"rows": list(range(batch // 2))},
                "fault_frozen_state": {"freeze": True}}
    for name, kwargs in variants.items():
        if name in skip:
            continue
        alt = decoder.follow_steps(seed, lm, eps, batches, p["optimizer"],
                                   **kwargs)
        prog = {"losses": driver.epoch_means(alt["losses"], epochs),
                "mu_norm": alt["mu_norm"],
                "change_norm": alt["change_norm"]}
        numbers, readings = driver.compare(prog, ref, p["limits"])
        # the verdict a run would give with this in the program's place
        correct, compared = harness.judge(numbers)
        out[name] = {"correct": correct,
                     "failed": [k for k, e in compared.items()
                                if not (e["value"] is not None
                                        and e["value"] <= e["limit"])],
                     "readings": readings}
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--controls", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", default="")
    ap.add_argument("--controls-child", type=int, default=None)
    ap.add_argument("--skip", default="",
                    help="variants the controls leave out, by name "
                         "(a state left unchanged reads 1 and needs no run)")
    args = ap.parse_args(argv)
    if args.controls_child is not None:
        controls_child(args.workload, args.controls_child, args.rehearse,
                       tuple(s for s in args.skip.split(",") if s))
        return 0
    seeds = [int(s) for s in args.seeds.split(",") if s]
    rehearse = ["--rehearse", args.rehearse] if args.rehearse else []
    lines = []

    def child(cmd):
        t0 = time.monotonic()
        proc = subprocess.run([sys.executable] + cmd, cwd=ROOT,
                              capture_output=True, text=True)
        tail = [ln for ln in proc.stdout.splitlines() if ln.strip()]
        row = {"rc": proc.returncode, "cmd": cmd[1:],
               "wall_s": time.monotonic() - t0}
        try:
            row["line"] = json.loads(tail[-1]) if tail else None
        except json.JSONDecodeError:
            row["line"] = None
        row["stderr_tail"] = proc.stderr[-4000:]
        lines.append(row)
        print(json.dumps(row)[:6000], flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")

    for i, seed in enumerate(seeds):
        child([os.path.join(HERE, "run.py"), "--workload", args.workload,
               "--seed", str(seed), "--seconds", str(args.seconds),
               "--trace", str(args.trace)] + rehearse)
        if i < args.controls:
            child([os.path.abspath(__file__), "--workload", args.workload,
                   "--controls-child", str(seed), "--skip", args.skip]
                  + rehearse)
    return 0


if __name__ == "__main__":
    sys.exit(main())
