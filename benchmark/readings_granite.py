"""Not part of a run: what the limits of the hybrid state-space cell's
``correct`` are set from, beside the program's own readings (a run's
standard error carries them whole, on the line ``raw:``).

For each seed, the plain reference (``reference/granite_hybrid.py``)
follows the cell's checked steps, and then again in the program's place,
once for each of ``VARIANTS``: in the precision the configuration states
(every product's operands in bfloat16: has to be judged correct), in the
next lower one (float8_e4m3: the control) and with each planted fault.
Each variant's numbers are the ones a run compares, judged by the cell's
limits.

    python3 benchmark/readings_granite.py --seeds 101,102 \
        [--rehearse tiny] [--variants bf16,fault_dropped_state] \
        [--out chiprun_out/x.jsonl]
    python3 benchmark/readings_granite.py --judge FILE [FILE ...]

``--judge`` judges kept lines again, without the chip, under the limits
as they are now: lines of ``--out``, and the standard error of runs
(``raw:``). It prints each line's readings without the matrices.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.readings_sdar import judged, kept_lines  # noqa: E402

WORKLOAD = "granite-4.0-h-micro-l10.train-ssm-seq8k"

# name -> follow_steps' arguments. The cell's limits have to judge
# ``bf16`` correct and every other one not.
VARIANTS = {
    "bf16": {"precision": "bf16"},
    "control_fp8": {"precision": "fp8"},
    "fault_half_batch": {"fault": "half"},
    "fault_dropped_state": {"fault": "drop_state"},
    "fault_no_gate": {"fault": "no_gate"},
}


def cell(rehearse: str = ""):
    """(driver, traffic parameters, model, rms_norm_eps) of the cell."""
    from benchmark import harness

    _, entry, config, traffic = harness.find_cell(WORKLOAD)
    lm = dict(config["language_model"])
    p = dict(traffic)
    if rehearse:
        p.update(traffic.get("rehearsal") or {})
        lm = dict(p["language_model"])
    else:
        harness.Device.require(int(entry["chips"]), False)
    driver = harness.load_module("drivers", traffic["driver"])
    return driver, p, lm, float(config["rms_norm_eps"])


def controls(seed: int, rehearse: str, variants=None):
    from benchmark.reference import granite_hybrid

    driver, p, lm, eps = cell(rehearse)
    steps, batch, seq = p["steps_per_epoch"], p["batch_size"], p["seq"]
    epochs = int(p["check_epochs"])
    data = driver.token_rows(seed, steps * batch, seq, lm["vocab_size"])
    batches = np.concatenate([data.reshape(steps, batch, seq)] * epochs)
    follow = lambda **kw: granite_hybrid.follow_steps(  # noqa: E731
        seed, lm, eps, batches, p["optimizer"], **kw)
    ref = follow()
    for name, arguments in VARIANTS.items():
        if variants and name not in variants:
            continue
        prog = driver.in_the_programs_place(follow(**arguments), epochs)
        yield dict(judged(driver, p["limits"], prog, ref), seed=seed,
                   variant=name, raw=driver.raw_readings(prog, ref))


def judge_again(paths) -> None:
    from benchmark import harness

    traffic = harness.find_cell(WORKLOAD)[3]
    driver = harness.load_module("drivers", traffic["driver"])
    for label, raw in kept_lines(paths):
        line = judged(driver, traffic["limits"], raw["prog"], raw["ref"])
        line["readings"] = {k: v for k, v in line["readings"].items()
                            if not isinstance(v, (list, dict))}
        print(json.dumps(dict(line, run=label)), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rehearse", default="")
    ap.add_argument("--variants", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--judge", nargs="+", default=[])
    args = ap.parse_args(argv)
    if args.judge:
        judge_again(args.judge)
        return 0
    variants = tuple(s for s in args.variants.split(",") if s)
    unknown = set(variants) - set(VARIANTS)
    if unknown:
        sys.exit(f"no such variant: {sorted(unknown)}; {sorted(VARIANTS)}")
    for seed in (s for s in args.seeds.split(",") if s):
        for line in controls(int(seed), args.rehearse, variants):
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(line) + "\n")
            line.pop("raw")
            line["readings"] = {k: v for k, v in line["readings"].items()
                                if not isinstance(v, (list, dict))}
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
