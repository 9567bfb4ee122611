"""Not part of a run: what the limits of the block-diffusion cell's
``correct`` are set from, beside the program's own readings
(``readings.py --workload sdar-a3b-l6.train-bd4-seq4k --seeds ...``,
whose child is ``run.py`` and so generic).

For each seed, the plain reference follows the cell's checked steps, and
then again in the program's place: in the next lower precision (every
product's operands in float8_e4m3) and with each planted fault (half of
the batch left out; one held expert's output dropped). Each variant's
numbers are the ones a run compares, judged by the cell's limits.

    python3 benchmark/readings_sdar.py --seeds 101,102 [--rehearse tiny] \
        [--skip fault_half_batch]

One process for all the seeds, and one line for each seed and variant as
soon as it is judged: the sound reference and both faults share one
compiled step (the faults are its arguments), the control has its own.
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

WORKLOAD = "sdar-a3b-l6.train-bd4-seq4k"


def controls(seed: int, rehearse: str, skip=()):
    from benchmark import harness
    from benchmark.reference import sdar_moe

    _, cell, config, traffic = harness.find_cell(WORKLOAD)
    lm = dict(config["language_model"])
    p = dict(traffic)
    if rehearse:
        p.update(traffic.get("rehearsal") or {})
        lm = dict(p["language_model"])
    else:
        harness.Device.require(int(cell["chips"]), False)
    driver = harness.load_module("drivers", traffic["driver"])
    steps, batch, seq = p["steps_per_epoch"], p["batch_size"], p["seq"]
    epochs = int(p["check_epochs"])
    data = driver.token_rows(seed, steps * batch, seq, lm["vocab_size"])
    batches = np.concatenate([data.reshape(steps, batch, seq)] * epochs)
    follow = lambda **kw: sdar_moe.follow_steps(  # noqa: E731
        seed, lm, float(config["rms_norm_eps"]), batches, p["optimizer"],
        fit_seed=int(p["fit_seed"]), **kw)
    ref = follow()
    variants = {"control_fp8": {"precision": "fp8"},
                "fault_dropped_expert": {"drop_expert": 1},
                "fault_half_batch": {"rows": list(range(batch // 2))}}
    for name, kwargs in variants.items():
        if name in skip:
            continue
        alt = follow(**kwargs)
        prog = {"losses": driver.epoch_means(alt["losses"], epochs),
                "mu_norm": alt["mu_norm"],
                "change_norm": alt["change_norm"],
                "counters": driver.reference_counters(alt, epochs)}
        numbers, readings = driver.compare(prog, ref, p["limits"])
        correct, compared = harness.judge(numbers)
        yield {"seed": seed, "variant": name, "correct": correct,
               "failed": [k for k, e in compared.items()
                          if not (e["value"] is not None
                                  and e["value"] <= e["limit"])],
               "readings": readings}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", default="")
    ap.add_argument("--skip", default="")
    args = ap.parse_args(argv)
    skip = tuple(s for s in args.skip.split(",") if s)
    for seed in args.seeds.split(","):
        for line in controls(int(seed), args.rehearse, skip):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
