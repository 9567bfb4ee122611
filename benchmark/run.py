"""Run ONE cell of the benchmark once, in this process.

    python3 benchmark/run.py --workload <config>.<mix> --seed N \
        --seconds S --trace 0|1

asserts the accelerator, starts the program's REST server in this
process, lets the cell's driver warm up and measure for S seconds over
HTTP, decides ``correct`` against the plain reference, and prints one
JSON object as the last line of standard output. ``--rehearse tiny``
is the CPU rehearsal of the control flow: it is never the default, it
prints no metric, and its line says so.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class RunContext:
    """What a driver is handed: the cell's data, the server, the
    window's marks and the way to shut the program down."""

    def __init__(self, args, bench, cell, config, traffic, device, rehearsal):
        from benchmark import harness

        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.bench, self.cell, self.config = bench, cell, config
        self.rehearsal = rehearsal
        self.lm_kwargs = dict(config["language_model"])
        self.params = dict(traffic)
        if rehearsal:
            sizes = harness.load_json("rehearsal.json")[rehearsal]
            self.lm_kwargs = dict(sizes["language_model"])
            self.params.update(traffic.get("rehearsal") or {})
        self.eps = float(config["rms_norm_eps"])
        self.device = device
        self.compiles = harness.CompileCounter()
        self.server = harness.Server()
        self.profile = harness.Profile() if self.trace else None
        self.setup_s = None
        self._down = False

    def open_window(self, t_open: float) -> None:
        self.setup_s = t_open - T_START
        self.compiles.mark()

    def close_window(self, t_close: float):
        return self.compiles.since()

    def job_spans(self, trace_id: str):
        from learningorchestra_tpu.observability import trace as obs_trace

        return [{"name": s.name, "start": s.start, "end": s.end,
                 "attrs": dict(s.attrs)}
                for s in obs_trace.spans_of(trace_id)]

    def shutdown_program(self) -> None:
        """Stop the server and free what the program holds on the
        device, before the reference runs."""
        from benchmark import harness

        if not self._down:
            self._down = True
            self.server.stop()
            harness.free_device_memory()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="directory to copy the run's .xplane.pb into")
    ap.add_argument("--rehearse", default="",
                    help="CPU rehearsal size (tiny); prints no metric")
    args = ap.parse_args(argv)

    from benchmark import harness

    bench, cell, config, traffic = harness.find_cell(args.workload)
    device = harness.Device.require(int(cell["chips"]), bool(args.rehearse))
    ctx = RunContext(args, bench, cell, config, traffic, device,
                     args.rehearse)
    reduced = None
    try:
        driver = harness.load_module("drivers", traffic["driver"])
        result = driver.run(ctx)
        if ctx.profile is not None:
            reduced = ctx.profile.reduce()
    finally:
        ctx.shutdown_program()
        if ctx.profile is not None:
            ctx.profile.keep(args.keep_trace)  # also of a run that failed
            ctx.profile.cleanup()

    correct, compared = harness.judge(result["numbers"])
    facts = result["facts"]
    facts["setup_s"] = ctx.setup_s
    plain_facts = {k: v for k, v in facts.items()
                   if isinstance(v, (int, float))}
    mem = result["memory"]
    dev = {"platform": device.platform, "kind": device.kind,
           "count": device.count, "memory_peak_bytes": mem["peak"]}
    metrics = {}
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": dev}
    if not ctx.rehearsal:
        if not ctx.trace:
            values = dict(result["end_to_end"], setup_s=ctx.setup_s)
            for m in harness.metrics_of(bench, cell["name"], "end_to_end"):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        else:
            from benchmark import work

            reading = {"facts": facts, "trace": reduced, "memory": mem,
                       "lm": ctx.lm_kwargs, "config": config,
                       "peaks": work.peaks_for(device.kind),
                       "end_to_end": result["end_to_end"]}
            for m in harness.metrics_of(bench, cell["name"], "per_layer"):
                reader = harness.load_module("layer_metrics", m["name"])
                value = reader.read(reading)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        line["rehearsal"] = {"size": ctx.rehearsal,
                             "end_to_end_names": sorted(
                                 result["end_to_end"]),
                             "facts": plain_facts}
    if reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                             "idle_gaps": reduced["idle_gaps"][:10]}
    line["compared"] = compared
    print("facts: " + json.dumps(plain_facts), file=sys.stderr)
    for name, entry in compared.items():
        print(f"compared {name}: {entry['value']!r} limit "
              f"{entry['limit']!r}", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
