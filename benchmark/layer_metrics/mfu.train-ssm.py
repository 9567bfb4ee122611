"""The whole hybrid step's share of the chip's bf16 peak: model FLOPs of
a step (benchmark/work_granite.py: every kernel a token meets, the scan
in its chunked form at the configuration's chunk, causal attention in
the attention layer, the tied head; forward plus twice that,
recomputation not counted) times the steps of an epoch, over the epoch
program's start-to-start time in the device trace
(``trace_reduce.program_period_s``, as ``mfu.train`` takes it) and the
peak."""

from benchmark import trace_reduce, work_granite


def read(r):
    f = r["facts"]
    period = trace_reduce.program_period_s(r.get("trace"),
                                           f["program_module"])
    if not period:
        return None
    flops = work_granite.train_flops_per_step(r["lm"], f["batch"], f["seq"])
    return (100.0 * flops["total"] * f["steps"] / period
            / r["peaks"]["bf16_flops_per_s"])
