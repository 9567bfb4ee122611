"""Roofline share of the state-space scan (kernels layer): the least
time the chip could take for a Mamba-2 layer's scan, forward and
backward, in its chunked form at the configuration's chunk
(benchmark/work_granite.py:ssd_forward, ssd_backward: counted from
shapes alone, whatever implements it) over the device time of the
``%ssd_*`` custom-calls. Under recomputation the forward kernel runs
twice a layer and step and the algorithm needs it once: that time
counts against the share. Layer-steps traced: ``ssd_bwd`` runs once a
layer and step."""

from benchmark import harness, work, work_granite

kernel_seconds = harness.load_module(
    "layer_metrics", "ssd_ms.train-ssm").kernel_seconds


def read(r):
    seconds, layer_steps = kernel_seconds(r.get("trace"))
    if not layer_steps or seconds <= 0:
        return None
    f = r["facts"]
    fo, fb = work_granite.ssd_forward(r["lm"], r["config"], f["batch"],
                                      f["seq"])
    bo, bb = work_granite.ssd_backward(r["lm"], r["config"], f["batch"],
                                       f["seq"])
    least = (work.roofline_seconds(fo, fb, r["peaks"])[0]
             + work.roofline_seconds(bo, bb, r["peaks"])[0])
    return 100.0 * least * layer_steps / seconds
