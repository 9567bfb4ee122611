"""Roofline share of the flash kernels in the hybrid's attention layers
(kernels layer), at heads of 64 and a given scale: the least time the
chip could take for a layer's causal attention, forward and backward
(benchmark/work.py's flash count, for one layer: work_granite.
attention_lm), over the device time of ``flash_fwd``, ``flash_bwd_dq``
and ``flash_bwd_dkv``, told by their names. Layer-steps traced:
``flash_bwd_dkv`` runs once an attention layer and step; under
recomputation the forward runs twice and the algorithm needs it once."""

import re

from benchmark import work, work_granite

KERNEL = re.compile(r"%flash_(fwd|bwd_dq|bwd_dkv)(\.[\w.]+)? custom-call")


def read(r):
    trace = r.get("trace") or {}
    seconds, layer_steps = 0.0, 0
    for name, secs in trace.get("ops", {}).items():
        m = KERNEL.fullmatch(name)
        if m:
            seconds += secs
            if m.group(1) == "bwd_dkv":
                layer_steps += trace["op_counts"][name]
    if not layer_steps or seconds <= 0:
        return None
    f = r["facts"]
    lm = work_granite.attention_lm(r["lm"])
    fo, fb = work.flash_forward(lm, r["config"], f["batch"], f["seq"])
    bo, bb = work.flash_backward(lm, r["config"], f["batch"], f["seq"])
    least = (work.roofline_seconds(fo, fb, r["peaks"])[0]
             + work.roofline_seconds(bo, bb, r["peaks"])[0])
    return 100.0 * least * layer_steps / seconds
