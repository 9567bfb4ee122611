"""Roofline share of attention under the block-diffusion mask (kernels
layer): the least time the chip could take for a layer's forward and
backward over exactly the keys the mask shows
(benchmark/work_sdar.py:flash_bd_forward, flash_bd_backward) over the
device time of ``flash_bd_fwd``, ``flash_bd_bwd_dq`` and
``flash_bd_bwd_dkv``. The noisy half's own block (``block_length`` keys
a query, plain XLA under the scope ``bd_diag``) is in the operations
and not in the kernels' time: 4 keys of some 2,050. Under recomputation
the forward kernel runs twice a layer and step and the algorithm needs
it once. Steps traced: ``flash_bd_bwd_dkv`` runs once a layer and step."""

import re

from benchmark import work, work_sdar

KERNEL = re.compile(r"%flash_bd_(fwd|bwd_dq|bwd_dkv)(\.[\w.]+)? custom-call")


def read(r):
    trace = r.get("trace") or {}
    seconds, dkv_calls = 0.0, 0
    for name, secs in trace.get("ops", {}).items():
        m = KERNEL.fullmatch(name)
        if m:
            seconds += secs
            if m.group(1) == "bwd_dkv":
                dkv_calls += trace["op_counts"][name]
    if not dkv_calls or seconds <= 0:
        return None
    f = r["facts"]
    layers = work_sdar.sizes(r["lm"])["layers"]
    steps_traced = dkv_calls / float(layers)
    fo, fb = work_sdar.flash_bd_forward(r["lm"], r["config"], f["batch"],
                                        f["seq"])
    bo, bb = work_sdar.flash_bd_backward(r["lm"], r["config"], f["batch"],
                                         f["seq"])
    least = (work.roofline_seconds(fo, fb, r["peaks"])[0]
             + work.roofline_seconds(bo, bb, r["peaks"])[0])
    return 100.0 * least * layers * steps_traced / seconds
