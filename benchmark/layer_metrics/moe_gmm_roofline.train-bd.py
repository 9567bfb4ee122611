"""Roofline share of the expert layer's grouped products (kernels layer):
the least time the chip could take for the nine products a layer and
step needs over the EXPECTED copies (benchmark/work_sdar.py:moe_gmm)
over the device time of the kernels ``moe_gmm_fwd``, ``moe_gmm_dx`` and
``moe_gmm_dw``. A step under recomputation calls the forward kernel
twice a product; the algorithm needs it once, so that time counts
against the share. Steps traced: ``moe_gmm_dw`` runs three times a
layer and step, recomputation or not."""

import re

from benchmark import work, work_sdar

KERNEL = re.compile(r"%moe_gmm_(fwd|dx|dw)(\.[\w.]+)? custom-call")


def kernel_seconds(trace, pattern):
    """({kind: seconds}, {kind: calls}) of the ops ``pattern`` names."""
    seconds, calls = {}, {}
    for name, secs in (trace or {}).get("ops", {}).items():
        m = pattern.fullmatch(name)
        if m:
            seconds[m.group(1)] = seconds.get(m.group(1), 0.0) + secs
            calls[m.group(1)] = calls.get(m.group(1), 0) \
                + trace["op_counts"][name]
    return seconds, calls


def read(r):
    seconds, calls = kernel_seconds(r.get("trace"), KERNEL)
    if not calls.get("dw") or sum(seconds.values()) <= 0:
        return None
    f = r["facts"]
    layers = work_sdar.sizes(r["lm"])["layers"]
    steps_traced = calls["dw"] / (3.0 * layers)
    need = work_sdar.moe_gmm(r["lm"], r["config"], f["batch"], f["seq"])
    least = sum(work.roofline_seconds(ops, byt, r["peaks"])[0]
                for ops, byt in need.values())
    return 100.0 * least * layers * steps_traced / sum(seconds.values())
