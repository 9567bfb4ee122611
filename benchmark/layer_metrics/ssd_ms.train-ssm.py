"""Device milliseconds of the state-space scan's kernels a Mamba-2 layer
and step, forward and backward (kernels layer): the time of every
``%ssd_*`` custom-call in the trace over the layer-steps traced.
``ssd_bwd`` runs once a layer and step, so its calls count them; under
per-block recomputation ``ssd_fwd`` runs twice, and both are in the
time."""

import re

KERNEL = re.compile(r"%ssd_(\w+?)(\.[\w.]+)? custom-call")


def kernel_seconds(trace):
    """(seconds of all ``ssd_*`` kernels, calls of ``ssd_bwd``)."""
    seconds, bwd_calls = 0.0, 0
    for name, secs in (trace or {}).get("ops", {}).items():
        m = KERNEL.fullmatch(name)
        if m:
            seconds += secs
            if m.group(1) == "bwd":
                bwd_calls += trace["op_counts"][name]
    return seconds, bwd_calls


def read(r):
    seconds, layer_steps = kernel_seconds(r.get("trace"))
    if not layer_steps or seconds <= 0:
        return None
    return 1e3 * seconds / layer_steps
