"""Roofline share of the flash attention kernels (forward and the two
backward calls): the least time the chip could take for the attention
the traced steps needed (benchmark/work.py: operations and bytes at the
stated precision) over the device time of the kernels' events."""

from benchmark import work

# The program gives its Pallas kernels no name of their own: they show
# as the step's custom-call ops (%attn.<n>). The step's other custom
# calls are markers of no length, told apart by their mean duration.
KERNEL_OPCODE = "custom-call"
MIN_MEAN_SECONDS = 1e-5


def read(r):
    trace = r.get("trace") or {}
    f = r["facts"]
    counts = trace.get("op_counts", {})
    kernels = {k: v for k, v in trace.get("ops", {}).items()
               if k.endswith(" " + KERNEL_OPCODE)
               and v / max(counts.get(k, 1), 1) > MIN_MEAN_SECONDS}
    seconds = sum(kernels.values())
    if seconds <= 0:
        return None
    layers = work.sizes(r["lm"])["layers"]
    # a step calls one forward and two backward kernels in each layer;
    # the window cuts epochs, so count the calls that were traced
    calls = sum(counts[k] for k in kernels)
    steps_traced = calls / (3.0 * layers)
    fo, fb = work.flash_forward(r["lm"], r["config"], f["batch"], f["seq"])
    bo, bb = work.flash_backward(r["lm"], r["config"], f["batch"], f["seq"])
    least = (work.roofline_seconds(fo, fb, r["peaks"])[0]
             + work.roofline_seconds(bo, bb, r["peaks"])[0])
    return 100.0 * least * layers * steps_traced / seconds
