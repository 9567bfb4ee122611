"""Device milliseconds of one layer-step's flash backward (kernels
layer): one ``flash_bwd_dq`` call and one ``flash_bwd_dkv`` call, each
the time of its ``custom-call`` ops in the trace over their count."""

from benchmark import harness

KERNELS = ("flash_bwd_dq", "flash_bwd_dkv")
per_call_ms = harness.load_module("layer_metrics",
                                  "flash_fwd_ms.train").per_call_ms


def read(r):
    parts = [per_call_ms(r.get("trace"), k) for k in KERNELS]
    return None if None in parts else sum(parts)
