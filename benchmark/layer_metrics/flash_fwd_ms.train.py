"""Device milliseconds of one flash forward call (kernels layer): the
time of the ``%flash_fwd.<n> custom-call`` ops in the trace over their
count. One call is one layer of one step."""

import re


def per_call_ms(trace, kernel):
    """Mean device time of one call of ``kernel``, or None."""
    name = re.compile(rf"%{kernel}(\.[\w.]+)? custom-call")
    ops = {k: v for k, v in (trace or {}).get("ops", {}).items()
           if name.fullmatch(k)}
    calls = sum(trace["op_counts"][k] for k in ops)
    return 1e3 * sum(ops.values()) / calls if calls else None


def read(r):
    return per_call_ms(r.get("trace"), "flash_fwd")
