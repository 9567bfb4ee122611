"""Share of the traced window in which no operation ran on the device.
The window is the device's own (first device event to last, see
``trace_reduce``), so what is read is the gaps between and inside the
programs; a busy time longer than the window would be a fault of the
reduction and shows as a negative share."""


def read(r):
    trace = r.get("trace") or {}
    if not trace.get("window_s") or not trace.get("busy_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
