"""Median host milliseconds of ``totalsRead`` (engine layer): inside
``epochEnd``, the epoch's totals taken to the host one device array at
a time (the span's ``arrays``), over the fit's epochs whose
``dispatch`` built nothing. The device waits through it: the next
epoch's program has not been dispatched yet."""

import statistics


def read(r):
    spans = r["facts"].get("spans", [])
    built = {s["attrs"].get("epoch") for s in spans
             if s["name"] == "dispatch" and s["attrs"].get("builds")}
    reads = [s["end"] - s["start"] for s in spans
             if s["name"] == "totalsRead" and s["end"] is not None
             and s["attrs"].get("epoch") not in built]
    return 1e3 * statistics.median(reads) if reads else None
