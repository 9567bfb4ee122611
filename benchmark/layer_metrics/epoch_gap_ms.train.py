"""Median idle gap on the device between one epoch program's end and
the next one's start (engine layer; from the device trace)."""

import statistics


def read(r):
    trace = r.get("trace") or {}
    name = r["facts"]["program_module"]
    gaps = trace.get("same_program_gaps", {}).get(name)
    if not gaps:
        return None
    return 1e3 * statistics.median(gaps)
