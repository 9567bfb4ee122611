"""Median host time between one epoch's ``deviceWait`` ending and the
next epoch's ``dispatch`` returning (engine layer), over consecutive
epochs that built nothing: the host's share of the gap between two
epoch programs (``epoch_gap_ms.train`` is the device's view of it)."""

import statistics


def _by_epoch(spans, name):
    return {s["attrs"]["epoch"]: s for s in spans
            if s["name"] == name and s["end"] is not None
            and "epoch" in s["attrs"]}


def read(r):
    spans = r["facts"].get("spans", [])
    dispatch = _by_epoch(spans, "dispatch")
    wait = _by_epoch(spans, "deviceWait")
    built = {e for e, s in dispatch.items() if s["attrs"].get("builds")}
    turns = [dispatch[e + 1]["end"] - wait[e]["end"]
             for e in sorted(wait)
             if e + 1 in dispatch and not {e, e + 1} & built]
    if not turns:
        return None
    return 1e3 * statistics.median(turns)
