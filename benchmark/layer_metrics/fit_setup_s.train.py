"""Seconds from the window job's ``submit`` to the start of its first
``dispatch`` that built nothing (REST + jobs layer): everything a fit
pays before its first steady epoch, builds included. The trace's ring
drops a long fit's oldest epochs first and keeps every ``compile``
span: where a build's own dispatch is gone, the first steady one may be
gone with it, and nothing is read."""


def read(r):
    spans = r["facts"].get("spans", [])
    submit = [s["start"] for s in spans if s["name"] == "submit"]
    dispatch = [s for s in spans if s["name"] == "dispatch"
                and s["end"] is not None]
    kept = {s["attrs"].get("epoch") for s in dispatch}
    if any(s["attrs"].get("epoch") not in kept for s in spans
           if s["name"] == "compile" and "executable" in s["attrs"]):
        return None
    steady = [s["start"] for s in dispatch if not s["attrs"].get("builds")]
    if not submit or not steady:
        return None
    return min(steady) - min(submit)
