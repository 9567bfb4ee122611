"""Seconds of the window job's ``compile`` spans (engine layer): what
the fit spent tracing, lowering and compiling before its first step."""


def read(r):
    spans = [s for s in r["facts"].get("spans", [])
             if s["name"] == "compile" and s["end"] is not None]
    if not spans:
        return None
    return sum(s["end"] - s["start"] for s in spans)
