"""Seconds of the window job's ``artifactLoad`` span (REST + jobs
layer): reading the model's artifact back, which initialises a set of
parameters (``paramInit``) before it reads the weights
(``weightsRead``). Only a load that holds both is the model's; without
them there is nothing to read."""


def _inside(span, outer):
    return outer["start"] <= span["start"] and span["end"] <= outer["end"]


def read(r):
    spans = [s for s in r["facts"].get("spans", []) if s["end"] is not None]
    parts = {n: [s for s in spans if s["name"] == n]
             for n in ("paramInit", "weightsRead")}
    loads = [s for s in spans if s["name"] == "artifactLoad"
             and all(any(_inside(p, s) for p in parts[n]) for n in parts)]
    if not loads:
        return None
    return sum(s["end"] - s["start"] for s in loads)
