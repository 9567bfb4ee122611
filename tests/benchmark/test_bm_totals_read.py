"""``totals_read_ms.train``: the epoch's totals taken to the host, read
from the fit's ``totalsRead`` spans, and listed with the three cells
whose traced runs it reads."""

import pytest

from benchmark import harness

NAME = "totals_read_ms.train"
CELLS = ["internlm2-l4.train-seq4k", "sdar-a3b-l6.train-bd4-seq4k",
         "granite-4.0-h-micro-l10.train-ssm-seq8k"]


def span(name, start, end, **attrs):
    return {"name": name, "start": start, "end": end, "attrs": attrs}


def epoch(e, start, read_s, builds=0):
    """One epoch: a dispatch, a second's wait, then ``epochEnd`` with
    ``totalsRead`` (``read_s``) and ``recordWrite`` (2 ms) in it."""
    w1 = start + 1.001
    return [span("epoch", start, w1 + read_s + 0.002, epoch=e),
            span("dispatch", start, start + 0.001, epoch=e, builds=builds),
            span("deviceWait", start + 0.001, w1, epoch=e),
            span("epochEnd", w1, w1 + read_s + 0.002, epoch=e),
            span("totalsRead", w1, w1 + read_s, epoch=e, arrays=41),
            span("recordWrite", w1 + read_s, w1 + read_s + 0.002,
                 epoch=e)]


def read(spans):
    return harness.load_module("layer_metrics", NAME).read(
        {"facts": {"spans": spans}, "trace": None})


def test_the_median_of_the_epochs_that_built_nothing():
    spans = [*epoch(0, 0.0, 0.050, builds=1), *epoch(1, 2.0, 0.019),
             *epoch(2, 4.0, 0.021), *epoch(3, 6.0, 0.017),
             span("totalsRead", 8.0, None, epoch=4)]   # still open
    assert read(spans) == pytest.approx(19.0)


def test_a_program_without_the_span_reads_nothing():
    spans = [s for s in epoch(1, 2.0, 0.019)
             if s["name"] not in ("totalsRead", "recordWrite")]
    assert read(spans) is None
    assert read([]) is None


def test_listed_with_the_cells_whose_runs_it_reads():
    bench = harness.find_cell(CELLS[0])[0]
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry["workloads"] == CELLS
    assert (entry["moves"], entry["layer"], entry["source"]) == (
        "train_tokens_per_s", "engine", "program_span")
    for cell in CELLS:
        assert NAME in {m["name"] for m in harness.metrics_of(
            bench, cell, "per_layer")}
