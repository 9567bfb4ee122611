"""The per-layer readers that read the program's spans and its named
kernels, on hand-made ``facts["spans"]`` and ``trace["ops"]``: each
gives the hand-computed value, and nothing where its spans or kernels
are absent (as on a program from before the spans)."""

import pytest

from benchmark import harness

READERS = ("compile_count.train", "artifact_load_s.train",
           "fit_setup_s.train", "epoch_turnaround_ms.train",
           "flash_fwd_ms.train", "flash_bwd_ms.train")


def span(name, start, end, **attrs):
    return {"name": name, "start": start, "end": end, "attrs": attrs}


def epoch(e, start, dispatch_s, wait_s, end_s, **dispatch_attrs):
    """One epoch's spans, back to back from ``start``."""
    d1 = start + dispatch_s
    w1 = d1 + wait_s
    return [span("epoch", start, w1 + end_s, epoch=e),
            span("dispatch", start, d1, epoch=e, **dispatch_attrs),
            span("deviceWait", d1, w1, epoch=e),
            span("epochEnd", w1, w1 + end_s, epoch=e)]


def a_fit():
    """submit at 100 s; the artifact load 101–111 s; epochs 0 and 1
    build (9 s and 8 s), epoch 4 builds again (a late retrace), the
    others do not and turn around in 3, 7 and 3 ms."""
    spans = [
        span("submit", 100.0, 100.5),
        span("dataLoad", 101.0, 112.0),
        span("artifactLoad", 101.0, 111.0, artifact="m", bytes=10),
        span("paramInit", 101.0, 105.0),
        span("weightsRead", 105.0, 111.0, bytes=9),
        span("artifactLoad", 111.5, 111.75, artifact="rows", bytes=1),
        *epoch(0, 120.0, 9.0, 1.0, 0.002, builds=1, traceSeconds=3.0),
        span("compile", 120.0, 129.0, executable=1, epoch=0, cold=True),
        *epoch(1, 130.002, 8.0, 1.0, 0.002, builds=1, traceSeconds=3.0),
        span("compile", 130.002, 138.002, executable=2, epoch=1,
             cold=True),
        # dispatch[e+1].end - deviceWait[e].end = end_s + dispatch_s
        *epoch(2, 139.004, 0.001, 1.0, 0.002),
        *epoch(3, 140.007, 0.001, 1.0, 0.002),     # 3 ms after epoch 2
        *epoch(4, 141.010, 0.5, 1.0, 0.002, builds=1),  # built: left out
        span("compile", 141.010, 141.510, executable=3, epoch=4,
             cold=False),
        *epoch(5, 142.512, 0.001, 1.0, 0.006),     # follows a build
        *epoch(6, 143.519, 0.001, 1.0, 0.002),     # 7 ms after epoch 5
        *epoch(7, 144.522, 0.001, 1.0, 0.002),     # 3 ms after epoch 6
    ]
    return {"facts": {"spans": spans}, "trace": None}


def a_trace():
    """Two layers' kernels over 10 steps each, beside other ops."""
    ops = {"%flash_fwd.1 custom-call": 0.020,
           "%flash_fwd.2 custom-call": 0.022,
           "%flash_bwd_dq.1 custom-call": 0.026,
           "%flash_bwd_dq.2.remat custom-call": 0.026,
           "%flash_bwd_dkv.1 custom-call": 0.020,
           "%flash_bwd_dkv.2 custom-call": 0.020,
           "%flash_fwd_other.1 custom-call": 5.0,
           "%attn.7 custom-call": 5.0,
           "%fusion.1 fusion": 1.0}
    counts = {k: 10 for k in ops}
    return {"facts": {"spans": []},
            "trace": {"ops": ops, "op_counts": counts}}


def read(name, reading):
    return harness.load_module("layer_metrics", name).read(reading)


def test_every_new_reader_is_listed_with_its_cell():
    bench = harness.find_cell("internlm2-l4.train-seq4k")[0]
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert listed[name]["workloads"] == ["internlm2-l4.train-seq4k"]


@pytest.mark.parametrize("name,want", [
    ("compile_count.train", 3),
    ("compile_s.train", 9.0 + 8.0 + 0.5),
    ("artifact_load_s.train", 10.0),
    ("fit_setup_s.train", 139.004 - 100.0),
    # epochs 2->3, 5->6, 6->7: 3, 7 and 3 ms; 3->4, 4->5 built
    ("epoch_turnaround_ms.train", 3.0)])
def test_span_readers_give_the_hand_computed_value(name, want):
    assert read(name, a_fit()) == pytest.approx(want, abs=1e-6)


@pytest.mark.parametrize("name,want", [
    ("flash_fwd_ms.train", 1e3 * 0.042 / 20),
    ("flash_bwd_ms.train", 1e3 * (0.052 / 20 + 0.040 / 20))])
def test_kernel_readers_give_the_hand_computed_value(name, want):
    assert read(name, a_trace()) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_is_none(name):
    # the program before the spans: one whole-first-epoch ``compile``
    # without ``executable``, kernels named after the module
    old = {"facts": {"spans": [span("submit", 0.0, 0.1),
                               span("dataLoad", 1.0, 2.0),
                               span("compile", 2.0, 17.0, cold=True),
                               span("epoch", 2.0, 17.0, epoch=0)]},
           "trace": {"ops": {"%attn.160 custom-call": 1.0},
                     "op_counts": {"%attn.160 custom-call": 10}}}
    assert read(name, old) is None
    assert read(name, {"facts": {}, "trace": None}) is None


def test_turnaround_leaves_out_epochs_that_built():
    reading = a_fit()
    spans = reading["facts"]["spans"] = [
        s for s in reading["facts"]["spans"]
        if s["attrs"].get("epoch") in (3, 4, 5)]
    # 3 -> 4 and 4 -> 5 both touch the build of epoch 4
    assert read("epoch_turnaround_ms.train", reading) is None
    for s in spans:
        s["attrs"].pop("builds", None)
    # with no build known they count: 502 ms and 3 ms
    assert read("epoch_turnaround_ms.train", reading) == \
        pytest.approx((502.0 + 3.0) / 2, abs=1e-6)


def test_artifact_load_needs_both_of_its_parts():
    reading = a_fit()
    reading["facts"]["spans"] = [s for s in reading["facts"]["spans"]
                                 if s["name"] != "paramInit"]
    assert read("artifact_load_s.train", reading) is None


def test_fit_setup_reads_nothing_once_the_ring_dropped_a_build():
    reading = a_fit()
    reading["facts"]["spans"] = [
        s for s in reading["facts"]["spans"]
        if s["name"] == "compile" or s["attrs"].get("epoch", 9) >= 2]
    assert read("fit_setup_s.train", reading) is None
    assert read("compile_count.train", reading) == 3
