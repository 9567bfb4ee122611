"""benchmark/work.py against counts worked by hand, and the table of
peaks."""

import json
import os

import pytest

from benchmark import harness, work

INTERN = harness.load_json("configs", "internlm2-l4.json")


@pytest.mark.parametrize("config,key,want", [
    # q 2048x2048, k and v 2048x1024, o 2048x2048, three 2048x8192
    (INTERN, "layer_matmul", 62_914_560),
    (INTERN, "head", 189_530_112),
    (INTERN, "matmul", 4 * 62_914_560 + 189_530_112),
    (INTERN, "total", 630_736_896),
    (INTERN, "embed", 189_530_112),
])
def test_param_counts(config, key, want):
    assert work.param_counts(config["language_model"])[key] == want


def test_train_flops_per_token_internlm():
    lm = INTERN["language_model"]
    # forward: 2 x 441,188,352 + QK^T and PV over a mean of 2048.5 keys
    # in 4 layers of 16 heads of 128; backward twice that
    fwd = 2 * 441_188_352 + 4 * 4 * 16 * 128 * 2048.5
    assert work.train_flops_per_token(lm, 4096) == pytest.approx(3 * fwd)


@pytest.mark.parametrize("start,stop,window,want", [
    (0, 10, 0, 55), (0, 10, 4, 34), (3, 6, 4, 4 + 4 + 4),
    (0, 4, 4, 10), (2, 3, 0, 3)])
def test_keys_seen(start, stop, window, want):
    assert work.keys_seen(start, stop, window) == want
    # position p sees p + 1 keys, or the window where that is fewer
    assert want == sum(min(p + 1, window) if window else p + 1
                       for p in range(start, stop))


def test_flash_ops_and_bytes_internlm():
    lm = INTERN["language_model"]
    ops, byt = work.flash_forward(lm, INTERN, 2, 4096)
    assert ops == 4 * 2 * 16 * 128 * (4096 * 4097 / 2)
    assert byt == 2 * 4096 * 128 * 2 * (2 * 16 + 2 * 8)
    bops, bbyt = work.flash_backward(lm, INTERN, 2, 4096)
    assert bops == 2.5 * ops and bbyt == 2 * byt


def test_roofline_says_which_bound():
    peaks = work.peaks_for("TPU v5 lite")
    assert work.roofline_seconds(197e12, 1.0, peaks) == (1.0, "compute")
    t, bound = work.roofline_seconds(1.0, 819e9, peaks)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peaks_for("_source")


def test_benchmark_json_names_files_that_exist():
    root = harness.ROOT
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(root, c["file"]))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        traffic = harness.load_json("traffic", w["traffic"] + ".json")
        harness.load_module("drivers", traffic["driver"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"
    assert 1 <= bench["run_seconds"] <= 51
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert hasattr(harness.load_module("layer_metrics", m["name"]),
                       "read")
