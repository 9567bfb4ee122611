"""The new cell's four per-layer readers on a synthetic reading: the
numbers they give, and nothing (no error) where the program has no such
kernel or counter, as the parent commit has not."""

import pytest

from benchmark import harness, work, work_sdar

SDAR = harness.load_json("configs", "sdar-a3b-l6.json")
LM = SDAR["language_model"]
PEAKS = work.peaks_for("TPU v5 lite")
CELL = "sdar-a3b-l6.train-bd4-seq4k"


def _reader(name):
    return harness.load_module("layer_metrics", name)


def _reading(ops=None, counts=None, **facts):
    base = {"batch": 2, "seq": 4096, "tokens": 8 * 8192, "window_s": 4.0}
    return {"facts": dict(base, **facts), "lm": LM, "config": SDAR,
            "peaks": PEAKS, "memory": {}, "end_to_end": {},
            "trace": None if ops is None else {"ops": ops,
                                               "op_counts": counts}}


def test_mfu_is_model_flops_a_step_over_the_peak():
    # 8 steps of 8,192 row tokens in 4 s: 2 steps a second
    got = _reader("mfu.train-bd").read(_reading())
    flops = work_sdar.train_flops_per_step(LM, 2, 4096)["total"]
    assert got == pytest.approx(100 * flops * 2 / 197e12)
    assert 20 < got < 30
    assert _reader("mfu.train-bd").read(_reading(tokens=0)) is None


def test_moe_gmm_roofline_counts_steps_by_the_dw_kernel():
    # two steps traced: 6 layers x 3 products, forward run twice a
    # product (recomputation), every kernel call 1 ms
    ops = {"%moe_gmm_fwd.3 custom-call": 0.072,
           "%moe_gmm_dx.1 custom-call": 0.036,
           "%moe_gmm_dw custom-call": 0.036,
           "%flash_bd_fwd.2 custom-call": 1.0, "%fusion.7 fusion": 5.0}
    counts = {"%moe_gmm_fwd.3 custom-call": 72,
              "%moe_gmm_dx.1 custom-call": 36,
              "%moe_gmm_dw custom-call": 36,
              "%flash_bd_fwd.2 custom-call": 12, "%fusion.7 fusion": 9}
    got = _reader("moe_gmm_roofline.train-bd").read(_reading(ops, counts))
    need = work_sdar.moe_gmm(LM, SDAR, 2, 4096)
    least = sum(work.roofline_seconds(o, b, PEAKS)[0]
                for o, b in need.values())
    assert got == pytest.approx(100 * least * 6 * 2 / 0.144)
    assert 0 < got < 100


def test_flash_bd_roofline_reads_the_three_bd_kernels_only():
    ops = {"%flash_bd_fwd.2 custom-call": 0.30,
           "%flash_bd_bwd_dq.1 custom-call": 0.20,
           "%flash_bd_bwd_dkv.1 custom-call": 0.25,
           "%flash_fwd.9 custom-call": 9.0}
    counts = {"%flash_bd_fwd.2 custom-call": 24,
              "%flash_bd_bwd_dq.1 custom-call": 12,
              "%flash_bd_bwd_dkv.1 custom-call": 12,
              "%flash_fwd.9 custom-call": 3}
    got = _reader("flash_bd_roofline.train-bd").read(_reading(ops, counts))
    fo, fb = work_sdar.flash_bd_forward(LM, SDAR, 2, 4096)
    bo, bb = work_sdar.flash_bd_backward(LM, SDAR, 2, 4096)
    least = fo / 197e12 + bo / 197e12   # both compute-bound
    assert work.roofline_seconds(fo, fb, PEAKS)[1] == "compute"
    assert got == pytest.approx(100 * least * 6 * 2 / 0.75)


def test_expert_load_peak_is_busiest_over_mean_of_the_held():
    counters = {"copies": [[16000.0, 16800.0]], "masked": [4000.0],
                "busiest": [[1100.0, 1260.0]]}
    got = _reader("expert_load_peak.train-bd").read(
        _reading(moe_counters=counters))
    assert got == pytest.approx(100 * (1100 / 1000 + 1260 / 1050) / 2)


@pytest.mark.parametrize("name", [
    "moe_gmm_roofline.train-bd", "flash_bd_roofline.train-bd",
    "expert_load_peak.train-bd"])
def test_nothing_to_read_is_no_metric_and_no_error(name):
    reader = _reader(name)
    assert reader.read(_reading()) is None          # no trace at all
    dense = {"%flash_fwd.1 custom-call": 1.0}
    assert reader.read(_reading(dense, {"%flash_fwd.1 custom-call": 4})) \
        is None                                      # the parent's kernels
    nan = float("nan")
    assert reader.read(_reading(dense, {"%flash_fwd.1 custom-call": 4},
                                moe_counters={"copies": [[nan]],
                                              "busiest": [[nan]]})) is None


def test_benchmark_json_lists_the_cell_where_its_readers_read():
    bench, cell, config, traffic = harness.find_cell(CELL)
    assert cell["chips"] == 1 and traffic["driver"] == "train_fit_bd"
    e2e = [m["name"] for m in harness.metrics_of(bench, CELL, "end_to_end")]
    assert e2e == ["train_tokens_per_s", "setup_s"]
    per_layer = {m["name"] for m in harness.metrics_of(bench, CELL,
                                                       "per_layer")}
    assert per_layer == {
        "compile_s.train", "epoch_gap_ms.train", "device_idle_share.train",
        "hbm_peak_share.train", "mfu.train-bd", "moe_gmm_roofline.train-bd",
        "flash_bd_roofline.train-bd", "expert_load_peak.train-bd"}
    for name in per_layer:
        assert callable(_reader(name).read)
    # the dense cell's own: four count a dense decoder and three calls a
    # layer; four of PR 24's span readers are pinned to the dense cell
    # by an accepted test (test_bm_layer_spans), which this PR may not
    # edit (PERF.md section 7)
    dense_only = {m["name"] for m in bench["per_layer"]
                  if CELL not in m["workloads"]}
    assert dense_only == {"mfu.train", "flash_roofline.train",
                          "flash_fwd_ms.train", "flash_bwd_ms.train",
                          "compile_count.train", "artifact_load_s.train",
                          "fit_setup_s.train", "epoch_turnaround_ms.train"}
