"""The hybrid state-space cell's four per-layer readers on a synthetic
reading: the numbers they give, worked by hand, and nothing (no error)
where the program has no such kernel, as the parent commit has not."""

import json
import os

import pytest

from benchmark import harness, trace_reduce, work, work_granite

CONFIG = harness.load_json("configs", "granite-4.0-h-micro-l10.json")
LM = CONFIG["language_model"]
PEAKS = work.peaks_for("TPU v5 lite")
CELL = "granite-4.0-h-micro-l10.train-ssm-seq8k"
NEW = ("mfu.train-ssm", "ssd_roofline.train-ssm", "ssd_ms.train-ssm",
       "flash_roofline.train-ssm")


def _reader(name):
    return harness.load_module("layer_metrics", name)


def _reading(ops=None, counts=None, **facts):
    base = {"batch": 1, "seq": 8192, "tokens": 8 * 8192, "window_s": 4.0,
            "steps": 4, "program_module": "epoch_fn"}
    return {"facts": dict(base, **facts), "lm": LM, "config": CONFIG,
            "peaks": PEAKS, "memory": {}, "end_to_end": {},
            "trace": None if ops is None else {"ops": ops,
                                               "op_counts": counts}}


def _epochs_trace(starts, length, cut=1.0):
    runs = [("jit_epoch_fn(7)", 0.0, cut)] + [
        ("jit_epoch_fn(7)", s, length) for s in starts]
    return trace_reduce.reduce_events({"/device:TPU:0": {
        "modules": runs, "ops": [("%f.1 fusion", s, d) for _, s, d in runs]}})


def test_mfu_is_model_flops_a_step_over_the_peak():
    # epochs of 4 steps start every 2 s on the device: 2 steps a second
    reading = dict(_reading(), trace=_epochs_trace([1.02, 3.02, 5.02], 1.98))
    got = _reader("mfu.train-ssm").read(reading)
    flops = work_granite.train_flops_per_step(LM, 1, 8192)["total"]
    assert got == pytest.approx(100 * flops * 2 / 197e12)
    assert got == pytest.approx(40.3, abs=0.1)       # 39.7 TFLOP, 0.5 s
    assert _reader("mfu.train-ssm").read(
        dict(_reading(), trace=_epochs_trace([1.02], 1.98))) is None
    assert _reader("mfu.train-ssm").read(_reading()) is None


# two steps of nine Mamba-2 layers: the forward kernel twice a layer and
# step (recomputation), the backward once; a %fusion that is no kernel
SSD_OPS = {"%ssd_fwd.3 custom-call": 0.018, "%ssd_fwd.7 custom-call": 0.018,
           "%ssd_bwd.5 custom-call": 0.054, "%fusion.9 fusion": 5.0,
           "%flash_fwd.2 custom-call": 0.008}
SSD_COUNTS = {"%ssd_fwd.3 custom-call": 18, "%ssd_fwd.7 custom-call": 18,
              "%ssd_bwd.5 custom-call": 18, "%fusion.9 fusion": 100,
              "%flash_fwd.2 custom-call": 4}


def test_ssd_ms_is_the_kernels_time_a_layer_and_step():
    got = _reader("ssd_ms.train-ssm").read(_reading(SSD_OPS, SSD_COUNTS))
    # 90 ms of ssd_* over 18 layer-steps
    assert got == pytest.approx(5.0)


def test_ssd_roofline_is_least_time_over_kernel_time():
    got = _reader("ssd_roofline.train-ssm").read(
        _reading(SSD_OPS, SSD_COUNTS))
    fo, fb = work_granite.ssd_forward(LM, CONFIG, 1, 8192)
    bo, bb = work_granite.ssd_backward(LM, CONFIG, 1, 8192)
    least = max(fo / 197e12, fb / 819e9) + max(bo / 197e12, bb / 819e9)
    assert got == pytest.approx(100 * least * 18 / 0.090)
    assert got == pytest.approx(10.6, abs=0.1)    # 0.531 ms of 5 ms
    assert 0 < got < 100


FLASH_OPS = {"%flash_fwd.2 custom-call": 0.016,
             "%flash_bwd_dq.4 custom-call": 0.010,
             "%flash_bwd_dkv.6 custom-call": 0.014,
             "%ssd_bwd.5 custom-call": 0.054}
FLASH_COUNTS = {"%flash_fwd.2 custom-call": 4,
                "%flash_bwd_dq.4 custom-call": 2,
                "%flash_bwd_dkv.6 custom-call": 2,
                "%ssd_bwd.5 custom-call": 18}


def test_flash_roofline_counts_the_one_attention_layer_at_head_64():
    got = _reader("flash_roofline.train-ssm").read(
        _reading(FLASH_OPS, FLASH_COUNTS))
    keys = 8192 * 8193 / 2
    fwd_ops = 4 * 32 * 64 * keys
    fwd = max(fwd_ops / 197e12, 8192 * 64 * 2 * 80 / 819e9)
    bwd = max(2.5 * fwd_ops / 197e12, 8192 * 64 * 2 * 160 / 819e9)
    # two steps traced (two dkv calls), 40 ms of flash kernels
    assert got == pytest.approx(100 * (fwd + bwd) * 2 / 0.040)
    assert 0 < got < 100


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_gives_none_and_does_not_raise(name):
    """No trace; a trace with no such kernel (the parent's, or another
    cell's): the line leaves the metric out."""
    reader = _reader(name)
    assert reader.read(_reading()) is None
    other = {"%fusion.9 fusion": 5.0, "%moe_gmm_fwd.1 custom-call": 0.1}
    assert reader.read(_reading(other, {k: 3 for k in other})) is None
    # the forward alone (no whole layer-step traced) is nothing either
    fwd = {"%ssd_fwd.3 custom-call": 0.1, "%flash_fwd.2 custom-call": 0.1}
    assert reader.read(_reading(fwd, {k: 2 for k in fwd})) is None


SHARED = ("compile_s.train", "epoch_gap_ms.train", "device_idle_share.train",
          "hbm_peak_share.train")


def test_the_new_cell_lists_its_readers():
    """Only what this cell owns: a later cell may join the new readers'
    lists, and a later metric may join this cell's."""
    bench = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "train_tokens_per_s"
        assert os.path.isfile(os.path.join(
            harness.HERE, "layer_metrics", name + ".py"))
    for name in ("ssd_roofline.train-ssm", "ssd_ms.train-ssm",
                 "flash_roofline.train-ssm"):
        assert by_name[name]["layer"] == "kernels"
    assert by_name["mfu.train-ssm"]["layer"] == "model step"
    reported = {m["name"] for m in harness.metrics_of(bench, CELL,
                                                      "per_layer")}
    assert set(NEW) | set(SHARED) <= reported
    assert {"train_tokens_per_s", "setup_s"} <= {
        m["name"] for m in harness.metrics_of(bench, CELL, "end_to_end")}
