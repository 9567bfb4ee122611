"""The reduction from device events to busy time, per-name time and
idle gaps, on a hand-made trace and on the recorded one."""

import os

import pytest

from benchmark import harness, trace_reduce

SAMPLE = os.path.join(harness.HERE, "trace_sample",
                      "train_epoch.json.gz")


def hand_made():
    # two programs back to back, a 2 s gap, then the first again
    return {"/device:TPU:0": {
        "modules": [("jit_epoch_fn(1)", 0.0, 4.0),
                    ("jit_step(2)", 4.0, 1.0),
                    ("jit_epoch_fn(1)", 7.0, 3.0)],
        "ops": [("%fusion.1 fusion", 0.0, 2.0),
                ("%fusion.2 fusion", 1.5, 2.5),
                ("%while.9 while", 0.0, 4.0),
                ("%attn.3 custom-call", 4.0, 1.0),
                ("%fusion.1 fusion", 7.0, 3.0)]}}


def test_union_counts_overlap_once():
    assert trace_reduce.union_seconds(
        [("a", 0.0, 2.0), ("b", 1.5, 2.5), ("c", 10.0, 1.0)]) == 5.0


@pytest.mark.parametrize("name,want", [
    ("jit_epoch_fn(123456789)", "epoch_fn"), ("jit_step", "step"),
    ("pjit__lambda_(7)", "pjit__lambda_")])
def test_program_name(name, want):
    assert trace_reduce.program_name(name) == want


@pytest.mark.parametrize("name,want", [
    ("%attn.164 = (f32[16,4096,128]{2,1,0:T(8,128)}, f32[16,4096,128]"
     "{2,1,0:T(8,128)}) custom-call(bf16[16,8192,128]{2,1,0:T(8,128)(2,1)}"
     " %bitcast.1)", "%attn.164 custom-call"),
    ("%fusion.1127 = bf16[2048,92544]{1,0:T(8,128)(2,1)} fusion(bf16[1024,"
     "2048]{1,0:T(8,128)(2,1)S(1)} %copy-done.25)", "%fusion.1127 fusion"),
    ("%while.101 = (s32[]{:T(128)}, f32[92544,2048]{1,0:T(8,128)}) "
     "while(%tuple.3)", "%while.101 while"),
    ("no equals sign here", "no equals sign here")])
def test_short_name(name, want):
    assert trace_reduce.short_name(name) == want


def test_reduce_hand_made_trace():
    out = trace_reduce.reduce_events(hand_made())
    # the window is the device's own: first event's start to last's end
    assert out["busy_s"] == 8.0 and out["window_s"] == 10.0
    assert out["ops"]["%fusion.1 fusion"] == 5.0
    assert "%while.9 while" not in out["ops"]  # a container
    assert out["modules"]["epoch_fn"] == [4.0, 3.0]
    assert out["module_gaps"] == {"step -> epoch_fn": [2.0]}
    assert out["same_program_gaps"] == {"epoch_fn": [3.0]}
    assert out["device_ops"][0] == ["%fusion.1 fusion", 5.0]
    assert out["idle_gaps"] == [["step -> epoch_fn", 2.0]]


def test_reduce_nothing_is_nothing():
    assert trace_reduce.reduce_events({}) == {}


def test_readers_return_nothing_when_there_is_nothing_to_read():
    reading = {"facts": {"program_module": "epoch_fn", "steps": 8,
                         "batch": 2, "seq": 4096, "spans": []},
               "trace": {}, "memory": {"peak": 0, "limit": 0}}
    for name in ("epoch_gap_ms.train", "flash_roofline.train",
                 "device_idle_share.train", "hbm_peak_share.train",
                 "compile_s.train"):
        assert harness.load_module("layer_metrics", name).read(
            reading) is None


@pytest.mark.skipif(not os.path.isfile(SAMPLE), reason="no sample")
def test_recorded_sample():
    planes = trace_reduce.load_sample(SAMPLE)
    out = trace_reduce.reduce_events(planes)
    assert out["planes"] == 1
    assert 0 < out["busy_s"] <= out["window_s"]
    # two epoch programs cut by the trace's ends and one whole: what is
    # idle is the gaps between them, not the trace's edges
    idle = harness.load_module("layer_metrics", "device_idle_share.train"
                               ).read({"trace": out})
    assert 0.0 < idle < 1.0
    assert "epoch_fn" in out["modules"]
    gaps = out["same_program_gaps"].get("epoch_fn")
    assert gaps and all(g > 0 for g in gaps)
    kernels = [k for k in out["ops"] if k.endswith(" custom-call")]
    assert kernels  # the flash kernels, forward and backward


@pytest.mark.skipif(not os.path.isfile(SAMPLE), reason="no sample")
def test_flash_roofline_of_the_recorded_sample_is_a_share():
    from benchmark import work

    config = harness.load_json("configs", "internlm2-l4.json")
    trace = trace_reduce.reduce_events(trace_reduce.load_sample(SAMPLE))
    reading = {"facts": {"program_module": "epoch_fn", "steps": 8,
                         "batch": 2, "seq": 4096},
               "trace": trace, "lm": config["language_model"],
               "config": config, "peaks": work.peaks_for("TPU v5 lite")}
    share = harness.load_module(
        "layer_metrics", "flash_roofline.train").read(reading)
    # 12 kernel ops (4 layers x forward, dq, dkv); the 50-odd custom
    # calls of no length must not count as calls
    assert 30.0 < share < 45.0


def test_idle_share_does_not_hide_a_busy_time_over_the_window():
    read = harness.load_module("layer_metrics",
                               "device_idle_share.train").read
    assert read({"trace": {"busy_s": 3.0, "window_s": 4.0}}) == 25.0
    assert read({"trace": {"busy_s": 5.0, "window_s": 4.0}}) < 0.0


def test_host_planes_do_not_stretch_the_window():
    planes = hand_made()
    planes["/host:CPU"] = {"ops": [("python", -50.0, 100.0)], "modules": []}
    assert trace_reduce.reduce_events(planes)["window_s"] == 10.0
