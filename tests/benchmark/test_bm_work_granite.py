"""``benchmark/work_granite.py`` against counts made by hand for
granite-4.0-h-micro cut to one period (ISSUE 32's arithmetic)."""

import pytest

from benchmark import harness, work, work_granite

CONFIG = harness.load_json("configs", "granite-4.0-h-micro-l10.json")
LM = CONFIG["language_model"]


def test_parameters_by_hand():
    p = work_granite.param_counts(LM)
    in_proj = 2048 * (4096 + 4352 + 64)
    assert in_proj == 17_432_576 and p["mamba_proj"] == in_proj + 4096 * 2048
    small = 4 * 4352 + 4352 + 3 * 64 + 4096
    mlp = 3 * 2048 * 8192
    assert p["mlp"] == mlp == 50_331_648
    assert p["mamba_layer"] == in_proj + 8_388_608 + small + 2 * 2048 + mlp
    assert p["mamba_layer"] == 76_182_976                    # 76.18 M
    assert p["attn_layer"] == (2 * 2048 * 2048 + 2 * 2048 * 512
                               + 2 * 2048 + mlp) == 60_821_504  # 60.82 M
    assert p["table"] == 12544 * 2048 == 25_690_112
    assert (p["n_mamba"], p["n_attn"]) == (9, 1)
    assert p["total"] == 9 * 76_182_976 + 60_821_504 + 25_690_112 + 2048
    assert round(p["total"] / 1e6, 1) == 772.2
    # 12 bytes a parameter resident, 16 with float32 gradients
    assert round(12 * p["total"] / 1e9, 2) == 9.27
    assert round(16 * p["total"] / 1e9, 2) == 12.35


def test_a_steps_model_flops_by_hand():
    f = work_granite.train_flops_per_step(LM, 1, 8192)
    tokens = 8192
    assert f["mlp"] == 6 * 10 * 50_331_648 * tokens
    assert f["mamba_proj"] == 6 * 9 * 25_821_184 * tokens
    # a chunk of 256: C B^T once, and a head the masked square with dt x,
    # the state's part of the output and the state's update
    chunk = 2 * 256 * 256 * 128 + 64 * (2 * 256 * 256 * 64
                                        + 2 * 2 * 256 * 128 * 64)
    assert f["scan"] == 3 * 9 * 32 * chunk
    keys = 8192 * 8193 / 2
    assert f["attention"] == pytest.approx(
        6 * 10_485_760 * tokens + 3 * 4 * 32 * 64 * keys)
    assert f["head"] == 6 * 25_690_112 * 8191
    assert f["total"] == pytest.approx(39.7e12, rel=2e-3)
    share = {k: v / f["total"] for k, v in f.items()}
    assert share["mlp"] == pytest.approx(0.62, abs=0.005)
    assert share["mamba_proj"] == pytest.approx(0.29, abs=0.005)
    assert share["scan"] == pytest.approx(0.024, abs=0.001)
    assert share["attention"] == pytest.approx(0.034, abs=0.001)
    assert share["head"] == pytest.approx(0.032, abs=0.001)


def test_the_scans_least_time_is_bound_by_memory_and_compute_alike():
    peaks = work.peaks_for("TPU v5 lite")
    fo, fb = work_granite.ssd_forward(LM, CONFIG, 1, 8192)
    bo, bb = work_granite.ssd_backward(LM, CONFIG, 1, 8192)
    assert fo == 32 * (2 * 256 * 256 * 128 + 64 * 16_777_216)
    assert fb == 8192 * (2 * (2 * 4096 + 2 * 128) + 4 * 64)
    assert (bo, bb) == (2 * fo, 8192 * (2 * (4 * 4096 + 4 * 128) + 8 * 64))
    t_f, bound_f = work.roofline_seconds(fo, fb, peaks)
    t_b, bound_b = work.roofline_seconds(bo, bb, peaks)
    assert (bound_f, bound_b) == ("compute", "compute")
    assert t_f == pytest.approx(fo / 197e12)
    assert fb / 819e9 == pytest.approx(t_f, rel=0.05)     # near the ridge
    assert 1e3 * (t_f + t_b) == pytest.approx(0.531, abs=0.002)   # ms
    # a row that ends inside a chunk pays for the whole chunk
    assert work_granite.ssd_forward(LM, CONFIG, 1, 8192 + 1)[0] \
        == pytest.approx(fo * 33 / 32)


def test_attention_is_counted_for_the_one_layer_at_head_64():
    lm = work_granite.attention_lm(LM)
    z = work.sizes(lm)
    assert (z["hd"], z["heads"], z["kv"], z["layers"], z["window"]) \
        == (64, 32, 8, 1, 0)
    ops, byt = work.flash_forward(lm, CONFIG, 1, 8192)
    assert ops == 4 * 32 * 64 * (8192 * 8193 / 2)
    assert byt == 8192 * 64 * 2 * (2 * 32 + 2 * 8)
    with pytest.raises(ValueError):
        work_granite.attention_lm(dict(LM, head_dim=128))
