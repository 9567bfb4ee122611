"""benchmark/work_sdar.py against counts worked by hand, and the new
configuration's file against the catalog's numbers."""

import pytest

from benchmark import harness, work_sdar

SDAR = harness.load_json("configs", "sdar-a3b-l6.json")
LM = SDAR["language_model"]
BF16 = {"torch_dtype": "bfloat16"}


@pytest.mark.parametrize("key,want", [
    # q and o 2048x4096, k and v 2048x512
    ("attention", 2 * 2048 * 4096 + 2 * 2048 * 512),
    ("router", 2048 * 128),
    ("expert", 3 * 2048 * 768),
    # attention, router, 16 experts, two layer norms and two head norms
    ("layer", 18_874_368 + 262_144 + 16 * 4_718_592 + 2 * 2048 + 2 * 128),
    ("head", 2048 * 18_992),
    ("total", 645_623_296)])
def test_param_counts(key, want):
    assert work_sdar.param_counts(LM)[key] == want


@pytest.mark.parametrize("seq,block,want", [
    # L 4, blocks of 2 (the mask written out in test_bm_reference_sdar):
    # noisy rows see 2, 2, 4, 4 keys, clean rows 2, 2, 4, 4
    (4, 2, 24), (8, 4, 8 * 4 + 64), (4096, 4, 4096 * 4 + 4096 ** 2)])
def test_keys_seen_under_the_mask(seq, block, want):
    assert work_sdar.keys_seen_bd(seq, block) == want
    from benchmark.reference import sdar_moe
    if seq <= 8:
        assert int(sdar_moe.visible(seq, block).sum()) == want


def test_expected_copies_and_masked_positions():
    # 16,384 positions, 8 choices each, 16 of 128 experts held
    assert work_sdar.expected_copies(LM, 2, 4096) == 16_384
    assert work_sdar.expected_masked(2, 4096) == pytest.approx(8192 * 0.55)


def test_step_flops_by_part():
    parts = work_sdar.train_flops_per_step(LM, 2, 4096)
    positions = 16_384
    assert parts["layer_products"] == 6 * 6 * (18_874_368 + 262_144) * positions
    assert parts["experts"] == 6 * 6 * 4_718_592 * 16_384
    pairs = 4096 * 4 + 4096 ** 2
    assert parts["attention"] == 3 * 6 * 4 * 2 * 32 * 128 * pairs
    assert parts["head"] == pytest.approx(6 * 38_895_616 * 8192 * 0.55)
    assert parts["total"] == pytest.approx(25.03e12, rel=1e-3)
    assert sum(v for k, v in parts.items() if k != "total") == parts["total"]


def test_kernel_work():
    fo, fb = work_sdar.flash_bd_forward(LM, BF16, 2, 4096)
    assert fo == 4 * 2 * 32 * 128 * (4096 * 4 + 4096 ** 2)
    # q and o of 32 heads, k and v of 4, over 2 x 8192 positions of 128
    assert fb == 2 * 8192 * 128 * 2 * (2 * 32 + 2 * 4)
    bo, bb = work_sdar.flash_bd_backward(LM, BF16, 2, 4096)
    assert (bo, bb) == (2.5 * fo, 2 * fb)
    gmm = work_sdar.moe_gmm(LM, BF16, 2, 4096)
    one = 2 * 16_384 * 2048 * 768
    assert gmm["forward"][0] == 3 * one and gmm["backward"][0] == 6 * one
    rows_d, rows_ff, matrix = 16_384 * 2048 * 2, 16_384 * 768 * 2, \
        16 * 2048 * 768 * 2
    assert gmm["forward"][1] == 3 * (rows_d + rows_ff + matrix)
    assert gmm["backward"][1] == 2 * gmm["forward"][1]


def test_configuration_file_keeps_every_published_width():
    published = {"attention_bias": False, "decoder_sparse_step": 1,
                 "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
                 "intermediate_size": 6144, "max_window_layers": 48,
                 "mlp_only_layers": [], "model_type": "sdar_moe",
                 "moe_intermediate_size": 768, "norm_topk_prob": True,
                 "num_attention_heads": 32, "num_experts_per_tok": 8,
                 "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
                 "rope_scaling": None, "rope_theta": 1000000,
                 "sliding_window": None, "tie_word_embeddings": False,
                 "use_sliding_window": False}
    for key, value in published.items():
        assert SDAR[key] == value, key
    assert set(SDAR["reduced"]) == {"num_hidden_layers", "num_experts",
                                    "vocab_size", "max_position_embeddings"}
    assert SDAR["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                 "vocab_size": 151936,
                                 "max_position_embeddings": 32768}
    assert (LM["d_model"], LM["n_heads"], LM["n_kv_heads"], LM["head_dim"],
            LM["d_ff"], LM["n_experts"], LM["moe_k"]) == (
        SDAR["hidden_size"], SDAR["num_attention_heads"],
        SDAR["num_key_value_heads"], SDAR["head_dim"],
        SDAR["moe_intermediate_size"], 128, SDAR["num_experts_per_tok"])
    assert (LM["n_layers"], LM["experts_held"], LM["vocab_size"],
            LM["max_len"]) == (SDAR["num_hidden_layers"], SDAR["num_experts"],
                               SDAR["vocab_size"],
                               SDAR["max_position_embeddings"])
    assert LM["mask_token_id"] == LM["vocab_size"] - 1
    assert LM["vocab_size"] * 8 == 151936 and LM["experts_held"] * 8 == 128
