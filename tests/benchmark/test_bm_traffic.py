"""The generators are functions of ``--seed`` alone, and the training
driver's comparison reads what it says."""

import math

import numpy as np
import pytest

from benchmark import harness, weights

train = harness.load_module("drivers", "train_fit")
MIX = harness.load_json("traffic", "train-seq4k.json")
BIG = 2**31 + 7


def test_token_rows_match_what_the_sandbox_is_sent():
    rows = train.token_rows(BIG, 6, 32, 1000)
    scope = {}
    exec(train.DATA_CODE.format(seed=BIG, rows=6, seq=32, vocab=1000),
         scope)
    assert np.array_equal(rows, scope["response"]["x"])
    assert rows.dtype == np.int32 and rows.min() >= 1 and rows.max() < 1000
    assert not np.array_equal(rows, train.token_rows(BIG + 1, 6, 32, 1000))
    assert len({tuple(r) for r in rows.tolist()}) == 6  # rows all differ


def test_weights_follow_the_seed_and_the_path_only():
    lm = harness.load_json("rehearsal.json")["tiny"]["language_model"]
    table = weights.leaf_table(lm)
    path, shape, kind = table[2]
    a = np.asarray(weights.make_leaf(weights.seed_key(BIG), path, shape,
                                     kind))
    tree = weights.make_tree(BIG, lm)
    node = tree
    for part in path:
        node = node[part]
    assert np.array_equal(a, np.asarray(node))  # one call or leaf by leaf
    b = np.asarray(weights.make_leaf(weights.seed_key(BIG + 1), path,
                                     shape, kind))
    assert not np.array_equal(a, b)
    n = sum(int(np.prod(s)) for _, s, _ in table)
    d, ff, v = lm["d_model"], lm["d_ff"], lm["vocab_size"]
    assert n == 2 * v * d + d + lm["n_layers"] * (
        2 * d + 2 * d * d + 2 * d * (d // 2) + 3 * d * ff)


def test_the_mix_ties_every_checked_epoch_to_the_window_job():
    assert MIX["driver"] == "train_fit"
    assert MIX["check_epochs"] >= 2  # past the first executable (F7)
    assert MIX["warm_epochs"] >= MIX["check_epochs"]
    for i in range(MIX["check_epochs"]):
        assert f"loss_epoch{i}_rel" in MIX["limits"]
    assert MIX["limits"]["epoch_tie"] == 0.0
    assert set(MIX["rehearsal"]["limits"]) == set(MIX["limits"])


REF = {"losses": [4.0, 2.0, 1.0, 1.0],
       "mu_norm": {"big": 10.0, "mid": 8.0, "small": 0.1},
       "change_norm": {"big": 1.0, "mid": 1.0, "small": 1.0}}
LIMITS = {"loss_epoch0_rel": 0.01, "loss_epoch1_rel": 0.01,
          "mu_norm_gap": 0.01, "change_norm_gap": 0.01, "epoch_tie": 0.0}


def sound():
    return {"losses": [3.0, 1.0], "window_losses": [3.0, 1.0],
            "mu_norm": dict(REF["mu_norm"]),
            "change_norm": dict(REF["change_norm"])}


def test_a_sound_run_compares_clean():
    numbers, readings = train.compare(sound(), REF, LIMITS)
    assert set(numbers) == set(LIMITS)
    assert all(v == 0.0 for v, _ in numbers.values())
    assert harness.judge(numbers)[0] is True
    # readings no limit names are read and not compared
    assert readings["mu_norm_gap_own"] == 0.0
    assert "mu_norm_gap_own" not in numbers


@pytest.mark.parametrize("number,plant", [
    ("loss_epoch1_rel", lambda p: p["losses"].__setitem__(1, 1.05)),
    ("epoch_tie", lambda p: p["window_losses"].__setitem__(1, 1.0 + 1e-7)),
    ("epoch_tie", lambda p: p["window_losses"].pop()),
    ("epoch_tie", lambda p: p["losses"].__setitem__(0, float("nan"))),
    ("mu_norm_gap", lambda p: p["mu_norm"].__setitem__("big", 11.0)),
    ("change_norm_gap", lambda p: p["change_norm"].__setitem__("mid", 2.0)),
    ("mu_norm_gap", lambda p: p.__setitem__("mu_norm", {})),
])
def test_each_number_fails_on_its_own_fault(number, plant):
    prog = sound()
    plant(prog)
    numbers, _ = train.compare(prog, REF, LIMITS)
    ok, compared = harness.judge(numbers)
    assert ok is False
    value, limit = numbers[number]
    assert not value <= limit
    if math.isnan(value):  # printed as null, so the line stays JSON
        assert compared[number]["value"] is None


def test_a_small_leaf_is_held_to_the_median_or_to_itself():
    prog = sound()
    prog["mu_norm"]["small"] = 0.2  # wholly wrong on the small leaf
    _, readings = train.compare(prog, REF, LIMITS)
    assert readings["mu_norm_gap"] == pytest.approx(0.1 / 8.0)
    assert readings["mu_norm_gap_own"] == pytest.approx(1.0)
    assert readings["worst_leaf"]["mu_norm_gap_own"] == "small"
    numbers, _ = train.compare(prog, REF, dict(LIMITS, mu_norm_gap_own=0.01))
    assert numbers["mu_norm_gap_own"] == (pytest.approx(1.0), 0.01)


def test_leaves_the_reference_does_not_move_are_left_out_of_the_change():
    ref = {"losses": [1.0, 1.0],
           "mu_norm": {"a": 1.0, "b": 1.0, "still": 1e-6},
           "change_norm": {"a": 1.0, "b": 1.0, "still": 1e-3}}
    prog = {"losses": [1.0, 1.0], "mu_norm": dict(ref["mu_norm"]),
            "change_norm": {"a": 1.0, "b": 1.0, "still": 0.5}}
    _, readings = train.compare(prog, ref, LIMITS)
    assert readings["change_norm_gap"] == 0.0


def test_epoch_means():
    assert train.epoch_means([1.0, 3.0, 5.0, 7.0], 2) == [2.0, 6.0]
