"""A whole run of the block-diffusion cell's driver at the rehearsal
size on the CPU, and ``correct`` coming out false for each planted
fault: half the batch left out (planted in the program, under the
driver), and the reference's own variants put in the program's place
(one held expert's output dropped; half the batch left out)."""

import numpy as np
import pytest

from benchmark import harness
from bm_runs import rehearse

CELL = "sdar-a3b-l6.train-bd4-seq4k"
NUMBERS = {"loss_epoch0_rel", "loss_epoch1_rel", "change_norm_gap",
           "epoch_tie", "copies_gap", "masked_tie"}


def test_last_line_of_a_rehearsal():
    proc, last = rehearse(CELL, extra=("--rehearse", "tiny"), trace=1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["correct"] is True, last["compared"]
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"] == {} and last["device"]["platform"] == "cpu"
    assert set(last["compared"]) == NUMBERS
    assert last["compared"]["epoch_tie"]["value"] == 0.0
    assert last["compared"]["masked_tie"]["value"] == 0.0
    facts = last["rehearsal"]["facts"]
    assert facts["compiles_in_window"] == 0
    # ROW tokens: steps x rows x 32, not the 64 positions a row runs as
    assert facts["tokens"] == facts["epochs_in_window"] * 3 * 2 * 32


def test_half_a_batch_left_out_is_not_correct():
    proc, last = rehearse(CELL, fault="half_batch",
                          extra=("--rehearse", "tiny"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["correct"] is False
    failed = {k for k, e in last["compared"].items()
              if e["value"] is None or e["value"] > e["limit"]}
    assert {"copies_gap", "masked_tie"} <= failed, last["compared"]


@pytest.fixture(scope="module")
def tiny():
    """(driver, traffic parameters, tiny model, rows, sound reference)."""
    from benchmark.reference import sdar_moe

    _, _, config, traffic = harness.find_cell(CELL)
    p = dict(traffic, **traffic["rehearsal"])
    lm = p["language_model"]
    driver = harness.load_module("drivers", traffic["driver"])
    steps, batch, seq = p["steps_per_epoch"], p["batch_size"], p["seq"]
    data = driver.token_rows(77, steps * batch, seq, lm["vocab_size"])
    batches = np.concatenate([data.reshape(steps, batch, seq)] * 2)
    follow = lambda **kw: sdar_moe.follow_steps(  # noqa: E731
        77, lm, float(config["rms_norm_eps"]), batches, p["optimizer"], **kw)
    return driver, p, follow, follow()


def _in_the_programs_place(tiny, **fault):
    driver, p, follow, ref = tiny
    alt = follow(**fault)
    prog = {"losses": driver.epoch_means(alt["losses"], 2),
            "mu_norm": alt["mu_norm"], "change_norm": alt["change_norm"],
            "counters": driver.reference_counters(alt, 2)}
    numbers, _ = driver.compare(prog, ref, p["limits"])
    return harness.judge(numbers)


def test_the_reference_in_its_own_place_is_correct(tiny):
    correct, compared = _in_the_programs_place(tiny)
    assert correct and compared["copies_gap"]["value"] == 0.0
    assert max(e["value"] for e in compared.values()) == 0.0
    assert 1 <= tiny[0].token_rows(5, 4, 8, 96).min()
    assert tiny[0].token_rows(5, 64, 64, 96).max() == 94   # MASK is 95


# The fp8 control is told from bf16 at the cell's size and not at the
# tiny one, where both are rounding noise over a few numbers. So the
# readings of the tree as committed are kept here as the chip gave them
# (my chip runs, PR 26, both fix sessions; PERF.md section 2): the program
# on seven seeds, and the reference in the program's place on the first
# of them. ``epoch_tie`` is the program's alone. Whoever moves a limit sees
# here which side of it each reading falls on.
CHIP_READINGS = {
    "program_2600000701": (set(), {
        "loss_epoch0_rel": 8.65003562304353e-06,
        "loss_epoch1_rel": 0.00014919047036644976,
        "mu_norm_gap": 0.2070350717151981,
        "change_norm_gap": 0.01322140885702679, "epoch_tie": 0.0,
        "copies_gap": 0.011044277360066834, "masked_tie": 0.0}),
    "program_2600000702": (set(), {
        "loss_epoch0_rel": 1.8825209902814338e-05,
        "loss_epoch1_rel": 0.00011168031658583687,
        "mu_norm_gap": 0.3605333321003939,
        "change_norm_gap": 0.020538574106852257, "epoch_tie": 0.0,
        "copies_gap": 0.011689310433222251, "masked_tie": 0.0}),
    "program_2600000703": (set(), {
        "loss_epoch0_rel": 5.971218451469221e-07,
        "loss_epoch1_rel": 6.290627241200116e-05,
        "mu_norm_gap": 0.24987263403214177,
        "change_norm_gap": 0.011967522855578562, "epoch_tie": 0.0,
        "copies_gap": 0.00315965553559259, "masked_tie": 0.0}),
    # the second fix session's runs (calls 39 to 42), each judged
    # correct on the chip under the committed limits
    "program_2600000801": (set(), {
        "loss_epoch0_rel": 5.910640192435782e-06,
        "loss_epoch1_rel": 0.00010605245358571146,
        "mu_norm_gap": 0.22352753903850653,
        "change_norm_gap": 0.007404618416126784, "epoch_tie": 0.0,
        "copies_gap": 0.01834090097444008, "masked_tie": 0.0}),
    "program_2600000802": (set(), {
        "loss_epoch0_rel": 6.213466113723414e-07,
        "loss_epoch1_rel": 6.094287700555238e-05,
        "mu_norm_gap": 0.1615551453514603,
        "change_norm_gap": 0.00847274916319736, "epoch_tie": 0.0,
        "copies_gap": 0.006528353234363294, "masked_tie": 0.0}),
    "program_2600000804": (set(), {
        "loss_epoch0_rel": 1.4700718484428855e-05,
        "loss_epoch1_rel": 0.0001326678259263468,
        "mu_norm_gap": 0.214606520690529,
        "change_norm_gap": 0.010566659301384857, "epoch_tie": 0.0,
        "copies_gap": 0.02577319587628866, "masked_tie": 0.0}),
    "program_2600000805": (set(), {
        "loss_epoch0_rel": 3.9986695001759646e-06,
        "loss_epoch1_rel": 0.00015968015544140198,
        "mu_norm_gap": 0.23861947171767456,
        "change_norm_gap": 0.007911627044510984, "epoch_tie": 0.0,
        "copies_gap": 0.005785112796182907, "masked_tie": 0.0}),
    "control_fp8_2600000701": ({"loss_epoch0_rel", "loss_epoch1_rel"}, {
        "loss_epoch0_rel": 0.0001481947901979627,
        "loss_epoch1_rel": 0.002034051275130423,
        "mu_norm_gap": 1.1174948999301004,
        "change_norm_gap": 0.029203089512000523,
        "copies_gap": 0.04967418546365915, "masked_tie": 0.0}),
    "fault_dropped_expert_2600000701": (
        {"change_norm_gap"}, {
            "loss_epoch0_rel": 5.949759952357984e-06,
            "loss_epoch1_rel": 0.00023772947919346472,
            "mu_norm_gap": 1.0, "change_norm_gap": 0.999999822290617,
            "copies_gap": 0.0039354522609094215, "masked_tie": 0.0}),
    "fault_half_batch_2600000701": (
        {"loss_epoch0_rel", "loss_epoch1_rel", "change_norm_gap",
         "copies_gap", "masked_tie"}, {
            "loss_epoch0_rel": 0.010509999235227009,
            "loss_epoch1_rel": 0.023874534504645464,
            "mu_norm_gap": 2.6437996849905367,
            "change_norm_gap": 0.17056890008828549,
            "copies_gap": 0.5268553629469123, "masked_tie": 3040.0}),
}


@pytest.mark.parametrize("run", sorted(CHIP_READINGS))
def test_the_committed_limits_split_the_chips_readings(run):
    must_fail, readings = CHIP_READINGS[run]
    limits = harness.find_cell(CELL)[3]["limits"]
    # ``mu_norm_gap`` is printed and not compared (PERF.md section 2)
    assert set(readings) - set(limits) == {"mu_norm_gap"}
    correct, compared = harness.judge(
        {k: (v, limits[k]) for k, v in readings.items() if k in limits})
    failed = {k for k, e in compared.items() if e["value"] > e["limit"]}
    assert correct is (not must_fail), compared
    assert must_fail <= failed, compared
    # room on both sides: no reading within a fifth of its limit
    for k, e in compared.items():
        if e["limit"]:
            assert not 0.8 < e["value"] / e["limit"] < 1.2, (k, e)


@pytest.mark.parametrize("fault,number", [
    ({"drop_expert": 1}, "change_norm_gap"),
    ({"rows": [0]}, "copies_gap")],
    ids=["dropped_expert", "half_batch"])
def test_a_planted_fault_in_the_programs_place_is_not_correct(
        tiny, fault, number):
    correct, compared = _in_the_programs_place(tiny, **fault)
    assert not correct
    assert compared[number]["value"] > compared[number]["limit"], compared
