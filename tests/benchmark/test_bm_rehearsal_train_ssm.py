"""A whole run of the hybrid state-space cell's driver at the rehearsal
size on the CPU, and ``correct`` coming out false for each planted
fault: a state left unchanged (planted in the program, under the
driver: reads 1), and the reference's own variants put in the program's
place (the fp8 control and the three planted faults; the bf16-operand
one has to pass). Then the chip's readings at the cell's own size, kept
beside this file, against the limits as committed."""

import json
import os

import numpy as np
import pytest

from benchmark import harness
from bm_runs import rehearse

CELL = "granite-4.0-h-micro-l10.train-ssm-seq8k"
NUMBERS = {"loss_epoch0_rel", "loss_epoch1_rel", "change_norm_gap",
           "mu_norm_gap", "state_rms_gap", "epoch_tie"}


def test_last_line_of_a_rehearsal():
    proc, last = rehearse(CELL, extra=("--rehearse", "tiny"), trace=1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["correct"] is True, last["compared"]
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"] == {} and last["device"]["platform"] == "cpu"
    assert set(last["compared"]) == NUMBERS
    assert last["compared"]["epoch_tie"]["value"] == 0.0
    facts = last["rehearsal"]["facts"]
    assert facts["compiles_in_window"] == 0
    assert facts["tokens"] == facts["epochs_in_window"] * 3 * 1 * 32
    # the counters of the three Mamba-2 layers reached the epoch records
    readings = json.loads(next(
        ln for ln in proc.stderr.splitlines()
        if ln.startswith("readings: "))[len("readings: "):])
    assert np.asarray(readings["state_rms_gaps"]).shape == (2, 3)
    assert readings["decay_mean_gap"] < 1e-3
    # the run says where its set-up went and how long it waited for a
    # record: the phases and the fit's builds add up to under setup_s
    said = next(ln for ln in proc.stderr.splitlines()
                if ln.startswith("set-up: "))
    parts = json.loads(said[len("set-up: "):said.index("; waits")])
    assert {"before_driver_s", "data_s", "model_s", "weights_s",
            "fit_to_window_s", "compile_s.train"} <= set(parts)
    phases = sum(v for k, v in parts.items() if k.endswith("_s"))
    assert phases == pytest.approx(facts["setup_s"], abs=0.05)
    assert parts["compile_s.train"] < parts["fit_to_window_s"]
    assert 0 < facts["record_gap_median_s"] <= facts["record_gap_max_s"]
    assert facts["record_gap_max_s"] < facts["window_s"]


def test_the_waits_between_records_name_a_stalled_epoch():
    driver = harness.load_module("drivers", "train_fit_ssm")
    # the window opens at 10.0; records seen every 2 s, one 1.9 s late
    got = driver.record_gaps(10.0, [12.0, 14.0, 17.9, 19.9, 21.9])
    assert got["record_gap_max_s"] == pytest.approx(3.9)
    assert got["record_gap_median_s"] == pytest.approx(2.0)
    assert got["record_gap_excess_s"] == pytest.approx(1.9)
    assert driver.record_gaps(0.0, []) == {}


def test_set_up_parts_are_the_accepted_readers_on_the_jobs_spans():
    driver = harness.load_module("drivers", "train_fit_ssm")

    def span(name, start, end, **attrs):
        return {"name": name, "start": start, "end": end, "attrs": attrs}

    spans = [span("submit", 1.0, 1.1),
             span("artifactLoad", 1.2, 9.2), span("paramInit", 1.3, 2.3),
             span("weightsRead", 2.3, 9.0),
             span("compile", 10.0, 70.5, executable=0, epoch=0),
             span("dispatch", 10.0, 70.6, epoch=0, builds=1),
             span("dispatch", 72.6, 72.7, epoch=1)]
    got = driver.setup_parts({"weights_s": 21.23456}, spans)
    assert got == {"weights_s": 21.235, "compile_count.train": 1,
                   "compile_s.train": 60.5, "artifact_load_s.train": 8.0,
                   "fit_setup_s.train": 71.6}
    # the parent's program, or a job with no spans: the phases alone
    assert driver.setup_parts({"data_s": 1.0}, []) == {"data_s": 1.0}


def test_a_state_left_unchanged_reads_one():
    proc, last = rehearse(CELL, fault="frozen_state",
                          extra=("--rehearse", "tiny"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["correct"] is False
    assert last["compared"]["change_norm_gap"]["value"] == pytest.approx(1.0)
    assert last["compared"]["epoch_tie"]["value"] == 0.0


@pytest.fixture(scope="module")
def tiny():
    """(driver, traffic parameters, follow_steps at the tiny size, the
    sound reference)."""
    from benchmark.reference import granite_hybrid

    _, _, config, traffic = harness.find_cell(CELL)
    p = dict(traffic, **traffic["rehearsal"])
    lm = p["language_model"]
    driver = harness.load_module("drivers", traffic["driver"])
    steps, batch, seq = p["steps_per_epoch"], p["batch_size"], p["seq"]
    data = driver.token_rows(77, steps * batch, seq, lm["vocab_size"])
    batches = np.concatenate([data.reshape(steps, batch, seq)] * 2)
    follow = lambda **kw: granite_hybrid.follow_steps(  # noqa: E731
        77, lm, float(config["rms_norm_eps"]), batches, p["optimizer"], **kw)
    return driver, p, follow, follow()


def _in_the_programs_place(tiny, **variant):
    driver, p, follow, ref = tiny
    prog = driver.in_the_programs_place(follow(**variant), 2)
    numbers, readings = driver.compare(prog, ref, p["limits"])
    return harness.judge(numbers) + (readings,)


def test_the_reference_in_its_own_place_is_correct(tiny):
    correct, compared, _ = _in_the_programs_place(tiny)
    assert correct and set(compared) == NUMBERS - {"epoch_tie"}
    assert max(e["value"] for e in compared.values()) == 0.0
    rows = tiny[0].token_rows(5, 64, 64, 96)
    assert rows.min() == 1 and rows.max() == 95     # 0 is padding


def test_a_second_bf16_implementation_is_correct(tiny):
    """Every product's operands in bfloat16, forward and backward, and
    the scan's: the precision the configuration states, so it has to
    pass."""
    correct, compared, _ = _in_the_programs_place(tiny, precision="bf16")
    assert correct, compared
    assert compared["loss_epoch0_rel"]["value"] > 0.0   # it did round


# The fp8 control is told from bf16 at the cell's size and not at the
# tiny one, where both are rounding noise over a few numbers: its
# readings on the chip are among those kept below.
@pytest.mark.parametrize("variant,number", [
    ({"fault": "half"}, "mu_norm_gap"),
    ({"fault": "drop_state"}, "state_rms_gap"),
    ({"fault": "no_gate"}, "mu_norm_gap"),
    ({"freeze": True}, "change_norm_gap")],
    ids=["half_batch", "dropped_state", "no_gate", "frozen"])
def test_a_planted_fault_in_the_programs_place_is_not_correct(
        tiny, variant, number):
    """At the tiny size (a chunk of 8, rows of 32): which number finds
    a fault at the cell's size is the chip's to say (the readings kept
    below)."""
    correct, compared, readings = _in_the_programs_place(tiny, **variant)
    assert not correct
    assert compared[number]["value"] > compared[number]["limit"], compared
    if variant.get("fault") == "drop_state":
        # the decays do not know of the state: the counter beside it
        assert readings["decay_mean_gap"] < 1e-3
    if variant.get("freeze"):
        assert compared["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_counters_are_means_over_an_epochs_steps(tiny):
    driver = tiny[0]
    records = [{"ssmStateRms_l0": 1.0, "ssmDecayMean_l0": 0.9,
                "ssmStateRms_l2": 2.0, "ssmDecayMean_l2": 0.8}]
    got = driver.record_counters(records, 2, [0, 2])
    assert got["state_rms"][0] == [1.0, 2.0]
    assert got["decay_mean"][0] == [0.9, 0.8]
    assert all(v != v for v in got["state_rms"][1])    # a missing record
    ref = {"state_rms": [[1.0, 2.0], [3.0, 4.0], [1.0, 1.0], [1.0, 3.0]],
           "decay_mean": [[0.5, 0.5]] * 4}
    assert driver.reference_counters(ref, 2)["state_rms"] == [[2.0, 3.0],
                                                              [1.0, 2.0]]
    # a counter that is missing is no number, and no number fails
    prog = {"losses": [1.0, 1.0], "mu_norm": {}, "change_norm": {},
            "counters": got}
    full = dict(ref, losses=[1.0] * 4, mu_norm={"a": 1.0},
                change_norm={"a": 1.0})
    numbers, _ = driver.compare(prog, full, {"state_rms_gap": 0.5})
    assert harness.judge(numbers)[0] is True      # epoch 0 is whole
    prog["counters"] = driver.record_counters([], 2, [0, 2])
    numbers, _ = driver.compare(prog, full, {"state_rms_gap": 0.5})
    assert harness.judge(numbers)[0] is False


def test_every_limit_carries_its_reason():
    traffic = harness.find_cell(CELL)[3]
    for name in traffic["limits"]:
        assert name in traffic["limits_from"], name
    assert set(traffic["limits"]) == NUMBERS
    assert set(traffic["rehearsal"]["limits"]) == NUMBERS


# Every reading the chip gave at the cell's own size is kept beside this
# file (my chip runs, PR 32; PERF.md section 2; the runs' standard error
# and ``benchmark/readings_granite.py``'s lines are in ``chiprun_out/p32/``):
# the program, and the reference in the program's place as each variant.
# Whoever moves a limit sees here which side of it each reading falls on.
with open(os.path.join(os.path.dirname(__file__),
                       "chip_readings_granite.json")) as _f:
    CHIP_READINGS = json.load(_f)

# the number that each variant is FOR: it has to refuse it, by 1.5 times
# its limit or more
FOR = {"control_fp8": {"loss_epoch1_rel", "state_rms_gap"},
       "fault_half_batch": {"loss_epoch0_rel", "mu_norm_gap"},
       "fault_dropped_state": {"state_rms_gap", "loss_epoch0_rel"},
       "fault_no_gate": {"loss_epoch0_rel"}}


@pytest.mark.parametrize("run", sorted(CHIP_READINGS))
def test_the_committed_limits_split_the_chips_readings(run):
    variant = run.rpartition("_")[0]
    readings = CHIP_READINGS[run]["readings"]
    limits = harness.find_cell(CELL)[3]["limits"]
    assert set(limits) - set(readings) <= {"epoch_tie"}   # the program's
    correct, compared = harness.judge(
        {k: (v, limits[k]) for k, v in readings.items() if k in limits})
    failed = {k for k, e in compared.items() if e["value"] > e["limit"]}
    if variant in ("program", "bf16"):
        # sound: judged correct, every number at most two thirds of its
        # limit
        assert correct, compared
        for k, e in compared.items():
            assert e["value"] <= e["limit"] * 2 / 3, (k, e)
        return
    assert not correct and FOR[variant] <= failed, compared
    for k in FOR[variant]:
        assert compared[k]["value"] >= 1.5 * compared[k]["limit"], (k, compared)
