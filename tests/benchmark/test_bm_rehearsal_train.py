"""A whole run of the training cell's driver at the rehearsal size on
the CPU: the last line's shape, and ``correct`` coming out false when
the timed path is broken underneath."""

import pytest

from bm_runs import rehearse

CELL = "internlm2-l4.train-seq4k"
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_last_line_of_a_rehearsal():
    proc, last = rehearse(CELL, extra=("--rehearse", "tiny"), trace=1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert list(last)[:5] == KEYS and list(last)[-1] == "compared"
    assert last["correct"] is True, last["compared"]
    assert last["failed"] == 0 and last["attempted"] > 0
    # a CPU run prints no metric under any name
    assert last["metrics"] == {} and last["device"]["platform"] == "cpu"
    assert "busy_s" not in last["device"]
    assert set(last["compared"]) == {
        "loss_epoch0_rel", "loss_epoch1_rel", "mu_norm_gap",
        "mu_norm_gap_own", "change_norm_gap", "change_norm_gap_own",
        "epoch_tie"}
    assert last["compared"]["epoch_tie"]["value"] == 0.0
    assert "compared mu_norm_gap" in proc.stderr
    assert last["rehearsal"]["facts"]["compiles_in_window"] == 0


@pytest.mark.parametrize("fault,number", [
    ("frozen_state", "change_norm_gap"), ("half_batch", "mu_norm_gap"),
    # sound through the first executable, broken in the second: only a
    # check that crosses into the second epoch sees it
    ("frozen_from_epoch1", "change_norm_gap")])
def test_broken_step_is_not_correct(fault, number):
    proc, last = rehearse(CELL, fault=fault, extra=("--rehearse", "tiny"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["correct"] is False
    entry = last["compared"][number]
    assert entry["value"] > entry["limit"]


def test_a_job_lost_inside_the_window_is_counted_and_the_run_ends():
    proc, last = rehearse(CELL, fault="dies_in_window",
                          extra=("--rehearse", "tiny"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["failed"] == 1 and last["attempted"] > 1
    # the check job and the reference still run: the verdict is theirs
    assert last["compared"]["epoch_tie"]["value"] == 0.0
