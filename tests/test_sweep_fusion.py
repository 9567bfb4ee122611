"""Vectorized sweep fusion (docs/PERFORMANCE.md "Sweep fusion"):
cohort planner semantics, fused-vs-unfused numerical parity,
heterogeneous fallback, early-stop masking, and trial fault
isolation."""

import numpy as np
import pytest

from learningorchestra_tpu import config as config_mod
from learningorchestra_tpu.models import GridSearch, NeuralModel
from learningorchestra_tpu.runtime import engine as engine_lib
from learningorchestra_tpu.services import faults


@pytest.fixture(autouse=True)
def _cfg(tmp_path):
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), mesh_shape="auto",
        compute_dtype="float32"))
    yield
    config_mod.reset_config()


def _set_cfg(tmp_path, **overrides):
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), mesh_shape="auto",
        compute_dtype="float32", **overrides))


def _estimator():
    model = NeuralModel([
        {"kind": "dense", "units": 16, "activation": "relu"},
        {"kind": "dense", "units": 2, "activation": "softmax"},
    ], name="toy")
    model.compile({"kind": "adam", "learning_rate": 1e-3})
    return model


def _data(n=64):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 8)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)
    x[:, 1] = y * 2.0  # separable
    return x, y


# ---------------------------------------------------------------------
# cohort planner
# ---------------------------------------------------------------------
def test_planner_fuses_homogeneous_lr_grid():
    sweep = GridSearch(_estimator(), {"learning_rate": [1e-4, 1e-3]},
                       refit=False)
    combos = sweep._combinations()
    cohorts, residual = sweep._plan_cohorts(combos)
    assert residual == []
    assert len(cohorts) == 1
    assert cohorts[0]["indices"] == [0, 1]
    assert cohorts[0]["hyper"] == [{"learning_rate": 1e-4},
                                   {"learning_rate": 1e-3}]


def test_planner_groups_by_program_shaping_keys():
    """batch_size changes the traced program, so a lr x batch_size
    grid splits into one cohort per batch size."""
    sweep = GridSearch(_estimator(),
                       {"learning_rate": [1e-4, 1e-3],
                        "batch_size": [8, 16]}, refit=False)
    combos = sweep._combinations()
    cohorts, residual = sweep._plan_cohorts(combos)
    assert residual == []
    assert len(cohorts) == 2
    assert sorted(len(c["indices"]) for c in cohorts) == [2, 2]
    for cohort in cohorts:
        sizes = {combos[i]["batch_size"] for i in cohort["indices"]}
        assert len(sizes) == 1  # never mixes batch sizes


def test_planner_leaves_unfusable_grid_residual():
    """No vmappable scalar varies -> everything stays on the trial
    path (and `lr` normalizes to learning_rate when it does vary)."""
    sweep = GridSearch(_estimator(), {"batch_size": [8, 16]},
                       refit=False)
    combos = sweep._combinations()
    cohorts, residual = sweep._plan_cohorts(combos)
    assert cohorts == []
    assert residual == [0, 1]
    sweep = GridSearch(_estimator(), {"lr": [1e-4, 1e-3]}, refit=False)
    cohorts, residual = sweep._plan_cohorts(sweep._combinations())
    assert len(cohorts) == 1
    assert cohorts[0]["hyper"][0] == {"learning_rate": 1e-4}


def test_planner_respects_estimator_opt_out():
    """Estimators without the fused protocol (or whose subclass
    overrides training) keep the slice-parallel path."""
    est = _estimator()
    sweep = GridSearch(est, {"learning_rate": [1e-4, 1e-3]},
                       refit=False)
    combos = sweep._combinations()

    class NoFusion(NeuralModel):
        def fit(self, *a, **k):  # overriding training opts out
            return super().fit(*a, **k)

    opted_out = NoFusion(est.layer_configs)
    assert not opted_out.supports_sweep_fusion()
    sweep_out = GridSearch(opted_out, {"learning_rate": [1e-4, 1e-3]},
                           refit=False)
    assert sweep_out._plan_cohorts(combos) == ([], [0, 1])


# ---------------------------------------------------------------------
# fusion correctness
# ---------------------------------------------------------------------
def test_fused_matches_unfused_trials(tmp_path):
    """Fused per-trial final metrics match independently trained
    unfused trials for the same seeds (ISSUE 7 acceptance)."""
    x, y = _data()
    grid = {"learning_rate": [1e-5, 5e-2]}
    fused = GridSearch(_estimator(), grid, validation_split=0.25,
                       refit=False)
    fused.fit(x, y, epochs=4, batch_size=16)
    assert fused.fusion_info_["fusedTrials"] == 2
    assert fused.fusion_info_["cohorts"] == 1

    _set_cfg(tmp_path, sweep_fusion=False)
    serial = GridSearch(_estimator(), grid, validation_split=0.25,
                        refit=False)
    serial.fit(x, y, epochs=4, batch_size=16)
    assert serial.fusion_info_["fusedTrials"] == 0

    assert fused.best_params_ == serial.best_params_
    for fm, sm in zip(fused.cv_results_["metrics"],
                      serial.cv_results_["metrics"]):
        for k in sm:
            assert abs(fm[k] - sm[k]) < 1e-4, (k, fm[k], sm[k])


def test_fused_sweep_traces_once():
    """One cohort = one traced fused epoch program, regardless of how
    many sweep points it carries."""
    x, y = _data()
    before = engine_lib.fused_epoch_traces()
    sweep = GridSearch(_estimator(),
                       {"learning_rate": [1e-4, 1e-3, 1e-2, 5e-2]},
                       validation_split=0.25, refit=False)
    sweep.fit(x, y, epochs=3, batch_size=16)
    assert sweep.fusion_info_["fusedTrials"] == 4
    assert engine_lib.fused_epoch_traces() - before == 1


def test_warm_fused_sweep_retraces_nothing():
    """A second sweep of the same cohort (a new GridSearch, a new
    estimator of the same architecture) finds the first one's fused
    epoch program: it traces nothing. Three points, so that no other
    test of this file has built the program first."""
    x, y = _data()
    grid = {"learning_rate": [1e-4, 1e-3, 1e-2]}

    def run():
        sweep = GridSearch(_estimator(), grid, validation_split=0.25,
                           refit=False)
        sweep.fit(x, y, epochs=3, batch_size=16)
        assert sweep.fusion_info_["fusedTrials"] == 3
        return sweep

    cold = run()
    before = engine_lib.fused_epoch_traces()
    warm = run()
    assert engine_lib.fused_epoch_traces() == before
    assert warm.best_params_ == cold.best_params_


def test_heterogeneous_grid_falls_back_bit_for_bit(tmp_path):
    """A grid with no fusable axis behaves identically with the
    planner on and off — same cv_results_, no error column."""
    x, y = _data(32)
    grid = {"batch_size": [8, 16]}
    on = GridSearch(_estimator(), grid, validation_split=0.25,
                    refit=False)
    on.fit(x, y, epochs=2)
    assert on.fusion_info_["fusedTrials"] == 0

    _set_cfg(tmp_path, sweep_fusion=False)
    off = GridSearch(_estimator(), grid, validation_split=0.25,
                     refit=False)
    off.fit(x, y, epochs=2)
    assert on.cv_results_["params"] == off.cv_results_["params"]
    assert on.cv_results_["mean_test_score"] == \
        off.cv_results_["mean_test_score"]
    assert on.cv_results_["metrics"] == off.cv_results_["metrics"]
    assert "error" not in on.cv_results_
    assert "error" not in off.cv_results_


def test_earlystop_margin_never_changes_unstopped_sweep(tmp_path):
    """With a margin no trial can trail by, the early-stop machinery
    arms but never fires — results must equal the margin-0 run."""
    x, y = _data()
    grid = {"learning_rate": [1e-3, 5e-2]}
    baseline = GridSearch(_estimator(), grid, validation_split=0.25,
                          refit=False)
    baseline.fit(x, y, epochs=3, batch_size=16)

    _set_cfg(tmp_path, sweep_earlystop_margin=1e9,
             sweep_earlystop_min_epochs=1)
    armed = GridSearch(_estimator(), grid, validation_split=0.25,
                       refit=False)
    armed.fit(x, y, epochs=3, batch_size=16)
    assert armed.fusion_info_["earlyStopped"] == 0
    assert armed.cv_results_["metrics"] == \
        baseline.cv_results_["metrics"]
    assert armed.best_params_ == baseline.best_params_


def test_earlystop_freezes_trailing_config(tmp_path):
    """A small margin stops the hopeless trial; the winner (and its
    score) are unaffected by the masking."""
    x, y = _data()
    _set_cfg(tmp_path, sweep_earlystop_margin=0.05,
             sweep_earlystop_min_epochs=2)
    sweep = GridSearch(_estimator(),
                       {"learning_rate": [1e-5, 5e-2]},
                       validation_split=0.25, refit=False)
    sweep.fit(x, y, epochs=6, batch_size=16)
    assert sweep.fusion_info_["fusedTrials"] == 2
    assert sweep.fusion_info_["earlyStopped"] >= 1
    assert sweep.best_params_["learning_rate"] == 5e-2


# ---------------------------------------------------------------------
# trial fault isolation
# ---------------------------------------------------------------------
def test_failing_trial_does_not_abort_sweep(tmp_path):
    x, y = _data(32)
    _set_cfg(tmp_path, sweep_fusion=False,
             fault_inject="sweep_trial:1")
    faults.reset()
    try:
        sweep = GridSearch(_estimator(),
                           {"learning_rate": [1e-4, 5e-2]},
                           validation_split=0.25, max_parallel=1,
                           refit=False)
        sweep.fit(x, y, epochs=1, batch_size=16)
    finally:
        faults.reset()
    errors = sweep.cv_results_["error"]
    assert errors[0] and "InjectedFault" in errors[0]
    assert errors[1] is None
    assert sweep.cv_results_["mean_test_score"][0] == float("-inf")
    # the surviving trial wins
    assert sweep.best_params_ == {"learning_rate": 5e-2}
    assert "_exc" not in sweep.cv_results_  # raw exception stays out


def test_all_trials_failed_reraises_cause(tmp_path):
    x, y = _data(32)
    _set_cfg(tmp_path, sweep_fusion=False,
             fault_inject="sweep_trial:2")
    faults.reset()
    try:
        sweep = GridSearch(_estimator(),
                           {"learning_rate": [1e-4, 5e-2]},
                           validation_split=0.25, max_parallel=1,
                           refit=False)
        with pytest.raises(faults.InjectedFault):
            sweep.fit(x, y, epochs=1, batch_size=16)
    finally:
        faults.reset()


def test_unknown_scoring_names_available_metrics():
    """The late-failure path now raises a ValueError naming the
    reported metrics instead of a bare KeyError."""
    x, y = _data(32)
    sweep = GridSearch(_estimator(), {"learning_rate": [1e-3]},
                       scoring="f1", validation_split=0.25,
                       refit=False)
    with pytest.raises(ValueError, match="accuracy"):
        sweep.fit(x, y, epochs=1, batch_size=16)


# ---------------------------------------------------------------------
# submit-time scoring validation (services/validators.py)
# ---------------------------------------------------------------------
def test_valid_scoring_rejects_unknown_metric():
    from learningorchestra_tpu.services import validators as V

    with pytest.raises(V.HttpError) as err:
        V.valid_scoring("f1")
    assert err.value.status == V.HTTP_NOT_ACCEPTABLE
    assert "accuracy" in err.value.message
    for ok in ("auto", "loss", "accuracy", "precision", "recall", None):
        V.valid_scoring(ok)


def test_model_service_gates_sweep_scoring():
    from learningorchestra_tpu.services import validators as V
    from learningorchestra_tpu.services.model_service import \
        _valid_sweep_scoring

    with pytest.raises(V.HttpError):
        _valid_sweep_scoring(GridSearch, {"scoring": "f1"})
    _valid_sweep_scoring(GridSearch, {"scoring": "accuracy"})
    _valid_sweep_scoring(GridSearch, {})
    # non-sweep classes never consult the scoring validator
    _valid_sweep_scoring(NeuralModel, {"scoring": "f1"})


def test_fusion_stats_surface():
    from learningorchestra_tpu.models import sweep as sweep_lib

    stats = sweep_lib.fusion_stats()
    for key in ("fusedTrials", "cohorts", "fallbackTrials",
                "earlyStopped", "trialErrors", "fusedEpochTraces"):
        assert key in stats
