"""Roofline performance observability (docs/OBSERVABILITY.md
"Roofline & perf reports"): platform registry matching and env
overrides, roofline classification math, the engine's flops/bytes
extraction and its custom-call floor interplay, the timeline perf
block, the REST perf report for train jobs and live serving sessions,
the new Prometheus gauges, and null-safety with the tracking disabled
or no hardware roofline known."""

import time
import types

import numpy as np
import pytest

from learningorchestra_tpu import config as config_mod
from learningorchestra_tpu.observability import hist as obs_hist
from learningorchestra_tpu.observability import perf as obs_perf
from learningorchestra_tpu.observability import timeline as obs_timeline
from learningorchestra_tpu.observability import trace as obs_trace
from learningorchestra_tpu.services import faults

PREFIX = "/api/learningOrchestra/v1"


@pytest.fixture(autouse=True)
def _fresh_perf(monkeypatch):
    """The report registry is process-global and the peak overrides
    leak through os.environ; every test starts from a clean slate on
    the CPU backend (no hardware roofline unless pinned)."""
    monkeypatch.delenv("LO_PEAK_TFLOPS_PER_CHIP", raising=False)
    monkeypatch.delenv("LO_PEAK_HBM_GBPS", raising=False)
    monkeypatch.delenv("LO_PERF", raising=False)
    obs_perf.reset()
    obs_trace.reset()
    obs_timeline.reset()
    obs_hist.reset()
    faults.reset()
    yield
    obs_perf.reset()
    obs_trace.reset()
    obs_timeline.reset()
    obs_hist.reset()
    faults.reset()


@pytest.fixture()
def api(tmp_path):
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), compute_dtype="float32",
        serve_max_wait_ms=1.0))
    from learningorchestra_tpu.services.server import Api

    a = Api()
    yield a
    a.ctx.close()
    config_mod.reset_config()


def _wait(api, name, verb, timeout=90.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        st, body, _ = api.dispatch(
            "GET", f"{PREFIX}/{verb}/{name}", {"limit": "1"}, None)
        if st == 200 and body["metadata"].get("finished"):
            return body["metadata"]
        docs = api.ctx.catalog.get_documents(name)
        errs = [d["exception"] for d in docs if d.get("exception")]
        assert not errs, errs
        time.sleep(0.05)
    raise AssertionError(f"{verb}/{name} never finished")


# ----------------------------------------------- platform registry
def test_peaks_none_on_cpu_without_override():
    assert obs_perf.peak_flops_per_chip() is None
    assert obs_perf.peak_hbm_bytes_per_chip() is None
    summary = obs_perf.platform_summary()
    assert summary["platform"] == "cpu"
    assert summary["peakTflopsPerChip"] is None
    assert summary["peakHbmGbPerSec"] is None
    assert "ridgeFlopsPerByte" not in summary


def test_env_overrides_pin_a_roofline(monkeypatch):
    monkeypatch.setenv("LO_PEAK_TFLOPS_PER_CHIP", "1")
    monkeypatch.setenv("LO_PEAK_HBM_GBPS", "10")
    assert obs_perf.peak_flops_per_chip() == pytest.approx(1e12)
    assert obs_perf.peak_hbm_bytes_per_chip() == pytest.approx(10e9)
    summary = obs_perf.platform_summary()
    assert summary["peakTflopsPerChip"] == pytest.approx(1.0)
    assert summary["peakHbmGbPerSec"] == pytest.approx(10.0)
    assert summary["ridgeFlopsPerByte"] == pytest.approx(100.0)


def test_bad_override_falls_through(monkeypatch):
    monkeypatch.setenv("LO_PEAK_TFLOPS_PER_CHIP", "not-a-number")
    assert obs_perf.peak_flops_per_chip() is None  # CPU backend


def test_table_matching_is_substring_ordered():
    # v5e chips report device_kind "TPU v5 lite"; the generic "v5"
    # entry (v5p peak) must NOT shadow it
    assert obs_perf._match(obs_perf.PEAK_FLOPS_BF16, "TPU v5 lite") == \
        ("v5 lite", pytest.approx(197e12))
    assert obs_perf._match(
        obs_perf.PEAK_FLOPS_BF16, "tpu v5p")[1] == pytest.approx(459e12)
    assert obs_perf._match(
        obs_perf.PEAK_HBM_BYTES, "tpu v4")[1] == pytest.approx(1228e9)


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform = platform
        self.device_kind = device_kind


def test_v5e_device_kind_reads_its_table_row(monkeypatch):
    """The kind a v5e chip reports resolves to the published v5e
    peaks, and the summary names the kind and the row it matched."""
    monkeypatch.setattr(obs_perf, "_device",
                        lambda: _FakeDevice("tpu", "TPU v5 lite"))
    assert obs_perf.peak_flops_per_chip() == pytest.approx(197e12)
    assert obs_perf.peak_hbm_bytes_per_chip() == pytest.approx(819e9)
    summary = obs_perf.platform_summary()
    assert summary["deviceKind"] == "TPU v5 lite"
    assert summary["peakRow"] == "v5 lite"
    assert summary["peakTflopsPerChip"] == pytest.approx(197.0)
    assert summary["peakHbmGbPerSec"] == pytest.approx(819.0)


@pytest.mark.parametrize("platform,kind", [("tpu", "TPU v9 mega"),
                                           ("gpu", "NVIDIA H100")])
def test_unknown_accelerator_device_kind_raises(monkeypatch, platform,
                                                kind):
    """An accelerator no table row matches is an error naming the
    kind — never a silent None that drops MFU from every report."""
    monkeypatch.setattr(obs_perf, "_device",
                        lambda: _FakeDevice(platform, kind))
    with pytest.raises(obs_perf.UnknownDeviceKind, match=kind):
        obs_perf.peak_flops_per_chip()
    with pytest.raises(obs_perf.UnknownDeviceKind, match=kind):
        obs_perf.peak_hbm_bytes_per_chip()
    with pytest.raises(obs_perf.UnknownDeviceKind):
        obs_perf.platform_summary()
    # the explicit override still pins a roofline for such a chip
    monkeypatch.setenv("LO_PEAK_TFLOPS_PER_CHIP", "100")
    assert obs_perf.peak_flops_per_chip() == pytest.approx(100e12)


# ------------------------------------------------- roofline math
def test_roofline_compute_vs_bandwidth_bound(monkeypatch):
    monkeypatch.setenv("LO_PEAK_TFLOPS_PER_CHIP", "1")   # 1e12 f/s
    monkeypatch.setenv("LO_PEAK_HBM_GBPS", "10")         # ridge = 100
    # intensity 1000 flops/byte >> ridge -> compute-bound
    out = obs_perf.roofline(1e9, 1e6, steps=100, dt=1.0, n_chips=1)
    assert out["tflopsPerSecPerChip"] == pytest.approx(0.1)
    assert out["mfu"] == pytest.approx(0.1)
    assert out["gbPerSecPerChip"] == pytest.approx(0.1)
    assert out["arithmeticIntensity"] == pytest.approx(1000.0)
    assert out["hbmBwUtil"] == pytest.approx(0.01)
    assert out["boundBy"] == "compute"
    # intensity 10 flops/byte << ridge -> bandwidth-bound; achieved
    # bytes/s hits the peak so utilization caps at exactly 1.0
    out = obs_perf.roofline(1e9, 1e8, steps=100, dt=1.0, n_chips=1)
    assert out["arithmeticIntensity"] == pytest.approx(10.0)
    assert out["hbmBwUtil"] == 1.0
    assert out["boundBy"] == "bandwidth"


def test_roofline_null_safety_without_peaks():
    # CPU, no override: achieved rates still emitted, every
    # peak-relative field absent — never a ratio against a made-up peak
    out = obs_perf.roofline(1e9, 1e6, steps=10, dt=1.0, n_chips=1)
    assert out["tflopsPerSecPerChip"] == pytest.approx(0.01)
    assert out["gbPerSecPerChip"] == pytest.approx(0.01)
    assert out["arithmeticIntensity"] == pytest.approx(1000.0)
    for absent in ("mfu", "hbmBwUtil", "boundBy"):
        assert absent not in out


def test_roofline_degenerate_inputs_are_empty_or_legacy():
    assert obs_perf.roofline(0.0, 1e6, 10, 1.0, 1) == {}
    assert obs_perf.roofline(1e9, 1e6, 0, 1.0, 1) == {}
    assert obs_perf.roofline(1e9, 1e6, 10, 0.0, 1) == {}
    # no bytes: legacy tflops field only
    out = obs_perf.roofline(1e9, 0.0, 10, 1.0, 1)
    assert list(out) == ["tflopsPerSecPerChip"]


def test_lo_perf_0_keeps_legacy_fields_only(monkeypatch):
    monkeypatch.setenv("LO_PERF", "0")
    monkeypatch.setenv("LO_PEAK_TFLOPS_PER_CHIP", "1")
    monkeypatch.setenv("LO_PEAK_HBM_GBPS", "10")
    out = obs_perf.roofline(1e9, 1e6, 100, 1.0, 1)
    assert set(out) == {"tflopsPerSecPerChip", "mfu"}


# ------------------------------------------------- report registry
def test_registry_upsert_lru_and_disabled(monkeypatch):
    for i in range(obs_perf._MAX_JOBS + 5):
        obs_perf.record_job(f"job{i}", {"mfu": i / 1000.0})
    names = obs_perf.known_jobs()
    assert len(names) == obs_perf._MAX_JOBS
    assert "job0" not in names and f"job{obs_perf._MAX_JOBS + 4}" in names
    report = obs_perf.job_report(f"job{obs_perf._MAX_JOBS + 4}")
    assert report["mfu"] == pytest.approx(
        (obs_perf._MAX_JOBS + 4) / 1000.0)
    assert report["updatedAt"] > 0
    latest = obs_perf.latest(limit=2)
    assert len(latest) == 2
    assert obs_perf.job_report("job0") is None
    monkeypatch.setenv("LO_PERF", "0")
    obs_perf.record_job("off", {"mfu": 0.5})
    assert obs_perf.job_report("off") is None


# ------------------------- engine extraction + custom-call floor
def _measure(floor_fn=None, **kw):
    import jax
    import jax.numpy as jnp

    from learningorchestra_tpu.runtime.engine import Engine

    @jax.jit
    def step(state, batch, rng):
        return state + jnp.sum(batch["x"] @ batch["x"].T)

    eng = types.SimpleNamespace(
        _step_flops=None, _step_bytes=None, _flops_key=None,
        _flops_floor_fn=floor_fn, _train_step=None,
        _exec_key=lambda *a, **k: None,
        _note_signature=lambda key: None,
        _capture_xray=lambda *a, **k: None,
        _record_compile_xray=lambda *a, **k: None)
    batch = {"x": np.ones((64, 64), np.float32)}
    Engine._measure_flops(eng, np.float32(0.0), batch,
                          jax.random.PRNGKey(0), step_fn=step, **kw)
    return eng


def test_measure_flops_extracts_flops_and_bytes():
    eng = _measure()
    # 64x64 @ 64x64 matmul ~ 2*64^3 flops; XLA's count must be at
    # least that, and the operands/result must show up as bytes
    assert eng._step_flops >= 2 * 64 ** 3 * 0.5
    assert eng._step_bytes > 0


def test_flops_floor_raises_flops_but_not_bytes():
    base = _measure()
    floored = _measure(floor_fn=lambda batch: base._step_flops * 10)
    assert floored._step_flops == pytest.approx(base._step_flops * 10)
    # custom calls report zero FLOPs but their operand/result bytes
    # ARE counted — the floor must leave the byte side untouched
    assert floored._step_bytes == pytest.approx(base._step_bytes)
    # a floor below the measured value never lowers it
    low = _measure(floor_fn=lambda batch: 1.0)
    assert low._step_flops == pytest.approx(base._step_flops)


def test_count_only_takes_the_models_count_and_lowers_nothing():
    """The scanned path of a model that counts its own step: the step
    that exists only to be measured is not lowered, so no bytes are
    read from it (a lowering reports them, as the tests above show)."""
    eng = _measure(floor_fn=lambda batch: 123.0, count_only=True)
    assert (eng._step_flops, eng._step_bytes) == (123.0, 0.0)


# ------------------------------------ fit history + timeline block
def _fit_small(monkeypatch, tmp_path, epochs=3, units=32):
    monkeypatch.setenv("LO_PEAK_TFLOPS_PER_CHIP", "0.05")
    monkeypatch.setenv("LO_PEAK_HBM_GBPS", "1")
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), compute_dtype="float32"))
    from learningorchestra_tpu.models.neural import NeuralModel

    rng = np.random.default_rng(0)
    x = rng.normal(size=(1024, 32)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)
    model = NeuralModel([
        {"kind": "dense", "units": units, "activation": "relu"},
        {"kind": "dense", "units": 2, "activation": "softmax"}])
    with obs_trace.span("job", trace="perf_fit", phase="run"):
        model.fit(x, y, epochs=epochs, batch_size=128, shuffle=False)
    config_mod.reset_config()
    return model


def test_fit_history_carries_roofline_block(monkeypatch, tmp_path):
    model = _fit_small(monkeypatch, tmp_path)
    best = model.history[-1]
    for key in ("tflopsPerSecPerChip", "mfu", "gbPerSecPerChip",
                "arithmeticIntensity", "hbmBwUtil", "boundBy"):
        assert key in best, key
    assert best["boundBy"] in ("compute", "bandwidth")
    assert 0.0 <= best["hbmBwUtil"] <= 1.0
    assert best["arithmeticIntensity"] > 0


def test_timeline_summary_emits_perf_percentiles(monkeypatch, tmp_path):
    # the rates are rounded to four decimals of a TFLOP/s: at 32 units
    # an epoch is 7 MFLOP, and beside five other xdist workers on the
    # same cores its rate read 0.0000 (the driver's run of PR 31). 512
    # units are 107 MFLOP an epoch, clear of the rounding a hundredfold.
    _fit_small(monkeypatch, tmp_path, units=512)
    tl = obs_timeline.summary("perf_fit")
    perf = tl.get("perf")
    assert perf, tl
    for key in ("mfu", "tflopsPerSecPerChip", "hbmBwUtil"):
        block = perf[key]
        assert block["p50"] <= block["p90"] <= block["max"]
        assert block["max"] > 0 or key == "mfu"
    assert perf["boundBy"] in ("compute", "bandwidth")
    # the registry holds the job's latest window under the trace id
    report = obs_perf.job_report("perf_fit")
    assert report and report["kind"] == "train"


def test_lo_perf_0_fit_skips_extended_block(monkeypatch, tmp_path):
    monkeypatch.setenv("LO_PERF", "0")
    model = _fit_small(monkeypatch, tmp_path)
    best = model.history[-1]
    assert "tflopsPerSecPerChip" in best and "mfu" in best  # legacy
    assert "gbPerSecPerChip" not in best
    assert "boundBy" not in best
    assert obs_perf.job_report("perf_fit") is None


# --------------------------------------------------- REST surface
def _train_job(api, monkeypatch):
    monkeypatch.setenv("LO_PEAK_TFLOPS_PER_CHIP", "0.05")
    monkeypatch.setenv("LO_PEAK_HBM_GBPS", "1")
    st, body, _ = api.dispatch(
        "POST", f"{PREFIX}/function/python", {}, {
            "name": "pf_data", "functionParameters": {},
            "function": ("import numpy as np\n"
                         "rng = np.random.default_rng(0)\n"
                         "x = rng.normal(size=(1024, 32))"
                         ".astype(np.float32)\n"
                         "y = (x[:, 0] > 0).astype(np.int32)\n"
                         "response = {'x': x, 'y': y}\n")})
    assert st == 201, body
    _wait(api, "pf_data", "function/python")
    st, body, _ = api.dispatch(
        "POST", f"{PREFIX}/model/tensorflow", {}, {
            "modelName": "pf_model",
            "modulePath": "learningorchestra_tpu.models",
            "class": "NeuralModel",
            "classParameters": {"layer_configs": [
                {"kind": "dense", "units": 32, "activation": "relu"},
                {"kind": "dense", "units": 2,
                 "activation": "softmax"}]}})
    assert st == 201, body
    _wait(api, "pf_model", "model/tensorflow")
    st, body, _ = api.dispatch(
        "POST", f"{PREFIX}/train/tensorflow", {}, {
            "name": "pf_train", "modelName": "pf_model",
            "method": "fit",
            "methodParameters": {
                "x": "$pf_data.x", "y": "$pf_data.y", "epochs": 3,
                "batch_size": 128, "shuffle": False}})
    assert st == 201, body
    return _wait(api, "pf_train", "train/tensorflow")


def test_rest_perf_report_for_train_job(api, monkeypatch):
    meta = _train_job(api, monkeypatch)
    # terminal metadata carries the perf summary stamp
    assert meta.get("perf"), meta
    assert meta["perf"]["boundBy"] in ("compute", "bandwidth")
    st, report, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/perf/pf_train", {}, None)
    assert st == 200, report
    assert report["kind"] == "train" and report["job"] == "pf_train"
    blk = report["perf"]
    for key in ("mfu", "tflopsPerSecPerChip", "gbPerSecPerChip",
                "hbmBwUtil", "boundBy"):
        assert key in blk, blk
    assert report["platform"]["platform"] == "cpu"
    # index route lists the job + the platform roofline
    st, index, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/perf", {}, None)
    assert st == 200 and "pf_train" in index["jobs"]
    assert index["platform"]["peakTflopsPerChip"] == pytest.approx(0.05)


def test_rest_perf_report_for_live_serving(api, monkeypatch):
    from learningorchestra_tpu.models.estimators import \
        LogisticRegressionJAX

    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 8)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)
    clf = LogisticRegressionJAX(epochs=2, batch_size=128)
    clf.fit(x, y)
    api.ctx.artifacts.save(clf, "pf_clf", "train/tensorflow")
    st, body, _ = api.dispatch("POST", f"{PREFIX}/serve/pf_clf", {}, {})
    assert st == 201, body
    rows = [[float(v) for v in r] for r in rng.normal(size=(4, 8))]
    for _ in range(4):
        st, body, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/pf_clf/predict", {}, {"x": rows})
        assert st == 200, body
    st, report, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/perf/pf_clf", {}, None)
    assert st == 200, report
    assert report["kind"] == "serving" and report["model"] == "pf_clf"
    perf = report["perf"]
    assert perf["predictsTotal"] >= 4
    assert perf["rowsPerSecPerChip"] > 0
    assert 0 < perf["goodputFrac"] <= 1.0
    api.dispatch("DELETE", f"{PREFIX}/serve/pf_clf", {}, None)


def test_rest_perf_report_unknown_is_404(api):
    st, body, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/perf/nope", {}, None)
    assert st == 404, body


def test_metrics_expose_perf_and_gateway_gauges(api, monkeypatch):
    _train_job(api, monkeypatch)
    st, metrics, _ = api.dispatch("GET", "/metrics", {}, None)
    assert st == 200
    assert "pf_train" in metrics["perf"]["jobs"]
    gw = metrics["gateway"]
    for key in ("inflight", "abandonedInflight", "abandonedTotal",
                "saturatedTotal", "maxInflight"):
        assert key in gw, gw
    st, text, ctype = api.dispatch(
        "GET", "/metrics", {"format": "prometheus"}, None)
    assert st == 200 and ctype.startswith("text/plain")
    text = text.decode() if isinstance(text, bytes) else text
    assert 'lo_mfu{job="pf_train"}' in text
    assert 'lo_tflops_per_chip{job="pf_train"}' in text
    assert 'lo_hbm_bw_util_frac{job="pf_train"}' in text
    assert "lo_abandoned_dispatches " in text
    assert "lo_abandoned_dispatches_total " in text
    assert "lo_gateway_inflight " in text
