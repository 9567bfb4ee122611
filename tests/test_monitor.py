"""Cluster resource monitor, SLO watchdog and closed-loop footprint
calibration (docs/OBSERVABILITY.md "Cluster monitor", docs/SCALING.md
§7).

The monitor is tested against injected collectors (no service layer),
the watchdog against the real histogram module with synthetic clocks,
and calibration end-to-end down to the SliceLease grant size — the
acceptance property is that a measured peak produces a SMALLER slice
than the padded static estimate.
"""

import json
import time
import types

import pytest

from learningorchestra_tpu.observability import hist as obs_hist
from learningorchestra_tpu.observability import monitor as mon
from learningorchestra_tpu.observability import slo as slo_mod


@pytest.fixture(autouse=True)
def _reset_telemetry():
    obs_hist.reset()
    mon.reset_calibration()
    yield
    obs_hist.reset()
    mon.reset_calibration()


def _fake_devices(in_use=2 << 30, peak=3 << 30, limit=16 << 30, n=2):
    def collect():
        return [{"device": i, "platform": "tpu",
                 "bytesInUse": in_use, "peakBytesInUse": peak,
                 "bytesLimit": limit} for i in range(n)]
    return collect


# ----------------------------------------------------------------------
# sampler
# ----------------------------------------------------------------------

def test_sample_once_builds_rings_and_latest():
    sched = {"devicesBusy": 5, "fragmentation": 0.25}
    serving = {"queueDepth": 3, "batchFill": 0.5}
    jobs = {"running": 2, "queued": 1, "deadLettered": 0}
    arena = {"bytesInUse": 1024, "evictions": 7}
    m = mon.ClusterMonitor(
        interval_seconds=0.5, ring=16,
        scheduler_stats=lambda: sched, serving_stats=lambda: serving,
        job_stats=lambda: jobs, arena_stats=lambda: arena,
        device_stats=_fake_devices())
    for t in (100.0, 101.0, 102.0):
        m.sample_once(now=t)
    latest = m.latest()
    assert latest["hbm"]["bytesInUse"] == 2 * (2 << 30)
    assert latest["hbm"]["peakBytesInUse"] == 2 * (3 << 30)
    assert latest["hbm"]["headroomFrac"] == pytest.approx(
        1 - (2 * (2 << 30)) / (2 * (16 << 30)), abs=1e-6)
    assert latest["scheduler"]["fragmentation"] == 0.25
    assert len(latest["devices"]) == 2
    assert len(m.series("hbmBytesInUse")) == 3
    assert m.series("sliceFragmentation")[-1] == [102.0, 0.25]
    assert m.series("servingQueueDepth")[-1][1] == 3
    assert m.series("jobQueueDepth")[-1][1] == 1
    # windowing: only the two newest samples fall in a 1.5s window
    assert len(m.series_window("hbmBytesInUse", 1.5, now=102.0)) == 2
    snap = m.snapshot()
    assert snap["samples"] == 3 and snap["sampleErrors"] == 0
    assert "arenaBytesInUse" in snap["series"]


def test_ring_is_bounded():
    m = mon.ClusterMonitor(ring=8, device_stats=_fake_devices())
    for t in range(20):
        m.sample_once(now=float(t))
    assert len(m.series("hbmBytesInUse")) == 8
    assert m.series("hbmBytesInUse")[0][0] == 12.0  # oldest evicted


def test_failing_collector_is_counted_not_raised():
    def boom():
        raise RuntimeError("collector down")

    m = mon.ClusterMonitor(scheduler_stats=boom,
                           device_stats=_fake_devices())
    sample = m.sample_once(now=1.0)
    assert sample["scheduler"] is None
    assert m.snapshot()["sampleErrors"] == 1


def test_background_thread_samples_and_stops():
    m = mon.ClusterMonitor(interval_seconds=0.01,
                           device_stats=_fake_devices())
    m.start()
    deadline = time.monotonic() + 5.0
    while m.snapshot()["samples"] < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    m.stop()
    assert m.snapshot()["samples"] >= 2
    n = m.snapshot()["samples"]
    time.sleep(0.05)
    assert m.snapshot()["samples"] == n  # really stopped


def test_device_stats_and_rss_never_raise():
    stats = mon.device_memory_stats()
    assert isinstance(stats, list)
    for entry in stats:
        assert {"device", "platform", "bytesInUse"} <= set(entry)
    peak = mon.peak_hbm_bytes()
    assert peak is None or peak > 0
    assert mon.host_rss_bytes() > 0


# ----------------------------------------------------------------------
# calibration registry
# ----------------------------------------------------------------------

def test_calibration_registry_keeps_high_water():
    mon.record_peak("m:fit", 100)
    mon.record_peak("m:fit", 50)       # lower: ignored
    assert mon.measured_peak("m:fit") == 100
    mon.record_peak("m:fit", 150)
    assert mon.measured_peak("m:fit") == 150
    mon.record_peak(None, 10)          # no key: dropped
    mon.record_peak("m:fit", None)     # no measurement: dropped
    assert mon.measured_peak("other") is None


def test_calibrated_bytes_margin_and_clamps():
    # margin applies, and margins below 1 never shrink the measurement
    assert mon.calibrated_hbm_bytes(1000, 10_000, 1.25) == 1250
    assert mon.calibrated_hbm_bytes(1000, 10_000, 0.5) == 1000
    # clamped to [estimate/10, estimate*10]
    assert mon.calibrated_hbm_bytes(10, 10_000, 1.0) == 1000
    assert mon.calibrated_hbm_bytes(10**9, 10_000, 1.0) == 100_000


def test_calibrate_prefers_measured_peak(tmp_config):
    from learningorchestra_tpu.services.execution import \
        ExecutionService

    tmp_config.footprint_calibrate = True
    tmp_config.footprint_margin = 1.25
    fake = types.SimpleNamespace(
        _ctx=types.SimpleNamespace(config=tmp_config))
    root = {"name": "titanic_model"}

    # first execution: no measurement yet — the static estimate
    # stands, but the key is stamped so the job can record its peak
    fp = {"hbmBytes": 6 << 30}
    ExecutionService._calibrate(fake, fp, root, "fit")
    assert fp["calibrationKey"] == "titanic_model:fit"
    assert fp["hbmBytes"] == 6 << 30

    # the job measured 1.5 GiB — a repeat execution's footprint is the
    # margined measurement, far below the padded estimate
    mon.record_peak("titanic_model:fit", int(1.5 * (1 << 30)))
    fp2 = {"hbmBytes": 6 << 30}
    ExecutionService._calibrate(fake, fp2, root, "fit")
    assert fp2["estimator"] == "measured-peak"
    assert fp2["estimatedHbmBytes"] == 6 << 30
    assert fp2["hbmBytes"] == int(1.5 * (1 << 30) * 1.25)
    assert fp2["hbmBytes"] < 6 << 30


def test_calibrate_off_by_default(tmp_config):
    from learningorchestra_tpu.services.execution import \
        ExecutionService

    mon.record_peak("m:fit", 1)
    fake = types.SimpleNamespace(
        _ctx=types.SimpleNamespace(config=tmp_config))
    fp = {"hbmBytes": 1000}
    ExecutionService._calibrate(fake, fp, {"name": "m"}, "fit")
    assert "calibrationKey" not in fp and fp["hbmBytes"] == 1000


def test_calibrated_slice_grant_is_smaller(tmp_config):
    """ISSUE acceptance: with LO_FOOTPRINT_CALIBRATE a repeat
    execution's granted slice is sized from the measured peak — fewer
    devices than the padded static estimate demands."""
    from learningorchestra_tpu.services.execution import \
        ExecutionService
    from learningorchestra_tpu.services.scheduler import SliceLease

    gib = 1 << 30
    lease = SliceLease(leases=4, total_devices=8, aging_seconds=0.0,
                       device_bytes=gib)

    # static estimate: 6 GiB -> 6 of 8 devices
    fp = {"hbmBytes": 6 * gib}
    g1 = lease.acquire("train", footprint=fp)
    assert len(g1.devices) == 6
    lease.release("train", 0.0, grant=g1)

    # measured: the job actually peaked at 1.5 GiB
    tmp_config.footprint_calibrate = True
    fake = types.SimpleNamespace(
        _ctx=types.SimpleNamespace(config=tmp_config))
    mon.record_peak("titanic_model:fit", int(1.5 * gib))
    fp2 = {"hbmBytes": 6 * gib}
    ExecutionService._calibrate(fake, fp2, {"name": "titanic_model"},
                                "fit")
    g2 = lease.acquire("train", footprint=fp2)
    assert len(g2.devices) == 2   # ceil(1.875 GiB / 1 GiB)
    assert len(g2.devices) < len(g1.devices)
    lease.release("train", 0.0, grant=g2)


# ----------------------------------------------------------------------
# scheduler fragmentation + job queue stats
# ----------------------------------------------------------------------

def test_scheduler_stats_fragmentation():
    from learningorchestra_tpu.services.scheduler import SliceLease

    lease = SliceLease(leases=4, total_devices=8, aging_seconds=0.0)
    a = lease.acquire("train", footprint={"devices": 1})
    b = lease.acquire("train", footprint={"devices": 1})
    c = lease.acquire("train", footprint={"devices": 1})
    stats = lease.stats()
    assert stats["devicesBusy"] == 3 and stats["devicesFree"] == 5
    # free run 3..7 is contiguous: no fragmentation
    assert stats["largestFreeRun"] == 5
    assert stats["fragmentation"] == 0.0
    # free the MIDDLE device: free = {1, 3..7} -> largest run 5 of 6
    lease.release("train", 0.0, grant=b)
    stats = lease.stats()
    assert stats["devicesFree"] == 6
    assert stats["largestFreeRun"] == 5
    assert stats["fragmentation"] == pytest.approx(1 - 5 / 6, abs=1e-6)
    lease.release("train", 0.0, grant=a)
    lease.release("train", 0.0, grant=c)
    assert lease.stats()["fragmentation"] == 0.0


def test_queue_stats_and_peak_hbm_metadata(tmp_config, catalog,
                                           monkeypatch):
    """Jobs report running/queued split to the monitor, and a mesh job
    stamps its measured ``peakHbmBytes`` on the terminal metadata and
    into the calibration registry."""
    import threading

    from learningorchestra_tpu.services.jobs import JobManager

    monkeypatch.setattr(mon, "peak_hbm_bytes", lambda: 7 << 30)
    jobs = JobManager(catalog, max_workers=1, mesh_leases=1)
    catalog.create_collection("first", "train/tensorflow")
    catalog.create_collection("second", "train/tensorflow")
    release = threading.Event()
    started = threading.Event()

    def hold():
        started.set()
        release.wait(20)
        return "done"

    jobs.submit("first", hold, needs_mesh=True, pool="train",
                footprint={"devices": 1,
                           "calibrationKey": "root:fit"})
    assert started.wait(10)
    jobs.submit("second", lambda: "x", needs_mesh=False, pool="train")
    qs = jobs.queue_stats()
    assert qs["running"] == 1 and qs["queued"] == 1
    assert jobs.active_job() == "first"
    release.set()
    assert jobs.wait("first", timeout=20) == "done"
    jobs.wait("second", timeout=10)
    meta = catalog.get_metadata("first")
    assert meta["peakHbmBytes"] == 7 << 30
    assert mon.measured_peak("root:fit") == 7 << 30
    jobs.shutdown()


def test_dead_letter_counter_feeds_queue_stats(tmp_config, catalog):
    from learningorchestra_tpu.services.jobs import JobManager

    jobs = JobManager(catalog, max_workers=1, retry_backoff=0.01)
    catalog.create_collection("always_fails", "function/python")

    def boom():
        raise ValueError("no")

    jobs.submit("always_fails", boom, pool="function", max_retries=0)
    # terminal failure is recorded in the documents, not raised
    assert jobs.wait("always_fails", timeout=10) is None
    assert jobs.queue_stats()["deadLettered"] == 1
    jobs.shutdown()


# ----------------------------------------------------------------------
# SLO watchdog
# ----------------------------------------------------------------------

def _tick(watchdog, now, monitor=None):
    watchdog.evaluate(now=now, monitor=monitor)


def test_hist_window_quantile_diffs_snapshots():
    w = slo_mod._HistWindow("lo_serving_request_seconds")
    w.observe(now=0.0)                     # zero-traffic baseline
    for _ in range(100):
        obs_hist.observe("lo_serving_request_seconds", 0.003)
    w.observe(now=10.0)
    # whole history: ~3ms traffic
    assert w.quantile_over(0.99, window=100.0, now=10.0) <= 0.01
    # a window that starts AFTER the traffic sees none
    for _ in range(100):
        obs_hist.observe("lo_serving_request_seconds", 2.0)
    w.observe(now=20.0)
    q = w.quantile_over(0.99, window=5.0, now=20.0)
    assert q is not None and q >= 2.0 - 1e-9
    # a window wide enough to reach the zero-traffic baseline blends
    # both bursts: the p50 is the fast traffic, the p99 the slow
    assert w.quantile_over(0.50, window=100.0, now=20.0) <= 0.01
    assert w.quantile_over(0.99, window=100.0, now=20.0) >= 2.0


def test_serving_p99_alert_fires_and_resolves(tmp_config, tmp_path):
    tmp_config.event_log = str(tmp_path / "events.jsonl")
    tmp_config.slo_serving_p99_ms = 100.0
    tmp_config.slo_fast_window_s = 1.0
    tmp_config.slo_slow_window_s = 5.0
    w = slo_mod.SloWatchdog(active_trace=lambda: "serve/lm/1")
    t0 = 1000.0
    _tick(w, t0)                            # healthy baseline
    assert w.firing_count() == 0
    # slow traffic (500ms >> the 100ms objective)
    for _ in range(50):
        obs_hist.observe("lo_serving_request_seconds", 0.5)
    _tick(w, t0 + 1.0)
    firing = w.firing()
    assert len(firing) == 1
    assert firing[0]["name"] == "servingP99"
    assert firing[0]["severity"] == "page"
    assert firing[0]["value"] > 100.0
    assert firing[0]["trace"] == "serve/lm/1"
    assert w.page_firing()
    # fault clears: the fast window drains and the alert resolves
    _tick(w, t0 + 3.0)
    assert w.firing_count() == 0 and not w.page_firing()
    snap = w.snapshot()
    transitions = [(h["name"], h["transition"]) for h in
                   snap["history"]]
    assert transitions == [("servingP99", "firing"),
                           ("servingP99", "resolved")]
    # satellite: both transitions landed in the JSONL event log with
    # the serving trace attached
    lines = [json.loads(line) for line in
             open(tmp_config.event_log).read().splitlines()]
    alerts = [e for e in lines if e["kind"] == "alert"]
    assert [e["name"] for e in alerts] == \
        ["servingP99.firing", "servingP99.resolved"]
    assert all(e["traceId"] == "serve/lm/1" for e in alerts)
    assert alerts[0]["severity"] == "page"
    assert alerts[0]["threshold"] == 100.0


def test_transient_spike_does_not_page(tmp_config):
    """Breach in the fast window only (slow window still healthy)
    must not fire — that's the burn-rate double-window contract."""
    tmp_config.slo_serving_p99_ms = 100.0
    tmp_config.slo_fast_window_s = 1.0
    tmp_config.slo_slow_window_s = 60.0
    w = slo_mod.SloWatchdog()
    t0 = 2000.0
    _tick(w, t0)
    # long healthy history dominates the slow window
    for _ in range(2000):
        obs_hist.observe("lo_serving_request_seconds", 0.001)
    _tick(w, t0 + 1.0)
    # brief spike: 5 slow requests in the fast window
    for _ in range(5):
        obs_hist.observe("lo_serving_request_seconds", 0.5)
    _tick(w, t0 + 2.0)
    assert w.firing_count() == 0


def test_hbm_headroom_alert(tmp_config):
    tmp_config.slo_hbm_headroom_frac = 0.2
    tmp_config.slo_fast_window_s = 1.0
    tmp_config.slo_slow_window_s = 2.0
    w = slo_mod.SloWatchdog()
    m = mon.ClusterMonitor(
        device_stats=_fake_devices(in_use=15 << 30, limit=16 << 30,
                                   n=1),
        watchdog=w)
    t0 = 3000.0
    for dt in (0.0, 1.0, 2.0, 3.0):
        m.sample_once(now=t0 + dt)    # headroom 1/16 < 0.2 sustained
    firing = w.firing()
    assert [a["name"] for a in firing] == ["hbmHeadroom"]
    assert firing[0]["severity"] == "page"
    assert firing[0]["value"] == pytest.approx(1 / 16, abs=1e-6)


def test_deadletter_rate_alert(tmp_config):
    tmp_config.slo_deadletter_rate = 1.0    # > 1 dead letter / minute
    tmp_config.slo_fast_window_s = 60.0
    tmp_config.slo_slow_window_s = 120.0
    dead = {"n": 0}
    w = slo_mod.SloWatchdog()
    m = mon.ClusterMonitor(
        job_stats=lambda: {"running": 0, "queued": 0,
                           "deadLettered": dead["n"]},
        device_stats=lambda: [], watchdog=w)
    t0 = 5000.0
    m.sample_once(now=t0)
    dead["n"] = 10                           # 10 dead letters in 30s
    m.sample_once(now=t0 + 30.0)
    m.sample_once(now=t0 + 31.0)
    firing = w.firing()
    assert [a["name"] for a in firing] == ["deadLetterRate"]
    assert firing[0]["severity"] == "ticket"
    assert not w.page_firing()               # ticket severity


def test_disabled_objectives_never_fire(tmp_config):
    # all thresholds default 0 = disabled
    w = slo_mod.SloWatchdog()
    for _ in range(50):
        obs_hist.observe("lo_serving_request_seconds", 30.0)
    _tick(w, 100.0)
    _tick(w, 101.0)
    assert w.firing_count() == 0
    assert w.snapshot()["history"] == []


def test_objectives_reflect_config(tmp_config):
    tmp_config.slo_serving_p99_ms = 250.0
    w = slo_mod.SloWatchdog()
    objectives = w.objectives()
    assert objectives["servingP99"]["threshold"] == 250.0
    assert objectives["servingP99"]["severity"] == "page"
    assert set(objectives) == {"servingP99", "queueWait",
                               "hbmHeadroom", "deadLetterRate",
                               "unattributedGrowth", "servingDrift"}
    # leak detector ships disabled; evaluate() retires thr<=0 objectives
    assert objectives["unattributedGrowth"]["threshold"] == 0.0
    # quantized-serving drift objective follows the config bound
    assert objectives["servingDrift"]["severity"] == "ticket"
    assert objectives["servingDrift"]["threshold"] == tmp_config.serve_drift_max


# ----------------------------------------------------------------------
# REST surface: /observability/cluster, /observability/alerts, /healthz,
# /metrics gauges, /profile stop-path
# ----------------------------------------------------------------------

import json as _json
import re
import urllib.error
import urllib.request


@pytest.fixture()
def slo_server(tmp_config):
    """Live server with SLOs configured and the background sampler
    effectively parked (1h interval) so tests drive every monitor /
    watchdog tick deterministically."""
    from learningorchestra_tpu.services.server import RestServer

    tmp_config.monitor_interval_ms = 3_600_000.0
    tmp_config.slo_serving_p99_ms = 100.0
    tmp_config.slo_fast_window_s = 1.0
    tmp_config.slo_slow_window_s = 5.0
    srv = RestServer(host="127.0.0.1", port=0).start()
    yield srv
    srv.stop()


API = "/api/learningOrchestra/v1"


def _call(server, method, path, body=None, params=""):
    url = f"{server.base_url}{path}{params}"
    data = _json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    if data:
        req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req, timeout=60) as resp:
            raw, ctype, status = (resp.read(),
                                  resp.headers.get("Content-Type", ""),
                                  resp.status)
    except urllib.error.HTTPError as e:
        raw, ctype, status = (e.read(),
                              e.headers.get("Content-Type", ""), e.code)
    return status, _json.loads(raw) if "json" in ctype else raw


def test_cluster_endpoint_document(slo_server):
    monitor = slo_server.api.ctx.monitor
    assert monitor is not None
    monitor.sample_once()
    status, doc = _call(slo_server, "GET",
                        f"{API}/observability/cluster")
    assert status == 200
    latest = doc["latest"]
    assert isinstance(latest["devices"], list)
    assert set(latest["hbm"]) == {"bytesInUse", "peakBytesInUse",
                                  "bytesLimit", "headroomFrac"}
    assert "fragmentation" in latest["scheduler"]
    assert "queueDepth" in latest["serving"]
    assert latest["jobs"]["running"] == 0
    assert latest["hostRssBytes"] > 0
    assert "bytesInUse" in latest["arena"]
    assert doc["samples"] >= 1 and "hostRssBytes" in doc["series"]
    # the context wires real collectors: arena + scheduler present
    assert doc["intervalSeconds"] == 3600.0


def test_alerts_fire_resolve_healthz_and_gauges(slo_server,
                                               tmp_config):
    """ISSUE acceptance: an injected serving-latency breach flips
    ``lo_alerts_firing`` >= 1 AND /healthz to 503; both healthy after
    the fault clears."""
    watchdog = slo_server.api.ctx.monitor.watchdog
    status, body = _call(slo_server, "GET", "/healthz")
    assert status == 200 and body["status"] == "ok"

    t0 = time.time()
    watchdog.evaluate(now=t0)
    for _ in range(50):   # 700ms >> the 100ms p99 objective
        obs_hist.observe("lo_serving_request_seconds", 0.7)
    watchdog.evaluate(now=t0 + 1.0)
    assert watchdog.page_firing()

    status, body = _call(slo_server, "GET", "/healthz")
    assert status == 503 and body["status"] == "failing"
    assert body["alerts"][0]["name"] == "servingP99"

    status, m = _call(slo_server, "GET", "/metrics")
    assert m["alertsFiring"] >= 1
    assert m["alerts"][0]["severity"] == "page"
    assert "cluster" in m
    status, raw = _call(slo_server, "GET", "/metrics",
                        params="?format=prometheus")
    text = raw.decode()
    assert re.search(r"^lo_alerts_firing [1-9]", text, re.M)
    assert 'lo_alert_firing{alert="servingP99",severity="page"} 1' \
        in text

    status, doc = _call(slo_server, "GET",
                        f"{API}/observability/alerts")
    assert status == 200
    assert doc["objectives"]["servingP99"]["threshold"] == 100.0
    assert [a["name"] for a in doc["firing"]] == ["servingP99"]
    assert doc["history"][0]["transition"] == "firing"

    # fault clears: the fast window drains, everything goes healthy
    watchdog.evaluate(now=t0 + 3.0)
    status, body = _call(slo_server, "GET", "/healthz")
    assert status == 200 and body["status"] == "ok"
    status, raw = _call(slo_server, "GET", "/metrics",
                        params="?format=prometheus")
    assert re.search(r"^lo_alerts_firing 0", raw.decode(), re.M)


def test_serving_step_latency_fault_pages_through_a_live_session(
        slo_server, tmp_config):
    """The chaos site end to end: an armed ``serving_step`` latency
    fault slows a live predict session's iterations, the requests'
    own latencies land in ``lo_serving_request_seconds``, the
    watchdog pages on them with the session's trace, and /healthz
    follows the alert up and down."""
    import numpy as np

    from learningorchestra_tpu.models.estimators import (
        LogisticRegressionJAX)
    from learningorchestra_tpu.services import faults

    ctx = slo_server.api.ctx
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    clf = LogisticRegressionJAX(epochs=1, batch_size=32)
    clf.fit(x, (x[:, 0] > 0).astype(np.int64))
    ctx.artifacts.save(clf, "mon_clf", "train/tensorflow")
    status, body = _call(slo_server, "POST", f"{API}/serve/mon_clf", {})
    assert status == 201, body
    watchdog = ctx.monitor.watchdog
    t0 = time.time()
    watchdog.evaluate(now=t0)
    try:
        # 0.3 s an iteration, three times the 100 ms objective
        tmp_config.fault_inject = "serving_step:3:latency:0.3"
        faults.reset()
        for _ in range(3):
            status, body = _call(
                slo_server, "POST", f"{API}/serve/mon_clf/predict",
                {"x": [[0.1, 0.2, 0.3, 0.4]]})
            assert status == 200, body
        watchdog.evaluate(now=t0 + 1.0)
        (alert,) = watchdog.firing()
        assert alert["name"] == "servingP99" and alert["value"] >= 300.0
        assert alert["trace"].startswith("serve/mon_clf")
        status, body = _call(slo_server, "GET", "/healthz")
        assert status == 503 and body["status"] == "failing"
        # the budget of three is spent: no restart, the window drains
        watchdog.evaluate(now=t0 + 3.0)
        assert not watchdog.page_firing()
        status, body = _call(slo_server, "GET", "/healthz")
        assert status == 200 and body["status"] == "ok"
    finally:
        tmp_config.fault_inject = ""
        faults.reset()
        _call(slo_server, "DELETE", f"{API}/serve/mon_clf")


def test_healthz_503_while_draining(slo_server):
    slo_server.api.ctx.begin_drain()
    status, body = _call(slo_server, "GET", "/healthz")
    assert status == 503 and body["status"] == "draining"


def test_monitor_disabled_404(tmp_config):
    from learningorchestra_tpu.services.server import RestServer

    tmp_config.monitor = False
    srv = RestServer(host="127.0.0.1", port=0).start()
    try:
        assert srv.api.ctx.monitor is None
        status, _ = _call(srv, "GET", f"{API}/observability/cluster")
        assert status == 404
        status, _ = _call(srv, "GET", f"{API}/observability/alerts")
        assert status == 404
        # /healthz still answers without the watchdog
        status, body = _call(srv, "GET", "/healthz")
        assert status == 200 and body["status"] == "ok"
        status, m = _call(srv, "GET", "/metrics")
        assert "cluster" not in m and "alertsFiring" not in m
    finally:
        srv.stop()


# ----------------------------------------------------------------------
# /profile stop-path leak (satellite 1)
# ----------------------------------------------------------------------

def test_profile_lifecycle_with_stubbed_profiler(slo_server,
                                                 monkeypatch):
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    # GET before anything: inactive, empty listing
    status, body = _call(slo_server, "GET", f"{API}/profile")
    assert status == 200
    assert body == {"active": False, "traces": []}
    # stop without start -> 406
    status, _ = _call(slo_server, "POST", f"{API}/profile",
                      body={"action": "stop"})
    assert status == 406
    # bad action -> 406
    status, _ = _call(slo_server, "POST", f"{API}/profile",
                      body={"action": "pause"})
    assert status == 406
    status, body = _call(slo_server, "POST", f"{API}/profile",
                         body={"action": "start"})
    assert status == 201
    # double start -> 406
    status, _ = _call(slo_server, "POST", f"{API}/profile",
                      body={"action": "start"})
    assert status == 406
    status, body = _call(slo_server, "POST", f"{API}/profile",
                         body={"action": "stop"})
    assert status == 200 and body["files"] == 0
    status, body = _call(slo_server, "GET", f"{API}/profile")
    assert status == 200
    assert body["active"] is False and len(body["traces"]) == 1


def test_profile_stop_failure_clears_active_state(slo_server,
                                                  monkeypatch):
    """The leak this PR fixes: a raising ``stop_trace`` left
    ``_profile_dir`` set, so every later start 406'd forever with no
    live profiler behind it. Now the failure surfaces as a 500 and
    the profiler is startable again."""
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)

    def broken_stop():
        raise RuntimeError("profiler session lost")

    monkeypatch.setattr(jax.profiler, "stop_trace", broken_stop)
    status, _ = _call(slo_server, "POST", f"{API}/profile",
                      body={"action": "start"})
    assert status == 201
    status, body = _call(slo_server, "POST", f"{API}/profile",
                         body={"action": "stop"})
    assert status == 500
    assert "profiler session lost" in body["result"]
    # state cleared: a new start succeeds (pre-fix: 406 forever)
    status, body = _call(slo_server, "GET", f"{API}/profile")
    assert body["active"] is False
    status, _ = _call(slo_server, "POST", f"{API}/profile",
                      body={"action": "start"})
    assert status == 201
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    status, _ = _call(slo_server, "POST", f"{API}/profile",
                      body={"action": "stop"})
    assert status == 200


# ----------------------------------------------------------------------
# strict Prometheus exposition (satellite 3)
# ----------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r'([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (\S+)')
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape(raw):
    out, i = [], 0
    while i < len(raw):
        if raw[i] == "\\" and i + 1 < len(raw):
            out.append({"\\": "\\", '"': '"', "n": "\n"}.get(
                raw[i + 1], raw[i:i + 2]))
            i += 2
        else:
            out.append(raw[i])
            i += 1
    return "".join(out)


def test_prometheus_exposition_is_strictly_well_formed(slo_server):
    """Satellite: every series has a # TYPE, histogram buckets are
    cumulative/monotone with +Inf == _count, and every label value
    survives an escape_label_value round-trip."""
    from learningorchestra_tpu.services.server import \
        escape_label_value

    # traffic with label values that exercise the escaper
    _call(slo_server, "GET", "/health")
    _call(slo_server, "GET", f"{API}/dataset/csv")
    obs_hist.observe("lo_serving_request_seconds", 0.02)
    obs_hist.observe("lo_serving_request_seconds", 4.0)
    slo_server.api.ctx.monitor.sample_once()

    status, raw = _call(slo_server, "GET", "/metrics",
                        params="?format=prometheus")
    assert status == 200
    text = raw.decode()
    types = {}
    samples = []
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            assert name not in types, f"duplicate TYPE for {name}"
            assert kind in ("gauge", "counter", "histogram")
            types[name] = kind
            continue
        assert not line.startswith("#"), line
        match = _SAMPLE_RE.fullmatch(line)
        assert match, f"malformed sample line: {line!r}"
        name, labelstr, value = match.groups()
        float(value)  # parseable
        labels = {}
        if labelstr is not None:
            consumed = 0
            for lm in _LABEL_RE.finditer(labelstr):
                labels[lm.group(1)] = lm.group(2)
                consumed = lm.end()
            # nothing but separators between/after label pairs
            assert not labelstr[consumed:].strip(", "), line
        samples.append((name, labels, float(value)))
    assert samples, "empty exposition"

    histogram_buckets = {}
    for name, labels, value in samples:
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            stripped = name[:-len(suffix)] \
                if name.endswith(suffix) else None
            if stripped and types.get(stripped) == "histogram":
                base = stripped
                break
        assert base in types, f"sample {name} has no # TYPE"
        if types[base] == "histogram" and name.endswith("_bucket"):
            assert "le" in labels, line
            key = (base, tuple(sorted((k, v) for k, v in
                                      labels.items() if k != "le")))
            histogram_buckets.setdefault(key, []).append(
                (float("inf") if labels["le"] == "+Inf"
                 else float(labels["le"]), value))
        # label values survive the escaping round-trip
        for raw_value in labels.values():
            assert escape_label_value(_unescape(raw_value)) == \
                raw_value

    counts = {(n, tuple(sorted(lbl.items()))): v
              for n, lbl, v in samples if n.endswith("_count")}
    assert histogram_buckets, "no histogram series in exposition"
    for (base, label_key), buckets in histogram_buckets.items():
        buckets.sort()
        values = [v for _, v in buckets]
        assert values == sorted(values), \
            f"{base} buckets not cumulative/monotone"
        assert buckets[-1][0] == float("inf"), f"{base} missing +Inf"
        count = counts.get((f"{base}_count", label_key))
        assert count is not None, f"{base}_count missing"
        assert buckets[-1][1] == count, \
            f"{base} +Inf bucket != _count"
