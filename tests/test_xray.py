"""HBM attribution ledger + compiled-artifact X-ray
(docs/OBSERVABILITY.md "HBM attribution & X-ray"): ledger
register/release math, host-entry exclusion from the device
subtraction, retrace and transfer sentinels, the REST surface
(/observability/memory, /observability/compile), event-log rotation,
monitor/SLO integration, and a concurrent /metrics scrape while the
ledger and arena mutate underneath it."""

import json
import os
import threading
import time

import numpy as np
import pytest

from learningorchestra_tpu import config as config_mod
from learningorchestra_tpu.observability import export as obs_export
from learningorchestra_tpu.observability import hist as obs_hist
from learningorchestra_tpu.observability import timeline as obs_timeline
from learningorchestra_tpu.observability import trace as obs_trace
from learningorchestra_tpu.observability import xray

PREFIX = "/api/learningOrchestra/v1"


@pytest.fixture(autouse=True)
def _fresh_xray():
    """The ledger, compile registry and sentinel counters are
    process-global; start and end every test with them empty."""
    xray.reset()
    obs_trace.reset()
    obs_timeline.reset()
    obs_hist.reset()
    yield
    xray.reset()
    obs_trace.reset()
    obs_timeline.reset()
    obs_hist.reset()


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


# ------------------------------------------------------------- ledger
def test_ledger_register_release_and_owner_sums():
    xray.register("arena", ("k", 1), 100, name="jobA")
    xray.register("arena", ("k", 2), 50)
    xray.register("train-state", 42, 200, name="jobA")
    # zero-filled: every known owner present even with no entries
    assert xray.by_owner() == {"arena": 150, "train-state": 200,
                               "serving-params": 0, "kv-cache": 0,
                               "snapshot": 0}
    assert xray.attributed_bytes() == 350
    # re-registering a live key REPLACES its byte count
    xray.register("train-state", 42, 300, name="jobA")
    assert xray.by_owner()["train-state"] == 300
    xray.release("arena", ("k", 1))
    assert xray.by_owner()["arena"] == 50
    # unknown key: no-op, never raises
    xray.release("arena", ("never", "seen"))
    xray.release("kv-cache", 7)
    assert xray.attributed_bytes() == 350


def test_disabled_registration_keeps_releases_active(monkeypatch):
    xray.register("arena", "a", 10)
    monkeypatch.setenv("LO_XRAY", "0")
    assert not xray.enabled()
    xray.register("arena", "b", 20)       # no-op while disabled
    assert xray.attributed_bytes() == 10
    xray.release("arena", "a")            # release still active
    assert xray.attributed_bytes() == 0
    monkeypatch.setenv("LO_XRAY", "1")
    assert xray.enabled()


def test_memory_report_excludes_host_entries_from_unattributed(
        monkeypatch):
    xray.register("serving-params", "p", 1000, name="m")
    xray.register("snapshot", "s", 4000, name="t", host=True)
    monkeypatch.setattr(xray, "device_bytes_in_use",
                        lambda: (1500, "memoryStats"))
    rep = xray.memory_report()
    assert rep["owners"] == {"serving-params": 1000, "snapshot": 4000,
                             "arena": 0, "train-state": 0,
                             "kv-cache": 0}
    assert rep["attributedBytes"] == 5000
    # host snapshot bytes do NOT subtract from device bytes-in-use
    assert rep["attributedDeviceBytes"] == 1000
    assert rep["bytesInUse"] == 1500
    assert rep["unattributedBytes"] == 500
    # unattributed clamps at zero rather than faking negative temps
    monkeypatch.setattr(xray, "device_bytes_in_use",
                        lambda: (900, "memoryStats"))
    assert xray.memory_report()["unattributedBytes"] == 0


def test_memory_report_filters_by_name():
    xray.register("arena", "a", 100, name="jobA")
    xray.register("arena", "b", 50, name="jobB")
    rep = xray.memory_report("jobA")
    assert rep["name"] == "jobA"
    assert rep["owners"] == {"arena": 100}
    assert len(rep["entries"]) == 1
    # the process-wide remainder is meaningless for a ledger slice
    assert "unattributedBytes" not in rep
    assert xray.memory_report("nobody")["entries"] == []


def test_ring_sample_matches_report(monkeypatch):
    xray.register("arena", "a", 700)
    xray.register("snapshot", "s", 300, host=True)
    monkeypatch.setattr(xray, "device_bytes_in_use",
                        lambda: (1000, "memoryStats"))
    assert xray.ring_sample() == (1000, 300)
    monkeypatch.setattr(xray, "device_bytes_in_use",
                        lambda: (None, "unavailable"))
    assert xray.ring_sample() == (1000, None)


def test_arena_entries_ledger_and_release(tmp_path):
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home")))
    from learningorchestra_tpu.runtime import arena

    try:
        arena.reset_default_arena()
        ar = arena.get_default_arena()
        entry = ar.get_or_put(
            ("t", "x"), lambda: {"a": np.ones(1024, np.float32)},
            tags=("jobX",))
        assert xray.by_owner().get("arena", 0) >= 4096
        rows = xray.memory_report("jobX")["entries"]
        assert rows and rows[0]["owner"] == "arena"
        entry.release()
        ar.clear()
        assert xray.by_owner().get("arena", 0) == 0
    finally:
        arena.reset_default_arena()
        config_mod.reset_config()


def test_fit_ledgers_train_state_while_it_runs(tmp_path, monkeypatch):
    """A fit holds a ``train-state`` entry from its first step to its
    exit: read at the moment the engine releases it, then after."""
    from learningorchestra_tpu.models.neural import NeuralModel

    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), compute_dtype="float32"))
    held = []
    release = xray.release

    def spy(owner, key):
        if owner == "train-state":
            held.append(xray.by_owner().get("train-state", 0))
        return release(owner, key)

    monkeypatch.setattr(xray, "release", spy)
    try:
        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 6)).astype(np.float32)
        model = NeuralModel([
            {"kind": "dense", "units": 7, "activation": "relu"},
            {"kind": "dense", "units": 2, "activation": "softmax"}])
        model.fit(x, (x[:, 0] > 0).astype(np.int32), epochs=1,
                  batch_size=16, shuffle=False)
        # at least the parameters and Adam's two moments of them
        n_params = 6 * 7 + 7 + 7 * 2 + 2
        assert held and held[0] >= 3 * 4 * n_params
        assert xray.by_owner().get("train-state", 0) == 0
    finally:
        config_mod.reset_config()


def test_inflight_async_snapshot_is_ledgered_then_released(tmp_path):
    """An async checkpoint's host snapshot is a ``snapshot`` entry of
    its bytes while the commit is in flight, and none once it landed."""
    from learningorchestra_tpu.runtime.async_ckpt import (
        AsyncCheckpointManager)
    from learningorchestra_tpu.runtime.checkpoint import Checkpointer

    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home")))
    gate = threading.Event()

    class Gated(Checkpointer):
        def _commit_host(self, step, host):
            assert gate.wait(timeout=60)
            return super()._commit_host(step, host)

    mgr = AsyncCheckpointManager(Gated(str(tmp_path / "ckpt")),
                                 inflight=2)
    try:
        mgr.save(1, {"w": np.ones((256, 256), np.float32)})
        assert xray.by_owner()["snapshot"] >= 256 * 256 * 4
        (row,) = [r for r in xray.memory_report()["entries"]
                  if r["owner"] == "snapshot"]
        assert row["host"] is True
        gate.set()
        mgr.wait_until_finished()
        assert xray.by_owner().get("snapshot", 0) == 0
        assert mgr.latest_step() == 1
    finally:
        gate.set()
        mgr.close()
        config_mod.reset_config()


# -------------------------------------------------- retrace sentinel
def test_retrace_sentinel_counts_signature_changes():
    prog = ("engine", 1)
    sig_a = (("x", (16, 8)),)
    sig_b = (("x", (13, 8)),)
    assert xray.note_signature(prog, sig_a, name="t") is False
    assert xray.note_signature(prog, sig_a, name="t") is False
    assert xray.counters()["retraces"] == 0
    assert xray.note_signature(prog, sig_b, name="t") is True
    assert xray.counters()["retraces"] == 1
    (ev,) = xray.retrace_events()
    assert ev["prevSignature"] == str(sig_a)
    assert ev["newSignature"] == str(sig_b)
    assert ev["name"] == "t"
    # a different program key is NOT a retrace of the first
    assert xray.note_signature(("engine", 2), sig_a) is False


def test_retrace_event_reaches_event_log(tmp_path):
    log = tmp_path / "events.jsonl"
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), event_log=str(log)))
    try:
        xray.note_signature("p", "sigA", name="t")
        xray.note_signature("p", "sigB", name="t")
        entries = [json.loads(line)
                   for line in log.read_text().splitlines()]
        retraces = [e for e in entries if e["kind"] == "retrace"]
        assert retraces, entries
        assert retraces[0]["prevSignature"] == "sigA"
        assert retraces[0]["newSignature"] == "sigB"
    finally:
        config_mod.reset_config()


# ------------------------------------------------- transfer sentinel
def test_guarded_call_off_is_plain_call(tmp_path):
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), transfer_guard=""))
    try:
        assert xray.guarded_call(lambda a, b: a + b, 1, 2) == 3
        assert xray.counters()["implicitTransfers"] == 0
    finally:
        config_mod.reset_config()


def test_guarded_call_log_mode_counts_and_proceeds(tmp_path):
    import jax
    import jax.numpy as jnp

    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), transfer_guard="log"))
    try:
        fn = jax.jit(lambda v: jnp.sum(v * 2.0))
        host_arg = np.ones(4, np.float32)  # implicit h2d transfer
        out = xray.guarded_call(fn, host_arg, name="t")
        assert float(out) == 8.0
        assert xray.counters()["implicitTransfers"] >= 1
        ev = xray.transfer_events()[0]
        assert "host-to-device" in ev["direction"]
        assert ev["signature"]  # carries the offending abstract value
        assert ev["name"] == "t"
        # device-resident args pass through the guard uncounted
        before = xray.counters()["implicitTransfers"]
        dev_arg = jnp.ones(4, jnp.float32)
        assert float(xray.guarded_call(fn, dev_arg)) == 8.0
        assert xray.counters()["implicitTransfers"] == before
    finally:
        config_mod.reset_config()


def test_guarded_call_fail_mode_raises(tmp_path):
    import jax
    import jax.numpy as jnp

    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), transfer_guard="fail"))
    try:
        fn = jax.jit(lambda v: jnp.sum(v))
        with pytest.raises(Exception, match="[Dd]isallowed"):
            xray.guarded_call(fn, np.ones(4, np.float32))
        assert xray.counters()["implicitTransfers"] >= 1
    finally:
        config_mod.reset_config()


def test_guarded_call_unrelated_errors_propagate(tmp_path):
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), transfer_guard="log"))
    try:
        def boom():
            raise ValueError("not a transfer")

        with pytest.raises(ValueError, match="not a transfer"):
            xray.guarded_call(boom)
        assert xray.counters()["implicitTransfers"] == 0
    finally:
        config_mod.reset_config()


# ------------------------------------------- compiled-artifact X-ray
def test_extract_memory_and_cost_analysis_real_executable():
    import jax
    import jax.numpy as jnp

    lowered = jax.jit(lambda v: jnp.dot(v, v)).lower(
        jnp.ones((32, 32), jnp.float32))
    compiled = lowered.compile()
    mem = xray.extract_memory_analysis(compiled)
    assert mem, "memory_analysis produced no named int fields"
    assert mem["argumentBytes"] >= 32 * 32 * 4
    assert "peakBytesEstimate" in mem
    assert "serialized_hlo_proto" not in str(mem)
    cost = (xray.extract_cost_analysis(compiled)
            or xray.extract_cost_analysis(lowered))
    if cost:  # cost model availability varies per backend
        assert cost.get("flops", 0) > 0


def test_compile_registry_records_and_evicts_lru():
    xray.record_compile("t", "trainStep", {"memory": {"tempBytes": 1}})
    xray.record_compile("t", "evalStep", {"memory": {"tempBytes": 2}})
    rep = xray.compile_report("t")
    assert set(rep["programs"]) == {"trainStep", "evalStep"}
    assert rep["programs"]["trainStep"]["memory"]["tempBytes"] == 1
    assert rep["programs"]["trainStep"]["updatedAt"] > 0
    assert xray.compile_report("never") is None
    for i in range(140):  # LRU bound holds
        xray.record_compile(f"n{i}", "p", {})
    assert len(xray.known_compiles()) <= 128
    assert xray.compile_report("t") is None  # aged out


# ------------------------------------------------------ REST surface
def test_memory_and_compile_routes(api):
    xray.register("arena", "a", 256, name="jobA")
    xray.register("snapshot", "s", 64, name="jobA", host=True)
    st, rep, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/memory", {}, None)
    assert st == 200, rep
    assert rep["owners"]["arena"] == 256
    assert rep["attributedDeviceBytes"] == 256
    assert rep["bytesSource"] in ("memoryStats", "liveArrays",
                                  "unavailable")
    assert rep["retracesTotal"] == 0

    st, rep, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/memory/jobA", {}, None)
    assert st == 200 and rep["name"] == "jobA"
    assert len(rep["entries"]) == 2
    st, body, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/memory/never-ran", {}, None)
    assert st == 404, body

    xray.record_compile("jobA", "trainStep",
                        {"memory": {"tempBytes": 5}})
    st, listing, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/compile", {}, None)
    assert st == 200 and listing["result"] == ["jobA"]
    st, rep, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/compile/jobA", {}, None)
    assert st == 200
    assert rep["programs"]["trainStep"]["memory"]["tempBytes"] == 5
    st, body, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/compile/never-ran", {}, None)
    assert st == 404, body


def test_metrics_expose_xray_gauges(api):
    xray.register("kv-cache", "k", 512, name="m")
    xray.note_signature("p", "a")
    xray.note_signature("p", "b")
    xray.note_transfer("host-to-device", "f32[4]")
    st, m, _ = api.dispatch("GET", "/metrics", {}, None)
    assert st == 200
    assert m["xray"]["owners"]["kv-cache"] == 512
    assert m["xray"]["counters"] == {"retraces": 1,
                                     "implicitTransfers": 1}
    text = api.metrics_prometheus().decode()
    assert 'lo_hbm_attributed_bytes{owner="kv-cache"} 512' in text
    assert "lo_retraces_total 1" in text
    assert "lo_implicit_transfers_total 1" in text


# -------------------------------------------- end-to-end attribution
def test_train_job_records_compile_xray(api):
    st, _, _ = api.dispatch(
        "POST", f"{PREFIX}/function/python",
        {}, {"name": "d", "functionParameters": {}, "function":
             "import numpy as np\nrng = np.random.default_rng(0)\n"
             "x = rng.normal(size=(64, 10)).astype(np.float32)\n"
             "y = (x[:, 0] > 0).astype(np.int32)\n"
             "response = {'x': x, 'y': y}\n"})
    assert st == 201
    _wait(api, "d", "function/python")
    st, _, _ = api.dispatch(
        "POST", f"{PREFIX}/model/tensorflow",
        {}, {"modelName": "m",
             "modulePath": "learningorchestra_tpu.models",
             "class": "NeuralModel",
             "classParameters": {"layer_configs": [
                 # distinct dims from other test files' pipelines — the
                 # engine's compiled-step cache is module-global, and a
                 # colliding (config, shape) key would rob their cold-
                 # compile assertions
                 {"kind": "dense", "units": 5, "activation": "relu"},
                 {"kind": "dense", "units": 2,
                  "activation": "softmax"}]}})
    assert st == 201
    _wait(api, "m", "model/tensorflow")
    st, _, _ = api.dispatch(
        "POST", f"{PREFIX}/train/tensorflow",
        {}, {"name": "t", "modelName": "m", "method": "fit",
             "methodParameters": {"x": "$d.x", "y": "$d.y",
                                  "epochs": 2, "batch_size": 16}})
    assert st == 201
    _wait(api, "t", "train/tensorflow")

    st, rep, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/compile/t", {}, None)
    assert st == 200, rep
    prog = rep["programs"]["trainStep"]
    assert prog["memory"].get("peakBytesEstimate", 0) > 0
    assert prog["batchShapes"]["x"] == [16, 10]
    # the fit's train-state registration released at fit exit
    assert xray.by_owner().get("train-state", 0) == 0


def test_lm_serving_attributes_params_and_kv_cache(api):
    from learningorchestra_tpu.models.transformer import LanguageModel

    lm = LanguageModel(vocab_size=48, d_model=32, n_layers=1,
                       n_heads=2, d_ff=64, max_len=32, attention="dot")
    rng = np.random.default_rng(1)
    tokens = rng.integers(1, 48, size=(16, 16)).astype(np.int32)
    lm.fit(tokens, batch_size=16, epochs=1)
    api.ctx.artifacts.save(lm, "slm", "train/tensorflow")

    st, body, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/slm", {},
        {"maxSlots": 2, "cacheLen": 32})
    assert st == 201, body
    owners = xray.by_owner()
    assert owners.get("serving-params", 0) > 0
    assert owners.get("kv-cache", 0) > 0
    # params were RE-TAGGED from arena, not double-counted: no arena
    # row shares the serving pin's key
    rows = xray.memory_report("slm")["entries"]
    assert {r["owner"] for r in rows} == {"serving-params", "kv-cache"}
    (kv,) = [r for r in rows if r["owner"] == "kv-cache"]
    assert kv["slots"] == 2 and kv["cacheLen"] == 32

    st, body, _ = api.dispatch(
        "DELETE", f"{PREFIX}/serve/slm", {}, None)
    assert st == 200, body
    owners = xray.by_owner()
    assert owners.get("serving-params", 0) == 0
    assert owners.get("kv-cache", 0) == 0


# ------------------------------------------- monitor/SLO integration
def test_monitor_samples_xray_and_slo_pages_on_growth(tmp_path):
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"),
        slo_unattributed_growth_bytes=1000,
        slo_fast_window_s=5.0, slo_slow_window_s=10.0))
    try:
        from learningorchestra_tpu.observability.monitor import (
            ClusterMonitor)
        from learningorchestra_tpu.observability.slo import SloWatchdog

        watchdog = SloWatchdog()
        mon = ClusterMonitor(device_stats=lambda: [],
                             watchdog=watchdog)
        xray.register("arena", "a", 100)
        now = time.time()
        # grow the unattributed remainder past the threshold inside
        # the FAST window (so both burn-rate windows see the jump):
        # fake in-use numbers around the ledger's 100 bytes
        orig = xray.device_bytes_in_use
        try:
            xray.device_bytes_in_use = lambda: (100, "memoryStats")
            sample = mon.sample_once(now=now - 8)
            assert sample["xray"]["owners"]["arena"] == 100
            assert sample["xray"]["attributedBytes"] == 100
            assert mon.series("xrayAttributedBytes")
            mon.sample_once(now=now - 6)
            mon.sample_once(now=now - 1)
            xray.device_bytes_in_use = lambda: (5100, "memoryStats")
            mon.sample_once(now=now)
        finally:
            xray.device_bytes_in_use = orig
        firing = {a["name"] for a in watchdog.firing()}
        assert "unattributedGrowth" in firing
        (alert,) = [a for a in watchdog.firing()
                    if a["name"] == "unattributedGrowth"]
        assert alert["severity"] == "page"
    finally:
        config_mod.reset_config()


# --------------------------------------------- event-log rotation
def test_event_log_rotates_at_size_bound(tmp_path):
    log = tmp_path / "events.jsonl"
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), event_log=str(log),
        event_log_max_bytes=400))
    try:
        for i in range(40):
            obs_export.log_event("test", f"event-{i}",
                                 payload="x" * 64)
        rolled = tmp_path / "events.jsonl.1"
        assert rolled.exists(), "no keep-1 rollover happened"
        # neither generation grows past bound + one record
        assert log.stat().st_size <= 400 + 256
        assert rolled.stat().st_size <= 400 + 256
        # both generations hold valid JSONL
        for p in (log, rolled):
            for line in p.read_text().splitlines():
                json.loads(line)
    finally:
        config_mod.reset_config()


def test_event_log_rotation_disabled_at_zero(tmp_path):
    log = tmp_path / "events.jsonl"
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), event_log=str(log),
        event_log_max_bytes=0))
    try:
        for i in range(50):
            obs_export.log_event("test", f"event-{i}",
                                 payload="x" * 64)
        assert not (tmp_path / "events.jsonl.1").exists()
        assert log.stat().st_size > 2000
    finally:
        config_mod.reset_config()


# ------------------------------- concurrent scrape (satellite test)
def test_concurrent_metrics_scrape_while_ledger_mutates(api):
    """/metrics (JSON and prometheus text) scraped from one thread
    while others churn the ledger and the arena: every exposition must
    parse cleanly and every gauge line carry a finite number — torn
    reads or half-registered entries may not corrupt the text."""
    from learningorchestra_tpu.runtime import arena

    ar = arena.get_default_arena()
    stop = threading.Event()
    errors = []

    def churn_ledger(seed):
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            key = ("churn", seed, int(rng.integers(0, 8)))
            xray.register("train-state", key,
                          int(rng.integers(1, 1 << 20)), name="churn")
            xray.note_signature(("churn", seed),
                                str(rng.integers(0, 3)))
            xray.release("train-state", key)

    def churn_arena():
        i = 0
        while not stop.is_set():
            i += 1
            key = ("scrape", i % 4)
            ar.get_or_put(
                key, lambda: {"a": np.ones(256, np.float32)},
                tags=("scrape",)).release()
            if i % 3 == 0:
                ar.invalidate("scrape")

    threads = [threading.Thread(target=churn_ledger, args=(s,))
               for s in (1, 2)] + [
        threading.Thread(target=churn_arena)]
    for t in threads:
        t.start()
    try:
        for _ in range(30):
            st, m, _ = api.dispatch("GET", "/metrics", {}, None)
            assert st == 200
            assert isinstance(m["xray"]["attributedBytes"], int)
            for owner, n in m["xray"]["owners"].items():
                assert isinstance(owner, str) and n >= 0
            text = api.metrics_prometheus().decode()
            gauge_lines = [ln for ln in text.splitlines()
                           if ln.startswith(("lo_hbm_attributed_bytes",
                                             "lo_retraces_total",
                                             "lo_implicit_transfers"))
                           and not ln.startswith("#")]
            for ln in gauge_lines:
                value = float(ln.rsplit(" ", 1)[1])
                assert value >= 0, ln
    except Exception as exc:  # noqa: BLE001 — re-raised after join
        errors.append(exc)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        arena.reset_default_arena()
    assert not errors, errors
