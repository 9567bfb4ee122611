"""End-to-end job tracing, per-step telemetry and latency histograms
(docs/OBSERVABILITY.md): span nesting/thread-safety, ring bounding,
Chrome trace_event schema, histogram bucket math, disabled no-op path,
Prometheus escaping with hostile names, best-effort event-log export,
and the full REST surface over a real train job and serving session."""

import json
import threading
import time

import numpy as np
import pytest

from learningorchestra_tpu import config as config_mod
from learningorchestra_tpu.observability import export as obs_export
from learningorchestra_tpu.observability import hist as obs_hist
from learningorchestra_tpu.observability import timeline as obs_timeline
from learningorchestra_tpu.observability import trace as obs_trace
from learningorchestra_tpu.services import faults

PREFIX = "/api/learningOrchestra/v1"


@pytest.fixture(autouse=True)
def _fresh_observability():
    """Tracer/timeline/histogram registries are process-global rings;
    start and end every test with them empty."""
    obs_trace.reset()
    obs_timeline.reset()
    obs_hist.reset()
    faults.reset()
    yield
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


def _span_names(tree):
    out = []

    def walk(sp):
        out.append(sp["name"])
        for c in sp["children"]:
            walk(c)

    for root in tree["spans"]:
        walk(root)
    return out


# ------------------------------------------------------------- tracer
def test_span_nesting_builds_tree(tmp_config):
    with obs_trace.span("job", trace="j1", phase="run") as root:
        with obs_trace.span("inner") as child:
            obs_trace.annotate(step=3)
            assert obs_trace.current() == ("j1", child.span_id)
        assert obs_trace.current() == ("j1", root.span_id)
    assert obs_trace.current() is None

    tree = obs_trace.tree("j1")
    assert tree["traceId"] == "j1" and tree["spanCount"] == 2
    (job,) = tree["spans"]
    assert job["name"] == "job" and job["attrs"] == {"phase": "run"}
    (inner,) = job["children"]
    assert inner["name"] == "inner" and inner["attrs"] == {"step": 3}
    assert inner["parentId"] == job["spanId"]
    assert not inner["inFlight"] and not job["inFlight"]
    assert inner["startSeconds"] >= job["startSeconds"] >= 0.0


def test_span_records_error_attr_on_exception(tmp_config):
    with pytest.raises(ValueError):
        with obs_trace.span("boom", trace="j2"):
            raise ValueError("nope")
    (sp,) = obs_trace.spans_of("j2")
    assert sp.attrs["error"] == "ValueError" and sp.end is not None


def test_add_retro_span_returns_id_for_parenting(tmp_config):
    t0 = time.monotonic()
    root = obs_trace.add("request", "serve/m/1", t0, t0 + 1.0, kind="lm")
    child = obs_trace.add("queueWait", "serve/m/1", t0, t0 + 0.25,
                          parent=root)
    assert isinstance(root, int) and isinstance(child, int)
    tree = obs_trace.tree("serve/m/1")
    (req,) = tree["spans"]
    assert req["durationSeconds"] == pytest.approx(1.0)
    assert [c["name"] for c in req["children"]] == ["queueWait"]
    assert obs_trace.durations_by_name("serve/m/1") == {
        "request": 1.0, "queueWait": 0.25}


def test_tracer_thread_safety_under_concurrent_traces(tmp_config):
    errors = []

    def worker(i):
        try:
            for k in range(50):
                with obs_trace.span("outer", trace=f"tr{i % 4}", k=k):
                    with obs_trace.span("inner"):
                        pass
                obs_trace.add("retro", f"tr{i % 4}",
                              time.monotonic(), time.monotonic())
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert not errors
    for i in range(4):
        spans = obs_trace.spans_of(f"tr{i}")
        assert spans and all(s.end is not None for s in spans)
        # nesting stayed thread-local: every inner's parent is an outer
        by_id = {s.span_id: s for s in spans}
        for s in spans:
            if s.name == "inner" and s.parent_id in by_id:
                assert by_id[s.parent_id].name == "outer"


def test_trace_ring_bounds_spans_and_keeps_open_ones(tmp_path):
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), trace_ring=8))
    try:
        ctx = obs_trace.span("held-open", trace="ring")
        ctx.__enter__()
        for i in range(40):
            obs_trace.add(f"s{i}", "ring", 0.0, 0.1)
        spans = obs_trace.spans_of("ring")
        assert len(spans) == 8
        assert any(s.name == "held-open" for s in spans), \
            "ring evicted an open span"
        # survivors are the newest finished spans
        finished = [s.name for s in spans if s.end is not None]
        assert finished == [f"s{i}" for i in range(33, 40)]
        ctx.__exit__(None, None, None)
    finally:
        config_mod.reset_config()


def test_trace_table_is_lru_bounded(tmp_config):
    for i in range(obs_trace._MAX_TRACES + 20):
        obs_trace.add("s", f"t{i}", 0.0, 0.1)
    known = obs_trace.known_traces()
    assert len(known) == obs_trace._MAX_TRACES
    assert "t0" not in known and f"t{obs_trace._MAX_TRACES + 19}" in known


def test_disabled_mode_is_shared_noop(tmp_path):
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), trace=False))
    try:
        assert obs_trace.span("x", trace="t") is obs_trace.NOOP
        assert obs_trace.span("y") is obs_trace.NOOP
        with obs_trace.span("x", trace="t") as s:
            s.set(a=1)  # still a no-op surface
        assert obs_trace.add("x", "t", 0.0, 1.0) is None
        assert obs_trace.current() is None
        obs_timeline.record("j", step=1, dt=0.1)
        assert obs_trace.known_traces() == []
        assert obs_timeline.known_jobs() == []
    finally:
        config_mod.reset_config()


def test_span_without_trace_or_current_is_noop(tmp_config):
    assert obs_trace.span("orphan") is obs_trace.NOOP
    assert obs_trace.known_traces() == []


# ----------------------------------------------------------- timeline
def test_timeline_ring_bounds_and_summary_percentiles(tmp_path):
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), timeline_ring=8))
    try:
        for i in range(1, 41):
            obs_timeline.record(
                "job", step=i, dt=0.01 * i, examples_per_second=100.0,
                loss=1.0 / i, retrace=(i == 33))
        rows = obs_timeline.entries("job")
        assert len(rows) == 8 and rows[0]["step"] == 33
        s = obs_timeline.summary("job")
        assert s["windows"] == 8 and s["steps"] == 40
        assert s["retraces"] == 1
        assert s["dtSeconds"]["p50"] == pytest.approx(0.37)
        assert s["dtSeconds"]["p99"] == pytest.approx(0.40)
        assert s["examplesPerSecond"]["p50"] == pytest.approx(100.0)
        assert s["lastLoss"] == pytest.approx(1.0 / 40)
        assert "entries" not in s  # the ring is read via entries()
        assert obs_timeline.summary("unknown") is None
    finally:
        config_mod.reset_config()


# --------------------------------------------------------- histograms
def test_histogram_bucket_math_against_known_samples(tmp_config):
    h = obs_hist.Histogram("h", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    h.observe(float("nan"))  # dropped, not counted
    snap = h.snapshot()
    assert snap["buckets"] == {"0.01": 1, "0.1": 2, "1.0": 3, "+Inf": 4}
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(5.555)
    assert h.quantile(0.5) == 0.1
    assert h.quantile(0.9) == float("inf")
    # boundary lands in the bucket whose upper bound it equals (le)
    h2 = obs_hist.Histogram("h2", buckets=(0.01, 0.1))
    h2.observe(0.1)
    assert h2.snapshot()["buckets"] == {"0.01": 0, "0.1": 1, "+Inf": 1}


def test_histogram_registry_never_raises_and_exposes_text(tmp_config):
    obs_hist.observe("lo_test_seconds", 0.02)
    obs_hist.observe("lo_test_seconds", "garbage")  # swallowed
    assert obs_hist.snapshot_all()["lo_test_seconds"]["count"] == 1

    from learningorchestra_tpu.services.server import escape_label_value
    lines = obs_hist.prometheus_lines(escape_label_value)
    assert "# TYPE lo_test_seconds histogram" in lines
    assert 'lo_test_seconds_bucket{le="0.025"} 1' in lines
    assert 'lo_test_seconds_bucket{le="+Inf"} 1' in lines
    assert "lo_test_seconds_sum 0.02" in lines
    assert "lo_test_seconds_count 1" in lines
    # cumulative counts never decrease across the bucket series
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in lines
              if ln.startswith("lo_test_seconds_bucket")]
    assert counts == sorted(counts)


# ------------------------------------------------------ chrome export
def test_chrome_trace_schema(tmp_config):
    with obs_trace.span("job", trace="c1", collection="t"):
        with obs_trace.span("epoch", epoch=0):
            pass
    doc = obs_export.chrome_trace("c1")
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert events[0] == {"ph": "M", "pid": 1, "tid": 0,
                         "name": "process_name",
                         "args": {"name": "learningorchestra:c1"}}
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"job", "epoch"}
    for e in xs:
        assert e["pid"] == 1 and e["ts"] >= 0 and e["dur"] >= 0
        assert "spanId" in e["args"]
    metas = [e for e in events if e["ph"] == "M"
             and e["name"] == "thread_name"]
    assert metas and {e["tid"] for e in metas} >= {xs[0]["tid"]}
    assert {e["ph"] for e in events} == {"M", "X"}
    json.dumps(doc)  # whole document must be JSON-serializable
    assert obs_export.chrome_trace("never-recorded") is None


# ------------------------------------------- prometheus escaping (b)
def test_escape_label_value_hostile_names():
    from learningorchestra_tpu.services.server import escape_label_value

    assert escape_label_value('plain') == 'plain'
    assert escape_label_value('a"b') == r'a\"b'
    assert escape_label_value('a\\b') == r'a\\b'
    assert escape_label_value('a\nb') == r'a\nb'
    # backslash escaped FIRST: a literal backslash-n stays
    # distinguishable from an escaped newline
    assert escape_label_value('\\n') == r'\\n'
    assert escape_label_value('"\n\\') == r'\"\n\\'


def test_metrics_prometheus_survives_hostile_route_names(api):
    hostile = f'{PREFIX}/weird"svc\\x\ny/end'
    api._record_metrics("GET", hostile, 200, 0.001)
    text = api.metrics_prometheus().decode()
    bad = [ln for ln in text.splitlines() if "weird" in ln]
    assert bad, "hostile route never surfaced in exposition"
    for ln in bad:
        # one well-formed sample per line: escaped quote/backslash/
        # newline inside the label, numeric value at the end
        assert r'\"' in ln and r'\\' in ln and r'\n' in ln
        float(ln.rsplit(" ", 1)[1])
    # a raw newline inside a label would have produced a dangling
    # fragment line that is neither a comment nor name<space>value
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        float(ln.rsplit(" ", 1)[1])


# ------------------------------------------- event log + fault (d)
def test_event_log_appends_jsonl(tmp_path):
    log = tmp_path / "events.jsonl"
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), event_log=str(log)))
    try:
        obs_export.log_event("job", "submit", trace_id="t1", verb="train")
        obs_export.log_event("job", "finish", trace_id="t1")
        rows = [json.loads(ln) for ln in
                log.read_text().strip().splitlines()]
        assert [r["name"] for r in rows] == ["submit", "finish"]
        assert rows[0]["kind"] == "job" and rows[0]["traceId"] == "t1"
        assert rows[0]["verb"] == "train" and rows[0]["ts"] > 0
    finally:
        config_mod.reset_config()


def test_event_log_disabled_writes_nothing(tmp_config):
    import os
    assert tmp_config.event_log == ""  # default: off
    obs_export.log_event("job", "submit", trace_id="t1")
    assert not (os.path.isdir(tmp_config.home)
                and any(p.endswith(".jsonl")
                        for p in os.listdir(tmp_config.home)))


def test_failing_or_slow_trace_export_never_fails_the_job(tmp_path):
    """Satellite (d): arm the ``trace_export`` fault in both raise and
    latency modes against a real job — the job must still succeed and
    only the faulted export lines go missing."""
    log = tmp_path / "events.jsonl"
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), event_log=str(log),
        fault_inject="trace_export:2:raise"))
    from learningorchestra_tpu.services.server import Api

    api = Api()
    try:
        st, _, _ = api.dispatch(
            "POST", f"{PREFIX}/function/python",
            {}, {"name": "f1", "functionParameters": {},
                 "function": "response = {'v': 41}"})
        assert st == 201
        meta = _wait(api, "f1", "function/python")
        assert meta.get("finished") and not meta.get("failed")

        # latency mode: export is delayed, the job is not stalled
        faults.reset()
        config_mod.set_config(config_mod.Config(
            home=str(tmp_path / "lo_home"), event_log=str(log),
            fault_inject="trace_export:1:latency:0.2"))
        st, _, _ = api.dispatch(
            "POST", f"{PREFIX}/function/python",
            {}, {"name": "f2", "functionParameters": {},
                 "function": "response = {'v': 42}"})
        assert st == 201
        meta = _wait(api, "f2", "function/python")
        assert meta.get("finished") and not meta.get("failed")
        # the non-faulted exports still landed as valid JSONL
        if log.exists():
            for ln in log.read_text().strip().splitlines():
                json.loads(ln)
    finally:
        api.ctx.close()
        config_mod.reset_config()


# ------------------------------------------- live fit spans (engine)
def _tiny_lm(rows=16):
    from learningorchestra_tpu.models import LanguageModel

    lm = LanguageModel(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                       max_len=16)
    x = np.random.default_rng(0).integers(
        1, 64, size=(rows, 16)).astype(np.int32)
    return lm, x


def _fit_under_profiler(tmp_path, traced):
    """Names of the host-plane events a tiny fit leaves in a profiler
    capture, by count."""
    import collections
    import glob

    import jax
    from jax.profiler import ProfileData

    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), compute_dtype="float32",
        trace=traced))
    try:
        lm, x = _tiny_lm()
        out = str(tmp_path / ("prof_on" if traced else "prof_off"))
        jax.profiler.start_trace(out)
        try:
            with obs_trace.span("job", trace="prof"):
                lm.fit(x, batch_size=4, epochs=3, shuffle=False)
        finally:
            jax.profiler.stop_trace()
    finally:
        config_mod.reset_config()
    (path,) = glob.glob(out + "/**/*.xplane.pb", recursive=True)
    names = collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


def test_live_spans_reach_the_profiler_capture(tmp_path):
    """A fit under ``jax.profiler.start_trace`` leaves its live spans
    as host events of the same names in the ``.xplane.pb`` (one clock
    with the device's ops), and none of them with ``LO_TRACE=0``."""
    names = _fit_under_profiler(tmp_path, traced=True)
    for want in ("epoch", "dispatch", "deviceWait", "epochEnd"):
        assert names[want] == 3, (want, names[want])
    assert names["job"] == 1 and names["initState"] == 1
    obs_trace.reset()
    names = _fit_under_profiler(tmp_path, traced=False)
    for gone in ("job", "epoch", "dispatch", "deviceWait", "epochEnd"):
        assert names[gone] == 0, (gone, names[gone])


def test_compile_spans_follow_the_builds_jax_reports(tmp_config):
    """``compile`` spans of a cold fit: one per dispatch during which
    ``jax.monitoring`` reported a build, each with what the build cost,
    none over a ``deviceWait``; a second fit of the same shapes builds
    nothing."""
    import jax

    builds = []  # (thread, monotonic time) of every executable built

    def on_build(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            builds.append((threading.get_ident(), time.monotonic()))

    jax.monitoring.register_event_duration_secs_listener(on_build)
    try:
        lm, x = _tiny_lm()
        with obs_trace.span("job", trace="cold"):
            lm.fit(x, batch_size=4, epochs=3, shuffle=False)
        with obs_trace.span("job", trace="warm"):
            lm.fit(x, batch_size=4, epochs=3, shuffle=False)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_build)
    me = threading.get_ident()
    spans = obs_trace.spans_of("cold")
    dispatches = [s for s in spans if s.name == "dispatch"]
    assert len(dispatches) == 3
    building = [d for d in dispatches
                if any(t == me and d.start <= at <= d.end
                       for t, at in builds)]
    compiles = [s for s in spans if s.name == "compile"]
    assert len(compiles) == len(building) >= 1
    assert [c.attrs["executable"] for c in compiles] == \
        list(range(1, len(compiles) + 1))
    waits = [s for s in spans if s.name == "deviceWait"]
    (job,) = [s for s in spans if s.name == "job"]
    for c, d in zip(compiles, building):
        assert c.attrs["traceSeconds"] > 0 and c.attrs["builds"] >= 1
        assert c.attrs["epoch"] == d.attrs["epoch"]
        # the dispatch call's own interval, beside the epochs
        assert d.start <= c.start and c.end <= d.end
        assert c.parent_id == job.span_id
        assert c.attrs["traceSeconds"] <= c.duration
        assert not any(w.start < c.end and c.start < w.end for w in waits)
    # measureFlops (a second trace of the step) is a span of its own,
    # outside every compile span
    (flops,) = [s for s in spans if s.name == "measureFlops"]
    assert not any(flops.start < c.end and c.start < flops.end
                   for c in compiles)
    warm = obs_trace.spans_of("warm")
    assert len([s for s in warm if s.name == "dispatch"]) == 3
    assert not [s for s in warm if s.name == "compile"
                and s.attrs.get("cold")]
    assert not [s for s in warm if s.name == "measureFlops"]


def test_nested_jit_traces_are_counted_once(tmp_config):
    """A jit traced inside another's trace reports its seconds first,
    and the caller's hold them again: the span keeps the outermost."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2

    @jax.jit
    def outer(x):
        return inner(x) + inner(x * 3)

    x = jnp.ones((3,))
    with obs_trace.span("root", trace="nest") as sp:
        outer(x).block_until_ready()
    assert sp.attrs["builds"] == 1
    assert 0 < sp.attrs["traceSeconds"] <= sp.sp.duration
    assert sp.attrs["backendCompileSeconds"] > 0


def test_per_step_fit_spans_one_dispatch_for_the_loop(tmp_path):
    """The per-step path: the fit's first step is one ``dispatch``,
    the rest of each epoch's feed loop another (``steps`` on it), never
    a span a step."""
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), compute_dtype="float32",
        scan_fit_max_bytes=0))
    try:
        lm, x = _tiny_lm(rows=32)  # 4 steps of 8 (the 8-device mesh)
        with obs_trace.span("job", trace="steps"):
            lm.fit(x, batch_size=8, epochs=2, shuffle=False)
    finally:
        config_mod.reset_config()
    spans = obs_trace.spans_of("steps")
    by_epoch = {e: [s.attrs["steps"] for s in spans
                    if s.name == "dispatch" and s.attrs["epoch"] == e]
                for e in (0, 1)}
    assert by_epoch == {0: [1, 3], 1: [4]}
    assert len([s for s in spans if s.name == "deviceWait"]) == 3
    assert len([s for s in spans if s.name == "epochEnd"]) == 2
    # here the step is first built by measureFlops, under the first
    # dispatch; the loop span's ``builds`` also counts the metric sums'
    # small adds, which are no step call's and leave no compile span
    (flops,) = [s for s in spans if s.name == "measureFlops"]
    first = next(s for s in spans if s.name == "dispatch")
    assert flops.parent_id == first.span_id and flops.attrs["builds"] >= 1
    assert len([s for s in spans if s.name == "compile"]) <= len(
        [s for s in spans if s.name == "dispatch"
         and s.attrs.get("builds")])


def test_ring_drops_whole_old_epochs_before_anything_else(tmp_path):
    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), trace_ring=16))
    try:
        with obs_trace.span("job", trace="ring"):
            with obs_trace.span("dataLoad"):
                pass
            for e in range(20):
                with obs_trace.span("epoch", epoch=e) as ep:
                    with obs_trace.span("dispatch", epoch=e):
                        pass
                    with obs_trace.span("epochEnd", epoch=e):
                        with obs_trace.span("checkpointCommit"):
                            pass
                if e < 2:
                    obs_trace.add("compile", "ring", 0.0, 0.1,
                                  parent=ep.sp.parent_id, executable=e + 1)
        spans = obs_trace.spans_of("ring")
    finally:
        config_mod.reset_config()
    assert len(spans) <= 16
    names = [s.name for s in spans]
    assert names.count("compile") == 2 and "dataLoad" in names
    kept = sorted(s.attrs["epoch"] for s in spans if s.name == "epoch")
    assert kept == list(range(20 - len(kept), 20)) and len(kept) >= 2
    # an epoch goes with its children: none is left without its parent
    ids = {s.span_id for s in spans}
    assert all(s.parent_id in ids for s in spans
               if s.name in ("dispatch", "epochEnd", "checkpointCommit"))


# -------------------------------------------------- end-to-end (REST)
def test_train_job_trace_timeline_and_histograms(api):
    """The acceptance path: train 2 epochs with checkpoints, then read
    the span tree (queue/lease wait, cold compile, epochs, checkpoint
    commits), the Chrome export, the per-step timeline, the latency
    histograms in both /metrics formats, and the metadata
    attribution."""
    st, _, _ = api.dispatch(
        "POST", f"{PREFIX}/function/python",
        {}, {"name": "d", "functionParameters": {}, "function":
             "import numpy as np\nrng = np.random.default_rng(0)\n"
             "x = rng.normal(size=(64, 8)).astype(np.float32)\n"
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
                 {"kind": "dense", "units": 4, "activation": "relu"},
                 {"kind": "dense", "units": 2,
                  "activation": "softmax"}]}})
    assert st == 201
    _wait(api, "m", "model/tensorflow")
    t0 = time.monotonic()
    st, _, _ = api.dispatch(
        "POST", f"{PREFIX}/train/tensorflow",
        {}, {"name": "t", "modelName": "m", "method": "fit",
             "methodParameters": {"x": "$d.x", "y": "$d.y",
                                  "epochs": 2, "batch_size": 16,
                                  "checkpoint": True}})
    assert st == 201
    meta = _wait(api, "t", "train/tensorflow")
    wall = time.monotonic() - t0

    # span tree: the full submit -> ... -> checkpointCommit path
    st, tree, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/trace/t", {}, None)
    assert st == 200, tree
    names = _span_names(tree)
    for want in ("submit", "job", "queueWait", "leaseWait", "attempt",
                 "dataLoad", "compile", "epoch", "checkpointCommit"):
        assert want in names, (want, names)
    assert names.count("epoch") == 2
    (job,) = [s for s in tree["spans"] if s["name"] == "job"]
    # traced job duration tracks the observed wall clock (acceptance:
    # within 20%; wall includes a poll interval of slack on top)
    assert job["durationSeconds"] <= wall + 0.1
    assert job["durationSeconds"] >= 0.5 * wall - 0.2
    compiles = [s.to_dict() for s in obs_trace.spans_of("t")
                if s.name == "compile"]
    assert any(c["attrs"].get("cold") for c in compiles), compiles
    # one ``compile`` span for each dispatch during which jax built
    built = [s for s in obs_trace.spans_of("t")
             if s.name == "dispatch" and s.attrs.get("builds")]
    assert len(compiles) == len(built) >= 1

    # chrome export loads as trace_event JSON
    st, chrome, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/trace/t",
        {"format": "chrome"}, None)
    assert st == 200
    assert chrome["displayTimeUnit"] == "ms"
    assert {e["ph"] for e in chrome["traceEvents"]} == {"M", "X"}
    assert len([e for e in chrome["traceEvents"]
                if e["ph"] == "X"]) == tree["spanCount"]

    # timeline: one window per epoch on the scan fast path; the step
    # counter matches the sentinel's count (64 rows / 16 batch * 2)
    st, tl, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/timeline/t", {}, None)
    assert st == 200, tl
    assert tl["summary"]["windows"] == len(tl["timeline"]) == 2
    assert tl["summary"]["steps"] == 8
    assert tl["timeline"][-1]["step"] == 8
    assert tl["summary"]["dtSeconds"]["sum"] > 0

    # metadata attribution rode along on the finished document
    assert meta["compileSeconds"] > 0
    assert meta["checkpointCommitSeconds"] > 0
    assert meta["leaseWaitSeconds"] >= 0

    # histograms present in JSON /metrics and in the text exposition
    st, m, _ = api.dispatch("GET", "/metrics", {}, None)
    hists = m["latencyHistograms"]
    for want in ("lo_dispatch_seconds", "lo_lease_wait_seconds",
                 "lo_compile_seconds", "lo_checkpoint_commit_seconds"):
        assert want in hists, (want, sorted(hists))
        assert hists[want]["count"] >= 1
        assert hists[want]["buckets"]["+Inf"] == hists[want]["count"]
    # one observation per build (per ``compile`` span)
    n = len(compiles)
    assert hists["lo_compile_seconds"]["count"] == n
    text = api.metrics_prometheus().decode()
    assert "# TYPE lo_dispatch_seconds histogram" in text
    assert f'lo_compile_seconds_bucket{{le="+Inf"}} {n}' in text
    assert "lo_compile_seconds_sum" in text
    assert f"lo_compile_seconds_count {n}" in text
    # the old sum/count-only summaries are gone (TYPE must be unique)
    assert "lo_dispatch_seconds summary" not in text
    assert "lo_lease_wait_seconds summary" not in text

    # discovery + 404 behavior
    st, listing, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/trace", {}, None)
    assert st == 200 and "t" in listing["result"]
    st, body, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/trace/never-ran", {}, None)
    assert st == 404, body
    st, body, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/timeline/never-ran", {}, None)
    assert st == 404, body


def test_long_fit_keeps_its_setup_and_compile_spans(api):
    """A fit of 200 epochs leaves some 1,200 spans in a ring of 512
    (the default): whole old epochs go, and ``submit``, ``dataLoad``,
    every ``compile`` span (``compileSeconds`` with them) and the
    newest epochs stay."""
    st, _, _ = api.dispatch(
        "POST", f"{PREFIX}/function/python",
        {}, {"name": "d", "functionParameters": {}, "function":
             "import numpy as np\nrng = np.random.default_rng(0)\n"
             "x = rng.normal(size=(64, 8)).astype(np.float32)\n"
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
                 # a width no other test of this file builds: a cold fit
                 {"kind": "dense", "units": 5, "activation": "relu"},
                 {"kind": "dense", "units": 2,
                  "activation": "softmax"}]}})
    assert st == 201
    _wait(api, "m", "model/tensorflow")
    st, _, _ = api.dispatch(
        "POST", f"{PREFIX}/train/tensorflow",
        {}, {"name": "long", "modelName": "m", "method": "fit",
             "methodParameters": {"x": "$d.x", "y": "$d.y",
                                  "epochs": 200, "batch_size": 16}})
    assert st == 201
    meta = _wait(api, "long", "train/tensorflow", timeout=240.0)
    spans = obs_trace.spans_of("long")
    assert len(spans) <= 512  # the default ring
    names = [s.name for s in spans]
    for want in ("submit", "validate", "job", "attempt", "dataLoad",
                 "artifactLoad", "initState", "stage", "artifactSave"):
        assert want in names, (want, sorted(set(names)))
    compiles = [s for s in spans if s.name == "compile"]
    st, m, _ = api.dispatch("GET", "/metrics", {}, None)
    assert len(compiles) == \
        m["latencyHistograms"]["lo_compile_seconds"]["count"] >= 1
    assert compiles[0].attrs["epoch"] == 0  # older than every epoch kept
    assert meta["compileSeconds"] == pytest.approx(
        sum(c.duration for c in compiles), abs=1e-4)
    kept = sorted(s.attrs["epoch"] for s in spans if s.name == "epoch")
    assert kept == list(range(200 - len(kept), 200)) and len(kept) > 50
    assert 0 not in kept
    for child in ("dispatch", "deviceWait", "epochEnd"):
        assert sorted(s.attrs["epoch"] for s in spans
                      if s.name == child) == kept


def test_serving_request_traces(api):
    """Each serving request gets its own ``serve/{model}/{seq}`` trace
    with the admit -> queueWait -> batchForm -> predict -> respond
    story, and feeds ``lo_serving_request_seconds``."""
    from learningorchestra_tpu.models.estimators import (
        LogisticRegressionJAX)

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int64)
    clf = LogisticRegressionJAX(epochs=2, batch_size=32)
    clf.fit(x, y)
    api.ctx.artifacts.save(clf, "clf", "train/tensorflow")

    st, _, _ = api.dispatch("POST", f"{PREFIX}/serve/clf", {}, {})
    assert st == 201
    rows = [[0.1] * 4, [0.2] * 4]
    for _ in range(3):
        st, body, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/clf/predict", {}, {"x": rows})
        assert st == 200, body

    tids = sorted(t for t in obs_trace.known_traces()
                  if t.startswith("serve/clf/"))
    assert tids == ["serve/clf/1", "serve/clf/2", "serve/clf/3"]
    st, tree, _ = api.dispatch(
        "GET", f"{PREFIX}/observability/trace/{tids[0]}", {}, None)
    assert st == 200, tree
    names = _span_names(tree)
    for want in ("request", "queueWait", "batchForm", "predict",
                 "respond"):
        assert want in names, (want, names)
    (req,) = [s for s in tree["spans"] if s["name"] == "request"]
    assert req["attrs"]["model"] == "clf"
    child_spans = req["children"]
    assert all(c["startSeconds"] >= req["startSeconds"]
               for c in child_spans)

    st, m, _ = api.dispatch("GET", "/metrics", {}, None)
    assert m["latencyHistograms"][
        "lo_serving_request_seconds"]["count"] == 3
    st, _, _ = api.dispatch("DELETE", f"{PREFIX}/serve/clf", {}, None)
    assert st == 200
