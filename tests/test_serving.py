"""Resident serving plane (docs/SERVING.md): session lifecycle over
REST, continuous-batch bit-identity to solo decode, bucket padding
correctness, and the serving-lease/gang-job no-deadlock property."""

import threading
import time

import numpy as np
import pytest

from learningorchestra_tpu import config as config_mod
from learningorchestra_tpu.services.scheduler import (
    ServingLease,
    SliceLease,
)

PREFIX = "/api/learningOrchestra/v1"


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


def _fit_clf(api):
    from learningorchestra_tpu.models.estimators import (
        LogisticRegressionJAX)

    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 4)).astype(np.float32)
    y = (x @ np.array([1.0, -2.0, 0.5, 1.5]) > 0).astype(np.int64)
    clf = LogisticRegressionJAX(epochs=3, batch_size=128)
    clf.fit(x, y)
    api.ctx.artifacts.save(clf, "clf", "train/tensorflow")
    return clf


def _fit_lm(api):
    from learningorchestra_tpu.models.transformer import LanguageModel

    lm = LanguageModel(vocab_size=48, d_model=32, n_layers=1,
                       n_heads=2, d_ff=64, max_len=32, attention="dot")
    rng = np.random.default_rng(1)
    tokens = rng.integers(1, 48, size=(16, 16)).astype(np.int32)
    lm.fit(tokens, batch_size=16, epochs=1)
    api.ctx.artifacts.save(lm, "slm", "train/tensorflow")
    # compare against the RELOADED instance: the session loads its own
    # copy, so both sides must see the same post-round-trip params
    return api.ctx.artifacts.load("slm", "train/tensorflow")


# ------------------------------------------------------------ lifecycle
def test_session_lifecycle_over_rest(api):
    """create -> warm predict -> overload 429 -> lease preemption by a
    batch gang acquire -> teardown."""
    clf = _fit_clf(api)

    # create
    status, body, _ = api.dispatch("POST", f"{PREFIX}/serve/clf", {}, {})
    assert status == 201, body
    assert body["kind"] == "predict"
    assert body["lease"]["pool"] == "serving"
    # duplicate create conflicts
    status, body, _ = api.dispatch("POST", f"{PREFIX}/serve/clf", {}, {})
    assert status == 409, body

    # warm predict matches the instance's own predict exactly
    rng = np.random.default_rng(2)
    rows = [[float(v) for v in r] for r in rng.normal(size=(3, 4))]
    status, body, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/clf/predict", {}, {"x": rows})
    assert status == 200, body
    assert body["predictions"] == clf.predict(np.asarray(rows)).tolist()

    # overload: block the worker inside predict, fill the bounded
    # queue (shrunk to 2), and the next request must be rejected 429
    session = api.ctx.serving._sessions["clf"]
    session._depth = 2
    entered = threading.Event()
    release = threading.Event()
    orig_predict = session._instance.predict

    def slow_predict(x):
        entered.set()
        release.wait(10)
        return orig_predict(x)

    session._instance.predict = slow_predict
    results = []

    def client():
        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/clf/predict", {}, {"x": rows})
        results.append(s)

    blocker = threading.Thread(target=client)
    blocker.start()
    assert entered.wait(10), "worker never reached predict"
    fillers = [threading.Thread(target=client) for _ in range(2)]
    for t in fillers:
        t.start()
    deadline = time.time() + 10
    while len(session._queue) < 2 and time.time() < deadline:
        time.sleep(0.005)
    assert len(session._queue) == 2, "queue never filled"
    status, body, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/clf/predict", {}, {"x": rows})
    assert status == 429, body
    release.set()
    blocker.join(timeout=10)
    for t in fillers:
        t.join(timeout=10)
    del session._instance.predict
    assert results == [200, 200, 200]
    stats = api.dispatch("GET", f"{PREFIX}/serve/clf", {}, None)[1]
    assert stats["rejectedTotal"] >= 1

    # lease preemption: a batch gang acquire on the SAME allocator must
    # go through (the session yields), then the session re-acquires
    got = threading.Event()

    def gang():
        grant = api.ctx.jobs.slice_lease.acquire("batch")
        got.set()
        time.sleep(0.05)
        api.ctx.jobs.slice_lease.release("batch", 0.05, grant=grant)

    t = threading.Thread(target=gang)
    t.start()
    assert got.wait(10), "gang job deadlocked behind the serving lease"
    t.join(timeout=10)
    deadline = time.time() + 10
    while time.time() < deadline:
        stats = api.dispatch("GET", f"{PREFIX}/serve/clf", {}, None)[1]
        if stats["lease"]["yields"] >= 1 and stats["lease"]["held"]:
            break
        time.sleep(0.02)
    assert stats["lease"]["yields"] >= 1
    assert stats["lease"]["held"]
    # still serving after the re-pin
    status, body, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/clf/predict", {}, {"x": rows})
    assert status == 200, body

    # teardown
    status, body, _ = api.dispatch(
        "DELETE", f"{PREFIX}/serve/clf", {}, None)
    assert status == 200 and body["deleted"] is True
    status, body, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/clf/predict", {}, {"x": rows})
    assert status == 404, body
    assert api.dispatch("GET", f"{PREFIX}/serve", {}, None)[1] == \
        {"result": []}


# ----------------------------------------------------- LM bit-identity
def test_continuous_batch_bit_identical_to_solo_decode(api):
    """Requests joining and leaving the continuous batcher at
    staggered token boundaries must each emit EXACTLY the tokens a solo
    ``generate`` of that request produces — same key schedule, same
    masked attention, bit for bit."""
    lm = _fit_lm(api)
    status, body, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/slm", {}, {
            "maxSlots": 4, "cacheLen": 32,
            "temperature": 0.7, "topK": 12})
    assert status == 201, body
    assert body["kind"] == "lm" and body["slots"] == 4

    rng = np.random.default_rng(3)
    specs = []  # (prompt, new, seed)
    for i, (plen, new) in enumerate(
            [(3, 5), (5, 8), (8, 6), (4, 9), (6, 7), (7, 5)]):
        prompt = [int(t) for t in rng.integers(1, 48, size=plen)]
        specs.append((prompt, new, 100 + i))
    out = [None] * len(specs)

    def client(i):
        prompt, new, seed = specs[i]
        time.sleep(0.03 * i)  # join mid-flight of earlier requests
        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {}, {
                "prompt": prompt, "maxNewTokens": new, "seed": seed})
        assert s == 200, b
        out[i] = b["tokens"]

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(specs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    for i, (prompt, new, seed) in enumerate(specs):
        solo = lm.generate(np.asarray([prompt], np.int32),
                           max_new_tokens=new, temperature=0.7,
                           top_k=12, seed=seed)
        assert out[i] == [int(t) for t in solo[0][len(prompt):]], \
            f"request {i} diverged from its solo decode"
    stats = api.dispatch("GET", f"{PREFIX}/serve/slm", {}, None)[1]
    assert stats["tokensTotal"] == sum(n for _, n, _ in specs)
    api.dispatch("DELETE", f"{PREFIX}/serve/slm", {}, None)


def test_lm_serving_validates_requests(api):
    _fit_lm(api)
    status, body, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/slm", {}, {"cacheLen": 16})
    assert status == 201, body
    for bad in ({}, {"prompt": []}, {"prompt": "abc"},
                {"prompt": [1, 2], "maxNewTokens": 16},   # >= cacheLen
                {"prompt": [1, 2], "maxNewTokens": 0},
                {"prompt": [1, 2], "seed": "x"}):
        status, _, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {}, bad)
        assert status == 406, bad


# ------------------------------------------------------ bucket padding
def test_bucket_padding_correctness(api):
    """Padding a burst up to the precompiled bucket shape must never
    change any real row's prediction; ragged rows are rejected."""
    clf = _fit_clf(api)
    status, body, _ = api.dispatch("POST", f"{PREFIX}/serve/clf", {}, {})
    assert status == 201, body
    rng = np.random.default_rng(4)
    for n, bucket in ((1, 1), (3, 4), (5, 8)):
        rows = [[float(v) for v in r] for r in rng.normal(size=(n, 4))]
        status, body, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/clf/predict", {}, {"x": rows})
        assert status == 200, body
        assert body["bucket"] == bucket
        assert body["predictions"] == \
            clf.predict(np.asarray(rows)).tolist()

    # concurrent burst: aggregated into shared bucketed calls, every
    # request still gets exactly its own rows' predictions back
    sizes = (1, 2, 3)
    rows_by_req = [
        [[float(v) for v in r] for r in rng.normal(size=(n, 4))]
        for n in sizes]
    got = [None] * len(sizes)

    def client(i):
        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/clf/predict", {},
            {"x": rows_by_req[i]})
        assert s == 200, b
        got[i] = b["predictions"]

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(sizes))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    for i in range(len(sizes)):
        assert got[i] == \
            clf.predict(np.asarray(rows_by_req[i])).tolist()

    # ragged rows inside one request do not stack -> 406
    status, body, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/clf/predict", {},
        {"x": [[1.0, 2.0], [1.0, 2.0, 3.0]]})
    assert status == 406, body
    status, _, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/clf/predict", {}, {"x": []})
    assert status == 406


# ------------------------------------------------- scheduler property
def test_serving_leases_never_deadlock_gang_jobs():
    """Property: with preempt-policy serving sessions occupying the
    whole device line and continuously re-acquiring, EVERY full-mesh
    gang job still completes — the idle-tick yield plus the
    anti-starvation freeze guarantee forward progress."""
    lease = SliceLease(leases=4, total_devices=8, aging_seconds=0.5)
    sessions = [ServingLease(lease, footprint={"devices": d})
                for d in (2, 2, 4)]
    for s in sessions:
        s.acquire()
    stop = threading.Event()

    def pump(s):
        # the session worker loop: offer the slice back on every tick
        while not stop.is_set():
            s.maybe_yield()
            time.sleep(0.002)

    pumps = [threading.Thread(target=pump, args=(s,), daemon=True)
             for s in sessions]
    for t in pumps:
        t.start()
    done = []

    def gang(i):
        grant = lease.acquire("batch")  # full mesh, exclusively
        time.sleep(0.01)
        lease.release("batch", 0.01, grant=grant)
        done.append(i)

    gangs = [threading.Thread(target=gang, args=(i,)) for i in range(5)]
    for t in gangs:
        t.start()
    for t in gangs:
        t.join(timeout=60)
    assert sorted(done) == list(range(5)), \
        f"gang jobs starved behind serving leases: {sorted(done)}"
    stop.set()
    for t in pumps:
        t.join(timeout=30)
    # the sessions all came back up after the batch burst drained
    for s in sessions:
        assert s.held()
        assert s.yields >= 1
    for s in sessions:
        s.release()


def test_hold_policy_keeps_slice_until_release():
    lease = SliceLease(leases=2, total_devices=8)
    sess = ServingLease(lease, policy="hold", footprint={"devices": 4})
    sess.acquire()
    assert sess.maybe_yield() is False  # hold never yields
    got = threading.Event()

    def gang():
        grant = lease.acquire("batch")
        got.set()
        lease.release("batch", 0.0, grant=grant)

    t = threading.Thread(target=gang, daemon=True)
    t.start()
    assert not got.wait(0.3), "gang ran while hold-session kept mesh"
    assert sess.maybe_yield() is False
    sess.release()
    assert got.wait(10), "gang never ran after session release"
    t.join(timeout=10)


# ---------------------------------------------------- paged KV serving
def _api_with(tmp_path, **overrides):
    """An Api under a bespoke Config (fault_inject / tenant weights
    need their own Config object, which the shared fixture can't
    take). Pair with :func:`_close_api` in a try/finally."""
    from learningorchestra_tpu.services import faults

    config_mod.set_config(config_mod.Config(
        home=str(tmp_path / "lo_home"), compute_dtype="float32",
        serve_max_wait_ms=1.0, **overrides))
    faults.reset()
    from learningorchestra_tpu.services.server import Api

    return Api()


def _close_api(api):
    from learningorchestra_tpu.services import faults

    api.ctx.close()
    faults.reset()
    config_mod.reset_config()


def _paged_session(api, **extra):
    body = {"kv": "paged", "pageLen": 8, "maxSlots": 4, "cacheLen": 32,
            "temperature": 0.7, "topK": 12}
    body.update(extra)
    status, resp, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/slm", {}, body)
    assert status == 201, resp
    assert resp["kv"]["mode"] == "paged"
    return resp


def _solo(lm, prompt, new, seed):
    out = lm.generate(np.asarray([prompt], np.int32),
                      max_new_tokens=new, temperature=0.7,
                      top_k=12, seed=seed)
    return [int(t) for t in out[0][len(prompt):]]


def test_paged_serving_bit_identical_to_solo_decode(api):
    """The paged pool + block-table decode must emit EXACTLY the slot
    path's tokens: same fold_in key schedule, garbage pages masked to
    exact zeros — bit for bit against solo ``generate``."""
    lm = _fit_lm(api)
    resp = _paged_session(api)
    # auto pool size = slots x pages-per-stream (+ trash page, which
    # pagesTotal already excludes) — the slot cache's HBM budget
    assert resp["kv"]["pageLen"] == 8
    assert resp["kv"]["pagesTotal"] == 4 * (32 // 8)

    rng = np.random.default_rng(5)
    specs = []
    for i, (plen, new) in enumerate(
            [(3, 5), (5, 8), (8, 6), (4, 9), (6, 7), (7, 5)]):
        prompt = [int(t) for t in rng.integers(1, 48, size=plen)]
        specs.append((prompt, new, 300 + i))
    out = [None] * len(specs)

    def client(i):
        prompt, new, seed = specs[i]
        time.sleep(0.03 * i)  # join mid-flight of earlier requests
        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {}, {
                "prompt": prompt, "maxNewTokens": new, "seed": seed})
        assert s == 200, b
        out[i] = b["tokens"]

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(specs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    for i, (prompt, new, seed) in enumerate(specs):
        assert out[i] == _solo(lm, prompt, new, seed), \
            f"paged request {i} diverged from its solo decode"

    stats = api.dispatch("GET", f"{PREFIX}/serve/slm", {}, None)[1]
    assert stats["tokensTotal"] == sum(n for _, n, _ in specs)
    assert stats["kv"]["mode"] == "paged"
    assert stats["kv"]["allocFailures"] == 0
    # manager roll-up + Prometheus rows exist while the session lives
    mgr = api.ctx.serving.stats()
    assert mgr["kv"]["pagesTotal"] == 16
    text = api.metrics_prometheus()
    assert b"lo_serving_kv_pages_free" in text
    assert b"lo_serving_kv_prefills_skipped_total" in text
    api.dispatch("DELETE", f"{PREFIX}/serve/slm", {}, None)


def test_paged_prefix_reuse_shares_pages_and_skips_prefill(api):
    """Prefix caching over the refcounted pool: an exact repeat skips
    the prefill entirely, a shared-prefix prompt reuses the full
    pages — and the pool-allocation ledger proves the sharing (fewer
    fresh pages than a cold admit would take)."""
    lm = _fit_lm(api)
    _paged_session(api, maxSlots=2)

    rng = np.random.default_rng(6)
    prompt = [int(t) for t in rng.integers(1, 48, size=12)]
    new = 6  # ceil((12+6)/8) = 3 pages cold

    s, b, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/slm/predict", {},
        {"prompt": prompt, "maxNewTokens": new, "seed": 7})
    assert s == 200 and b["tokens"] == _solo(lm, prompt, new, 7)

    # exact repeat, different seed: full hit — prefill skipped, the
    # shared full page increfed, first token resampled bit-identically
    # from the cached prefill logits under THIS request's key
    s, b, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/slm/predict", {},
        {"prompt": prompt, "maxNewTokens": new, "seed": 11})
    assert s == 200 and b["tokens"] == _solo(lm, prompt, new, 11)

    # same first page, different tail: partial chain hit — prefill
    # runs but the shared page is reused, not re-allocated
    prompt2 = prompt[:8] + [int(t) for t in rng.integers(1, 48, size=4)]
    assert prompt2 != prompt
    s, b, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/slm/predict", {},
        {"prompt": prompt2, "maxNewTokens": new, "seed": 13})
    assert s == 200 and b["tokens"] == _solo(lm, prompt2, new, 13)

    kv = api.dispatch("GET", f"{PREFIX}/serve/slm", {}, None)[1]["kv"]
    prefix = kv["prefix"]
    assert prefix["hitsFull"] == 1
    assert prefix["hitsPartial"] == 1
    assert prefix["prefillsSkipped"] == 1
    assert prefix["pagesReused"] == 2
    # allocation accounting: cold 3, full hit 3-1, partial hit 3-1 —
    # NOT 9; the shared page was never re-taken from the free list
    assert kv["allocTotal"] == 7
    # two cache entries hold (full, tailA) and (full again, tailC):
    # 3 distinct pages held, the shared full page refcounted twice
    assert kv["pagesFree"] == kv["pagesTotal"] - 3
    assert kv["pagesShared"] == 1
    api.dispatch("DELETE", f"{PREFIX}/serve/slm", {}, None)


def test_paged_admission_survives_prefix_eviction_under_pressure(api):
    """Pool pressure during a prefix-HIT admission LRU-evicts prefix
    entries — possibly the very entry backing the hit. The admission
    pins the looked-up pages before quota/alloc, so they can neither
    return to the free list nor be re-handed out as `fresh` (aliasing
    would let the tail clone overwrite shared prompt KV). With
    nothing else reclaimable the request 429s, every reference taken
    is released (no pool shrink, no quota inflation), and the pool
    serves the next request normally."""
    lm = _fit_lm(api)
    _paged_session(api, maxSlots=2)  # 8 usable pages
    session = api.ctx.serving._sessions["slm"]

    rng = np.random.default_rng(9)
    prompt = [int(t) for t in rng.integers(1, 48, size=12)]
    new = 6  # 3 pages: 1 full prompt page + tail + decode

    s, b, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/slm/predict", {},
        {"prompt": prompt, "maxNewTokens": new, "seed": 17})
    assert s == 200, b
    assert len(session.prefix) == 1  # entry holds full + tail pages

    # drain the free list: the prefix entry is the only reclaimable
    # tier left when the repeat admission needs fresh pages
    hog = session.pool.alloc(session.pool.free_count(), "hog")

    s, b, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/slm/predict", {},
        {"prompt": prompt, "maxNewTokens": new, "seed": 19})
    assert s == 429, b
    assert len(session.prefix) == 0  # the LRU entry was reclaimed
    # the admission's shared/tail pins were released on failure, so
    # the evicted entry's two pages are back on the free list and the
    # tenant's quota charge is gone
    assert session.pool.free_count() == 2
    assert session.pool.tenant_pages("default") == 0

    # pool integrity: with the pressure gone the same request admits
    # cold, bit-identical to the solo decode
    session.pool.decref(hog, "hog")
    s, b, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/slm/predict", {},
        {"prompt": prompt, "maxNewTokens": new, "seed": 19})
    assert s == 200, b
    assert b["tokens"] == _solo(lm, prompt, new, 19)
    api.dispatch("DELETE", f"{PREFIX}/serve/slm", {}, None)


def test_paged_admission_failure_releases_pages(api, monkeypatch):
    """A failure AFTER page allocation (prefill compile/device error)
    must decref everything the admission took — otherwise the pool
    permanently shrinks and the tenant's quota stays inflated until
    admissions starve. The retry then serves normally."""
    lm = _fit_lm(api)
    _paged_session(api)
    session = api.ctx.serving._sessions["slm"]
    free0 = session.pool.free_count()

    real_prefill_for = session._pprefill_for

    def boom(s):
        raise RuntimeError("injected prefill failure")

    monkeypatch.setattr(session, "_pprefill_for", boom)
    rng = np.random.default_rng(10)
    prompt = [int(t) for t in rng.integers(1, 48, size=10)]
    s, b, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/slm/predict", {},
        {"prompt": prompt, "maxNewTokens": 5, "seed": 23})
    assert s == 503, b
    assert session.pool.free_count() == free0
    assert session.pool.tenant_pages("default") == 0

    monkeypatch.setattr(session, "_pprefill_for", real_prefill_for)
    s, b, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/slm/predict", {},
        {"prompt": prompt, "maxNewTokens": 5, "seed": 23})
    assert s == 200, b
    assert b["tokens"] == _solo(lm, prompt, 5, 23)
    api.dispatch("DELETE", f"{PREFIX}/serve/slm", {}, None)


def test_paged_tenant_series_cardinality_is_bounded(tmp_path):
    """The tenant tag is client-controlled: distinct values beyond the
    configured weights plus ``_MAX_TENANT_SERIES`` ad-hoc names must
    collapse into the ``other`` series instead of minting unbounded
    histograms, latency trackers, and watchdog objectives."""
    api = _api_with(tmp_path, serve_tenant_weights="vip:3")
    try:
        _fit_lm(api)
        _paged_session(api)
        session = api.ctx.serving._sessions["slm"]
        monkeypatch_cap = 2
        session._MAX_TENANT_SERIES = monkeypatch_cap

        rng = np.random.default_rng(11)
        for i, tenant in enumerate(
                ["vip", "t0", "t1", "t2", "t3", "vip"]):
            prompt = [int(t) for t in rng.integers(1, 48, size=6)]
            s, b, _ = api.dispatch(
                "POST", f"{PREFIX}/serve/slm/predict", {},
                {"prompt": prompt, "maxNewTokens": 4,
                 "seed": 31 + i, "tenant": tenant})
            assert s == 200, b

        # configured tenant + first `cap` ad-hoc tenants keep their
        # own series; the overflow lands in `other`
        assert set(session._tenant_requests) == \
            {"vip", "t0", "t1", "other"}
        assert session._tenant_requests["vip"] == 2
        assert session._tenant_requests["other"] == 2
        from learningorchestra_tpu.observability import hist as obs_hist

        names = obs_hist.names()
        assert "lo_serving_request_seconds_tenant_other" in names
        assert "lo_serving_request_seconds_tenant_t2" not in names
        assert "lo_serving_request_seconds_tenant_t3" not in names
    finally:
        _close_api(api)


def test_paged_tenant_quota_and_weighted_qos(tmp_path):
    """Weighted-fair page quotas: with another tenant live, a
    weight-1 tenant over its share is 429'd while a weight-3 tenant's
    identical demand admits; a sole tenant may use the whole pool.
    Per-tenant latency series feed per-tenant servingP99 objectives."""
    api = _api_with(tmp_path, serve_tenant_weights="vip:3,std:1")
    try:
        lm = _fit_lm(api)
        # pages=7 -> 6 usable; a 4-page request is over a half-pool
        # quota (3) but within a 3/4-pool quota (4)
        _paged_session(api, maxSlots=2, pages=7)
        session = api.ctx.serving._sessions["slm"]

        # a second tenant holding pages arms the quota (deterministic
        # stand-in for a concurrent victim stream)
        held = session.pool.alloc(2, "victim")

        rng = np.random.default_rng(8)
        p_std = [int(t) for t in rng.integers(1, 48, size=8)]
        p_vip = [int(t) for t in rng.integers(1, 48, size=8)]
        big = {"maxNewTokens": 24, "seed": 21}  # ceil(32/8) = 4 pages

        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {},
            dict(big, prompt=p_std, tenant="std"))
        assert s == 429, b  # 0+4 > int(6 * 1/2)

        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {},
            dict(big, prompt=p_vip, tenant="vip"))
        assert s == 200, b  # 0+4 <= int(6 * 3/4)
        assert b["tokens"] == _solo(lm, p_vip, 24, 21)

        # victim gone -> std is the sole tenant: whole pool available
        session.pool.decref(held, "victim")
        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {},
            dict(big, prompt=p_std, tenant="std"))
        assert s == 200, b
        assert b["tokens"] == _solo(lm, p_std, 24, 21)

        stats = session.stats()
        assert stats["rejectedTotal"] >= 1
        tenants = stats["kv"]["tenants"]
        assert tenants["vip"]["weight"] == 3.0
        assert tenants["vip"]["requests"] == 1
        assert tenants["std"]["requests"] == 1
        assert tenants["std"]["latency"]["count"] >= 1

        # the per-tenant histogram series exists and the watchdog
        # discovers a per-tenant page-severity objective from it
        from learningorchestra_tpu.observability import hist as obs_hist
        from learningorchestra_tpu.observability.slo import SloWatchdog

        assert "lo_serving_request_seconds_tenant_vip" in \
            obs_hist.names()
        wd = SloWatchdog()
        wd.evaluate()
        objectives = wd.objectives()
        assert "servingP99:vip" in objectives
        assert objectives["servingP99:vip"]["severity"] == "page"
    finally:
        _close_api(api)


def test_paged_kv_alloc_transient_fault_is_retryable(tmp_path):
    """A transient kv_page_alloc fault surfaces as one 429; the
    retry admits normally and the session stays on the paged path."""
    api = _api_with(tmp_path, fault_inject="kv_page_alloc:1")
    try:
        lm = _fit_lm(api)
        _paged_session(api)
        rng = np.random.default_rng(9)
        prompt = [int(t) for t in rng.integers(1, 48, size=6)]

        body = {"prompt": prompt, "maxNewTokens": 5, "seed": 31}
        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {}, body)
        assert s == 429, b

        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {}, body)
        assert s == 200, b
        assert b["tokens"] == _solo(lm, prompt, 5, 31)

        stats = api.dispatch("GET", f"{PREFIX}/serve/slm", {}, None)[1]
        assert stats["kv"]["mode"] == "paged"
        assert stats["rejectedTotal"] == 1
    finally:
        _close_api(api)


def test_paged_kv_alloc_latched_fault_degrades_to_slot(tmp_path):
    """A latched kv_page_alloc fault (3 consecutive failures) walks
    one rung down the degradation ladder: the session rebuilds the
    contiguous slot path and every later request serves through it,
    still bit-identical to solo decode."""
    api = _api_with(tmp_path, fault_inject="kv_page_alloc:100")
    try:
        lm = _fit_lm(api)
        _paged_session(api)
        rng = np.random.default_rng(10)
        prompt = [int(t) for t in rng.integers(1, 48, size=6)]

        for _ in range(3):
            s, b, _ = api.dispatch(
                "POST", f"{PREFIX}/serve/slm/predict", {},
                {"prompt": prompt, "maxNewTokens": 5, "seed": 41})
            assert s == 429, b

        stats = api.dispatch("GET", f"{PREFIX}/serve/slm", {}, None)[1]
        assert stats["kv"]["mode"] == "slot-degraded"

        # the slot path never calls kv_page_alloc: the still-armed
        # fault budget cannot touch it
        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {},
            {"prompt": prompt, "maxNewTokens": 5, "seed": 41})
        assert s == 200, b
        assert b["tokens"] == _solo(lm, prompt, 5, 41)
    finally:
        _close_api(api)


def test_two_sessions_time_share_single_lease_mesh(api):
    """On the default counting mesh (LO_MESH_LEASES=1) a second
    session's create must NOT hang behind the first: sessions never
    finish, so the preempt policy yields to same-pool waiters too and
    the two sessions time-share the lease (regression — create used
    to deadlock because holders only yielded to OTHER pools)."""
    _fit_clf(api)
    lm = _fit_lm(api)

    status, body, _ = api.dispatch("POST", f"{PREFIX}/serve/clf", {}, {})
    assert status == 201, body

    created = {}

    def create_second():
        created["resp"] = api.dispatch(
            "POST", f"{PREFIX}/serve/slm", {},
            {"maxSlots": 2, "cacheLen": 24, "temperature": 0.7,
             "topK": 8})

    t = threading.Thread(target=create_second, daemon=True)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive(), \
        "second serving create deadlocked behind the first session"
    status, body, _ = created["resp"]
    assert status == 201, body

    # both sessions answer while coexisting
    rng = np.random.default_rng(3)
    rows = [[float(v) for v in r] for r in rng.normal(size=(2, 4))]
    prompt = [int(v) for v in rng.integers(1, 48, size=5)]
    for _ in range(3):
        status, body, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/clf/predict", {}, {"x": rows})
        assert status == 200, body
        status, body, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {},
            {"prompt": prompt, "maxNewTokens": 4, "seed": 9})
        assert status == 200, body
        assert len(body["tokens"]) == 4
        # the hand-offs are real lease yields, and bit-identity holds
        # across them
        solo = np.asarray(lm.generate([prompt], max_new_tokens=4,
                                      temperature=0.7, top_k=8, seed=9))
        assert body["tokens"] == [int(v) for v in solo[0][-4:]]

    stats = api.ctx.serving.stats()
    assert stats["sessions"] == 2
    assert stats["leaseYields"] >= 1

    for name in ("clf", "slm"):
        status, body, _ = api.dispatch(
            "DELETE", f"{PREFIX}/serve/{name}", {}, {})
        assert status == 200, body


# ------------------------------------------------- quantized serving
def test_quantized_session_streams_with_drift_and_dtype_stamps(
        tmp_path):
    """int8 KV + int8 weights session end to end: streams serve, the
    stats/perf surfaces stamp both dtypes, the create-time drift probe
    sits under LO_SERVE_DRIFT_MAX, and the true quantized footprint
    (int8 payload + f32 scales) shows up as bytes per cached token."""
    api = _api_with(tmp_path)
    try:
        _fit_lm(api)
        # a slot session must refuse an EXPLICIT quantized pool ask
        s, b, _ = api.dispatch("POST", f"{PREFIX}/serve/slm", {}, {
            "maxSlots": 2, "cacheLen": 32, "kvDtype": "int8"})
        assert s == 406, b
        # and a bad dtype is a validation error naming the choices
        s, b, _ = api.dispatch("POST", f"{PREFIX}/serve/slm", {}, {
            "kv": "paged", "pageLen": 8, "kvDtype": "int4"})
        assert s == 406 and "int8" in str(b), b

        resp = _paged_session(api, kvDtype="int8", weights="int8")
        assert resp["kv"]["dtype"] == "int8"
        rng = np.random.default_rng(70)
        prompt = [int(t) for t in rng.integers(1, 48, size=6)]
        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {},
            {"prompt": prompt, "maxNewTokens": 6, "seed": 4})
        assert s == 200, b
        assert len(b["tokens"]) == 6

        stats = api.dispatch("GET", f"{PREFIX}/serve/slm", {}, None)[1]
        assert stats["kv"]["dtype"] == "int8"
        assert stats["weights"]["dtype"] == "int8"
        drift = stats["drift"]
        assert drift["probes"] >= 1
        assert drift["value"] <= drift["max"], drift
        assert set(drift["parts"]) == {"kv", "weights"}
        assert stats["kv"]["bytesPerToken"] > 0

        text = api.metrics_prometheus().decode()
        assert 'lo_serving_drift{model="slm"}' in text
        assert 'lo_serving_kv_bytes_per_token{model="slm"}' in text
        assert "lo_serving_quant_degrades_total" in text

        s, perf, _ = api.dispatch(
            "GET", "/observability/perf", {}, None)
        row = (perf.get("serving") or {}).get("slm") or {}
        if row:  # steady-state window may not have closed yet
            assert row.get("quantized", {}).get("kv") == "int8"
        api.dispatch("DELETE", f"{PREFIX}/serve/slm", {}, None)
    finally:
        _close_api(api)


def test_quantized_kv_bytes_match_xray_claim_and_release(tmp_path):
    """Satellite accounting: the int8 session's kv-cache X-ray claim
    is exactly the int8 payload pools PLUS their f32 scale pools —
    computed analytically from the model shape — the Prometheus
    lo_serving_kv_pages row reflects the pool, and the claim releases
    on DELETE so the unattributed-growth leak detector sees nothing."""
    from learningorchestra_tpu.observability import xray

    api = _api_with(tmp_path)
    try:
        _fit_lm(api)
        base = xray.by_owner().get("kv-cache", 0)
        resp = _paged_session(api, kvDtype="int8")
        sess = api.ctx.serving._sessions["slm"]
        # slm: 1 layer, kv=2 heads x d=16 head dim; pool holds
        # pagesTotal + the reserved trash page
        pages_total = resp["kv"]["pagesTotal"] + 1
        page_len = resp["kv"]["pageLen"]
        kv, d = 2, 16
        payload = 2 * pages_total * page_len * kv * d  # int8: 1 byte
        scales = 2 * pages_total * kv * 4              # f32 per head
        assert sess._cache_bytes == payload + scales, (
            sess._cache_bytes, payload, scales)
        assert xray.by_owner()["kv-cache"] - base == sess._cache_bytes
        text = api.metrics_prometheus().decode()
        assert (f'lo_serving_kv_pages{{model="slm"}} '
                f'{resp["kv"]["pagesTotal"]}') in text
        api.dispatch("DELETE", f"{PREFIX}/serve/slm", {}, None)
        assert xray.by_owner().get("kv-cache", 0) == base
    finally:
        _close_api(api)


def test_quantized_kv_transient_fault_is_retryable_429(tmp_path):
    """A transient kv_quant fault surfaces as one 429 and the retry
    serves through the still-quantized pool."""
    api = _api_with(tmp_path, fault_inject="kv_quant:1")
    try:
        _fit_lm(api)
        _paged_session(api, kvDtype="int8")
        rng = np.random.default_rng(71)
        prompt = [int(t) for t in rng.integers(1, 48, size=5)]
        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {},
            {"prompt": prompt, "maxNewTokens": 4, "seed": 2})
        assert s == 429, b
        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {},
            {"prompt": prompt, "maxNewTokens": 4, "seed": 2})
        assert s == 200, b
        stats = api.dispatch("GET", f"{PREFIX}/serve/slm", {}, None)[1]
        assert stats["kv"]["dtype"] == "int8"
    finally:
        _close_api(api)


def test_quantized_kv_latched_fault_degrades_to_exact_bf16(tmp_path):
    """A latched kv_quant fault walks the quantization rung of the
    degrade ladder: three 429s, then the session rebuilds over exact
    bf16 pages AND bf16 weights — still paged — and later requests are
    bit-identical to solo decode (degraded means exact, never a
    corrupted stream). The degrade is counted for /metrics."""
    from learningorchestra_tpu.runtime import health as health_lib

    api = _api_with(tmp_path, fault_inject="kv_quant:100")
    try:
        lm = _fit_lm(api)
        _paged_session(api, kvDtype="int8", weights="int8")
        before = health_lib.health_stats()["quantDegrades"]
        rng = np.random.default_rng(72)
        prompt = [int(t) for t in rng.integers(1, 48, size=6)]
        for _ in range(3):
            s, b, _ = api.dispatch(
                "POST", f"{PREFIX}/serve/slm/predict", {},
                {"prompt": prompt, "maxNewTokens": 5, "seed": 51})
            assert s == 429, b

        stats = api.dispatch("GET", f"{PREFIX}/serve/slm", {}, None)[1]
        assert stats["kv"]["dtype"] == "bf16", stats["kv"]
        assert stats["kv"]["mode"] == "paged", stats["kv"]
        assert stats["weights"]["dtype"] == "bf16", stats["weights"]
        assert health_lib.health_stats()["quantDegrades"] == before + 1

        # the bf16 path never consults kv_quant: the still-armed
        # budget cannot touch it, and bit-identity to solo holds
        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {},
            {"prompt": prompt, "maxNewTokens": 5, "seed": 51})
        assert s == 200, b
        assert b["tokens"] == _solo(lm, prompt, 5, 51)
    finally:
        _close_api(api)


def test_bf16_paged_session_is_unchanged_by_quant_plumbing(tmp_path):
    """Quantization is opt-in: a default paged session stamps bf16,
    carries no drift block, no scale pools in its cache bytes, and
    stays bit-identical to solo decode (the PR-15 contract)."""
    api = _api_with(tmp_path)
    try:
        lm = _fit_lm(api)
        resp = _paged_session(api)
        assert resp["kv"]["dtype"] == "bf16"
        sess = api.ctx.serving._sessions["slm"]
        pages_total = resp["kv"]["pagesTotal"] + 1
        # f32 compute dtype in tests: plain pools only, no scales
        assert sess._cache_bytes == 2 * pages_total * 8 * 2 * 16 * 4
        rng = np.random.default_rng(73)
        prompt = [int(t) for t in rng.integers(1, 48, size=7)]
        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {},
            {"prompt": prompt, "maxNewTokens": 6, "seed": 5})
        assert s == 200 and b["tokens"] == _solo(lm, prompt, 6, 5)
        stats = api.dispatch("GET", f"{PREFIX}/serve/slm", {}, None)[1]
        assert "drift" not in stats
        assert stats["weights"]["dtype"] == "bf16"
    finally:
        _close_api(api)


def _streams_per_decode_step(api, create_body, n_requests=8):
    """Create a session of "slm", queue ``n_requests`` equal one-page
    requests while its serve loop is held, let it go, and return how
    many streams a decode step carried on average, with the session's
    KV bytes. Holding the loop makes the count exact: every request is
    queued before the first admission."""
    status, resp, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/slm", {},
        dict(create_body, temperature=0.7, topK=12))
    assert status == 201, resp
    session = api.ctx.serving._sessions["slm"]
    go = threading.Event()
    serve_once = session._serve_once

    def held():
        assert go.wait(timeout=60)
        return serve_once()

    session._serve_once = held
    rng = np.random.default_rng(17)
    prompts = [[int(t) for t in rng.integers(1, 48, size=4)]
               for _ in range(n_requests)]
    codes = [None] * n_requests

    def client(i):
        codes[i] = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {},
            {"prompt": prompts[i], "maxNewTokens": 4, "seed": i})[0]

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_requests)]
    for t in threads:
        t.start()
    try:
        assert _wait_until(
            lambda: session.stats()["queueDepth"] == n_requests)
    finally:
        go.set()
    for t in threads:
        t.join(timeout=120)
    assert codes == [200] * n_requests
    out = (session.decode_tokens_total / session.decode_steps,
           session._cache_bytes)
    api.dispatch("DELETE", f"{PREFIX}/serve/slm", {}, None)
    return out


@pytest.mark.parametrize("base,wide,bytes_ratio,floor", [
    # a 2-slot cache against a pool of its 64 token rows (8 pages of
    # 8) and the trash page on top: a request of 8 tokens funds one
    # page, not a slot of 32
    ({"maxSlots": 2, "cacheLen": 32},
     {"kv": "paged", "pageLen": 8, "maxSlots": 8, "cacheLen": 32,
      "pages": 9}, 9 / 8, 2.0),
    # a pool of 4 pages, a lane a page, against an int8 pool of fewer
    # bytes: an int8 page and its f32 scale rows are under half a
    # bf16 page
    ({"kv": "paged", "pageLen": 8, "maxSlots": 4, "cacheLen": 32,
      "pages": 5},
     {"kv": "paged", "pageLen": 8, "maxSlots": 8, "cacheLen": 32,
      "pages": 9, "kvDtype": "int8"}, 1.0, 1.8),
], ids=["paged_vs_slot", "int8_vs_bf16"])
def test_equal_kv_bytes_hold_more_streams_per_decode_step(
        api, base, wide, bytes_ratio, floor):
    """Capacity at equal KV memory, counted and not timed: the same
    eight one-page requests, all queued before the first admission,
    ride more streams a decode step through the wider layout, whose
    pool is no larger in bytes."""
    _fit_lm(api)
    base_streams, base_bytes = _streams_per_decode_step(api, base)
    wide_streams, wide_bytes = _streams_per_decode_step(api, wide)
    assert wide_bytes <= bytes_ratio * base_bytes
    assert base_streams <= base["maxSlots"]
    assert wide_streams >= floor * base_streams


# ---------------------------- disaggregated serving + speculative decode
_CYCLE = 16  # cycle length of the learnable successor stream


def _fit_cycle_lms(api):
    """Target ("slm") + draft ("sdraft") trained on the same cyclic-
    successor stream — token t is ALWAYS followed by t % P + 1, a
    bigram map both models actually learn — so the draft's greedy
    proposals mostly match the target's argmax and the accepted-
    tokens/step assertion measures real speculation. The draft sees
    the rows in a different order (close weights, not identical), and
    the spec tests mix in an off-pattern prompt so the rejection path
    runs too."""
    from learningorchestra_tpu.models.transformer import LanguageModel

    tokens = np.asarray(
        [[(off + i) % _CYCLE + 1 for i in range(16)]
         for off in range(64)], np.int32)
    lm = LanguageModel(vocab_size=48, d_model=32, n_layers=1,
                       n_heads=2, d_ff=64, max_len=32, attention="dot")
    lm.fit(tokens, batch_size=16, epochs=25)
    api.ctx.artifacts.save(lm, "slm", "train/tensorflow")
    draft = LanguageModel(vocab_size=48, d_model=32, n_layers=1,
                          n_heads=2, d_ff=64, max_len=32,
                          attention="dot")
    draft.fit(tokens[::-1].copy(), batch_size=16, epochs=25)
    api.ctx.artifacts.save(draft, "sdraft", "train/tensorflow")
    return api.ctx.artifacts.load("slm", "train/tensorflow")


def _solo_greedy(lm, prompt, new):
    out = lm.generate(np.asarray([prompt], np.int32),
                      max_new_tokens=new, temperature=0.0, seed=0)
    return [int(t) for t in out[0][len(prompt):]]


def _wait_until(cond, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


def _prefix_held(session):
    """Pages the prefix cache legitimately retains (its own uncharged
    increfs, dropped on evict/close) — the pool's idle free count is
    ``pagesTotal - _prefix_held``, not ``pagesTotal``."""
    with session.prefix._lock:
        return sum(len(e["held"])
                   for e in session.prefix._entries.values())


def test_disagg_spec_greedy_bit_identical_to_solo(api):
    """The tentpole contract: a disaggregated session with a draft
    model — prefill worker, refcounted page handoff, spec_k-token
    propose/verify rounds — emits EXACTLY the tokens of a solo greedy
    ``generate``, request by request, while landing >= 1 token per
    verify step (acceptedTokensPerStep >= 1 means speculation can
    only add throughput, never subtract)."""
    lm = _fit_cycle_lms(api)
    resp = _paged_session(api, disagg=True, draft="sdraft",
                          specK=3, temperature=0.0)
    assert resp["disagg"]["mode"] in ("colocated", "split")
    assert resp["spec"]["draft"] == "sdraft"
    assert resp["spec"]["specK"] == 3

    rng = np.random.default_rng(81)
    specs = []
    for phase, (plen, new) in enumerate(
            [(3, 6), (5, 8), (8, 5), (4, 7), (6, 6)]):
        specs.append(([(phase * 3 + i) % _CYCLE + 1
                       for i in range(plen)], new))
    # one off-pattern prompt: the draft and target disagree on junk
    # context, so the greedy REJECTION path runs inside this batch too
    specs.append(([int(t) for t in rng.integers(1, 48, size=6)], 6))
    out = [None] * len(specs)

    def client(i):
        prompt, new = specs[i]
        time.sleep(0.03 * i)
        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {},
            {"prompt": prompt, "maxNewTokens": new, "seed": 1})
        assert s == 200, b
        out[i] = b["tokens"]

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(specs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    for i, (prompt, new) in enumerate(specs):
        assert out[i] == _solo_greedy(lm, prompt, new), \
            f"spec request {i} diverged from its solo greedy decode"

    stats = api.dispatch("GET", f"{PREFIX}/serve/slm", {}, None)[1]
    assert stats["spec"]["steps"] > 0
    assert stats["spec"]["acceptedTokensPerStep"] >= 1.0
    assert stats["disagg"]["handoffsTotal"] == len(specs)
    assert stats["disagg"]["handoffQueue"] == 0
    # per-role latency (closed prefill/decode/draft set) + TTFT
    assert set(stats["roles"]) == {"prefill", "decode", "draft"}
    assert stats["ttft"]["count"] == len(specs)
    # pool drained leak-free: every handoff was adopted and retired
    # (the prefix cache's own holds are the only resident pages)
    session = api.ctx.serving._sessions["slm"]
    assert session.pool.free_count() == \
        stats["kv"]["pagesTotal"] - _prefix_held(session)
    text = api.metrics_prometheus().decode()
    assert 'lo_serving_accepted_tokens_per_step{model="slm"}' in text
    assert 'lo_serving_ttft_p99_ms{model="slm"}' in text
    assert ('lo_serving_role_latency_p99_ms{model="slm",'
            'role="draft"}') in text
    assert 'lo_serving_handoffs_total{model="slm"}' in text
    perf = api.dispatch(
        "GET", f"{PREFIX}/observability/perf/slm", {}, None)[1]
    assert perf["perf"].get("acceptedTokensPerStep", 0) >= 1.0
    api.dispatch("DELETE", f"{PREFIX}/serve/slm", {}, None)


def test_spec_sampled_acceptance_keeps_target_distribution(api):
    """Exact rejection sampling at the kernel level: over many seeds,
    the FIRST token a sampled-mode verify emits is distributed as the
    target's filtered softmax — whether the draft proposed the
    likeliest token (acceptance path) or a near-impossible one
    (residual path). Tolerance is total-variation distance with fixed
    seeds, so the check is deterministic."""
    import jax.numpy as jnp
    import jax.random as jr

    lm = _fit_lm(api)
    params = lm.params
    slots, cache_len, page_len, spec_k = 1, 32, 8, 2
    n_pages = 1 + cache_len // page_len
    _, prefill_for, join_paged, _, _ = lm.serve_fns_paged(
        slots, cache_len, page_len, n_pages, 0.7, 12)
    verify = lm.serve_fns_spec(slots, cache_len, page_len, n_pages,
                               spec_k, 0.7, 12)
    prompt = [3, 9, 17, 5]
    s = len(prompt)
    pool = lm.serve_cache_paged(n_pages, page_len)
    nxt, _last, pcache = prefill_for(s)(
        params, jnp.asarray(np.asarray(prompt, np.int32)[None]),
        jr.PRNGKey(0))
    pool = join_paged(pool, pcache, jnp.asarray(np.asarray([1],
                                                           np.int32)),
                      0)
    t0 = int(nxt[0])

    # exact target distribution for position s+1: prefill over
    # prompt+[t0] yields that position's logits; apply the same
    # temperature/topK filter the serve path uses
    _, last_logits, _ = prefill_for(s + 1)(
        params,
        jnp.asarray(np.asarray(prompt + [t0], np.int32)[None]),
        jr.PRNGKey(0))
    z = np.asarray(last_logits[0], np.float64) / 0.7
    kth = np.sort(z)[-12]
    z[z < kth] = -np.inf
    p_target = np.exp(z - z.max())
    p_target /= p_target.sum()

    bt = jnp.asarray(np.asarray([[1, 2, 3, 4]], np.int32))
    col = jnp.asarray(np.asarray([s], np.int32))
    tok = jnp.asarray(np.asarray([[t0]], np.int32))
    limit = jnp.asarray(np.asarray([cache_len - 1], np.int32))
    n_draws = 800
    for arm, d in (("accept", int(np.argmax(p_target))),
                   ("residual", int(np.argmin(p_target)))):
        drafts = jnp.asarray(np.asarray([[d, 0]], np.int32))
        counts = np.zeros(48, np.int64)
        for i in range(n_draws):
            keys = jnp.asarray(
                np.asarray(jr.PRNGKey(1000 + i))[None].astype(
                    np.uint32))
            emitted, _n_acc, pool = verify(
                params, pool, tok, drafts, col, keys, bt, limit)
            counts[int(np.asarray(emitted)[0, 0])] += 1
        freq = counts / float(n_draws)
        tv = 0.5 * float(np.abs(freq - p_target).sum())
        assert tv < 0.08, (arm, tv)


def test_disagg_handoff_refcounts_publish_adopt_and_drain(api):
    """The handoff protocol's refcount invariant: a published record
    holds its stream refs PLUS an uncharged publish hold, so the
    pages survive a prefill-worker teardown un-adopted (drain
    restores the free count exactly) and an adopted record's pages
    are freed exactly once when the stream retires."""
    from learningorchestra_tpu.services import serving as serving_mod
    from learningorchestra_tpu.services import validators as V

    lm = _fit_lm(api)
    resp = _paged_session(api, disagg=True)
    session = api.ctx.serving._sessions["slm"]
    assert isinstance(session, serving_mod.DisaggLMServingSession)
    pages_total = resp["kv"]["pagesTotal"]
    assert session.pool.free_count() == pages_total

    # e2e through the prefill worker first: bit-identity holds and
    # the pool drains back to full after retire
    rng = np.random.default_rng(91)
    prompt = [int(t) for t in rng.integers(1, 48, size=6)]
    s, b, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/slm/predict", {},
        {"prompt": prompt, "maxNewTokens": 5, "seed": 13})
    assert s == 200 and b["tokens"] == _solo(lm, prompt, 5, 13)
    # idle floor: everything free except the prefix cache's own holds
    assert _wait_until(
        lambda: session.pool.free_count()
        == pages_total - _prefix_held(session))
    base = session.pool.free_count()

    # publish without adoption: ceil((6+5)/8) = 2 pages funded, held
    # by stream refs + the publish hold
    req = serving_mod._Request(
        {"prompt": prompt, "maxNewTokens": 5, "seed": 17})
    rec = session._prepare(req)
    assert rec["published"] is True
    assert session.pool.free_count() == base - 2
    # prefill-worker teardown path: drain restores every reference
    session._discard_record(rec, V.HttpError(
        V.HTTP_UNAVAILABLE, "prefill worker torn down"))
    assert session.pool.free_count() == base
    assert session.pool.tenant_pages("default") == 0
    assert req.error is not None and req.error.status == 503

    # publish + adopt: the decode worker picks the record up, the
    # stream serves, and retire frees the pages exactly once
    req2 = serving_mod._Request(
        {"prompt": prompt, "maxNewTokens": 5, "seed": 19})
    rec2 = session._prepare(req2)
    with session._handoff_cv:
        session._ready.append(rec2)
        session.handoffs_total += 1
    with session._cv:
        session._cv.notify_all()
    assert req2.event.wait(30), "adopted stream never finished"
    assert req2.error is None
    assert req2.result["tokens"] == _solo(lm, prompt, 5, 19)
    assert _wait_until(
        lambda: session.pool.free_count() == base)
    assert session.pool.tenant_pages("default") == 0
    api.dispatch("DELETE", f"{PREFIX}/serve/slm", {}, None)


def test_disagg_handoff_latched_fault_collapses_to_fused(tmp_path):
    """Chaos at the kv_page_handoff site: three consecutive injected
    faults are three retryable 429s with every page reference
    restored, then the session collapses to fused prefill+decode —
    disagg.mode stamps fused-degraded, an incident fires, and later
    requests serve bit-identically through the fused path (the ladder
    degrades, never corrupts)."""
    from learningorchestra_tpu.observability import (
        incidents as obs_incidents)

    api = _api_with(tmp_path, fault_inject="kv_page_handoff:100")
    try:
        lm = _fit_lm(api)
        resp = _paged_session(api, disagg=True)
        pages_total = resp["kv"]["pagesTotal"]
        session = api.ctx.serving._sessions["slm"]
        rng = np.random.default_rng(92)
        prompt = [int(t) for t in rng.integers(1, 48, size=6)]

        for _ in range(3):
            s, b, _ = api.dispatch(
                "POST", f"{PREFIX}/serve/slm/predict", {},
                {"prompt": prompt, "maxNewTokens": 5, "seed": 43})
            assert s == 429, b
            assert session.pool.free_count() == pages_total

        assert _wait_until(
            lambda: api.dispatch(
                "GET", f"{PREFIX}/serve/slm", {},
                None)[1]["disagg"]["mode"] == "fused-degraded")

        # fused mode never reaches the handoff site: the still-armed
        # budget cannot touch it, and bit-identity to solo holds
        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {},
            {"prompt": prompt, "maxNewTokens": 5, "seed": 43})
        assert s == 200, b
        assert b["tokens"] == _solo(lm, prompt, 5, 43)
        assert session.pool.free_count() == \
            pages_total - _prefix_held(session)

        stats = api.dispatch("GET", f"{PREFIX}/serve/slm", {}, None)[1]
        assert stats["kv"]["mode"] == "paged"  # still paged, just fused
        recorder = obs_incidents.get_recorder()
        if recorder is not None:
            assert "serving:handoff-degrade" in \
                recorder.stats()["byTrigger"]
    finally:
        _close_api(api)


def test_disagg_split_mode_takes_two_leases(tmp_path):
    """With fleet capacity for two grants (LO_MESH_LEASES=2) the
    disaggregated session runs split: the decode lease is tagged
    ``decode``, the prefill worker queues for its OWN lease tagged
    ``prefill``, and requests stream through the handoff end to
    end."""
    api = _api_with(tmp_path, mesh_leases=2)
    try:
        lm = _fit_lm(api)
        resp = _paged_session(api, disagg=True)
        assert resp["disagg"]["mode"] == "split"
        leases = resp["disagg"]["leases"]
        assert leases["decode"]["role"] == "decode"
        assert leases["prefill"]["role"] == "prefill"

        rng = np.random.default_rng(93)
        prompt = [int(t) for t in rng.integers(1, 48, size=5)]
        s, b, _ = api.dispatch(
            "POST", f"{PREFIX}/serve/slm/predict", {},
            {"prompt": prompt, "maxNewTokens": 6, "seed": 29})
        assert s == 200, b
        assert b["tokens"] == _solo(lm, prompt, 6, 29)
        stats = api.dispatch("GET", f"{PREFIX}/serve/slm", {}, None)[1]
        assert stats["disagg"]["handoffsTotal"] >= 1
        # the prefill worker actually acquired its own grant
        assert stats["disagg"]["leases"]["prefill"]["held"] is True
    finally:
        _close_api(api)


def test_disagg_and_draft_rejected_on_slot_path(api):
    """The slot cache has no page handoff and no paged verify step:
    asking for disagg/draft without kv='paged' is a 406 at the door,
    not a silent downgrade."""
    _fit_lm(api)
    s, b, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/slm", {},
        {"kv": "slot", "disagg": True})
    assert s == 406, b
    s, b, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/slm", {},
        {"kv": "paged", "disagg": "yes"})
    assert s == 406, b
    s, b, _ = api.dispatch(
        "POST", f"{PREFIX}/serve/slm", {},
        {"kv": "paged", "draft": "nonexistent-draft"})
    assert s == 404, b
