"""Elastic slice autoscaler (docs/SCALING.md "Elastic autoscaling"):
pure policy targets never violate declared bounds, the closed loop
shrinks a running elastic job under aged-waiter pressure so the
waiter lands, resizes ride the migration path bit-identically, the
``autoscale_resize`` fault site rolls back to the old slice (transient
retries succeed; a latched fault dead-letters only the RESIZE ledger
while the job finishes untouched), and a racing defrag pick coalesces
with an in-flight resize."""

import dataclasses
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from learningorchestra_tpu.runtime import preempt
from learningorchestra_tpu.services.autoscaler import (
    SliceAutoscaler, grow_target, shrink_target)


def _token(devices, elastic):
    token = preempt.CancelToken()
    token.slice_devices = tuple(range(devices))
    token.migratable = True
    token.elastic = elastic
    return token


class _FakeJobs:
    """Just enough JobManager surface for SliceAutoscaler.tick():
    the coordinator candidate set, scheduler stats, and the resize
    latch (backed by REAL CancelTokens, so inflight/bounds semantics
    are the production ones)."""

    def __init__(self, tokens, stats):
        self.tokens = tokens
        self.stats = stats
        self.requests = []

    @property
    def migration(self):
        return self

    def elastic_jobs(self):
        return sorted(self.tokens.items())

    def scheduler_stats(self):
        return dict(self.stats)

    def request_resize(self, name, want, reason="autoscale"):
        ok = self.tokens[name].request_resize(int(want), reason)
        if ok:
            self.requests.append((name, int(want), reason))
        return ok


# ----------------------------------------------------------------------
# pure policy targets: property-style sweeps over the whole small grid
# ----------------------------------------------------------------------
def test_shrink_target_never_below_min():
    for current in range(1, 17):
        for min_d in range(1, 17):
            want = shrink_target(current, min_d)
            if want is None:
                assert current <= max(1, min_d)
            else:
                assert max(1, min_d) <= want < current


def test_grow_target_bounded_by_max_capacity_and_gang_line():
    for current in range(1, 17):
        for max_d in range(1, 17):
            for free in range(0, 17):
                for total in range(2, 17):
                    want = grow_target(current, max_d, free, total)
                    if want is None:
                        continue
                    assert current < want <= max_d
                    assert want <= current + free
                    # never a whole-mesh want: that would convert the
                    # job to an unresizable gang grant
                    assert want < total


def test_token_rejects_out_of_bounds_resize():
    token = _token(4, (2, 6))
    assert token.request_resize(1) is False  # below min
    assert token.request_resize(7) is False  # above max
    assert token.request_resize(2) is True
    # one placement change per job: second request coalesces
    assert token.request_resize(3) is False
    token.resize_done(True, (0, 1))
    assert token.resizes == 1
    assert token.request_resize(4) is True


# ----------------------------------------------------------------------
# policy loop over fake jobs (deterministic single ticks)
# ----------------------------------------------------------------------
def _autoscaler(jobs, **kw):
    kw.setdefault("interval_seconds", 60.0)  # never self-ticks
    kw.setdefault("backoff_seconds", 0.0)
    return SliceAutoscaler(jobs, **kw)


def test_shrinks_largest_job_on_aged_waiter_pressure():
    jobs = _FakeJobs(
        {"small": _token(4, (1, 8)), "big": _token(6, (2, 8))},
        {"sliced": True, "agedWaiters": 1, "waiters": 1,
         "devicesFree": 0, "devicesTotal": 8})
    scaler = _autoscaler(jobs)
    assert scaler.tick() == "big"
    assert jobs.requests == [("big", 3, "shrink:agedWaiters")]
    assert jobs.tokens["big"].resize_want == 3


def test_never_shrinks_below_declared_min():
    jobs = _FakeJobs(
        {"a": _token(2, (2, 8))},
        {"sliced": True, "agedWaiters": 1, "waiters": 1,
         "devicesFree": 0, "devicesTotal": 8})
    scaler = _autoscaler(jobs)
    assert scaler.tick() is None
    assert jobs.requests == []


def test_grows_smallest_job_on_quiet_cluster():
    jobs = _FakeJobs(
        {"small": _token(2, (1, 8)), "big": _token(4, (1, 8))},
        {"sliced": True, "agedWaiters": 0, "waiters": 0,
         "devicesFree": 2, "devicesTotal": 8})
    scaler = _autoscaler(jobs)
    assert scaler.tick() == "small"
    assert jobs.requests == [("small", 4, "grow:quietCluster")]


def test_no_grow_while_waiters_or_pages():
    class _PagingWatchdog:
        def page_firing(self):
            return True

    jobs = _FakeJobs(
        {"a": _token(2, (1, 8))},
        {"sliced": True, "agedWaiters": 0, "waiters": 1,
         "devicesFree": 4, "devicesTotal": 8})
    assert _autoscaler(jobs).tick() is None  # waiter present
    # a firing PAGE alert (serving p99 burn / hbm headroom floor)
    # flips the policy to shrink even with free devices
    jobs2 = _FakeJobs(
        {"a": _token(4, (1, 8))},
        {"sliced": True, "agedWaiters": 0, "waiters": 0,
         "devicesFree": 4, "devicesTotal": 8})
    scaler2 = _autoscaler(jobs2, watchdog_fn=lambda: _PagingWatchdog())
    assert scaler2.tick() == "a"
    assert jobs2.requests == [("a", 2, "shrink:sloPage")]


def test_rollbacks_back_off_then_dead_letter_resize_ledger():
    jobs = _FakeJobs(
        {"a": _token(8, (1, 8))},
        {"sliced": True, "agedWaiters": 1, "waiters": 1,
         "devicesFree": 0, "devicesTotal": 8})
    scaler = _autoscaler(jobs, retries=2)
    assert scaler.tick() == "a"
    # the engine's failure ladder: rollback, job keeps training
    jobs.tokens["a"].resize_done(False, tuple(range(8)),
                                 error="injected")
    # zero backoff: the settling tick immediately retries
    assert scaler.tick() == "a"
    assert scaler.stats()["counters"]["rollbacks"] == 1
    jobs.tokens["a"].resize_done(False, tuple(range(8)),
                                 error="injected")
    assert scaler.tick() is None  # budget burnt -> no retry latched
    assert scaler.stats()["counters"]["rollbacks"] == 2
    # budget exhausted: the RESIZE ledger is dead-lettered — no more
    # requests for this job, but nothing cancelled the job itself
    assert scaler.stats()["counters"]["deadLettered"] == 1
    n = len(jobs.requests)
    assert scaler.tick() is None
    assert len(jobs.requests) == n
    assert not jobs.tokens["a"].cancelled()
    ledger = scaler.stats()["jobs"]["a"]
    assert ledger["dead"] is True and ledger["attempts"] == 2


def test_successful_resize_resets_backoff_curve():
    jobs = _FakeJobs(
        {"a": _token(8, (1, 8))},
        {"sliced": True, "agedWaiters": 1, "waiters": 1,
         "devicesFree": 0, "devicesTotal": 8})
    scaler = _autoscaler(jobs, retries=3)
    assert scaler.tick() == "a"
    jobs.tokens["a"].resize_done(False, None, error="race")
    # zero backoff: the settling tick retries in the same pass
    assert scaler.tick() == "a"
    assert scaler.stats()["jobs"]["a"]["attempts"] == 1
    jobs.tokens["a"].slice_devices = tuple(range(4))
    jobs.tokens["a"].resize_done(True, tuple(range(4)))
    scaler.tick()
    ledger = scaler.stats()["jobs"]["a"]
    assert ledger["attempts"] == 0 and ledger["dead"] is False
    assert scaler.stats()["counters"]["shrinksCompleted"] == 1


# ----------------------------------------------------------------------
# defrag vs resize race: one placement change per job (satellite 3)
# ----------------------------------------------------------------------
class _Registry:
    """Minimal JobManager registry surface MigrationCoordinator
    reads (lock + job_info + live futures)."""

    def __init__(self, tokens):
        self._lock = threading.Lock()
        self._job_info = {name: {"needs_mesh": True, "token": token}
                          for name, token in tokens.items()}
        self._futures = {name: Future() for name in tokens}


def test_defrag_and_resize_coalesce_to_one_placement_change():
    from learningorchestra_tpu.services.migration import (
        MigrationCoordinator)

    token = _token(4, (2, 6))
    coord = MigrationCoordinator(_Registry({"a": token}))
    assert coord.request_resize("a", 2) is True
    # a defrag pick racing the in-flight resize coalesces: refusal,
    # not a double move
    assert coord.request("a", "defrag") is False
    assert coord.defrag_pick() is None
    assert coord.request_resize("a", 3) is False
    stats = coord.stats()
    assert stats["resizesRequested"] == 1
    assert stats["resizesRefused"] == 1 and stats["refused"] == 1
    # outcome reported -> the next placement change may proceed
    token.slice_devices = tuple(range(2))
    token.resize_done(True, (0, 1))
    assert coord.request("a", "defrag") is True
    # and the reverse order: a latched plain migrate blocks a resize
    token2 = _token(4, (2, 6))
    coord2 = MigrationCoordinator(_Registry({"b": token2}))
    assert coord2.request("b", "defrag") is True
    assert coord2.request_resize("b", 2) is False


def test_non_elastic_job_is_never_resized():
    from learningorchestra_tpu.services.migration import (
        MigrationCoordinator)

    token = _token(4, None)
    coord = MigrationCoordinator(_Registry({"rigid": token}))
    assert coord.elastic_jobs() == []
    assert coord.request_resize("rigid", 2) is False
    assert coord.stats()["resizesRefused"] == 1


# ----------------------------------------------------------------------
# end-to-end over the real engine/scheduler (8-device CPU mesh)
# ----------------------------------------------------------------------
def _make_jobs(catalog, **kw):
    from learningorchestra_tpu.services.jobs import JobManager

    kw.setdefault("max_workers", 4)
    kw.setdefault("mesh_leases", 2)
    return JobManager(catalog, **kw)


def _fit_job(ckpt_dir, epochs, sink):
    """Deterministic linear fit (same as tests/test_migration.py):
    two runs must end bit-identical regardless of mid-run resizes."""
    import jax.numpy as jnp
    import optax

    from learningorchestra_tpu.runtime import data as data_lib
    from learningorchestra_tpu.runtime import mesh as mesh_lib
    from learningorchestra_tpu.runtime.checkpoint import Checkpointer
    from learningorchestra_tpu.runtime.engine import (
        Engine, mse_loss, to_host)

    rng = np.random.RandomState(0)
    x = rng.randn(64, 4).astype(np.float32)
    y = (x @ np.array([[1.0], [2.0], [-1.0], [0.5]],
                      np.float32))[:, 0]

    def apply_fn(params, model_state, batch, train, step_rng):
        return batch["x"] @ params["w"], model_state

    def job():
        eng = Engine(apply_fn=apply_fn, loss_fn=mse_loss,
                     optimizer=optax.sgd(0.05),
                     mesh=mesh_lib.current_mesh(),
                     compute_dtype=jnp.float32, donate_state=False)
        state = eng.init_state({"w": jnp.zeros((4,), jnp.float32)})
        batcher = data_lib.ArrayBatcher({"x": x, "y": y},
                                        batch_size=16, seed=3)
        ckpt = Checkpointer(ckpt_dir)
        try:
            state, _ = eng.fit(state, batcher, epochs=epochs, seed=7,
                               checkpointer=ckpt, scan_batches=False)
        finally:
            ckpt.close()
        host = to_host(state)
        sink.append(host)
        return int(host.step)

    return job


_ELASTIC_FP = {"devices": 4, "elastic": {"min": 2, "max": 4}}


def _resize_until_accepted(jobs, name, want, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if jobs.request_resize(name, want):
            return True
        time.sleep(0.02)
    return False


def _perform_requested_resize(token):
    """What the engine does at a boundary, minus the engine: perform
    a latched placement change and report a resize as done."""
    if preempt.migrate_requested():
        want = token.resize_want
        performed, devices = preempt.perform_migrate()
        if performed and want is not None:
            token.resize_done(True, devices)


def _wait_counter(token, attr, value, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if getattr(token, attr) >= value:
            return True
        time.sleep(0.02)
    return False


def test_elastic_resized_twice_bit_identical(tmp_path, catalog):
    """Shrink 4→2 then grow 2→4 mid-fit: the final params must equal
    a rigid run's bit-for-bit (fold_in replay over the re-sharded
    batches), and the job's sliceHistory records both resizes."""
    jobs = _make_jobs(catalog)
    try:
        results = {}
        for tag in ("base", "ela"):
            name = f"as_{tag}"
            catalog.create_collection(name, "train/neural")
            sink = []
            results[tag] = sink
            jobs.submit(
                name, _fit_job(str(tmp_path / tag), 6, sink),
                needs_mesh=True, pool="train",
                footprint=(dict(_ELASTIC_FP) if tag == "ela"
                           else {"devices": 4}))
            if tag == "ela":
                token = jobs._job_info[name]["token"]
                assert _resize_until_accepted(jobs, name, 2)
                assert _wait_counter(token, "resizes", 1)
                assert len(token.slice_devices) == 2
                assert _resize_until_accepted(jobs, name, 4)
                assert _wait_counter(token, "resizes", 2)
                assert len(token.slice_devices) == 4
            jobs.wait(name, timeout=180)
        base, ela = results["base"][0], results["ela"][0]
        assert int(base.step) == int(ela.step)
        np.testing.assert_array_equal(np.asarray(base.params["w"]),
                                      np.asarray(ela.params["w"]))
        events = [e["event"] for e in token.slice_history]
        assert events.count("resize") == 2
        assert token.resize_rollbacks == 0
        meta = catalog.get_metadata("as_ela")
        assert [e["event"] for e in meta["sliceHistory"]].count(
            "resize") == 2
    finally:
        jobs.shutdown()


def test_resize_fault_transient_rolls_back_then_retry_succeeds(
        tmp_path, tmp_config, catalog, monkeypatch):
    """``autoscale_resize:1:raise`` fires inside the guarded region:
    the resize rolls back (old slice, job keeps training, incident
    fired with resize context), the budget is spent, and the NEXT
    request succeeds — final params bit-identical to a rigid run."""
    from learningorchestra_tpu import config as config_mod
    from learningorchestra_tpu.observability import (
        incidents as obs_incidents)
    from learningorchestra_tpu.services import faults

    config_mod.set_config(dataclasses.replace(
        tmp_config, fault_inject="autoscale_resize:1:raise"))
    faults.reset()
    fired = []
    monkeypatch.setattr(
        obs_incidents, "trigger",
        lambda name, **context: fired.append((name, context)) or False)
    jobs = _make_jobs(catalog)
    try:
        results = {}
        for tag in ("base", "chaos"):
            name = f"asf_{tag}"
            catalog.create_collection(name, "train/neural")
            sink = []
            results[tag] = sink
            jobs.submit(
                name, _fit_job(str(tmp_path / tag), 6, sink),
                needs_mesh=True, pool="train",
                footprint=(dict(_ELASTIC_FP) if tag == "chaos"
                           else {"devices": 4}))
            if tag == "chaos":
                token = jobs._job_info[name]["token"]
                assert _resize_until_accepted(jobs, name, 2)
                assert _wait_counter(token, "resize_rollbacks", 1)
                # rolled back to an old-size slice, still training
                assert len(token.slice_devices) == 4
                assert not token.cancelled()
                # retry: the transient budget is spent, so it lands
                assert _resize_until_accepted(jobs, name, 2)
                assert _wait_counter(token, "resizes", 1)
                assert len(token.slice_devices) == 2
            jobs.wait(name, timeout=180)
        base, chaos = results["base"][0], results["chaos"][0]
        assert int(base.step) == int(chaos.step)
        np.testing.assert_array_equal(np.asarray(base.params["w"]),
                                      np.asarray(chaos.params["w"]))
        rollbacks = [c for n, c in fired if n == "autoscaler:rollback"]
        assert rollbacks and rollbacks[0]["want"] == 2
        assert "InjectedFault" in rollbacks[0]["error"]
        assert any(e["event"] == "rollback"
                   for e in token.slice_history)
    finally:
        faults.reset()
        jobs.shutdown()


def test_resize_fault_latched_never_kills_the_job(
        tmp_path, tmp_config, catalog):
    """A LATCHED ``autoscale_resize`` fault (large count) fails every
    resize attempt: each rolls back to the old slice, and the job
    itself still finishes bit-identically — only the resize requests
    die."""
    from learningorchestra_tpu import config as config_mod
    from learningorchestra_tpu.services import faults

    config_mod.set_config(dataclasses.replace(
        tmp_config, fault_inject="autoscale_resize:99:raise"))
    faults.reset()
    jobs = _make_jobs(catalog)
    try:
        results = {}
        for tag in ("base", "latch"):
            name = f"asl_{tag}"
            catalog.create_collection(name, "train/neural")
            sink = []
            results[tag] = sink
            jobs.submit(
                name, _fit_job(str(tmp_path / tag), 6, sink),
                needs_mesh=True, pool="train",
                footprint=(dict(_ELASTIC_FP) if tag == "latch"
                           else {"devices": 4}))
            if tag == "latch":
                token = jobs._job_info[name]["token"]
                for attempt in (1, 2):
                    assert _resize_until_accepted(jobs, name, 2)
                    assert _wait_counter(token, "resize_rollbacks",
                                         attempt)
                    assert len(token.slice_devices) == 4
            jobs.wait(name, timeout=180)
        base, latch = results["base"][0], results["latch"][0]
        assert int(base.step) == int(latch.step)
        np.testing.assert_array_equal(np.asarray(base.params["w"]),
                                      np.asarray(latch.params["w"]))
        assert token.resizes == 0 and token.resize_rollbacks == 2
    finally:
        faults.reset()
        jobs.shutdown()


def test_closed_loop_shrink_places_aged_waiter(catalog):
    """The tentpole loop end-to-end: an elastic holder on 6/8 devices
    blocks a 4-device waiter; the running autoscaler sees the AGED
    waiter, shrinks the holder 6→3 (never preempt-kills it), and the
    waiter lands while the holder keeps running."""
    jobs = _make_jobs(catalog, slice_aging_seconds=0.3)
    scaler = SliceAutoscaler(jobs, interval_seconds=0.1,
                             backoff_seconds=0.1).start()
    started = threading.Event()
    stop = threading.Event()

    def holder():
        started.set()
        token = preempt.current_cancel()
        while not stop.is_set():
            _perform_requested_resize(token)
            time.sleep(0.02)
        return "held"

    try:
        catalog.create_collection("as_holder", "train/neural")
        catalog.create_collection("as_waiter", "train/neural")
        jobs.submit("as_holder", holder, needs_mesh=True, pool="train",
                    footprint={"devices": 6,
                               "elastic": {"min": 2, "max": 6}})
        assert started.wait(timeout=30)
        jobs.submit("as_waiter", lambda: "landed", needs_mesh=True,
                    pool="train", footprint={"devices": 4})
        # only a shrink can make room — the holder never exits on its
        # own and is never cancelled
        assert jobs.wait("as_waiter", timeout=60) == "landed"
        token = jobs._job_info["as_holder"]["token"]
        assert not token.cancelled()
        assert token.resizes >= 1
        counters = scaler.stats()["counters"]
        assert counters["shrinksRequested"] >= 1
        # the ledger settles on the NEXT tick after the engine reports
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            counters = scaler.stats()["counters"]
            if counters["shrinksCompleted"] + counters["rollbacks"] >= 1:
                break
            time.sleep(0.05)
        assert counters["shrinksCompleted"] + counters["rollbacks"] >= 1
    finally:
        scaler.stop()
        stop.set()
        try:
            jobs.wait("as_holder", timeout=30)
        finally:
            jobs.shutdown()


def test_slo_page_pressure_shrinks_a_live_job_that_still_finishes(
        catalog):
    """The other pressure source end to end: with a page alert firing
    and no waiter at all, the running autoscaler shrinks a live
    elastic job 4→2 at its next boundary; the job is never cancelled
    and returns its own result."""
    class _Paging:
        def page_firing(self):
            return True

    jobs = _make_jobs(catalog)
    scaler = SliceAutoscaler(jobs, interval_seconds=0.1,
                             backoff_seconds=0.1,
                             watchdog_fn=lambda: _Paging()).start()

    def victim():
        token = preempt.current_cancel()
        deadline = time.monotonic() + 60.0
        while token.resizes < 1 and time.monotonic() < deadline:
            _perform_requested_resize(token)
            time.sleep(0.02)
        return len(token.slice_devices)

    try:
        catalog.create_collection("as_victim", "train/neural")
        jobs.submit("as_victim", victim, needs_mesh=True, pool="train",
                    footprint=dict(_ELASTIC_FP))
        assert jobs.wait("as_victim", timeout=90) == 2
        token = jobs._job_info["as_victim"]["token"]
        assert token.resizes == 1 and not token.cancelled()
        assert [e["event"] for e in token.slice_history] == [
            "grant", "resize"]
        assert scaler.stats()["counters"]["shrinksRequested"] >= 1
    finally:
        scaler.stop()
        jobs.shutdown()


def test_scheduler_fairness_holds_with_elastic_jobs(catalog):
    """Aging anti-starvation still applies when elastic jobs are in
    the mix: a gang job enqueued behind a stream of sliced elastic
    jobs is not starved (grant order honors the aging freeze)."""
    jobs = _make_jobs(catalog, slice_aging_seconds=0.2)
    stop = threading.Event()

    def looper():
        while not stop.is_set():
            time.sleep(0.02)
        return "loop"

    try:
        catalog.create_collection("fair_e", "train/neural")
        jobs.submit("fair_e", looper, needs_mesh=True, pool="train",
                    footprint={"devices": 4,
                               "elastic": {"min": 2, "max": 4}})
        time.sleep(0.1)
        catalog.create_collection("fair_gang", "train/neural")
        gang = jobs.submit("fair_gang", lambda: "gang",
                           needs_mesh=True, pool="tune")
        # the gang job needs EVERY device; it can only land after the
        # elastic holder exits — but it must not be starved by fresh
        # sliced submissions once aged
        for i in range(3):
            catalog.create_collection(f"fair_s{i}", "train/neural")
            jobs.submit(f"fair_s{i}", lambda: "s", needs_mesh=True,
                        pool="train", footprint={"devices": 2})
        stop.set()
        jobs.wait("fair_e", timeout=30)
        assert gang.result(timeout=30) == "gang"
        for i in range(3):
            jobs.wait(f"fair_s{i}", timeout=30)
    finally:
        stop.set()
        jobs.shutdown()


# ----------------------------------------------------------------------
# REST surface + request validation
# ----------------------------------------------------------------------
def test_valid_slice_devices_elastic_bounds():
    from learningorchestra_tpu.services import validators as V

    assert V.valid_slice_devices({"min": 2, "max": 6}) == \
        {"min": 2, "max": 6}
    assert V.valid_slice_devices(3) == 3
    assert V.valid_slice_devices(None) is None
    for bad in ({"min": 0, "max": 4}, {"min": 2},
                {"min": 4, "max": 2}, {"min": 2, "max": 4, "x": 1},
                {"min": True, "max": 4}, {"min": 1.5, "max": 4},
                True, -1, "4"):
        with pytest.raises(V.HttpError):
            V.valid_slice_devices(bad)


def test_rest_observability_autoscaler(tmp_config):
    from learningorchestra_tpu.services.server import Api

    api = Api()
    prefix = tmp_config.api_prefix
    try:
        status, body, _ = api.dispatch(
            "GET", f"{prefix}/observability/autoscaler", {}, None)
        assert status == 200, body
        assert "counters" in body and "migration" in body
        # prometheus exposition carries the new counter families
        text = api.metrics_prometheus().decode()
        assert 'lo_autoscaler_resizes_total{direction="shrink"}' in text
        assert "lo_autoscaler_rollbacks_total" in text
    finally:
        api.ctx.close()
