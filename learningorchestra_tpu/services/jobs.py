"""Async job manager.

The reference's execution model, shared by every service
(SURVEY §L2): the POST handler validates synchronously, writes a
metadata document with ``finished: False``, submits the pipeline to a
``ThreadPoolExecutor`` and returns 201 immediately; clients poll the
``finished`` flag (binary_executor_image/binary_execution.py:118-175).
On success the flag flips and an execution document is appended; on
failure the flag stays False and the execution document records
``repr(exception)`` (binary_execution.py:160-175).

Beyond the reference (its in-flight jobs are simply lost on failure,
README.md:194-198):

- **Device leasing.** A TPU mesh is an exclusive resource; jobs that
  need it acquire a lease so concurrent REST jobs queue instead of
  fighting over HBM (SURVEY §7 hard part #1). The lease is FAIR
  across job classes (services/scheduler.py — fairscheduler.xml
  parity) and long fits yield it at epoch boundaries; a preempted
  job's device state stays in HBM, so LO_MESH_YIELD=0 restores
  strict serialization when concurrent footprints would not fit.
- **Lifecycle** (docs/LIFECYCLE.md). Every job carries a cooperative
  :class:`~learningorchestra_tpu.runtime.preempt.CancelToken`:
  per-job deadlines (``timeout`` request field / ``LO_JOB_TIMEOUT``),
  user cancellation (``DELETE .../run``), and a stall watchdog that
  flags jobs whose progress heartbeat went quiet
  (``LO_STALL_SECONDS``) — so a hung user function or wedged
  collective is reclaimed at the next yield point instead of holding
  the mesh lease forever. The metadata ``status`` field tracks
  queued → running → {finished, timedOut, cancelled, stalled,
  deadLettered, shutdownAborted}.
- **Classified retries.** ``max_retries`` re-runs a failed pipeline
  only for TRANSIENT errors (I/O, OOM/RESOURCE_EXHAUSTED, injected
  faults), with exponential backoff + jitter between attempts;
  permanent errors (validation, user-code bugs) dead-letter
  immediately, and an exhausted budget dead-letters too. NUMERICAL
  errors (health-sentinel divergence, runtime/health.py) carry their
  own ``LO_HEALTH_RETRIES`` budget — a retried checkpointed fit
  resumes from its last-good step instead of replaying the
  divergence (docs/RELIABILITY.md). Each attempt appends its own
  execution document.
- **Timing.** Every execution document records ``elapsedSeconds``
  (superset of the reference's builder-only ``fitTime``,
  builder.py:117-122) plus queue wait time for lease contention.
"""

from __future__ import annotations

import contextlib
import random
import threading
import time
import traceback
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, Optional

from learningorchestra_tpu.catalog import documents as D
from learningorchestra_tpu.catalog.store import Catalog
from learningorchestra_tpu.observability import export as obs_export
from learningorchestra_tpu.observability import incidents as obs_incidents
from learningorchestra_tpu.observability import monitor as obs_monitor
from learningorchestra_tpu.observability import perf as obs_perf
from learningorchestra_tpu.observability import trace as obs_trace
from learningorchestra_tpu.runtime import preempt
from learningorchestra_tpu.runtime.health import NumericalDivergence
from learningorchestra_tpu.services import faults
from learningorchestra_tpu.runtime import locks

TRANSIENT = "transient"
PERMANENT = "permanent"
# training diverged past its health policy (runtime/health.py): its own
# class because the right response is neither a plain re-run (the same
# divergence replays) nor dead-lettering — a bounded number of
# rollback-retries, each resuming from the last-good checkpoint
NUMERICAL = "numerical"

# message substrings that mark an otherwise-unclassified exception as
# retryable (XLA surfaces HBM OOM as XlaRuntimeError RESOURCE_EXHAUSTED,
# not MemoryError; grpc/gcs failures carry UNAVAILABLE; "TRANSIENT"
# honors errors that self-describe as retryable)
_TRANSIENT_MARKERS = ("RESOURCE_EXHAUSTED", "OUT OF MEMORY",
                      "UNAVAILABLE", "DATA_LOSS", "CONNECTION RESET",
                      "TRANSIENT")


def classify_error(exception: BaseException) -> str:
    """``transient`` (worth a retry: the same code may succeed on a
    re-run) vs ``numerical`` (training diverged: retry resumes from
    the last-good checkpoint, budgeted separately) vs ``permanent``
    (validation/user-code errors a retry would only repeat).
    :class:`faults.InjectedFault` is an IOError subclass, so injected
    faults exercise the transient path."""
    if isinstance(exception, NumericalDivergence):
        return NUMERICAL
    if isinstance(exception, (OSError, MemoryError, InterruptedError,
                              TimeoutError, ConnectionError)):
        return TRANSIENT
    text = f"{type(exception).__name__}: {exception}".upper()
    if any(marker in text for marker in _TRANSIENT_MARKERS):
        return TRANSIENT
    return PERMANENT


def _single_host() -> bool:
    """Stall escalation is single-host only — mirroring the lease's
    yield rule: on a multi-host pod a coordinator-side cancellation
    would diverge the SPMD program the workers are replaying."""
    try:
        from learningorchestra_tpu.runtime import distributed as dist

        if not dist.is_initialized():
            return True
        import jax

        return jax.process_count() <= 1
    except Exception:  # noqa: BLE001 — no runtime formed yet
        return True


class JobManager:
    def __init__(self, catalog: Catalog, max_workers: int = 8,
                 mesh_leases: int = 1,
                 pod_failure_fn: Optional[Callable[[], Optional[str]]]
                 = None,
                 pool_weights: Optional[Dict[str, float]] = None,
                 default_timeout: float = 0.0,
                 stall_seconds: float = 0.0,
                 stall_escalate: bool = True,
                 retry_backoff: float = 0.5,
                 retry_backoff_max: float = 30.0,
                 slice_min_devices: int = 1,
                 slice_aging_seconds: float = 30.0,
                 numerical_retries: int = 1,
                 slice_defrag: float = 0.0,
                 served_half_life_seconds: float = 600.0):
        from learningorchestra_tpu.services.migration import \
            MigrationCoordinator
        from learningorchestra_tpu.services.scheduler import SliceLease

        self._catalog = catalog
        self._pool = ThreadPoolExecutor(max_workers=max_workers,
                                        thread_name_prefix="lo-job")
        self._mesh = SliceLease(
            mesh_leases, pool_weights,
            min_devices=slice_min_devices,
            aging_seconds=slice_aging_seconds,
            served_half_life_seconds=served_half_life_seconds)
        self._migration = MigrationCoordinator(self)
        # LO_SLICE_DEFRAG > 0 arms defrag-via-migration: the value is
        # the fragmentation threshold past which a blocked waiter may
        # ask the cheapest migratable holder to vacate its slice
        if float(slice_defrag or 0.0) > 0:
            self._mesh.set_defrag_policy(self._migration.defrag_pick,
                                         threshold=float(slice_defrag))
        self._futures: Dict[str, Future] = {}
        # name -> {description, parameters, needs_mesh, token}: the
        # lifecycle registry (cancel API, stall watchdog, shutdown
        # documentation, worker-lost marking)
        self._job_info: Dict[str, Dict[str, Any]] = {}
        self._lock = locks.make_lock("jobs.manager")
        # returns a failure description when the multi-host pod has
        # lost a worker (runtime.distributed.pod_failure); mesh jobs
        # are then refused instead of hanging in a collective
        self._pod_failure_fn = pod_failure_fn or (lambda: None)
        self._default_timeout = max(0.0, float(default_timeout or 0.0))
        self._stall_seconds = max(0.0, float(stall_seconds or 0.0))
        self._stall_escalate = bool(stall_escalate)
        self._retry_backoff = max(0.0, float(retry_backoff))
        self._retry_backoff_max = max(self._retry_backoff,
                                      float(retry_backoff_max))
        # rollback-retry budget for the NUMERICAL error class
        # (LO_HEALTH_RETRIES): a checkpointed fit resumes from its
        # last-good step on each of these, so they are budgeted apart
        # from the transient max_retries
        self._numerical_retries = max(0, int(numerical_retries))
        self._counters: Dict[str, int] = {"retries": 0, "cancelled": 0,
                                          "timedOut": 0,
                                          "numericalRetries": 0,
                                          "deadLettered": 0}
        self._stalled: set = set()
        self._watchdog_stop = threading.Event()
        if self._stall_seconds > 0:
            threading.Thread(target=self._watch_stalls, daemon=True,
                             name="lo-stall-watchdog").start()

    # ------------------------------------------------------------------
    def mesh_lease(self, pool: str = "default", cancel=None,
                   footprint=None):
        """Context manager granting accelerator access through the
        fair queue (``with jobs.mesh_lease(): ...``). ``footprint``
        (``{"devices": n, "hbmBytes": b}``) sizes the slice grant when
        slicing is enabled."""
        return self._mesh.lease(pool, cancel=cancel, footprint=footprint)

    @property
    def slice_lease(self):
        """The shared SliceLease allocator — serving sessions wrap it
        in a ``ServingLease`` so resident sessions and batch gang jobs
        contend through ONE fair queue (a separate allocator would let
        both sides believe they own the whole mesh)."""
        return self._mesh

    def mesh_served(self) -> Dict[str, float]:
        """Cumulative mesh seconds per pool (observability)."""
        return self._mesh.served()

    def scheduler_stats(self) -> Dict[str, Any]:
        """Slice-allocator occupancy/grant/wait aggregates (exported
        as ``lo_mesh_devices_busy`` etc. by the Api)."""
        return self._mesh.stats()

    def queue_stats(self) -> Dict[str, int]:
        """Live job-queue depth for the cluster monitor: submitted
        jobs split into started-on-a-worker (``running``) vs still
        waiting for a thread (``queued``), plus the monotonic
        dead-letter counter the SLO watchdog rates."""
        with self._lock:
            live = [k for k, f in self._futures.items()
                    if not f.done()]
            started = 0
            for k in live:
                token = (self._job_info.get(k) or {}).get("token")
                if token is not None and getattr(token, "started",
                                                 None):
                    started += 1
        counters = self.lifecycle_counters()
        return {"running": started, "queued": len(live) - started,
                "deadLettered": counters.get("deadLettered", 0)}

    def lifecycle_counters(self) -> Dict[str, int]:
        """Monotonic lifecycle counters + the currently-stalled gauge
        (exported as ``lo_job_retries_total`` etc. by the Api)."""
        with self._lock:
            out = dict(self._counters)
            out["stalled"] = sum(
                1 for k in self._stalled
                if k in self._futures and not self._futures[k].done())
        return out

    # ------------------------------------------------------------------
    def _set_status(self, name: str, status: str) -> None:
        # advisory lifecycle state on the metadata document; a
        # collection deleted mid-run must not sink the job thread
        try:
            self._catalog.update_metadata(name, {D.STATUS_FIELD: status})
        except Exception:  # noqa: BLE001
            pass

    def _count(self, key: str, delta: int = 1) -> None:
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + delta

    def _count_cancel(self, status: str) -> None:
        self._count("timedOut" if status == D.STATUS_TIMED_OUT
                    else "cancelled")

    def _record_attribution(self, name: str,
                            footprint: Optional[Dict[str, Any]] = None,
                            measure_hbm: bool = False,
                            token: Optional[preempt.CancelToken] = None,
                            ) -> None:
        """Roll trace-derived wall-clock attribution into the job's
        metadata (docs/LIFECYCLE.md): ``leaseWaitSeconds`` (mesh
        grant wait), ``compileSeconds`` (the job's ``compile`` spans:
        the step calls that built an executable) and
        ``checkpointCommitSeconds`` (summed commit stalls) — so clients
        see where the time went without the trace endpoint.
        Mesh jobs additionally record ``peakHbmBytes`` — the process's
        device high-water mark while the job ran (an upper bound under
        slice concurrency) — and feed the footprint-calibration
        registry so a repeat execution's slice is sized from the
        measurement (docs/SCALING.md §7). Best-effort; requires
        LO_TRACE=1 (the default)."""
        try:
            totals = obs_trace.durations_by_name(name)
            meta: Dict[str, Any] = {}
            if "leaseWait" in totals:
                meta["leaseWaitSeconds"] = totals["leaseWait"]
            if "compile" in totals:
                meta["compileSeconds"] = totals["compile"]
            if "checkpointCommit" in totals:
                meta["checkpointCommitSeconds"] = \
                    totals["checkpointCommit"]
            if measure_hbm:
                peak = obs_monitor.peak_hbm_bytes()
                if peak:
                    meta["peakHbmBytes"] = int(peak)
                    key = (footprint.get("calibrationKey")
                           if isinstance(footprint, dict) else None)
                    obs_monitor.record_peak(key or name, peak)
            # roofline summary of the job's last steady-state window
            # (observability/perf): stamped on terminal metadata so
            # GET /observability/perf/{name} answers after the
            # in-process registry evicts the job
            perf_report = obs_perf.job_report(name)
            if perf_report:
                meta["perf"] = {k: perf_report[k] for k in (
                    "mfu", "tflopsPerSecPerChip", "gbPerSecPerChip",
                    "arithmeticIntensity", "hbmBwUtil", "boundBy")
                    if k in perf_report}
            if token is not None and token.slice_history:
                # placement timeline (grants, resizes, rollbacks) —
                # the "when did the autoscaler move my job" answer
                with token._lock:
                    meta["sliceHistory"] = \
                        [dict(e) for e in token.slice_history]
            if meta:
                self._catalog.update_metadata(name, meta)
        except Exception:  # noqa: BLE001 — observability is advisory
            pass

    def _backoff_seconds(self, attempt: int) -> float:
        """Exponential backoff with full jitter: base * 2^attempt,
        scaled by a uniform [0.5, 1.5) factor so synchronized retries
        (N jobs felled by one transient) don't re-converge."""
        if self._retry_backoff <= 0:
            return 0.0
        base = min(self._retry_backoff * (2 ** attempt),
                   self._retry_backoff_max)
        return base * (0.5 + random.random())

    # ------------------------------------------------------------------
    def submit(self, name: str, fn: Callable[[], Any], *,
               description: str = "",
               parameters: Optional[Dict[str, Any]] = None,
               needs_mesh: bool = False,
               pool: str = "default",
               max_retries: int = 0,
               on_success: Optional[Callable[[Any], None]] = None,
               mark_finished: bool = True,
               failure_names: Optional[list] = None,
               only_if_idle: bool = False,
               timeout: Optional[float] = None,
               footprint: Optional[Dict[str, Any]] = None,
               ) -> Future:
        """Run ``fn`` asynchronously under the reference's
        finished-flag contract for collection ``name`` (which must
        already exist with ``finished: False``). Multi-output jobs
        (Builder: one collection per classifier) pass
        ``failure_names`` so a TERMINAL job failure documents EVERY
        output — a client polling any of them must see the error, not
        hang on a forever-False finished flag. ``timeout`` (seconds)
        is this job's deadline; None falls back to the manager-wide
        default (``LO_JOB_TIMEOUT``), 0 disables. ``footprint``
        (``{"devices": n, "hbmBytes": b}``) sizes this mesh job's
        slice grant under the slice scheduler; None gang-acquires the
        full mesh. The granted slice flows into the job's thread as
        ``runtime.mesh.current_mesh()``."""
        doc_names = list(failure_names) if failure_names else [name]
        effective_timeout = (self._default_timeout if timeout is None
                             else max(0.0, float(timeout)))
        token = preempt.CancelToken(
            deadline=(time.monotonic() + effective_timeout)
            if effective_timeout > 0 else None)

        def fail_all(document: Dict[str, Any]) -> None:
            for n in doc_names:
                if n != name:
                    # outputs that already finished (e.g. classifiers
                    # that completed before a sibling's failure sank
                    # the job) keep their clean record
                    meta = self._catalog.get_metadata(n)
                    if meta is None or meta.get(D.FINISHED_FIELD):
                        continue
                self._catalog.append_document(n, dict(document))

        def record_cancel(exc: preempt.JobCancelled, attempt: int,
                          extra: Dict[str, Any]) -> None:
            status = exc.reason or D.STATUS_CANCELLED
            extra = dict(extra)
            extra.update({D.STATUS_FIELD: status, "cancelReason": status,
                          "attempt": attempt})
            fail_all(D.execution_document(
                description, parameters,
                exception=f"JobCancelled({status!r}: {exc})",
                extra=extra))
            self._set_status(name, status)
            self._count_cancel(status)
            obs_export.log_event("job", "cancelled", trace_id=name,
                                 reason=status)
            if status == D.STATUS_TIMED_OUT:
                obs_incidents.trigger("job:timedOut", job=name)

        def run() -> Any:
            submitted = time.monotonic()
            token.started = submitted
            # root span of this job's trace (trace id == collection
            # name); every nested span — lease, dataLoad, compile,
            # epochs, checkpoint commits — attaches under it through
            # the thread-local stack
            job_span = obs_trace.span("job", trace=name, pool=pool,
                                      needsMesh=needs_mesh)
            obs_export.log_event("job", "start", trace_id=name,
                                 pool=pool)
            attempts = max_retries + 1
            # attempt_no counts every try (documents/diagnostics);
            # transient failures burn the max_retries budget while
            # numerical (divergence) failures burn their own, so a
            # rollback-retry never eats the slot reserved for an
            # infra blip and vice versa
            attempt_no = 0
            transient_failures = 0
            numerical_used = 0
            preempt.install_cancel(token)
            job_span.__enter__()
            try:
                while True:
                    attempt_no += 1
                    if needs_mesh:
                        failure = self._pod_failure_fn()
                        if failure:
                            # a degraded pod cannot run mesh
                            # collectives: record a TERMINAL typed
                            # failure instead of entering a jit that
                            # would hang forever
                            fail_all(D.execution_document(
                                description, parameters,
                                exception=f"WorkerLost({failure!r})",
                                extra={"workerLost": True,
                                       "attempt": attempt_no}))
                            return None
                    try:
                        # cancelled/expired while queued in the thread
                        # pool or during retry backoff: terminal, no
                        # lease ever taken
                        token.check()
                        lease = (self._mesh.lease(pool, cancel=token,
                                                  footprint=footprint)
                                 if needs_mesh
                                 else contextlib.nullcontext())
                        with lease as lease_token, \
                                contextlib.ExitStack() as stack:
                            granted = time.monotonic()
                            queue_wait = granted - submitted
                            slice_devices = getattr(
                                lease_token, "devices", None)
                            # retro spans: pool-queue wait, then the
                            # fair-queue lease wait (the tail of it)
                            lease_wait = (getattr(
                                lease_token, "wait_seconds", 0.0)
                                if needs_mesh else 0.0)
                            lease_wait = min(max(lease_wait, 0.0),
                                             queue_wait)
                            obs_trace.add(
                                "queueWait", name, submitted,
                                granted - lease_wait,
                                parent=job_span.span_id,
                                attempt=attempt_no)
                            if needs_mesh:
                                # the lease-wait HISTOGRAM is fed at
                                # the scheduler's grant site; only the
                                # span is recorded here
                                obs_trace.add(
                                    "leaseWait", name,
                                    granted - lease_wait, granted,
                                    parent=job_span.span_id,
                                    pool=pool)
                            if slice_devices is not None:
                                # the granted sub-mesh becomes this
                                # thread's current_mesh() so engines
                                # train on the slice; a full-mesh
                                # grant (None) keeps the default-mesh
                                # fast path untouched
                                from learningorchestra_tpu.runtime \
                                    import mesh as mesh_lib
                                stack.enter_context(mesh_lib.use_mesh(
                                    mesh_lib.mesh_for_slice(
                                        slice_devices)))
                            self._set_status(name, D.STATUS_RUNNING)
                            if needs_mesh:
                                # surface WHY the job waited and WHERE
                                # it landed on the metadata document
                                meta = {"leaseWaitSeconds": round(
                                    getattr(lease_token, "wait_seconds",
                                            queue_wait), 6)}
                                if slice_devices is not None:
                                    meta["sliceDevices"] = \
                                        list(slice_devices)
                                try:
                                    self._catalog.update_metadata(
                                        name, meta)
                                except Exception:  # noqa: BLE001
                                    pass
                            start = time.monotonic()

                            def timing(extra_base):
                                # elapsedSeconds is the job's OWN
                                # runtime: epochs spent preempted
                                # (lease handed to another pool) are
                                # reported separately so throughput
                                # comparisons stay meaningful under
                                # contention
                                elapsed = time.monotonic() - start
                                preempted = getattr(
                                    lease_token, "preempted_seconds",
                                    0.0)
                                extra = dict(extra_base)
                                extra["elapsedSeconds"] = round(
                                    elapsed - preempted, 6)
                                if preempted > 0:
                                    extra["preemptedSeconds"] = round(
                                        preempted, 6)
                                    extra["leaseYields"] = \
                                        lease_token.yields
                                if needs_mesh:
                                    extra["leaseWaitSeconds"] = round(
                                        getattr(lease_token,
                                                "wait_seconds", 0.0), 6)
                                    if slice_devices is not None:
                                        extra["sliceDevices"] = \
                                            list(slice_devices)
                                return extra

                            try:
                                # chaos site: fires with the lease held
                                # (hang mode simulates a wedged job
                                # holding the mesh; raise mode a
                                # transient attempt failure)
                                faults.maybe_inject("job_run")
                                with obs_trace.span(
                                        "attempt",
                                        attempt=attempt_no):
                                    result = fn()
                                if on_success is not None:
                                    on_success(result)
                                # before ``finished`` shows: a client
                                # that polls the flag then reads
                                # compileSeconds / perf finds them
                                self._record_attribution(
                                    name, footprint,
                                    measure_hbm=needs_mesh,
                                    token=token)
                                if mark_finished:
                                    self._catalog.mark_finished(name)
                                self._set_status(name,
                                                 D.STATUS_FINISHED)
                                self._catalog.append_document(
                                    name, D.execution_document(
                                        description, parameters,
                                        extra=timing(
                                            {"queueWaitSeconds": round(
                                                queue_wait, 6),
                                             "attempt": attempt_no})))
                                obs_export.log_event(
                                    "job", "finished", trace_id=name,
                                    elapsedSeconds=round(
                                        time.monotonic() - start, 6))
                                return result
                            except preempt.JobCancelled as exc:
                                # deadline / DELETE / stall escalation
                                # fired at a cooperative check inside
                                # the job: terminal typed document,
                                # lease released by the CM. A
                                # checkpointed fit stays resumable — a
                                # PATCH re-run picks up at the latest
                                # checkpoint step.
                                record_cancel(exc, attempt_no, timing(
                                    {"queueWaitSeconds": round(
                                        queue_wait, 6)}))
                                return None
                            except Exception as exception:  # noqa: BLE001
                                traceback.print_exc()
                                kind = classify_error(exception)
                                if kind == PERMANENT:
                                    terminal = True
                                elif kind == NUMERICAL:
                                    terminal = (numerical_used >=
                                                self._numerical_retries)
                                else:
                                    terminal = (transient_failures + 1
                                                >= attempts)
                                extra = timing({"attempt": attempt_no,
                                                "errorKind": kind})
                                if kind == NUMERICAL:
                                    extra["numericalRetriesUsed"] = \
                                        numerical_used
                                if needs_mesh and self._pod_failure_fn():
                                    # a mesh job failing WHILE the pod
                                    # is degraded is a worker-loss
                                    # casualty (a collective erroring
                                    # out under it), not a code
                                    # failure — flag it so elastic
                                    # recovery requeues it on heal
                                    extra["workerLost"] = True
                                if terminal:
                                    # worker-lost jobs stay out of the
                                    # dead-letter state: the pod, not
                                    # the job, failed, and elastic /
                                    # boot recovery requeues them
                                    if not extra.get("workerLost"):
                                        extra[D.STATUS_FIELD] = \
                                            D.STATUS_DEAD_LETTERED
                                        extra["deadLettered"] = True
                                        self._count("deadLettered")
                                        if kind == PERMANENT and \
                                                max_retries > 0:
                                            extra["retriesSkipped"] = \
                                                "permanent error class"
                                        elif kind == NUMERICAL:
                                            extra["retriesSkipped"] = \
                                                ("numerical rollback-"
                                                 "retry budget "
                                                 "exhausted")
                                    doc = D.execution_document(
                                        description, parameters,
                                        exception=repr(exception),
                                        extra=extra)
                                    fail_all(doc)
                                    if not extra.get("workerLost"):
                                        self._set_status(
                                            name,
                                            D.STATUS_DEAD_LETTERED)
                                    self._record_attribution(
                                        name, footprint,
                                        measure_hbm=needs_mesh,
                                        token=token)
                                    obs_export.log_event(
                                        "job", "failed", trace_id=name,
                                        errorKind=kind,
                                        error=repr(exception))
                                    if not extra.get("workerLost"):
                                        obs_incidents.trigger(
                                            "job:deadLettered",
                                            job=name, errorKind=kind,
                                            error=repr(exception))
                                    # finished stays False (reference
                                    # parity)
                                    return None
                                backoff = self._backoff_seconds(
                                    attempt_no - 1)
                                extra["nextRetryInSeconds"] = round(
                                    backoff, 3)
                                self._catalog.append_document(
                                    name, D.execution_document(
                                        description, parameters,
                                        exception=repr(exception),
                                        extra=extra))
                                if kind == NUMERICAL:
                                    numerical_used += 1
                                    self._count("numericalRetries")
                                else:
                                    transient_failures += 1
                                    self._count("retries")
                                self._set_status(name, D.STATUS_QUEUED)
                                # cancel-aware sleep: a DELETE or the
                                # deadline interrupts the backoff and
                                # the next loop's token.check() records
                                # the terminal state
                                token.wait(backoff)
                    except preempt.JobCancelled as exc:
                        # cancelled before holding the lease (thread-
                        # pool queue, fair-queue wait, retry backoff)
                        record_cancel(exc, attempt_no, {
                            "elapsedSeconds": round(
                                time.monotonic() - submitted, 6),
                            "queuedOnly": True})
                        return None
            finally:
                job_span.__exit__(None, None, None)
                preempt.clear_cancel()

        with self._lock:
            existing = self._futures.get(name)
            if only_if_idle:
                # elastic-recovery guard vs a concurrent client PATCH:
                # the live-future check, the finished re-check and the
                # registration share one lock, so the same job can
                # never be double-submitted — and a job that FINISHED
                # between the caller's catalog read and this point is
                # not re-run either
                if existing is not None and not existing.done():
                    return existing
                meta = self._catalog.get_metadata(name)
                if meta is not None and meta.get(D.FINISHED_FIELD):
                    if existing is not None:
                        return existing
                    done_future: Future = Future()
                    done_future.set_result(None)
                    return done_future
            # status must be queued BEFORE the pool can start run()
            # (which flips it to running) — the reverse order could
            # overwrite running with queued
            self._set_status(name, D.STATUS_QUEUED)
            future = self._pool.submit(run)
            # prune finished entries so a long-lived server doesn't
            # leak a Future per job (results live in the catalog; wait()
            # on a pruned job returns immediately)
            done = [k for k, f in self._futures.items()
                    if f.done() and k != name]
            for k in done:
                del self._futures[k]
                self._job_info.pop(k, None)
                self._stalled.discard(k)
            self._futures[name] = future
            self._job_info[name] = {"description": description,
                                    "parameters": parameters,
                                    "needs_mesh": needs_mesh,
                                    "footprint": footprint,
                                    "token": token}
        obs_export.log_event("job", "queued", trace_id=name, pool=pool)
        return future

    # ------------------------------------------------------------------
    def cancel(self, name: str, reason: str = D.STATUS_CANCELLED) -> bool:
        """Request cooperative cancellation of job ``name`` (the
        ``DELETE /{service}/{tool}/{name}/run`` backend). A job still
        queued in the thread pool is cancelled outright (with its
        terminal document written here, since ``run`` never executes);
        a running job's token is flipped and the job records its own
        terminal state at the next cooperative check. Returns False
        when no live job exists under that name."""
        with self._lock:
            future = self._futures.get(name)
            info = self._job_info.get(name)
        if future is None or info is None or future.done():
            return False
        token: preempt.CancelToken = info["token"]
        if future.cancel():
            token.cancel(reason)
            try:
                self._catalog.append_document(
                    name, D.execution_document(
                        info.get("description", ""),
                        info.get("parameters"),
                        exception=f"JobCancelled({reason!r}: cancelled "
                                  f"before the job started)",
                        extra={D.STATUS_FIELD: reason,
                               "cancelReason": reason,
                               "attempt": 0, "queuedOnly": True}))
            except Exception:  # noqa: BLE001 — collection may be gone
                pass
            self._set_status(name, reason)
            self._count_cancel(reason)
            return True
        token.cancel(reason)
        return True

    # ------------------------------------------------------------------
    def migrate(self, name: str, reason: str = "migrate") -> bool:
        """Request live migration of mesh job ``name`` to a fresh
        slice placement (the ``POST .../{name}/migrate`` backend).
        Cooperative: the engine honors it at its next epoch boundary
        — snapshot, release, re-acquire, restore (docs/SCALING.md §7).
        Returns False when no live migratable mesh job exists under
        that name."""
        return self._migration.request(name, reason)

    def request_resize(self, name: str, want: int,
                       reason: str = "autoscale") -> bool:
        """Latch an elastic resize on mesh job ``name`` (the
        autoscaler's backend, services/autoscaler.py): the engine's
        next epoch boundary re-acquires a ``want``-device slice
        through the migrate path, rolling back to the old footprint
        on failure. Returns False when no live elastic job exists
        under that name, ``want`` violates its declared bounds, or a
        placement change is already in flight."""
        return self._migration.request_resize(name, want, reason)

    @property
    def migration(self):
        """The shared MigrationCoordinator — the autoscaler reads its
        ``elastic_jobs()`` candidate set and latches resizes through
        the same serialization as defrag picks."""
        return self._migration

    def migration_stats(self) -> Dict[str, int]:
        """Monotonic migration counters (requested/refused/defrag)."""
        return self._migration.stats()

    # ------------------------------------------------------------------
    def _watch_stalls(self) -> None:
        """Stall watchdog (single-host mirror of the multi-host pod
        guard): a live job whose progress heartbeat
        (:func:`preempt.heartbeat`) went quiet for more than
        ``stall_seconds`` is marked ``stalled`` in its metadata and —
        when escalation is enabled — cancelled through its token.
        Jobs that never beat (sklearn fits, ingests, functions) are
        exempt; only a job that WAS reporting progress and stopped is
        suspect. Heartbeat progress (step/epoch) is also published to
        the metadata document here, throttled to the watch interval."""
        interval = min(max(self._stall_seconds / 4.0, 0.05), 5.0)
        while not self._watchdog_stop.wait(interval):
            with self._lock:
                live = [(k, v["token"]) for k, v in
                        self._job_info.items()
                        if k in self._futures and
                        not self._futures[k].done()]
            for name, token in live:
                age = token.heartbeat_age()
                if age is None:
                    continue
                progress = token.progress_snapshot()
                if progress:
                    try:
                        self._catalog.update_metadata(
                            name, {D.PROGRESS_FIELD: dict(
                                progress,
                                heartbeatAgeSeconds=round(age, 3))})
                    except Exception:  # noqa: BLE001
                        pass
                if token.cancelled():
                    continue
                if age > self._stall_seconds:
                    with self._lock:
                        newly = name not in self._stalled
                        self._stalled.add(name)
                    if newly:
                        self._set_status(name, D.STATUS_STALLED)
                        self._count("stalledSeen")
                        obs_incidents.trigger(
                            "job:stalled", job=name,
                            heartbeatAgeSeconds=round(age, 3))
                        if self._stall_escalate and _single_host():
                            token.cancel(D.STATUS_STALLED)
                else:
                    with self._lock:
                        was = name in self._stalled
                        self._stalled.discard(name)
                    if was:
                        # heartbeats resumed (a long compile, not a
                        # wedge): un-flag, same as the pod guard's
                        # heal path
                        self._set_status(name, D.STATUS_RUNNING)

    # ------------------------------------------------------------------
    def fail_running_mesh_jobs(self, reason: str) -> int:
        """Append a terminal ``WorkerLost`` execution document to every
        in-flight mesh job (their threads are stuck in collectives a
        dead worker will never join — clients polling the documents
        must see a typed failure, not silence). Returns the count."""
        with self._lock:
            stuck = [(k, v) for k, v in self._job_info.items()
                     if v.get("needs_mesh") and k in self._futures
                     and not self._futures[k].done()]
        for name, info in stuck:
            self._catalog.append_document(
                name, D.execution_document(
                    info["description"], info["parameters"],
                    exception=f"WorkerLost({reason!r})",
                    extra={"workerLost": True}))
        return len(stuck)

    def resubmit(self, name: str, fn: Callable[[], Any],
                 **kwargs: Any) -> Future:
        """The PATCH verb: reset ``finished`` and re-run (reference
        Execution.update, binary_execution.py:136-145)."""
        self._catalog.update_metadata(name, {D.FINISHED_FIELD: False})
        return self.submit(name, fn, **kwargs)

    # ------------------------------------------------------------------
    def wait(self, name: str, timeout: Optional[float] = None) -> Any:
        """Block until job ``name`` completes (test/CLI convenience —
        REST clients poll the ``finished`` flag instead)."""
        with self._lock:
            future = self._futures.get(name)
        if future is None:
            return None
        return future.result(timeout=timeout)

    def running(self) -> int:
        with self._lock:
            return sum(1 for f in self._futures.values() if not f.done())

    def active_job(self) -> Optional[str]:
        """Name (= trace id) of one live job, for alert↔trace
        correlation; None when idle."""
        with self._lock:
            for name, future in self._futures.items():
                if not future.done():
                    return name
        return None

    def shutdown(self, cancel_futures: bool = True) -> None:
        self._watchdog_stop.set()
        self._pool.shutdown(wait=False, cancel_futures=cancel_futures)
        if not cancel_futures:
            return
        # queued jobs the pool dropped would otherwise be silent
        # finished=False orphans: record a terminal shutdownAborted
        # document (requeueable executions/functions are picked up by
        # the next boot's recover_unfinished)
        with self._lock:
            aborted = [(k, self._job_info.get(k) or {})
                       for k, f in self._futures.items()
                       if f.cancelled()]
        for name, info in aborted:
            try:
                self._catalog.append_document(
                    name, D.execution_document(
                        info.get("description", ""),
                        info.get("parameters"),
                        exception="ShutdownAborted('server shut down "
                                  "before this queued job started')",
                        extra={D.STATUS_FIELD: D.STATUS_SHUTDOWN_ABORTED,
                               "shutdownAborted": True}))
                self._set_status(name, D.STATUS_SHUTDOWN_ABORTED)
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
