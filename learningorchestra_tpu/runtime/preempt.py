"""Cooperative preemption + cancellation hooks for long device jobs.

The reference gives each Spark service its own FAIR scheduler pool so
a long job cannot monopolize the cluster
(reference spark_image/fairscheduler.xml:1-8, builder_image
server.py:57-63). The TPU analogue: the mesh is an exclusive lease
(services/scheduler.FairLease), and long engine fits offer to YIELD
the lease at epoch boundaries — per-epoch checkpoints make the
hand-off durable, and since all jobs share one process the model
state stays live in memory across the yield.

The engine can't import the services layer (layering), so the lease
installs a thread-local callback here and the engine's epoch loops
call :func:`maybe_yield` between epochs. No lease installed (direct
library use, tests, workers) → no-op.

The SAME yield points double as cancellation points: the job manager
installs a :class:`CancelToken` per job thread and the engine's
epoch/step loops call :func:`check_cancel` / :func:`heartbeat` — so a
deadline expiry or a ``DELETE .../run`` surfaces as
:class:`JobCancelled` at the next safe boundary, the lease is
released, and no single request can wedge the accelerator
(docs/LIFECYCLE.md).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Optional
from learningorchestra_tpu.runtime import locks

_tls = threading.local()


class JobCancelled(Exception):
    """Cooperative cancellation signal. ``reason`` is the terminal
    lifecycle state it produces: ``"timedOut"`` (deadline expired),
    ``"cancelled"`` (user DELETE), or ``"stalled"`` (watchdog
    escalation). Raised from :meth:`CancelToken.check` at the engine /
    sandbox / scheduler yield points, caught by the job manager."""

    def __init__(self, reason: str, message: str = ""):
        super().__init__(message or f"job {reason}")
        self.reason = reason


class CancelToken:
    """Per-job cancellation + progress record.

    - ``cancel(reason)`` flips a latched event (first reason wins:
      a user cancel that races the deadline keeps its attribution);
    - ``deadline`` (``time.monotonic`` basis) is checked lazily on
      every :meth:`cancelled` call, so an expired job cancels itself
      at its next cooperative check with no timer thread per job;
    - ``beat(**progress)`` publishes a heartbeat (step/epoch
      counters) the stall watchdog reads via :meth:`heartbeat_age`.
    """

    def __init__(self, deadline: Optional[float] = None):
        self._event = threading.Event()
        self._lock = locks.make_lock("preempt.token")
        self.deadline = deadline
        self.reason: Optional[str] = None
        self.progress: Dict[str, Any] = {}
        self.last_beat: Optional[float] = None
        self.started: Optional[float] = None
        # -- live migration (services/migration.py) --------------------
        # latched until the engine consumes it at a step boundary
        self.migrate_pending: Optional[str] = None
        self.migrations: int = 0
        # stamped by the slice lease at grant time: the job's current
        # device indices (None = whole mesh) and whether a migrate
        # request makes sense for it (sliced, single-host)
        self.slice_devices: Optional[tuple] = None
        self.migratable: bool = False
        # -- elastic resize (services/autoscaler.py) -------------------
        # declared (min, max) device bounds when the job's footprint
        # is elastic; ``resize_want`` rides the migrate latch to the
        # scheduler's migrate point, ``resize_inflight`` serializes
        # placement changes (one per job) until the engine reports the
        # outcome via :meth:`resize_done`
        self.elastic: Optional[tuple] = None
        self.resize_want: Optional[int] = None
        self.resize_inflight: bool = False
        self.resizes: int = 0
        self.resize_rollbacks: int = 0
        self.last_resize_error: Optional[str] = None
        # placement timeline (grants, resizes, rollbacks) — surfaced
        # as the job's ``sliceHistory`` metadata
        self.slice_history: list = []

    # -- migration signal ----------------------------------------------
    def request_migrate(self, reason: str = "migrate") -> bool:
        """Latch a cooperative migrate request. Returns False when the
        job is already cancelled (nothing to migrate) or a request is
        already pending (idempotent)."""
        with self._lock:
            if self.reason is not None or self._event.is_set():
                return False
            if self.migrate_pending is not None:
                return False
            self.migrate_pending = reason
            return True

    def consume_migrate(self) -> Optional[str]:
        """Take the pending request (engine, at a step boundary)."""
        with self._lock:
            reason, self.migrate_pending = self.migrate_pending, None
            return reason

    # -- elastic resize signal -----------------------------------------
    def request_resize(self, want: int, reason: str = "autoscale",
                       ) -> bool:
        """Latch a resize-via-migration request: the engine's next
        epoch boundary releases the slice and re-acquires ``want``
        devices. Refused (False) when the job is cancelled, another
        migrate/resize is already in flight (one placement change per
        job — a racing defrag or second resize coalesces), or ``want``
        violates the declared elastic bounds (the scheduler never sees
        a below-``min`` or above-``max`` target)."""
        with self._lock:
            if self.reason is not None or self._event.is_set():
                return False
            if self.migrate_pending is not None or self.resize_inflight:
                return False
            if self.elastic is not None:
                lo, hi = self.elastic
                if not lo <= int(want) <= hi:
                    return False
            self.resize_want = int(want)
            self.resize_inflight = True
            self.migrate_pending = f"resize:{reason}"
            return True

    def resize_done(self, ok: bool, devices=None,
                    error: Optional[str] = None) -> None:
        """Engine reports a consumed resize's outcome (state re-placed
        on the new slice, or rolled back to an old-size one). Clears
        the in-flight latch so the autoscaler may request again."""
        with self._lock:
            self.resize_want = None
            self.resize_inflight = False
            if self.migrate_pending is not None \
                    and self.migrate_pending.startswith("resize:"):
                # outcome reported before the engine consumed the
                # latch (request refused downstream): drop it so the
                # next placement change isn't wedged
                self.migrate_pending = None
            if ok:
                self.resizes += 1
            else:
                self.resize_rollbacks += 1
                self.last_resize_error = error
            entry: Dict[str, Any] = {
                "event": "resize" if ok else "rollback",
                "devices": (list(devices)
                            if devices is not None else None),
                "wallTime": time.time()}
            if error:
                entry["error"] = error
            self.slice_history.append(entry)

    def record_placement(self, event: str, devices) -> None:
        """Append a placement event (grant/migrate) to the job's
        ``sliceHistory`` timeline."""
        with self._lock:
            self.slice_history.append({
                "event": event,
                "devices": (list(devices)
                            if devices is not None else None),
                "wallTime": time.time()})

    # -- cancellation --------------------------------------------------
    def cancel(self, reason: str = "cancelled") -> bool:
        """Latch the token. Returns True if this call set the reason
        (False when already cancelled — the original reason stands)."""
        with self._lock:
            if self.reason is None:
                self.reason = reason
                self._event.set()
                return True
            return False

    def cancelled(self) -> bool:
        if self._event.is_set():
            return True
        if self.deadline is not None and \
                time.monotonic() >= self.deadline:
            self.cancel("timedOut")
            return True
        return False

    def check(self) -> None:
        if self.cancelled():
            raise JobCancelled(self.reason or "cancelled")

    def wait(self, seconds: float) -> bool:
        """Cancel-aware sleep (retry backoff): returns True the moment
        the token cancels, False after the full wait. Deadline-based
        expiry is honored too — the wait is clipped so a backoff never
        outsleeps the job's own deadline."""
        end = time.monotonic() + max(0.0, seconds)
        while True:
            if self.cancelled():
                return True
            now = time.monotonic()
            if now >= end:
                return False
            step = end - now
            if self.deadline is not None:
                step = min(step, max(0.0, self.deadline - now))
            if self._event.wait(min(step, 0.5) or 0.001):
                return True

    def remaining(self) -> Optional[float]:
        if self.deadline is None:
            return None
        return max(0.0, self.deadline - time.monotonic())

    # -- progress heartbeat --------------------------------------------
    def beat(self, **progress: Any) -> None:
        with self._lock:
            self.last_beat = time.monotonic()
            self.progress.update(progress)

    def heartbeat_age(self) -> Optional[float]:
        """Seconds since the last beat; None before the first beat
        (jobs that never publish progress — sklearn fits, ingests —
        are exempt from stall detection)."""
        last = self.last_beat
        return None if last is None else time.monotonic() - last

    def progress_snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self.progress)


# ----------------------------------------------------------------------
# thread-local install points (yield + cancel are separate slots: the
# lease CM owns the yield slot, the job manager owns the cancel slot)
# ----------------------------------------------------------------------
def install(fn: Callable[[], None],
            contended_fn: Optional[Callable[[], bool]] = None) -> None:
    """Register ``fn`` as this thread's between-epochs yield point
    (called by the mesh lease when a job thread acquires it).
    ``contended_fn`` lets long jobs ASK whether a yield is wanted
    without performing one — sweeps use it to drain in-flight trials
    before handing the lease over."""
    _tls.fn = fn
    _tls.contended = contended_fn


def clear() -> None:
    _tls.fn = None
    _tls.contended = None
    _tls.migrate = None


def current() -> Optional[Callable[[], None]]:
    return getattr(_tls, "fn", None)


def contended() -> bool:
    """True when another job is waiting for this thread's lease (a
    yield at the next safe point would hand it over). Always False
    outside the service layer."""
    fn = getattr(_tls, "contended", None)
    return bool(fn()) if fn is not None else False


def install_migrate(fn: Optional[Callable[[], Any]]) -> None:
    """Register this thread's migrate point (the slice lease CM):
    ``fn()`` releases the held slice, re-acquires a fresh placement
    through the fair queue, and returns the new grant's device
    indices (or None for a whole-mesh grant)."""
    _tls.migrate = fn


def migrate_requested() -> bool:
    """Peek (don't consume): does this thread's job have a pending
    migrate request AND a way to perform one?"""
    token = current_cancel()
    return (token is not None
            and token.migrate_pending is not None
            and getattr(_tls, "migrate", None) is not None)


def perform_migrate():
    """Consume the pending request and run the installed migrate
    point. Returns ``(performed, new_devices)`` — ``(False, None)``
    when there was nothing to do. Called by the ENGINE after it has
    snapshotted state off the devices (runtime/engine.py). A pending
    elastic resize threads its device-count target through to the
    migrate point, which re-acquires at the new size."""
    token = current_cancel()
    fn = getattr(_tls, "migrate", None)
    if token is None or fn is None:
        return False, None
    if token.consume_migrate() is None:
        return False, None
    want = token.resize_want
    if want is not None:
        return True, fn(want)
    return True, fn()


def migrate_fn():
    """The raw installed migrate point, if any. The engine's resize
    ROLLBACK path calls it directly with the old device count after a
    failed resize — no pending request needed."""
    return getattr(_tls, "migrate", None)


def snapshot():
    """(yield_fn, contended_fn, migrate_fn) for save/restore around
    nested installs (the lease CM restores its predecessor on exit)."""
    return (getattr(_tls, "fn", None),
            getattr(_tls, "contended", None),
            getattr(_tls, "migrate", None))


def restore(snap) -> None:
    # older 2-tuple snapshots (pre-migration callers) still restore
    if len(snap) == 2:
        _tls.fn, _tls.contended = snap
        _tls.migrate = None
    else:
        _tls.fn, _tls.contended, _tls.migrate = snap


def install_cancel(token: Optional[CancelToken]) -> None:
    """Bind ``token`` to this thread (job manager, around each job)."""
    _tls.cancel = token


def clear_cancel() -> None:
    _tls.cancel = None


def current_cancel() -> Optional[CancelToken]:
    return getattr(_tls, "cancel", None)


def check_cancel() -> None:
    """Raise :class:`JobCancelled` if this thread's job was cancelled
    or ran past its deadline. No token installed → no-op (direct
    library use, tests, workers)."""
    token = current_cancel()
    if token is not None:
        token.check()


def heartbeat(**progress: Any) -> None:
    """Publish step/epoch progress for the stall watchdog. No token
    installed → no-op."""
    token = current_cancel()
    if token is not None:
        token.beat(**progress)


def maybe_yield() -> None:
    """Engine epoch boundary: first honor any pending cancellation,
    then hand the mesh lease to a waiting job of another pool (if any)
    and re-acquire it through the fair queue."""
    check_cancel()
    fn = current()
    if fn is not None:
        fn()
