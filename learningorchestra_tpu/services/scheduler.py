"""Fair mesh scheduling with spatial slice multiplexing.

The reference runs every Spark service under a FAIR scheduler pool
(one ``<pool weight=1 minShare=2>`` per service, reference
spark_image/fairscheduler.xml:1-8, wired in builder_image
server.py:57-63) so concurrent Builder/Tune/Train requests share the
cluster instead of queuing behind each other. The round-4 rebuild had
a single FIFO ``BoundedSemaphore`` — one long train starved every
tune/evaluate behind it.

:class:`SliceLease` is the TPU-native replacement:

- **Pools** — each job class (``train``, ``tune``, ``evaluate``,
  ``predict``, …) is a pool. Grants go to the pool with the LOWEST
  served-time/weight among pools with waiters (weighted fair
  queuing), FIFO within a pool. A pool that has used the mesh least
  goes first, so a burst of tunes cannot starve a train and vice
  versa.
- **Device slices** (``LO_MESH_LEASES > 1``) — instead of N abstract
  leases timesharing the whole mesh, the scheduler packs concurrent
  jobs onto **disjoint contiguous device blocks** of the default
  mesh. A job declares a footprint (device count and/or HBM bytes,
  estimated by preflight); the allocator grants the first free
  contiguous block that fits (first-fit over the device index line —
  deterministic, so identical repeat jobs land on identical slices
  and executable/arena cache keys keep hitting). Jobs without a
  footprint **gang-acquire** the full mesh.
- **Aging anti-starvation** — a gang (or large) waiter blocked at the
  head of its pool permits smaller jobs to backfill free devices
  behind it, but only until it has waited ``aging_seconds``
  (``LO_SLICE_AGING``); after that, backfill freezes so releases
  drain devices toward the starved job. ``0`` disables the freeze.
- **Epoch-boundary preemption** — a granted lease installs a
  thread-local yield point (:mod:`runtime.preempt`); the engine's
  epoch loops call it between epochs. If ANOTHER pool is waiting, the
  holder releases, the waiter runs, and the holder re-queues through
  the same fair policy, re-acquiring its EXACT device block (its
  arrays still live there). Per-epoch checkpoints plus
  in-process state make the hand-off safe and nearly free.
- **Weights** — ``LO_POOL_WEIGHTS="train=2,tune=1"`` biases the
  fair-share ratio (fairscheduler.xml ``weight`` parity); unlisted
  pools weigh 1.

With the default ``LO_MESH_LEASES=1`` the device plane is never
resolved (no jax import) and the lease degrades to exactly the
single-holder weighted-fair queue that predates slicing.

Caveats (when preemption does NOT apply):

- **Multi-host pods** never yield: every host must replay the same
  collectives in the same order, and only the coordinator sees the
  lease — a coordinator-side yield would diverge the SPMD program
  and hang the pod. Single-host only.
- A preempted job's device state stays resident in HBM while the
  preemptor runs, so two jobs whose combined footprint exceeds HBM
  can OOM where strict serialization would not. Set
  ``LO_MESH_YIELD=0`` to disable epoch yielding (the lease then
  degrades to the strict FIFO-fair queue with no mid-job hand-off).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, Iterator, Optional, Sequence, Tuple

from learningorchestra_tpu.runtime import preempt
from learningorchestra_tpu.runtime import locks


def parse_pool_weights(spec: str) -> Dict[str, float]:
    """``"train=2,tune=1"`` -> ``{"train": 2.0, "tune": 1.0}``."""
    weights: Dict[str, float] = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, _, value = part.partition("=")
        try:
            weights[name.strip()] = float(value)
        except ValueError as exc:
            raise ValueError(
                f"bad pool weight {part!r} (want name=number)") from exc
    return weights


# _fit_locked sentinel: "this waiter cannot be granted right now"
# (``None`` is a real grant value — the full mesh)
_NOFIT = object()


class GrantTimeout(Exception):
    """A bounded :meth:`SliceLease.acquire` expired before a grant.
    Only raised when ``timeout=`` was passed — the elastic resize path
    uses it so a lease race (the freed devices got claimed) rolls the
    job back to an old-size slice instead of wedging the fit."""


class Grant:
    """A claimed (or reserved) allocation: ``devices`` is a tuple of
    indices into the default mesh's flat device order, or ``None``
    for the whole mesh (counting mode and gang grants)."""

    __slots__ = ("seq", "pool", "devices", "wait_seconds")

    def __init__(self, seq: int, pool: str,
                 devices: Optional[Tuple[int, ...]]):
        self.seq = seq
        self.pool = pool
        self.devices = devices
        self.wait_seconds = 0.0


class _Waiter:
    __slots__ = ("seq", "pool", "want", "exact", "enqueued")

    def __init__(self, seq: int, pool: str, want: Optional[int],
                 exact: Optional[Tuple[int, ...]], enqueued: float):
        self.seq = seq
        self.pool = pool
        self.want = want          # device count; None = full mesh
        self.exact = exact        # exact indices (post-yield re-acquire)
        self.enqueued = enqueued


class SliceLease:
    """Weighted-fair device lease: capacity ``leases`` concurrent
    holders, packed onto disjoint device slices when ``leases > 1``."""

    def __init__(self, leases: int = 1,
                 weights: Optional[Dict[str, float]] = None,
                 total_devices: Optional[int] = None,
                 min_devices: int = 1,
                 aging_seconds: float = 30.0,
                 device_bytes: Optional[int] = None,
                 served_half_life_seconds: float = 600.0):
        self._capacity = max(1, int(leases))
        self._weights = dict(weights or {})
        self._cv = locks.make_condition("scheduler.fair")
        # pool -> held mesh-seconds, exponentially decayed with the
        # half-life below so fair-share order reflects RECENT usage: a
        # pool that burned the mesh last week starts even, not in debt
        # forever (0 = no decay — all-time totals, the old behavior)
        self._served: Dict[str, float] = {}
        self._served_half_life = max(
            0.0, float(served_half_life_seconds or 0.0))
        self._served_decayed_at = time.monotonic()
        self._waiters: list = []              # [_Waiter] arrival order
        self._granted: Dict[int, Grant] = {}  # reserved, not yet claimed
        self._holders: Dict[int, Grant] = {}  # claimed
        self._seq = 0
        # device plane: injectable for tests; resolved lazily from the
        # default mesh otherwise (and never at all in counting mode)
        self._total = int(total_devices) if total_devices else None
        self._free: Optional[set] = None
        self._min_devices = max(1, int(min_devices or 1))
        self._aging = max(0.0, float(aging_seconds or 0.0))
        self._device_bytes = (int(device_bytes)
                              if device_bytes is not None else None)
        # observability (served by Api /metrics)
        self._grants_by_pool: Dict[str, int] = {}
        self._wait_sum = 0.0
        self._wait_count = 0
        self._wait_max = 0.0
        # defrag-via-migration policy (LO_SLICE_DEFRAG, armed by the
        # job manager when a MigrationCoordinator exists)
        self._defrag_cb = None
        self._defrag_threshold = 1.0
        self._defrags = 0

    # -- policy --------------------------------------------------------
    @property
    def _sliced(self) -> bool:
        return self._capacity > 1

    @property
    def capacity(self) -> int:
        """Concurrent-holder capacity (``leases``). Disaggregated
        serving consults this at session create: a prefill/decode
        lease split only makes sense when TWO grants can be live at
        once — at capacity 1 the workers would ping-pong one grant
        and serialize, so the session co-locates instead."""
        return self._capacity

    def _weight(self, pool: str) -> float:
        w = float(self._weights.get(pool, 1.0))
        return w if w > 0 else 1.0

    def _decay_served_locked(self) -> None:
        """With the lock held: lazily apply the exponential half-life
        to every pool's served seconds (no background thread — decay
        materializes whenever the totals are read or written)."""
        if not self._served_half_life:
            return
        now = time.monotonic()
        elapsed = now - self._served_decayed_at
        if elapsed <= 0.0:
            return
        self._served_decayed_at = now
        if not self._served:
            return
        factor = 0.5 ** (elapsed / self._served_half_life)
        for pool in list(self._served):
            decayed = self._served[pool] * factor
            if decayed < 1e-6:
                del self._served[pool]  # prune fully-forgotten pools
            else:
                self._served[pool] = decayed

    def _ensure_devices_locked(self) -> None:
        if self._total is None:
            from learningorchestra_tpu.runtime import mesh as mesh_lib

            self._total = max(1, int(mesh_lib.get_default_mesh().size))
        if self._free is None:
            self._free = set(range(self._total))

    def _per_device_bytes(self) -> Optional[int]:
        """HBM bytes per device, for footprints declared in bytes;
        None (e.g. CPU backends without memory_stats) degrades the
        bytes path to a conservative full-mesh request."""
        if self._device_bytes is None:
            try:
                import jax

                stats = jax.local_devices()[0].memory_stats() or {}
                self._device_bytes = int(stats.get("bytes_limit") or 0)
            except Exception:  # noqa: BLE001 — backend has no stats
                self._device_bytes = 0
        return self._device_bytes or None

    def _requested_devices(self, footprint: Optional[Dict[str, Any]],
                           ) -> Optional[int]:
        """Footprint -> device count (None = full mesh). Explicit
        ``devices`` wins; ``hbmBytes`` is converted through per-device
        HBM; an unconvertible footprint gang-acquires (conservative:
        never grant a slice the job may not fit on)."""
        if not isinstance(footprint, dict):
            return None
        want = footprint.get("devices")
        if want is None:
            hbm = footprint.get("hbmBytes")
            per = self._per_device_bytes() if hbm else None
            if not hbm or not per:
                return None
            want = -(-int(hbm) // per)  # ceil
        want = int(want)
        if want >= self._total:
            return None
        return max(self._min_devices, min(want, self._total))

    def _fit_locked(self, waiter: _Waiter):
        """Devices for ``waiter`` right now, or ``_NOFIT``. Counting
        mode always fits (capacity is the caller's guard). Slices are
        the FIRST free contiguous run of the device index line that
        holds the request — deterministic first-fit, so a repeated
        arrival pattern reproduces identical placements."""
        if not self._sliced:
            return None
        if waiter.exact is not None:
            if self._free.issuperset(waiter.exact):
                return waiter.exact
            return _NOFIT
        if waiter.want is None:
            # gang: the whole mesh, exclusively
            if len(self._free) == self._total:
                return None
            return _NOFIT
        run = start = 0
        for i in range(self._total):
            if i in self._free:
                if run == 0:
                    start = i
                run += 1
                if run >= waiter.want:
                    return tuple(range(start, start + waiter.want))
            else:
                run = 0
        return _NOFIT

    def _grant_next(self) -> None:
        """With the lock held: hand out free capacity/devices to the
        waiter of the most-deserving pool (min served/weight; FIFO
        inside a pool). A pool head that doesn't FIT is skipped so
        smaller jobs backfill around it — unless it has aged past
        ``aging_seconds``, which freezes all further grants until
        releases drain enough devices for it (anti-starvation)."""
        self._decay_served_locked()
        while self._waiters and \
                len(self._holders) + len(self._granted) < self._capacity:
            now = time.monotonic()
            aged = [w for w in self._waiters
                    if self._aging and now - w.enqueued >= self._aging]
            if aged:
                # starvation freeze: once ANY waiter has aged past the
                # bound, only the oldest aged waiter is eligible —
                # fair-share order would let fitting small jobs keep
                # leapfrogging it, so backfill stops until releases
                # drain enough devices for it
                heads = [min(aged, key=lambda w: w.seq)]
            else:
                heads = []
                seen: set = set()
                for w in self._waiters:
                    if w.pool not in seen:
                        seen.add(w.pool)
                        heads.append(w)
                heads.sort(key=lambda w: (
                    self._served.get(w.pool, 0.0) / self._weight(w.pool),
                    w.seq))
            progressed = False
            for w in heads:
                devices = self._fit_locked(w)
                if devices is not _NOFIT:
                    self._waiters.remove(w)
                    if self._sliced:
                        # a gang grant (devices None = whole mesh)
                        # reserves EVERY device — nothing may backfill
                        # under it
                        self._free.difference_update(
                            range(self._total) if devices is None
                            else devices)
                    self._granted[w.seq] = Grant(w.seq, w.pool, devices)
                    self._cv.notify_all()
                    progressed = True
                    break
            if not progressed:
                return

    def _return_devices(self, grant: Grant) -> None:
        if self._free is None:
            return
        self._free.update(range(self._total) if grant.devices is None
                          else grant.devices)

    def _fragmentation_locked(self) -> float:
        """0 = every free device is one grantable contiguous block,
        ->1 = free capacity exists but is shredded into unusable
        holes (same gauge :meth:`stats` reports)."""
        if not self._sliced or not self._free:
            return 0.0
        run = largest = 0
        for i in range(self._total):
            if i in self._free:
                run += 1
                largest = max(largest, run)
            else:
                run = 0
        return 1.0 - largest / len(self._free)

    def set_defrag_policy(self, callback,
                          threshold: float = 0.5) -> None:
        """Arm defrag-via-migration (``LO_SLICE_DEFRAG``):
        ``callback(want)`` fires from a blocked waiter's poll loop
        when the waiter cannot fit AND either the fragmentation gauge
        exceeds ``threshold`` or the waiter has aged past the
        anti-starvation bound. The callback (services/migration.py)
        asks the cheapest migratable holder to vacate its slice;
        ``None`` disarms."""
        with self._cv:
            self._defrag_cb = callback
            self._defrag_threshold = max(
                0.0, min(1.0, float(threshold)))

    def _maybe_defrag_locked(self, waiter: _Waiter,
                             last: float) -> float:
        """acquire()'s poll loop, lock held: fire the defrag policy
        for a waiter that still cannot fit. Throttled to ~1 Hz per
        waiter; the callback runs with the lock RELEASED (it walks
        the job table and the holder it signals will re-enter this
        scheduler to release + re-queue). Returns the updated
        last-fired timestamp."""
        cb = self._defrag_cb
        if cb is None or not self._sliced or self._free is None:
            return last
        now = time.monotonic()
        if now - last < 1.0:
            return last
        if self._fit_locked(waiter) is not _NOFIT:
            return last
        aged = bool(self._aging) and \
            now - waiter.enqueued >= self._aging
        if not aged and \
                self._fragmentation_locked() < self._defrag_threshold:
            return last
        self._defrags += 1
        self._cv.release()
        try:
            cb(waiter.want)
        except Exception:  # noqa: BLE001 — defrag is best-effort
            pass
        finally:
            self._cv.acquire()
        return now

    # -- mechanics -----------------------------------------------------
    def acquire(self, pool: str = "default",
                cancel: Optional["preempt.CancelToken"] = None,
                footprint: Optional[Dict[str, Any]] = None,
                exact: Optional[Sequence[int]] = None,
                timeout: Optional[float] = None) -> Grant:
        """Block until granted; returns the :class:`Grant` (``devices``
        None = full mesh). With a ``cancel`` token the wait is
        cooperative: a cancelled/expired job raises
        :class:`preempt.JobCancelled` from the QUEUE — it never takes
        a lease it can no longer use, and a grant (with its device
        reservation) that races the cancellation is handed back to the
        next waiter. ``exact`` re-acquires a specific device block
        (post-yield: the job's arrays still live on it). ``timeout``
        bounds the wait: past it the waiter is withdrawn and
        :class:`GrantTimeout` raised (the elastic resize path — a
        grant that never comes must not wedge the job)."""
        t0 = time.monotonic()
        deadline = None if timeout is None else t0 + float(timeout)
        with self._cv:
            if self._sliced:
                self._ensure_devices_locked()
            seq = self._seq
            self._seq += 1
            if not self._sliced:
                want, exact_t = None, None
            elif exact is not None:
                want, exact_t = None, tuple(int(i) for i in exact)
            else:
                want, exact_t = self._requested_devices(footprint), None
            waiter = _Waiter(seq, pool, want, exact_t, t0)
            self._waiters.append(waiter)
            self._grant_next()
            last_defrag = 0.0
            while seq not in self._granted:
                self._cv.wait(0.1 if cancel is not None
                              or deadline is not None else None)
                if cancel is not None and cancel.cancelled():
                    grant = self._granted.pop(seq, None)
                    if grant is not None:
                        self._return_devices(grant)
                    elif waiter in self._waiters:
                        # releasing a blocked (possibly aged) waiter
                        # can unfreeze backfill for everyone behind it
                        self._waiters.remove(waiter)
                    self._grant_next()
                    raise preempt.JobCancelled(
                        cancel.reason or "cancelled",
                        "cancelled while waiting for the mesh lease")
                if seq in self._granted:
                    break
                if deadline is not None and \
                        time.monotonic() >= deadline:
                    if waiter in self._waiters:
                        self._waiters.remove(waiter)
                    self._grant_next()
                    raise GrantTimeout(
                        f"no {want or 'gang'}-device grant within "
                        f"{timeout}s (pool {pool})")
                last_defrag = self._maybe_defrag_locked(
                    waiter, last_defrag)
            grant = self._granted.pop(seq)
            self._holders[seq] = grant
            grant.wait_seconds = time.monotonic() - t0
            self._wait_sum += grant.wait_seconds
            self._wait_count += 1
            self._wait_max = max(self._wait_max, grant.wait_seconds)
            self._grants_by_pool[pool] = \
                self._grants_by_pool.get(pool, 0) + 1
            # every grant (job gang/slice AND serving lease) feeds the
            # lease-wait histogram here — the one authoritative site
            from learningorchestra_tpu.observability import hist

            hist.observe("lo_lease_wait_seconds", grant.wait_seconds)
            return grant

    def release(self, pool: str, held_seconds: float,
                grant: Optional[Grant] = None) -> None:
        with self._cv:
            if grant is not None:
                self._holders.pop(grant.seq, None)
                self._return_devices(grant)
            elif self._holders:
                # legacy (pool, seconds) surface: drop this pool's
                # oldest holder (counting mode has no devices anyway)
                seq = next((s for s in sorted(self._holders)
                            if self._holders[s].pool == pool),
                           min(self._holders))
                self._return_devices(self._holders.pop(seq))
            self._decay_served_locked()
            self._served[pool] = self._served.get(pool, 0.0) \
                + max(0.0, held_seconds)
            self._grant_next()

    def contended(self) -> bool:
        with self._cv:
            return bool(self._waiters)

    def contended_by_other(self, pool: str) -> bool:
        """A waiter from a DIFFERENT pool exists — the only condition
        under which a holder should yield (same-pool waiters are
        served FIFO when the holder finishes). Waiters still queued
        are exactly the currently-ungrantable ones: ``_grant_next``
        runs at every state change."""
        with self._cv:
            return any(w.pool != pool for w in self._waiters)

    def total_devices(self) -> int:
        """Mesh device count (lazily resolved from the default mesh).
        Disaggregated serving consults this to carve prefill/decode
        footprints into DISJOINT sub-slices: a ``footprint=None``
        grant is a full-mesh gang, and two gangs can never be live
        at once in sliced mode."""
        with self._cv:
            self._ensure_devices_locked()
            return int(self._total)

    def contended(self) -> bool:
        """ANY waiter is queued (waiters still queued are exactly the
        currently-ungrantable ones — ``_grant_next`` runs at every
        state change). Long-lived holders (serving sessions) yield on
        this broader condition: unlike a batch job, a serving session
        never finishes, so a same-pool waiter behind it — another
        serving session — would starve forever under the
        same-pool-FIFO rule of :meth:`contended_by_other`."""
        with self._cv:
            return bool(self._waiters)

    def served(self) -> Dict[str, float]:
        """Per-pool recent mesh seconds (observability) — decayed by
        ``served_half_life_seconds``, so this is a leaky integral of
        usage, not an all-time total."""
        with self._cv:
            self._decay_served_locked()
            return dict(self._served)

    def stats(self) -> Dict[str, Any]:
        """Scheduler observability: device occupancy, grant counts and
        lease-wait aggregates. In counting mode (``leases == 1``) the
        device plane is never resolved, so ``devicesBusy`` counts busy
        LEASES there (0 or 1) and ``devicesTotal`` is None."""
        with self._cv:
            busy = len(self._holders) + len(self._granted)
            free_n = largest = 0
            fragmentation = 0.0
            if self._sliced and self._free is not None:
                busy = self._total - len(self._free)
                free_n = len(self._free)
                run = 0
                for i in range(self._total):
                    if i in self._free:
                        run += 1
                        largest = max(largest, run)
                    else:
                        run = 0
                # 1 - largest contiguous free run / free total: 0 =
                # all free devices are one grantable block, ->1 = free
                # capacity exists but is shredded into unusable holes
                if free_n:
                    fragmentation = round(1.0 - largest / free_n, 6)
            now = time.monotonic()
            aged = sum(1 for w in self._waiters if self._aging
                       and now - w.enqueued >= self._aging)
            oldest = max((now - w.enqueued for w in self._waiters),
                         default=0.0)
            return {
                "sliced": self._sliced,
                "capacity": self._capacity,
                "devicesTotal": self._total,
                "devicesBusy": busy,
                "devicesFree": free_n,
                "largestFreeRun": largest,
                "fragmentation": fragmentation,
                "waiters": len(self._waiters),
                "agedWaiters": aged,
                "oldestWaitSeconds": round(oldest, 6),
                "defrags": self._defrags,
                "grantsByPool": dict(self._grants_by_pool),
                "leaseWaitSum": self._wait_sum,
                "leaseWaitCount": self._wait_count,
                "leaseWaitMax": self._wait_max,
            }

    # -- job-facing surface --------------------------------------------
    @contextlib.contextmanager
    def lease(self, pool: str = "default",
              cancel: Optional["preempt.CancelToken"] = None,
              footprint: Optional[Dict[str, Any]] = None,
              ) -> Iterator["LeaseToken"]:
        """Hold the mesh (or a footprint-sized slice of it) fairly;
        installs the epoch-boundary yield point for the duration (so
        engine fits running on this thread hand the device to waiting
        pools between epochs). Yields a :class:`LeaseToken` whose
        ``devices`` is the granted slice (None = full mesh), whose
        ``wait_seconds`` is the queue wait, and whose
        ``preempted_seconds`` lets callers subtract hand-off idle time
        from a job's own runtime. With a ``cancel`` token, both the
        initial acquire and every post-yield re-acquire abort with
        :class:`preempt.JobCancelled` the moment the job is cancelled
        or past its deadline — a preempted-then-cancelled job never
        reclaims the device."""
        grant = self.acquire(pool, cancel, footprint=footprint)
        token = LeaseToken()
        token.devices = grant.devices
        token.wait_seconds = grant.wait_seconds
        current = [grant]
        start = [time.monotonic()]
        held = [True]
        # mutable footprint holder: a successful elastic resize
        # rewrites the size every later migrate/re-acquire uses
        fp = [dict(footprint) if isinstance(footprint, dict)
              else footprint]
        can_yield = _yield_enabled()
        if cancel is not None:
            # advertise migratability (services/migration.py reads
            # these to pick defrag candidates): a whole-mesh or
            # counting-mode grant has nowhere else to go
            cancel.slice_devices = grant.devices
            cancel.migratable = (can_yield and self._sliced
                                 and grant.devices is not None)
            elastic = (footprint or {}).get("elastic") \
                if isinstance(footprint, dict) else None
            if isinstance(elastic, dict) and cancel.migratable:
                cancel.elastic = (int(elastic["min"]),
                                  int(elastic["max"]))
            cancel.record_placement("grant", grant.devices)

        def yield_point() -> None:
            if not can_yield or not self.contended_by_other(pool):
                return
            self.release(pool, time.monotonic() - start[0],
                         grant=current[0])
            held[0] = False
            t_wait = time.monotonic()
            # re-acquire the SAME device block: the preempted job's
            # sharded arrays live on those devices
            current[0] = self.acquire(pool, cancel,
                                      exact=current[0].devices)
            held[0] = True
            start[0] = time.monotonic()
            token.preempted_seconds += start[0] - t_wait
            token.yields += 1

        def migrate_point(want: Optional[int] = None,
                          ) -> Optional[Tuple[int, ...]]:
            # unlike yield_point this re-acquire is NOT exact=: the
            # job ABANDONS its device block (starved waiters may claim
            # it) and comes back wherever the packer now fits the same
            # footprint. The engine has already snapshotted state off
            # the devices before preempt.perform_migrate() lands here.
            # ``want`` (elastic resize) re-acquires at a NEW device
            # count instead, under a bounded wait — a lease race rolls
            # back to an old-footprint slice, so the job always holds
            # a valid grant when this returns OR raises GrantTimeout.
            self.release(pool, time.monotonic() - start[0],
                         grant=current[0])
            held[0] = False
            t_wait = time.monotonic()
            timed_out: Optional[GrantTimeout] = None
            if want is None:
                new_grant = self.acquire(pool, cancel,
                                         footprint=fp[0])
            else:
                from learningorchestra_tpu.config import get_config

                new_fp = dict(fp[0]) if isinstance(fp[0], dict) else {}
                new_fp["devices"] = int(want)
                try:
                    new_grant = self.acquire(
                        pool, cancel, footprint=new_fp,
                        timeout=get_config().resize_grant_timeout)
                    fp[0] = new_fp
                except GrantTimeout as exc:
                    timed_out = exc
                    new_grant = self.acquire(pool, cancel,
                                             footprint=fp[0])
            current[0] = new_grant
            held[0] = True
            start[0] = time.monotonic()
            token.preempted_seconds += start[0] - t_wait
            token.migrations += 1
            token.devices = new_grant.devices
            if cancel is not None:
                cancel.slice_devices = new_grant.devices
                cancel.migrations += 1
            if timed_out is not None:
                raise timed_out
            return new_grant.devices

        previous = preempt.snapshot()
        preempt.install(
            yield_point,
            contended_fn=lambda: can_yield and
            self.contended_by_other(pool))
        preempt.install_migrate(migrate_point)
        try:
            yield token
        finally:
            preempt.restore(previous)
            if held[0]:
                self.release(pool, time.monotonic() - start[0],
                             grant=current[0])


class ServingLease:
    """Long-lived slice grant for a resident serving session
    (docs/SERVING.md). Batch jobs hold the mesh for the span of one
    ``lease()`` context; a serving session holds its slice for the
    session's LIFETIME — so it goes through the same
    :class:`SliceLease` allocator (pool ``"serving"``) and, under the
    default ``"preempt"`` policy, periodically offers the slice back:

    - between decode/micro-batch iterations (and on an idle tick) the
      session calls :meth:`maybe_yield`; if ANY other waiter exists —
      a batch job from another pool or another serving session — the
      session releases its grant and blockingly re-queues through the
      fair policy. Gang jobs need EVERY device free, so this is what
      guarantees a resident session can never deadlock a full-mesh
      batch job; yielding to same-pool waiters too is what lets
      multiple sessions time-share an oversubscribed mesh instead of
      the second ``create`` hanging forever behind a holder that
      never finishes.
    - the re-acquire is NOT ``exact=``: the session may come back on a
      different device block, so :meth:`maybe_yield` returns True and
      the session re-pins its params/caches for the new slice.

    ``"hold"`` disables yielding (a latency-critical session keeps its
    slice until deleted — operator opt-in, documented as able to
    starve gang jobs until teardown).
    """

    def __init__(self, slices: SliceLease, pool: str = "serving",
                 policy: str = "preempt",
                 footprint: Optional[Dict[str, Any]] = None,
                 role: str = ""):
        self._slices = slices
        self._pool = pool
        self._policy = policy if policy in ("preempt", "hold") \
            else "preempt"
        self._footprint = dict(footprint) if footprint else None
        # disaggregated serving: which worker holds this lease
        # ("prefill"/"decode"; "" = the whole fused session)
        self._role = str(role or "")
        self._grant: Optional[Grant] = None
        self._acquired = 0.0
        self._lock = locks.make_lock("scheduler.servinglease")
        self.yields = 0
        self.wait_seconds = 0.0

    @property
    def pool(self) -> str:
        return self._pool

    @property
    def policy(self) -> str:
        return self._policy

    @property
    def devices(self) -> Optional[Tuple[int, ...]]:
        """The currently-granted device slice (None = full mesh /
        counting mode), or None while yielded."""
        with self._lock:
            return self._grant.devices if self._grant else None

    def held(self) -> bool:
        with self._lock:
            return self._grant is not None

    def acquire(self, cancel: Optional["preempt.CancelToken"] = None,
                ) -> Optional[Tuple[int, ...]]:
        """Blockingly acquire the session's slice through the fair
        queue. Returns the granted device indices (None = full mesh)."""
        grant = self._slices.acquire(self._pool, cancel,
                                     footprint=self._footprint)
        with self._lock:
            self._grant = grant
            self._acquired = time.monotonic()
            self.wait_seconds += grant.wait_seconds
        return grant.devices

    def contended(self) -> bool:
        """Some other waiter wants devices this session is sitting
        on (any pool — including another serving session's)."""
        return self._slices.contended()

    def maybe_yield(self,
                    cancel: Optional["preempt.CancelToken"] = None,
                    ) -> bool:
        """Yield the slice to waiting batch jobs and re-acquire
        (``"preempt"`` policy only). Returns True when a hand-off
        actually happened — the caller must then treat its device
        placement as invalid and re-pin on :attr:`devices`."""
        if self._policy != "preempt":
            return False
        if not self._slices.contended():
            return False
        with self._lock:
            grant = self._grant
            if grant is None:
                return False
            self._slices.release(
                self._pool, time.monotonic() - self._acquired,
                grant=grant)
            self._grant = None
        # re-queue OUTSIDE the lock: the wait can be long (the batch
        # job runs to completion) and stats()/devices must stay
        # readable meanwhile
        grant = self._slices.acquire(self._pool, cancel,
                                     footprint=self._footprint)
        with self._lock:
            self._grant = grant
            self._acquired = time.monotonic()
            self.wait_seconds += grant.wait_seconds
            self.yields += 1
        return True

    def release(self) -> None:
        """Give the slice back for good (session teardown)."""
        with self._lock:
            grant = self._grant
            if grant is None:
                return
            self._grant = None
            held = time.monotonic() - self._acquired
        self._slices.release(self._pool, held, grant=grant)

    def refit(self, footprint: Optional[Dict[str, Any]]) -> None:
        """Swap the footprint and blockingly re-acquire on it.
        Disaggregated split serving uses this at session create: the
        decode lease shrinks from its full-mesh grant onto a
        sub-slice BEFORE params pin, leaving the rest of the device
        line free for the prefill worker's own grant."""
        with self._lock:
            grant = self._grant
            self._footprint = dict(footprint) if footprint else None
            self._grant = None
            held = time.monotonic() - self._acquired
        if grant is not None:
            self._slices.release(self._pool, held, grant=grant)
        grant = self._slices.acquire(self._pool,
                                     footprint=self._footprint)
        with self._lock:
            self._grant = grant
            self._acquired = time.monotonic()
            self.wait_seconds += grant.wait_seconds

    @property
    def role(self) -> str:
        return self._role

    def set_role(self, role: str) -> None:
        """Tag which disagg worker holds this lease (stats only)."""
        self._role = str(role or "")

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "pool": self._pool,
                "policy": self._policy,
                "role": self._role,
                "held": self._grant is not None,
                "devices": list(self._grant.devices)
                if self._grant is not None and
                self._grant.devices is not None else None,
                "yields": self.yields,
                "waitSeconds": round(self.wait_seconds, 6),
            }


# Backwards-compatible alias: the counting behavior of the historical
# FairLease is exactly SliceLease at leases=1.
FairLease = SliceLease


class LeaseToken:
    """Per-hold accounting: the granted device slice (None = full
    mesh), how long the grant took (queue wait), how long the holder
    sat preempted (lease handed to another pool) and how many
    hand-offs happened."""

    def __init__(self) -> None:
        self.preempted_seconds = 0.0
        self.yields = 0
        self.migrations = 0
        self.devices: Optional[Tuple[int, ...]] = None
        self.wait_seconds = 0.0


def _yield_enabled() -> bool:
    """Epoch-boundary yielding is single-host only (a multi-host pod
    must replay identical collectives in identical order on every
    host; a coordinator-side yield would diverge the SPMD program and
    hang the pod) and can be disabled outright with LO_MESH_YIELD=0
    (config ``mesh_yield``) for HBM-tight deployments."""
    from learningorchestra_tpu.config import get_config

    if not get_config().mesh_yield:
        return False
    try:
        from learningorchestra_tpu.runtime import distributed as dist

        if not dist.is_initialized():
            return True
        import jax

        return jax.process_count() <= 1
    except Exception:  # noqa: BLE001 — no runtime formed yet
        return True
