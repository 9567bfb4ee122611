"""Resident serving plane: continuous-batched LM decode and
shape-bucketed predict behind long-lived serving leases.

The batch path (``POST /model/train`` then poll) pays catalog writes,
job scheduling, artifact (re)loads and a mesh gang-acquire on EVERY
request. A serving session pays them ONCE: the fitted model stays
resident (params pinned in the HBM arena), the slice is held under a
``ServingLease`` (services/scheduler.py) that periodically yields to
batch gang jobs, and requests flow through an admission-controlled
bounded queue straight into compiled kernels.

Two session kinds (docs/SERVING.md):

- :class:`LMServingSession` — iteration-level continuous batching
  (Orca-style): a fixed-width slot cache decodes every in-flight
  request one token per step; requests join at any token boundary via
  a per-length prefill scattered into their slot and leave the moment
  they finish. Slot reuse never recompiles (the slot index is a traced
  argument), and each slot's token stream is bit-identical to decoding
  that request alone through ``LanguageModel.generate`` (tested).
- :class:`PagedLMServingSession` (``LO_SERVE_KV=paged``) — the same
  batcher over a shared HBM page pool instead of a fixed slot cache:
  per-stream block tables, page-granular admission with OOM-safe
  429s, refcounted prompt-prefix page reuse and weighted-fair
  per-tenant QoS over the page budget. Token streams stay
  bit-identical to the slot path (and to a solo decode).
- :class:`BucketServingSession` — shape-bucketed micro-batching for
  classifiers/estimators: a burst of n queued requests pads to the
  smallest precompiled bucket >= n and runs ONE ``predict`` call, so
  warm predicts never retrace and per-request latency is amortized.

Admission control: a full queue rejects with 429 (back off + retry), a
closed/tearing-down session with 503. p50/p99 latency per session is
exported through ``/metrics``.
"""

from __future__ import annotations

import collections
import re
import threading
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

import numpy as np

from learningorchestra_tpu.observability import export as obs_export
from learningorchestra_tpu.observability import hist as obs_hist
from learningorchestra_tpu.observability import incidents as obs_incidents
from learningorchestra_tpu.observability import perf as obs_perf
from learningorchestra_tpu.observability import trace as obs_trace
from learningorchestra_tpu.observability import xray as obs_xray
from learningorchestra_tpu.services import faults
from learningorchestra_tpu.services import validators as V
from learningorchestra_tpu.services.scheduler import ServingLease
from learningorchestra_tpu.runtime import health as health_lib
from learningorchestra_tpu.runtime import locks

_IDLE_TICK_SECONDS = 0.05  # lease-yield poll cadence when no traffic


class LatencyTracker:
    """Ring buffer of request latencies -> p50/p99 snapshot. Bounded
    (last 2048 requests) so a long-lived session's metrics reflect
    current behavior, not its lifetime average."""

    def __init__(self, maxlen: int = 2048):
        self._lat: Deque[float] = collections.deque(maxlen=maxlen)
        self._lock = locks.make_lock("serving.latency")
        self.count = 0

    def record(self, seconds: float) -> None:
        with self._lock:
            self._lat.append(seconds)
            self.count += 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            lat = sorted(self._lat)
            count = self.count
        if not lat:
            return {"count": 0, "p50Ms": 0.0, "p99Ms": 0.0}
        p50 = lat[int(0.50 * (len(lat) - 1))]
        p99 = lat[int(0.99 * (len(lat) - 1))]
        return {"count": count, "p50Ms": round(p50 * 1e3, 3),
                "p99Ms": round(p99 * 1e3, 3)}


class _Request:
    __slots__ = ("payload", "event", "result", "error", "queued_at",
                 "trace_id", "popped_at", "stages", "finished_at")

    def __init__(self, payload: Dict[str, Any]):
        self.payload = payload
        self.event = threading.Event()
        self.result: Optional[Dict[str, Any]] = None
        self.error: Optional[V.HttpError] = None
        self.queued_at = time.monotonic()
        # observability marks: the worker thread appends completed
        # (name, start, end, attrs) stage intervals; the client thread
        # replays them into a span tree after the response arrives
        self.trace_id = ""
        self.popped_at = 0.0
        self.stages: List[Any] = []
        self.finished_at = 0.0

    def finish(self, result: Dict[str, Any]) -> None:
        self.result = result
        self.finished_at = time.monotonic()
        self.event.set()

    def fail(self, error: V.HttpError) -> None:
        self.error = error
        self.finished_at = time.monotonic()
        self.event.set()


class _SessionBase:
    """Queue + worker-thread + lease skeleton shared by both session
    kinds. Subclasses implement :meth:`_serve_once` (drain some queued
    work, return True if anything was done)."""

    kind = "base"

    def __init__(self, name: str, ctx, lease: ServingLease):
        self.name = name
        self._ctx = ctx
        self._lease = lease
        self._queue: Deque[_Request] = collections.deque()
        self._depth = int(ctx.config.serve_queue_depth)
        self._cv = locks.make_condition("serving.session")
        self._closed = False
        self.latency = LatencyTracker()
        self.requests_total = 0
        self.rejected_total = 0
        self.created_at = time.monotonic()
        self._thread = threading.Thread(
            target=self._run, name=f"serving-{name}", daemon=True)

    def start(self) -> None:
        self._thread.start()

    # -- request side --------------------------------------------------
    def submit(self, payload: Dict[str, Any],
               timeout: Optional[float] = None) -> Dict[str, Any]:
        req = _Request(payload)
        with self._cv:
            if self._closed:
                raise V.HttpError(V.HTTP_UNAVAILABLE,
                                  f"serving session {self.name} is "
                                  f"shutting down")
            if len(self._queue) >= self._depth:
                self.rejected_total += 1
                raise V.HttpError(
                    V.HTTP_TOO_MANY_REQUESTS,
                    f"serving queue full ({self._depth} requests "
                    f"queued) — retry with backoff")
            self.requests_total += 1
            req.trace_id = f"serve/{self.name}/{self.requests_total}"
            self._queue.append(req)
            self._cv.notify_all()
        if timeout is None:
            # 0 = no gateway deadline configured -> wait indefinitely
            # (the client's socket timeout still bounds the call)
            timeout = self._ctx.config.request_timeout_seconds or None
        if not req.event.wait(timeout):
            self._trace_request(req, time.monotonic(), error="timeout")
            raise V.HttpError(V.HTTP_UNAVAILABLE,
                              f"request timed out after {timeout}s "
                              f"(session overloaded or preempted)")
        if req.error is not None:
            self._trace_request(req, time.monotonic(),
                                error=type(req.error).__name__)
            raise req.error
        now = time.monotonic()
        elapsed = now - req.queued_at
        self.latency.record(elapsed)
        obs_hist.observe("lo_serving_request_seconds", elapsed)
        self._trace_request(req, now)
        assert req.result is not None
        return req.result

    def _trace_request(self, req: _Request, end: float,
                       error: Optional[str] = None) -> None:
        """Retro-build the request's span tree (``admit → queueWait →
        stage… → respond``) under its own trace id. The batcher thread
        only knows stage boundaries after the fact, so it stashes
        (name, start, end, attrs) marks on the request and the client
        thread replays them here once the response lands."""
        try:
            attrs: Dict[str, Any] = {"model": self.name,
                                     "kind": self.kind}
            if error is not None:
                attrs["error"] = error
            root = obs_trace.add("request", req.trace_id,
                                 req.queued_at, end, **attrs)
            if root is None:
                return
            picked = req.popped_at or min(
                (s[1] for s in req.stages), default=end)
            obs_trace.add("queueWait", req.trace_id, req.queued_at,
                          min(picked, end), parent=root)
            for name, start, stop, st_attrs in req.stages:
                obs_trace.add(name, req.trace_id, start, stop,
                              parent=root, **st_attrs)
            if req.finished_at:
                obs_trace.add("respond", req.trace_id,
                              req.finished_at, end, parent=root)
        except Exception:  # noqa: BLE001 — observability is advisory
            pass

    # -- worker side ---------------------------------------------------
    def _run(self) -> None:
        while True:
            with self._cv:
                if self._closed:
                    break
                if not self._have_work():
                    self._cv.wait(timeout=_IDLE_TICK_SECONDS)
                    if self._closed:
                        break
            try:
                # yield the slice to waiting batch gang jobs between
                # iterations (and on every idle tick) — this is the
                # no-deadlock guarantee: a gang acquire needs EVERY
                # device free, and a preempt-policy session never
                # holds its grant across a contended boundary
                if self._lease.maybe_yield():
                    self._on_reacquired()
                if self._have_work():
                    # chaos site (latency mode inflates request
                    # latency for the SLO watchdog's servingP99
                    # alert); gated on queued work so idle ticks
                    # don't burn a count-budgeted fault spec
                    faults.maybe_inject("serving_step")
                self._serve_once()
            except Exception as exc:  # noqa: BLE001 — fail requests, not the thread
                self._fail_all(V.HttpError(
                    V.HTTP_UNAVAILABLE, f"serving step failed: {exc}"))

    def _have_work(self) -> bool:
        return bool(self._queue)

    def _serve_once(self) -> bool:
        raise NotImplementedError

    def _on_reacquired(self) -> None:
        """Hook after a lease yield/re-acquire cycle (re-pin params)."""

    def _fail_all(self, error: V.HttpError) -> None:
        with self._cv:
            pending = list(self._queue)
            self._queue.clear()
        for req in pending:
            req.fail(error)

    def close(self) -> None:
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._cv.notify_all()
        self._thread.join(timeout=30.0)
        self._fail_all(V.HttpError(
            V.HTTP_UNAVAILABLE,
            f"serving session {self.name} was deleted"))
        self._lease.release()

    def _batch_fill(self) -> Optional[float]:
        """Fraction of the compiled batch the last iteration actually
        used (slot occupancy / bucket fill), for the cluster monitor;
        None before any batch formed."""
        return None

    def _n_chips(self) -> int:
        """Chips under the session's current grant (falls back to the
        process device count) — the per-chip denominator for goodput."""
        try:
            grant = getattr(self._lease, "_grant", None)
            devices = getattr(grant, "devices", None)
            if devices:
                return max(1, len(devices))
        except Exception:  # noqa: BLE001
            pass
        import jax

        return max(1, jax.device_count())

    def perf_stats(self) -> Dict[str, Any]:
        """Goodput/roofline block for the session (observability/perf);
        empty until the first served iteration."""
        return {}

    def stats(self) -> Dict[str, Any]:
        with self._cv:
            depth = len(self._queue)
        out = {
            "model": self.name,
            "kind": self.kind,
            "queueDepth": depth,
            "queueBound": self._depth,
            "batchFill": self._batch_fill(),
            "requestsTotal": self.requests_total,
            "rejectedTotal": self.rejected_total,
            "uptimeSeconds": round(time.monotonic() - self.created_at, 3),
            "latency": self.latency.snapshot(),
            "lease": self._lease.stats(),
            "perf": self.perf_stats(),
        }
        return out


class LMServingSession(_SessionBase):
    """Iteration-level continuous batcher over a fixed slot cache.

    Every worker iteration: (1) admit queued requests into free slots
    (per-length prefill, cache scattered into the slot by a traced
    index — no recompile per slot), (2) run ONE compiled ``step`` that
    advances every active slot a token, (3) retire finished requests.
    Per-slot key/position bookkeeping replays the exact schedule
    ``LanguageModel.generate`` uses, so the emitted tokens are
    bit-identical to a solo decode of the same request (tested in
    tests/test_serving.py)."""

    kind = "lm"

    def __init__(self, name: str, ctx, lease: ServingLease, model,
                 slots: int, cache_len: int, temperature: float,
                 top_k: Optional[int], top_p: Optional[float],
                 weights_dtype: str = "bf16"):
        super().__init__(name, ctx, lease)
        self._model = model
        self.slots = int(slots)
        self.cache_len = int(cache_len)
        self.temperature = float(temperature)
        self.top_k = top_k
        self.top_p = top_p
        # the session serves a read-only (possibly quantized) copy of
        # the params; the master tree stays untouched for training
        # (docs/SERVING.md "Quantized serving")
        self.weights_dtype = str(weights_dtype or "bf16")
        self._serve_params = self._quantize_params(self.weights_dtype)
        self._init_decode_path()
        self.tokens_total = 0
        # decode-phase goodput accounting (observability/perf): every
        # compiled step advances ALL slots; only active ones emit a
        # useful token, so goodput = tokens / (steps x slots)
        self.decode_steps = 0
        self.decode_tokens_total = 0
        self._decode_seconds = 0.0
        # per-role latency attribution (docs/SERVING.md "Disaggregated
        # serving & speculative decoding"): prefill = admit to first
        # token, decode = first token to retire, draft = one
        # speculative propose. The label set is CLOSED (_ROLES — no
        # client influence), so unlike tenant series no cardinality
        # cap is needed: three trackers and three histogram series,
        # ever. TTFT rides along for the stats/SLO surface.
        self._role_latency: Dict[str, LatencyTracker] = {}
        self._ttft = LatencyTracker()
        # analytic decode footprint: each step reads every param and
        # the whole slot KV cache from HBM (the classic reason decode
        # is bandwidth-bound), and costs ~2 flops per param per token.
        # Bytes come from the SERVING copy — quantized weights halve
        # (or quarter) the per-step HBM read the roofline charges.
        import jax

        self._param_count = int(sum(
            a.size for a in jax.tree_util.tree_leaves(model.params)))
        self._param_bytes = int(sum(
            a.nbytes
            for a in jax.tree_util.tree_leaves(self._serve_params)))
        # host-side slot state (device state is the KV cache)
        self._tok = np.zeros((self.slots, 1), np.int32)
        self._col = np.zeros((self.slots,), np.int32)
        self._keys = np.zeros((self.slots, 2), np.uint32)
        self._slot_req: List[Optional[_Request]] = [None] * self.slots
        self._slot_out: List[List[int]] = [[] for _ in range(self.slots)]
        self._slot_left = np.zeros((self.slots,), np.int64)
        self._slot_t0 = [0.0] * self.slots
        # pin params in the HBM arena for the session's lifetime —
        # tagged with the model name so a retrain invalidates the pin
        self._params_entry = self._pin_params()
        # the slot KV cache is the session's other standing HBM claim
        obs_xray.register("kv-cache", ("kv", self.name, id(self)),
                          self._cache_bytes, name=self.name,
                          slots=self.slots, cacheLen=self.cache_len)

    def _init_decode_path(self) -> None:
        """Build the decode-path compiles and the device KV state.
        The contiguous slot cache lives here so the paged subclass can
        swap in the shared page pool without inheriting a dead
        ``slots x cache_len`` allocation."""
        import jax

        model = self._model
        self._step, self._prefill_for, self._join = model.serve_fns(
            self.slots, self.cache_len, self.temperature,
            self.top_k, self.top_p)
        self._cache = model.serve_cache(self.slots, self.cache_len)
        self._cache_bytes = int(sum(
            a.nbytes for a in jax.tree_util.tree_leaves(self._cache)))

    def _quantize_params(self, dtype: str):
        """The tree the serve fns consume: the master params as-is for
        bf16, or a quantized copy (``quantize_serving_params``) whose
        dequant fuses into the jitted step."""
        from learningorchestra_tpu.models import transformer as tlm

        return tlm.quantize_serving_params(self._model.params, dtype)

    def _pin_params(self):
        import jax

        from learningorchestra_tpu.runtime import arena as arena_lib

        leaves = jax.tree_util.tree_leaves(self._serve_params)
        flat = {f"leaf{i}": a for i, a in enumerate(leaves)}
        # the dtype is part of the key: a quant→bf16 degrade re-pins a
        # DIFFERENT resident set, and a same-key get_or_put would hand
        # the old quantized entry back
        key = ("serving", self.name, id(self), self.weights_dtype)
        entry = arena_lib.get_default_arena().get_or_put(
            key, lambda: flat, tags=(self.name,))
        # re-tag the pin in the X-ray ledger: these bytes are THIS
        # session's resident params, not anonymous arena residency
        # (the arena's own registration would double-count them)
        obs_xray.release("arena", key)
        obs_xray.register("serving-params", key, entry.nbytes,
                          name=self.name, dtype=self.weights_dtype)
        self._params_pin_key = key
        return entry

    def _on_reacquired(self) -> None:
        # the slice changed hands while we were yielded: re-pin so
        # arena residency accounting follows the live grant
        self._params_entry.release()
        self._params_entry = self._pin_params()

    def _have_work(self) -> bool:
        return bool(self._queue) or any(
            r is not None for r in self._slot_req)

    def validate_request(self, payload: Dict[str, Any]) -> None:
        prompt = payload.get("prompt")
        if not isinstance(prompt, (list, tuple)) or not prompt or \
                not all(isinstance(t, int) and not isinstance(t, bool)
                        for t in prompt):
            raise V.HttpError(
                V.HTTP_NOT_ACCEPTABLE,
                f"{V.MESSAGE_INVALID_FIELD}: prompt must be a non-empty "
                f"list of token ids")
        new = V.valid_positive_int(payload.get("maxNewTokens"),
                                   "maxNewTokens", default=32)
        if new >= self.cache_len:
            raise V.HttpError(
                V.HTTP_NOT_ACCEPTABLE,
                f"{V.MESSAGE_INVALID_FIELD}: maxNewTokens={new} leaves "
                f"no prompt room in cacheLen={self.cache_len}")
        seed = payload.get("seed", 0)
        if isinstance(seed, bool) or not isinstance(seed, int):
            raise V.HttpError(
                V.HTTP_NOT_ACCEPTABLE,
                f"{V.MESSAGE_INVALID_FIELD}: seed must be an integer, "
                f"got {seed!r}")

    def _admit(self, slot: int, req: _Request) -> None:
        import jax.numpy as jnp
        import jax.random as jr

        admit_t0 = time.monotonic()
        payload = req.payload
        prompt = list(payload["prompt"])
        new = int(payload.get("maxNewTokens") or 32)
        seed = int(payload.get("seed", 0))
        # same sliding-window truncation generate() applies, bounded
        # by the session cache instead of max_len
        keep = self.cache_len - new
        if len(prompt) > keep:
            prompt = prompt[-keep:]
        s = len(prompt)
        # generate()'s key schedule: split once for the prefill sample,
        # split again for the decode loop's fold_in base
        key = jr.PRNGKey(seed)
        key, sub_prefill = jr.split(key)
        key, sub_decode = jr.split(key)
        prefill = self._prefill_for(s)
        tokens = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
        nxt, pcache = prefill(self._serve_params, tokens, sub_prefill)
        self._cache = self._join(self._cache, pcache, slot)
        req.stages.append(("prefill", admit_t0, time.monotonic(),
                           {"promptTokens": s, "slot": slot}))
        self._record_role("prefill", time.monotonic() - admit_t0)
        self._ttft.record(time.monotonic() - req.queued_at)
        first = int(nxt[0])
        self._slot_req[slot] = req
        self._slot_out[slot] = [first]
        self._slot_left[slot] = new - 1
        self._slot_t0[slot] = time.monotonic()
        self._tok[slot, 0] = first
        self._col[slot] = s  # next step attends positions <= s
        self._keys[slot] = np.asarray(sub_decode)
        self.tokens_total += 1
        if self._slot_left[slot] <= 0:
            self._retire(slot)

    _ROLES = ("prefill", "decode", "draft")

    def _record_role(self, role: str, seconds: float) -> None:
        """Per-role latency: a tracker for session stats plus a
        role-labelled histogram series
        (``lo_serving_request_seconds_role_<role>``) for prometheus
        and the SLO plane. ``role`` comes from the fixed ``_ROLES``
        set — the bounded-cardinality analog of ``_tenant_series``,
        bounded by construction instead of by cap."""
        if role not in self._ROLES:
            return
        tracker = self._role_latency.get(role)
        if tracker is None:
            tracker = self._role_latency.setdefault(
                role, LatencyTracker())
        tracker.record(seconds)
        obs_hist.observe("lo_serving_request_seconds_role_" + role,
                         seconds)

    def _retire(self, slot: int) -> None:
        req = self._slot_req[slot]
        self._slot_req[slot] = None
        if req is None:
            return
        self._record_role("decode",
                          time.monotonic() - self._slot_t0[slot])
        tokens = [int(t) for t in self._slot_out[slot]]
        req.stages.append(("decodeIters", self._slot_t0[slot],
                           time.monotonic(), {"tokens": len(tokens)}))
        req.finish({
            "tokens": tokens,
            "decodeSeconds": round(
                time.monotonic() - self._slot_t0[slot], 6),
        })
        self._slot_out[slot] = []

    def _pop_next(self) -> _Request:
        """Pick the next queued request (caller holds ``self._cv``).
        FIFO here; the paged session overrides with a weighted-fair
        pick over tenant page usage."""
        return self._queue.popleft()

    def _run_step(self):
        """One compiled continuous-batch step; returns the per-slot
        next-token device array."""
        import jax.numpy as jnp

        nxt, self._cache = self._step(
            self._serve_params, self._cache, jnp.asarray(self._tok),
            jnp.asarray(self._col), jnp.asarray(self._keys))
        return nxt

    def _admit_loop(self) -> bool:
        """Admit queued requests into free slots (one per request);
        returns True if anything was admitted. Split out of
        :meth:`_serve_once` so the disaggregated session's FUSED
        degrade rung can reuse it verbatim while its split mode moves
        admission onto the prefill worker."""
        admitted = False
        while True:
            with self._cv:
                free = [i for i, r in enumerate(self._slot_req)
                        if r is None]
                if not free or not self._queue:
                    break
                req = self._pop_next()
            req.popped_at = time.monotonic()
            try:
                self._admit(free[0], req)
                admitted = True
            except V.HttpError as exc:
                req.fail(exc)
            except Exception as exc:  # noqa: BLE001
                req.fail(V.HttpError(V.HTTP_UNAVAILABLE,
                                     f"prefill failed: {exc}"))
        return admitted

    def _active_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._slot_req)
                if r is not None]

    def _decode_round(self, active: List[int]) -> None:
        """One continuous-batch step + harvest/retire over ``active``
        slots. The speculative paged session overrides this with a
        propose/verify window that can emit up to spec_k+1 tokens per
        slot per round."""
        # every active slot advances a token; idle slots compute
        # masked garbage that is discarded
        step_t0 = time.monotonic()
        nxt = np.asarray(self._run_step())  # device sync — step wall
        # time ends here
        self._decode_seconds += time.monotonic() - step_t0
        self.decode_steps += 1
        self.decode_tokens_total += len(active)
        for slot in active:
            tok = int(nxt[slot])
            self._slot_out[slot].append(tok)
            self._slot_left[slot] -= 1
            self.tokens_total += 1
            self._tok[slot, 0] = tok
            self._col[slot] += 1
            if self._slot_left[slot] <= 0 or \
                    self._col[slot] >= self.cache_len - 1:
                self._retire(slot)

    def _serve_once(self) -> bool:
        admitted = self._admit_loop()
        active = self._active_slots()
        if not active:
            return admitted
        self._decode_round(active)
        return True

    def close(self) -> None:
        super().close()
        self._params_entry.release()
        obs_xray.release("serving-params", self._params_pin_key)
        obs_xray.release("kv-cache", ("kv", self.name, id(self)))

    def _batch_fill(self) -> Optional[float]:
        active = sum(1 for r in self._slot_req if r is not None)
        if not active and not self.tokens_total:
            return None
        return round(active / self.slots, 4)

    def perf_stats(self) -> Dict[str, Any]:
        if not self.decode_steps or self._decode_seconds <= 0:
            return {}
        n = self._n_chips()
        dt = self._decode_seconds
        tps = self.decode_tokens_total / dt
        out: Dict[str, Any] = {
            "decodeSteps": self.decode_steps,
            "decodeTokensPerSec": round(tps, 2),
            "decodeTokensPerSecPerChip": round(tps / n, 3),
            # batch-fill-weighted goodput: the fraction of slot-steps
            # the batcher spent on real tokens vs masked idle lanes
            "goodputFrac": round(
                self.decode_tokens_total /
                (self.decode_steps * self.slots), 4),
        }
        # analytic roofline for decode (XLA cost analysis never ran
        # here): ~2 flops per param per emitted token, and every step
        # streams params + the whole slot KV cache through HBM
        flops_per_step = 2.0 * self._param_count * (
            self.decode_tokens_total / self.decode_steps)
        out.update(obs_perf.roofline(
            flops_per_step,
            float(self._param_bytes + self._cache_bytes),
            self.decode_steps, dt, n))
        return out

    def stats(self) -> Dict[str, Any]:
        out = super().stats()
        out.update({
            "slots": self.slots,
            "activeSlots": sum(1 for r in self._slot_req
                               if r is not None),
            "cacheLen": self.cache_len,
            "tokensTotal": self.tokens_total,
            "temperature": self.temperature,
            "weights": {"dtype": self.weights_dtype,
                        "bytes": self._param_bytes},
            "ttft": self._ttft.snapshot(),
            "roles": {r: t.snapshot() for r, t in
                      sorted(self._role_latency.items())},
        })
        return out


class PoolExhausted(Exception):
    """Not enough free KV pages for an allocation (the session turns
    this into a 429 after trying prefix-cache eviction)."""


def _metric_tenant(tenant: str) -> str:
    return re.sub(r"[^0-9A-Za-z_]", "_", tenant)


def parse_tenant_weights(spec: str) -> Dict[str, float]:
    """``LO_SERVE_TENANT_WEIGHTS="gold:3,free:1"`` → weight map.
    Unlisted tenants weigh 1; malformed entries are skipped."""
    out: Dict[str, float] = {}
    for part in str(spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, _, w = part.partition(":")
        try:
            out[name.strip()] = max(float(w), 0.0) if w else 1.0
        except ValueError:
            continue
    return out


class PagedKVPool:
    """Host-side allocator over the shared device KV page pool.

    Page 0 is the TRASH page: the paged decode appends every batch
    lane's token KV unconditionally, so idle/retired lanes' block
    tables point at page 0 and it is never handed out (garbage there
    is masked to an exact zero by the attention, never read back).
    Pages are refcounted — prefix-cache hits share prompt pages
    across streams and a page returns to the free list only when its
    last reference drops. Per-tenant charge accounting (every
    reference a tenant's stream holds counts against that tenant, so
    sharing cannot game the quota) backs the weighted-fair admission.

    Allocation order is the worker thread's alone; ``stats`` may be
    read from REST threads, hence the lock.
    """

    def __init__(self, n_pages: int, page_len: int,
                 dtype: str = "bf16"):
        if n_pages < 2:
            raise ValueError(f"n_pages must be >= 2, got {n_pages}")
        self.n_pages = int(n_pages)
        self.page_len = int(page_len)
        # value dtype of the device pool this allocator fronts
        # ("bf16" or "int8" — int8 pages carry a parallel scale pool,
        # docs/SERVING.md "Quantized serving")
        self.dtype = str(dtype or "bf16")
        self._lock = locks.make_lock("serving.kvpool")
        self._free: Deque[int] = collections.deque(
            range(1, self.n_pages))
        self._refs: Dict[int, int] = {}
        self._tenant_pages: Dict[str, int] = {}
        self.alloc_total = 0
        self.alloc_failures = 0
        self.freed_total = 0

    @property
    def usable(self) -> int:
        return self.n_pages - 1  # page 0 is the trash page

    def free_count(self) -> int:
        with self._lock:
            return len(self._free)

    def shared_count(self) -> int:
        with self._lock:
            return sum(1 for c in self._refs.values() if c > 1)

    def alloc(self, n: int, tenant: Optional[str] = None) -> List[int]:
        """Take ``n`` pages off the free list (refcount 1 each).
        Raises :class:`PoolExhausted` (OOM-safe reject — the pool
        never over-commits) or ``faults.InjectedFault`` (chaos site
        ``kv_page_alloc``)."""
        faults.maybe_inject("kv_page_alloc")
        with self._lock:
            if n > len(self._free):
                self.alloc_failures += 1
                raise PoolExhausted(
                    f"need {n} KV pages, {len(self._free)} free "
                    f"of {self.usable}")
            pages = [self._free.popleft() for _ in range(n)]
            for p in pages:
                self._refs[p] = 1
            self.alloc_total += n
            if tenant is not None:
                self._charge(tenant, n)
        return pages

    def incref(self, pages: List[int],
               tenant: Optional[str] = None) -> None:
        with self._lock:
            for p in pages:
                self._refs[p] += 1
            if tenant is not None:
                self._charge(tenant, len(pages))

    def decref(self, pages: List[int],
               tenant: Optional[str] = None) -> None:
        with self._lock:
            for p in pages:
                c = self._refs.get(p, 0) - 1
                if c <= 0:
                    self._refs.pop(p, None)
                    self._free.append(p)
                    self.freed_total += 1
                else:
                    self._refs[p] = c
            if tenant is not None:
                self._charge(tenant, -len(pages))

    def _charge(self, tenant: str, n: int) -> None:
        cur = self._tenant_pages.get(tenant, 0) + n
        if cur <= 0:
            self._tenant_pages.pop(tenant, None)
        else:
            self._tenant_pages[tenant] = cur

    def tenant_pages(self, tenant: str) -> int:
        with self._lock:
            return self._tenant_pages.get(tenant, 0)

    def tenants(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._tenant_pages)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "dtype": self.dtype,
                "pageLen": self.page_len,
                "pagesTotal": self.usable,
                "pagesFree": len(self._free),
                "pagesShared": sum(
                    1 for c in self._refs.values() if c > 1),
                "allocTotal": self.alloc_total,
                "allocFailures": self.alloc_failures,
                "freedTotal": self.freed_total,
            }


class PrefixCache:
    """Page-granularity prompt-prefix cache (the serving analog of
    the feature cache's version keys).

    Two hit kinds against the refcounted pool:

    - **full** (exact prompt seen before): the prefill is SKIPPED —
      the entry holds the prompt's full pages (shared read-only: a
      full page's positions are never written again after prefill),
      its partially-filled tail page, and the prefill's final logit
      row. The new stream increfs the full pages, clones the tail
      page (copy-on-use: decode appends diverge per stream; the
      donor's own decode rows beyond the prompt inside the clone are
      position-masked until overwritten, so they are never read) and
      resamples the first token from the cached logits under its own
      key — bit-identical to running the prefill.
    - **partial** (longest cached run of FULL pages prefixing the
      prompt): the prefill still runs, but the shared pages are
      increfed and the page write starts after them — HBM page reuse
      without recomputed-KV writes. Safe because prefill KV at a
      position depends only on tokens at or before it (verified
      bitwise by tests/test_serving.py).

    Entries hold their own page references, so donor retirement
    never invalidates an entry; LRU entries are evicted under pool
    pressure before the session rejects with 429.

    Thread-safety: the disaggregated session looks prefixes up on the
    PREFILL worker while the decode worker inserts/evicts, so every
    mutation runs under its own ranked lock (``serving.prefix`` —
    between the serving lease and the fair queue, below the pool
    lock it calls into). Lookup-and-pin still composes: the caller
    increfs the returned pages before any alloc can evict the entry.
    """

    def __init__(self, pool: PagedKVPool, page_len: int,
                 max_entries: int = 64):
        self._pool = pool
        self._page_len = int(page_len)
        self._max = int(max_entries)
        self._lock = locks.make_lock("serving.prefix")
        # prompt tuple -> {fullPages, tailPage, logits, held}
        self._entries: "collections.OrderedDict" = \
            collections.OrderedDict()
        self._chains: Dict[Tuple[int, ...], Tuple[int, ...]] = {}
        self.hits_full = 0
        self.hits_partial = 0
        self.pages_reused = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def lookup_full(self, prompt: List[int]) -> Optional[Dict[str, Any]]:
        with self._lock:
            entry = self._entries.get(tuple(prompt))
            if entry is not None:
                self._entries.move_to_end(tuple(prompt))
                self.hits_full += 1
                self.pages_reused += len(entry["fullPages"])
            return entry

    def lookup_partial(
            self, prompt: List[int]) -> Tuple[Optional[List[int]], int]:
        """Longest cached chain of FULL pages prefixing ``prompt`` →
        (pages, n_pages); (None, 0) on miss. No references are taken
        here — the caller increfs once it commits to admission."""
        pl = self._page_len
        with self._lock:
            for k in range(len(prompt) // pl, 0, -1):
                key = self._chains.get(tuple(prompt[:k * pl]))
                if key is None:
                    continue
                entry = self._entries.get(key)
                if entry is None or len(entry["fullPages"]) < k:
                    continue
                self._entries.move_to_end(key)
                self.hits_partial += 1
                self.pages_reused += k
                return list(entry["fullPages"][:k]), k
            return None, 0

    def insert(self, prompt: List[int], full_pages: List[int],
               tail_page: Optional[int], logits: np.ndarray) -> None:
        key = tuple(prompt)
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                return
            held = list(full_pages)
            if tail_page is not None:
                held.append(tail_page)
            self._pool.incref(held)  # the cache's own hold — no tenant
            self._entries[key] = {
                "fullPages": list(full_pages), "tailPage": tail_page,
                "logits": np.asarray(logits), "held": held}
            pl = self._page_len
            for k in range(1, len(full_pages) + 1):
                self._chains[key[:k * pl]] = key
            while len(self._entries) > self._max:
                self._evict_one_locked()

    def _evict_one_locked(self) -> bool:
        if not self._entries:
            return False
        key, entry = self._entries.popitem(last=False)
        pl = self._page_len
        for k in range(1, len(entry["fullPages"]) + 1):
            if self._chains.get(key[:k * pl]) == key:
                del self._chains[key[:k * pl]]
        self._pool.decref(entry["held"])
        return True

    def evict_one(self) -> bool:
        """Drop the LRU entry and release its page references.
        Returns False when the cache is already empty."""
        with self._lock:
            return self._evict_one_locked()

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"entries": len(self._entries),
                    "hitsFull": self.hits_full,
                    "hitsPartial": self.hits_partial,
                    "pagesReused": self.pages_reused}


class PagedLMServingSession(LMServingSession):
    """vLLM-style paged-KV continuous batcher (``LO_SERVE_KV=paged``,
    docs/SERVING.md "Paged KV serving").

    Same iteration loop and bit-identical token streams as the slot
    session, but the per-layer KV cache is ONE shared
    ``(pages, page_len, kv, d)`` pool (arena-adjacent, X-ray-tagged
    under the session's ``kv-cache`` claim) and each stream owns
    exactly ``ceil((prompt+maxNew)/page_len)`` pages through its
    block-table row — admission is page-granular, so concurrency is
    bounded by ACTUAL token demand instead of ``slots x cache_len``
    worst case. On top of the pool: prompt prefix caching
    (:class:`PrefixCache`) and weighted-fair per-tenant QoS over the
    page budget with per-tenant latency histograms feeding per-tenant
    ``servingP99`` SLO objectives.

    A latched ``kv_page_alloc`` fault (``_DEGRADE_AFTER`` consecutive
    injected failures) degrades the session to the contiguous slot
    path: in-flight paged streams fail with 503, an incident bundle
    is triggered, and every later request serves through the
    inherited slot machinery unchanged.
    """

    _DEGRADE_AFTER = 3
    _MAX_TENANT_SERIES = 32

    def __init__(self, name: str, ctx, lease: ServingLease, model,
                 slots: int, cache_len: int, temperature: float,
                 top_k: Optional[int], top_p: Optional[float],
                 page_len: int, n_pages: int,
                 tenant_weights: Optional[Dict[str, float]] = None,
                 kv_dtype: str = "bf16",
                 weights_dtype: str = "bf16",
                 draft_model=None, draft_name: str = "",
                 spec_k: int = 4):
        # consumed by _init_decode_path, which the base __init__ calls
        self.page_len = int(page_len)
        self.n_pages = int(n_pages)
        self.kv_dtype = str(kv_dtype or "bf16")
        self._tenant_weights = dict(tenant_weights or {})
        # speculative decoding (docs/SERVING.md "Disaggregated
        # serving & speculative decoding"): a small draft model
        # proposes spec_k greedy tokens per round; the target
        # verifies all of them in ONE paged step with exact
        # acceptance sampling, so greedy sessions stay bit-identical
        # to solo decode and sampled sessions keep the target's exact
        # output distribution
        self._draft = draft_model
        self._draft_name = str(draft_name or "")
        self._spec_k = max(1, int(spec_k or 4))
        self.spec_steps = 0
        self.spec_slot_steps = 0
        self.spec_accepted_total = 0
        self.spec_emitted_total = 0
        super().__init__(name, ctx, lease, model, slots, cache_len,
                         temperature, top_k, top_p,
                         weights_dtype=weights_dtype)
        if self._draft is not None:
            self._init_spec_state()
        # quality gate at the door: a quantized session measures its
        # own drift before serving a single request, so a bad
        # quantization degrades at create, not in a user's stream
        self._maybe_probe_drift(force=True)

    def _init_decode_path(self) -> None:
        import jax

        if self.cache_len % self.page_len:
            raise ValueError(
                f"cacheLen={self.cache_len} must be a multiple of "
                f"pageLen={self.page_len}")
        model = self._model
        (self._pstep, self._pprefill_for, self._pjoin,
         self._copy_page, self._sample_first) = model.serve_fns_paged(
            self.slots, self.cache_len, self.page_len, self.n_pages,
            self.temperature, self.top_k, self.top_p,
            kv_dtype=self.kv_dtype)
        self._pool_tree = model.serve_cache_paged(
            self.n_pages, self.page_len, kv_dtype=self.kv_dtype)
        # speculative verify step: k+1 tokens scored in one dispatch.
        # Built here (not in _init_spec_state) because its compile
        # signature includes kv_dtype — a bf16 degrade rebuilds it
        self._verify = None
        if self._draft is not None:
            self._verify = model.serve_fns_spec(
                self.slots, self.cache_len, self.page_len,
                self.n_pages, self._spec_k, self.temperature,
                self.top_k, self.top_p, kv_dtype=self.kv_dtype)
        self._cache_bytes = int(sum(
            a.nbytes
            for a in jax.tree_util.tree_leaves(self._pool_tree)))
        self.pool = PagedKVPool(self.n_pages, self.page_len,
                                dtype=self.kv_dtype)
        self.prefix = PrefixCache(self.pool, self.page_len)
        self._pages_per_slot = self.cache_len // self.page_len
        self._bt = np.zeros((self.slots, self._pages_per_slot),
                            np.int32)
        self._slot_pages: List[List[int]] = [
            [] for _ in range(self.slots)]
        self._slot_tenant: List[Optional[str]] = [None] * self.slots
        self._tenant_latency: Dict[str, LatencyTracker] = {}
        self._tenant_requests: Dict[str, int] = {}
        self._adhoc_tenants: set = set()
        self._alloc_fault_streak = 0
        self._quant_fault_streak = 0
        self._degraded = False
        self.prefills_skipped = 0
        # drift gate state (quantized sessions only): last measured
        # quantized-vs-exact relative drift, its per-component parts,
        # and the decode-step countdown to the next periodic probe
        self._last_drift: Optional[float] = None
        self._drift_parts: Dict[str, float] = {}
        self._drift_probes = 0
        self._steps_since_probe = 0

    # -- speculative decoding ------------------------------------------
    def _spec_on(self) -> bool:
        return self._draft is not None and not self._degraded

    def _init_spec_state(self) -> None:
        """Draft-side state: the draft model's slot KV cache, its
        prefill/join fns (the draft shares the target's admission
        path) and the jitted spec_k-token greedy propose scan. The
        draft always serves bf16 over a SLOT cache — it is small by
        design, and keeping it exact keeps the one-hot proposal (and
        with it the acceptance-sampling exactness proof) trivially
        true."""
        import jax

        draft = self._draft
        (_, self._draft_prefill_for, self._draft_join) = \
            draft.serve_fns(self.slots, self.cache_len, 0.0,
                            None, None)
        self._draft_propose = draft.serve_fns_draft(
            self.slots, self.cache_len, self._spec_k)
        self._draft_params = draft.params
        self._draft_cache = draft.serve_cache(self.slots,
                                              self.cache_len)
        self._draft_cache_bytes = int(sum(
            a.nbytes
            for a in jax.tree_util.tree_leaves(self._draft_cache)))
        self._draft_param_bytes = int(sum(
            a.nbytes
            for a in jax.tree_util.tree_leaves(draft.params)))
        # the draft's resident bytes are this session's claim too —
        # X-ray rows must balance when the session (or the spec path
        # alone) tears down
        obs_xray.register(
            "kv-cache", ("kv", self.name + "#draft", id(self)),
            self._draft_cache_bytes, name=self.name, role="draft",
            slots=self.slots, cacheLen=self.cache_len)
        obs_xray.register(
            "serving-params",
            ("serving", self.name + "#draft", id(self), "bf16"),
            self._draft_param_bytes, name=self.name, role="draft")

    def _release_spec_state(self) -> None:
        """Drop the draft model's device state and its X-ray claims
        (idempotent — degrade-to-slot and close both call it)."""
        if self._draft is None:
            return
        self._draft = None
        self._draft_cache = None
        self._verify = None
        obs_xray.release("kv-cache",
                         ("kv", self.name + "#draft", id(self)))
        obs_xray.release(
            "serving-params",
            ("serving", self.name + "#draft", id(self), "bf16"))

    def close(self) -> None:
        super().close()
        self._release_spec_state()

    # -- disagg handoff hooks (overridden by the disagg session) -------
    def _publishes(self) -> bool:
        """Whether _prepare publishes handoff records (the extra
        publish incref + the ``kv_page_handoff`` chaos site). The
        fused session installs in the same thread — no window, no
        publish hold."""
        return False

    def _note_handoff_fault(self) -> None:
        """An injected ``kv_page_handoff`` fault was observed."""

    def _note_handoff_ok(self) -> None:
        """A publish made it past the chaos site (streak reset)."""

    # -- tenants -------------------------------------------------------
    @staticmethod
    def _tenant_of(payload: Dict[str, Any]) -> str:
        return str(payload.get("tenant") or "default")

    def _weight(self, tenant: str) -> float:
        return max(1e-6, float(self._tenant_weights.get(tenant, 1.0)))

    def _tenant_tracker(self, tenant: str) -> LatencyTracker:
        tracker = self._tenant_latency.get(tenant)
        if tracker is None:
            tracker = self._tenant_latency.setdefault(
                tenant, LatencyTracker())
        return tracker

    def _tenant_series(self, tenant: str) -> str:
        """Bounded observability cardinality for a client-controlled
        field: every distinct ``tenant`` value mints a global
        histogram series, a latency tracker, and a page-severity
        ``servingP99:{tenant}`` watchdog objective, none of which are
        ever pruned. Tenants named in ``LO_SERVE_TENANT_WEIGHTS``
        always get their own series; beyond those, only the first
        ``_MAX_TENANT_SERIES`` distinct ad-hoc values do — the rest
        collapse into ``other`` so an untrusted client cannot drive
        unbounded memory growth or alert-cardinality explosion.
        Quota/fairness accounting keeps the raw tenant (the pool's
        per-tenant charges self-prune at zero pages)."""
        if tenant in self._tenant_weights or \
                tenant in self._adhoc_tenants:
            return tenant
        if len(self._adhoc_tenants) < self._MAX_TENANT_SERIES:
            self._adhoc_tenants.add(tenant)
            return tenant
        return "other"

    def validate_request(self, payload: Dict[str, Any]) -> None:
        super().validate_request(payload)
        tenant = payload.get("tenant")
        if tenant is not None and (
                not isinstance(tenant, str) or not tenant
                or len(tenant) > 64):
            raise V.HttpError(
                V.HTTP_NOT_ACCEPTABLE,
                f"{V.MESSAGE_INVALID_FIELD}: tenant must be a "
                f"non-empty string of <= 64 chars")

    def submit(self, payload: Dict[str, Any],
               timeout: Optional[float] = None) -> Dict[str, Any]:
        tenant = self._tenant_of(payload)
        t0 = time.monotonic()
        result = super().submit(payload, timeout=timeout)
        elapsed = time.monotonic() - t0
        series = self._tenant_series(tenant)
        self._tenant_tracker(series).record(elapsed)
        self._tenant_requests[series] = \
            self._tenant_requests.get(series, 0) + 1
        # a per-tenant histogram series feeds the watchdog's
        # per-tenant servingP99 objective (observability/slo.py)
        obs_hist.observe("lo_serving_request_seconds_tenant_"
                         + _metric_tenant(series), elapsed)
        return result

    def _quota_check(self, tenant: str, need: int) -> None:
        """Weighted-fair admission over the page budget: with >1 live
        tenant, each may hold at most ``usable * w_t / sum(w)`` pages
        — an abusive tenant exhausts its OWN quota (429) and cannot
        starve another tenant's admissions or breach their SLO. A
        sole tenant may use the whole pool."""
        live = set(self.pool.tenants())
        live.add(tenant)
        if len(live) < 2:
            return
        total_w = sum(self._weight(t) for t in live)
        quota = int(self.pool.usable * self._weight(tenant) / total_w)
        used = self.pool.tenant_pages(tenant)
        if used + need > quota:
            self.rejected_total += 1
            raise V.HttpError(
                V.HTTP_TOO_MANY_REQUESTS,
                f"tenant {tenant!r} over its weighted page quota "
                f"({used}+{need} > {quota} of {self.pool.usable} "
                f"pages) — retry with backoff")

    def _pop_next(self) -> _Request:
        # weighted-fair pick: the queued request whose tenant holds
        # the fewest pages per unit weight goes first (FIFO within a
        # tenant), so a heavy tenant's backlog cannot starve a light
        # tenant behind it in the queue
        if self._degraded or len(self._queue) <= 1:
            return self._queue.popleft()
        best_i = 0
        best_key: Optional[Tuple[float, int]] = None
        for i, req in enumerate(self._queue):
            tenant = self._tenant_of(req.payload)
            k = (self.pool.tenant_pages(tenant) / self._weight(tenant),
                 i)
            if best_key is None or k < best_key:
                best_i, best_key = i, k
        req = self._queue[best_i]
        del self._queue[best_i]
        return req

    # -- paged admission ----------------------------------------------
    def _alloc_pages(self, need: int, tenant: str) -> List[int]:
        try:
            pages = self.pool.alloc(need, tenant)
            self._alloc_fault_streak = 0
            return pages
        except faults.InjectedFault as exc:
            self._alloc_fault_streak += 1
            if self._alloc_fault_streak >= self._DEGRADE_AFTER:
                self._degrade_to_slot()
            self.rejected_total += 1
            raise V.HttpError(
                V.HTTP_TOO_MANY_REQUESTS,
                f"KV page allocation failed ({exc}) — retry with "
                f"backoff")
        except PoolExhausted as exc:
            # pool pressure: prefix-cache holds are the reclaimable
            # tier — drop LRU entries before rejecting
            while self.prefix.evict_one():
                try:
                    pages = self.pool.alloc(need, tenant)
                    self._alloc_fault_streak = 0
                    return pages
                except PoolExhausted as retry_exc:
                    exc = retry_exc
            self.rejected_total += 1
            raise V.HttpError(
                V.HTTP_TOO_MANY_REQUESTS,
                f"KV page pool exhausted ({exc}) — retry with "
                f"backoff")

    def _admit(self, slot: int, req: _Request) -> None:
        if self._degraded:
            return super()._admit(slot, req)
        self._install(slot, self._prepare(req))

    def _prepare(self, req: _Request) -> Dict[str, Any]:
        """Funding + prefill compute for one admission, WITHOUT any
        pool-tree mutation: quota check, prefix lookup (+ page pins),
        page allocation, the target prefill forward and the draft
        prefill when speculation is on. Returns a handoff record the
        decode side consumes via :meth:`_install`. The fused session
        runs both halves back-to-back on the worker thread; the
        disaggregated session runs _prepare on the PREFILL worker and
        ships the record through the handoff queue — the device pool
        tree is only ever donated by the decode thread, so the two
        workers can never race a donation.

        On ANY failure every page reference this admission took is
        released before the error propagates; on success the record
        owns them until _install adopts them (or a teardown drain
        releases them)."""
        if self.kv_dtype == "int8":
            # chaos site for the quantized KV plane (services/faults.py
            # ``kv_quant``): a transient fault is a retryable 429; a
            # latched one walks the degrade ladder one rung — back to
            # exact bf16 pages/weights, never a corrupted stream
            try:
                faults.maybe_inject("kv_quant")
                self._quant_fault_streak = 0
            except faults.InjectedFault as exc:
                self._quant_fault_streak += 1
                if self._quant_fault_streak >= self._DEGRADE_AFTER:
                    self._degrade_to_bf16(
                        f"kv_quant fault latched ({exc})")
                self.rejected_total += 1
                raise V.HttpError(
                    V.HTTP_TOO_MANY_REQUESTS,
                    f"quantized KV path fault ({exc}) — retry with "
                    f"backoff")
        import jax.numpy as jnp
        import jax.random as jr

        admit_t0 = time.monotonic()
        payload = req.payload
        prompt = list(payload["prompt"])
        new = int(payload.get("maxNewTokens") or 32)
        seed = int(payload.get("seed", 0))
        tenant = self._tenant_of(payload)
        keep = self.cache_len - new
        if len(prompt) > keep:
            prompt = prompt[-keep:]
        s = len(prompt)
        pl = self.page_len
        # page-granular footprint: exactly the tokens this request
        # can touch, not the slot path's cache_len worst case
        total_pages = -(-(s + new) // pl)
        key = jr.PRNGKey(seed)
        key, sub_prefill = jr.split(key)
        key, sub_decode = jr.split(key)

        entry = self.prefix.lookup_full(prompt)
        if entry is not None:
            shared = list(entry["fullPages"])
            donor_tail = entry["tailPage"]
            donor_logits = entry["logits"]
        else:
            shared, _ = self.prefix.lookup_partial(prompt)
            shared = shared or []
            donor_tail = None
            donor_logits = None
        n_shared = len(shared)
        # Pin the looked-up pages BEFORE quota/alloc: under pool
        # pressure _alloc_pages LRU-evicts prefix entries, which could
        # drop the very entry backing this admission — its pages would
        # decref to 0 and come back as `fresh` (page aliasing: the
        # prefill/tail clone would overwrite live shared prompt KV).
        # Our own references keep them allocated. The donor tail pin
        # is transient (held only until the clone is dispatched) so it
        # is not charged to the tenant.
        if shared:
            self.pool.incref(shared, tenant)
        if donor_tail is not None:
            self.pool.incref([donor_tail])
        fresh: List[int] = []
        published = False
        row: List[int] = []
        try:
            # the shared pages are already charged to the tenant, so
            # the quota headroom needed is only the fresh pages
            self._quota_check(tenant, total_pages - n_shared)
            fresh = self._alloc_pages(total_pages - n_shared, tenant)
            row = shared + fresh
            rec: Dict[str, Any] = {
                "req": req, "s": s, "new": new, "tenant": tenant,
                "row": row, "fresh": fresh, "nShared": n_shared,
                "donorTail": donor_tail, "donorLogits": None,
                "admitT0": admit_t0, "first": None, "pcache": None,
                "dpcache": None, "writePages": [], "insert": None,
                "subPrefill": sub_prefill,
                "subDecode": np.asarray(sub_decode),
            }
            tokens = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
            if entry is not None:
                # FULL hit: no target prefill compute at all — the
                # pool-tree side (tail-page clone + first-token
                # resample from the cached logits) runs in _install
                rec["donorLogits"] = donor_logits
                self.prefills_skipped += 1
            else:
                prefill = self._pprefill_for(s)
                nxt, last_logits, pcache = prefill(
                    self._serve_params, tokens, sub_prefill)
                # prompt KV goes straight into this stream's pages,
                # starting after any shared prefix pages (_install)
                n_prefill_pages = -(-s // pl)
                rec["writePages"] = row[n_shared:n_prefill_pages]
                rec["pcache"] = pcache
                rec["first"] = int(nxt[0])
                n_full = s // pl
                tail_page = row[n_full] if s % pl else None
                rec["insert"] = (prompt, row[:n_full], tail_page,
                                 np.asarray(last_logits[0]))
            if self._spec_on():
                # the draft shares the target's admission path: its
                # prompt KV comes from its own per-length prefill and
                # joins its slot cache in _install (the draft cache
                # is donated by propose, so only the decode thread
                # may mutate it)
                dprefill = self._draft_prefill_for(s)
                _, dpcache = dprefill(self._draft_params, tokens,
                                      sub_prefill)
                rec["dpcache"] = dpcache
            if self._publishes():
                # disagg handoff point: the chaos site, then the
                # publish hold that keeps every page alive across the
                # push→adopt window even if the prefill worker dies
                faults.maybe_inject("kv_page_handoff")
                self._note_handoff_ok()
                self.pool.incref(row)
                published = True
                rec["published"] = True
            return rec
        except faults.InjectedFault as exc:
            # only kv_page_handoff reaches here un-wrapped (alloc
            # faults become HttpErrors inside _alloc_pages)
            self._note_handoff_fault()
            if shared or fresh:
                self.pool.decref(shared + fresh, tenant)
            if donor_tail is not None:
                self.pool.decref([donor_tail])
            self.rejected_total += 1
            raise V.HttpError(
                V.HTTP_TOO_MANY_REQUESTS,
                f"KV page handoff failed ({exc}) — retry with "
                f"backoff")
        except BaseException:
            # quota reject, alloc failure, or a prefill error:
            # release every reference this admission took, or the
            # pages (and the tenant's quota charge) leak and the pool
            # permanently shrinks toward starved admissions
            if published:
                self.pool.decref(row)
            if shared or fresh:
                self.pool.decref(shared + fresh, tenant)
            if donor_tail is not None:
                self.pool.decref([donor_tail])
            raise

    def _install(self, slot: int, rec: Dict[str, Any]) -> None:
        """Decode-side half of an admission: pool-tree writes (prefix
        join / tail-page clone), the draft-cache join, the prefix
        insert, and slot-state installation. Only the thread that
        owns the donated pool tree may call this."""
        import jax.numpy as jnp

        req = rec["req"]
        row, tenant = rec["row"], rec["tenant"]
        try:
            if rec["donorLogits"] is not None:
                # FULL hit: clone the donor's tail page (its decode
                # rows past the prompt are masked until this stream
                # overwrites them) and resample the first token from
                # the cached final logits — the same floats the
                # prefill epilogue would produce
                if rec["donorTail"] is not None:
                    self._pool_tree = self._copy_page(
                        self._pool_tree,
                        jnp.asarray(np.int32(rec["donorTail"])),
                        jnp.asarray(np.int32(rec["fresh"][0])))
                first = int(self._sample_first(
                    jnp.asarray(rec["donorLogits"]),
                    rec["subPrefill"]))
                req.stages.append(
                    ("prefixHit", rec["admitT0"], time.monotonic(),
                     {"promptTokens": rec["s"], "slot": slot,
                      "sharedPages": rec["nShared"],
                      "tenant": tenant}))
            else:
                if rec["writePages"]:
                    self._pool_tree = self._pjoin(
                        self._pool_tree, rec["pcache"],
                        jnp.asarray(np.asarray(rec["writePages"],
                                               np.int32)),
                        rec["nShared"] * self.page_len)
                first = rec["first"]
                req.stages.append(
                    ("prefill", rec["admitT0"], time.monotonic(),
                     {"promptTokens": rec["s"], "slot": slot,
                      "sharedPages": rec["nShared"],
                      "tenant": tenant}))
                if rec["insert"] is not None:
                    # only after the pages are WRITTEN does the entry
                    # become shareable — inserting in _prepare would
                    # let a concurrent lookup hit pages whose KV has
                    # not landed yet
                    self.prefix.insert(*rec["insert"])
            if rec["dpcache"] is not None and self._spec_on():
                self._draft_cache = self._draft_join(
                    self._draft_cache, rec["dpcache"],
                    jnp.asarray(np.int32(slot)))
        except BaseException:
            if rec.get("published"):
                self.pool.decref(row)
            self.pool.decref(row, tenant)
            if rec["donorTail"] is not None:
                self.pool.decref([rec["donorTail"]])
            raise
        if rec["donorTail"] is not None:
            self.pool.decref([rec["donorTail"]])
        if rec.get("published"):
            # adopt: the decode worker now owns the stream refs — the
            # publish hold has done its job
            self.pool.decref(row)
        now = time.monotonic()
        self._record_role("prefill", now - rec["admitT0"])
        self._ttft.record(now - req.queued_at)
        self._slot_req[slot] = req
        self._slot_out[slot] = [first]
        self._slot_left[slot] = rec["new"] - 1
        self._slot_t0[slot] = now
        self._tok[slot, 0] = first
        self._col[slot] = rec["s"]
        self._keys[slot] = rec["subDecode"]
        self._bt[slot, :] = 0
        self._bt[slot, :len(row)] = row
        self._slot_pages[slot] = row
        self._slot_tenant[slot] = tenant
        self.tokens_total += 1
        if self._slot_left[slot] <= 0:
            self._retire(slot)

    def _retire(self, slot: int) -> None:
        if not self._degraded:
            pages = self._slot_pages[slot]
            if pages:
                self.pool.decref(pages, self._slot_tenant[slot])
            self._slot_pages[slot] = []
            self._slot_tenant[slot] = None
            self._bt[slot, :] = 0  # lane appends go to the trash page
        super()._retire(slot)

    def _gather_width(self, extra: int = 0) -> int:
        """Bounded paged gather: slice every block table to the
        power-of-2 page bucket covering the longest LIVE stream, so
        short streams never pay HBM reads for long-stream pages (and
        the step compiles once per bucket, log2(pages/stream) total).
        ``extra`` widens the bucket for a speculative verify window,
        which appends up to spec_k tokens past each stream's col."""
        need = 1
        for slot in range(self.slots):
            if self._slot_req[slot] is not None:
                need = max(need, (int(self._col[slot]) + extra)
                           // self.page_len + 1)
        width = 1
        while width < need:
            width *= 2
        return min(width, self._pages_per_slot)

    def _run_step(self):
        if self._degraded:
            return super()._run_step()
        # periodic quality gate BEFORE the step (worker thread): a
        # breach degrades to bf16 here and the step below reroutes
        # through the rebuilt exact path cleanly
        self._steps_since_probe += 1
        if self._steps_since_probe >= max(
                1, int(getattr(self._ctx.config,
                               "serve_drift_every", 256))):
            self._maybe_probe_drift()
            if self._degraded:
                return super()._run_step()
        import jax.numpy as jnp

        width = self._gather_width()
        nxt, self._pool_tree = self._pstep(
            self._serve_params, self._pool_tree,
            jnp.asarray(self._tok), jnp.asarray(self._col),
            jnp.asarray(self._bt[:, :width]),
            jnp.asarray(self._keys))
        return nxt

    def _decode_round(self, active: List[int]) -> None:
        if self._spec_on():
            return self._spec_round(active)
        return super()._decode_round(active)

    def _spec_round(self, active: List[int]) -> None:
        """One speculative decode iteration: the draft proposes
        spec_k greedy tokens per live stream, the target scores the
        whole window in ONE paged verify step, and exact rejection
        sampling accepts a prefix — so each round lands 1..spec_k+1
        tokens per stream at roughly one target step's latency. The
        greedy path is bit-identical to solo decode by construction
        (accept iff the draft matched the target argmax)."""
        import jax.numpy as jnp

        draft_t0 = time.monotonic()
        tok = jnp.asarray(self._tok)
        col = jnp.asarray(self._col)
        drafts, self._draft_cache = self._draft_propose(
            self._draft_params, self._draft_cache, tok, col)
        drafts_np = np.asarray(drafts)  # sync: draft wall time
        draft_t1 = time.monotonic()
        self._record_role("draft", draft_t1 - draft_t0)
        # last FUNDED position per slot: appends past it are
        # trash-routed inside the verify kernel, and the host-side
        # `take` clamp below discards the matching garbage emissions
        limit = np.zeros((self.slots,), np.int32)
        for slot in range(self.slots):
            limit[slot] = max(
                0, len(self._slot_pages[slot]) * self.page_len - 1)
        width = self._gather_width(extra=self._spec_k)
        emitted, n_acc, self._pool_tree = self._verify(
            self._serve_params, self._pool_tree, tok,
            jnp.asarray(drafts_np), col, jnp.asarray(self._keys),
            jnp.asarray(self._bt[:, :width]), jnp.asarray(limit))
        emitted = np.asarray(emitted)
        n_acc = np.asarray(n_acc)
        self._decode_seconds += time.monotonic() - draft_t0
        self.decode_steps += 1
        self.spec_steps += 1
        self.spec_slot_steps += len(active)
        for slot in active:
            take = max(1, min(int(n_acc[slot]) + 1,
                              int(self._slot_left[slot]),
                              self.cache_len - 1 - int(self._col[slot])))
            toks = [int(x) for x in emitted[slot, :take]]
            self._slot_out[slot].extend(toks)
            self._slot_left[slot] -= take
            self.tokens_total += take
            self.decode_tokens_total += take
            self.spec_accepted_total += take - 1
            self.spec_emitted_total += take
            self._tok[slot, 0] = toks[-1]
            self._col[slot] += take
            if (self._slot_left[slot] <= 0
                    or self._col[slot] >= self.cache_len - 1):
                self._retire(slot)

    # -- degrade ladder ------------------------------------------------
    def _degrade_to_slot(self) -> None:
        """Latched ``kv_page_alloc``: fail in-flight paged streams,
        drop the pool, build the contiguous slot path, and serve
        every later request through the inherited machinery (one rung
        down the degradation ladder, never an outage)."""
        if self._degraded:
            return
        self._degraded = True
        # the slot path has no paged verify kernel — speculation ends
        # here (the draft model and its cache are dropped with it)
        self._release_spec_state()
        for slot in range(self.slots):
            req = self._slot_req[slot]
            self._slot_req[slot] = None
            self._slot_out[slot] = []
            self._slot_pages[slot] = []
            self._slot_tenant[slot] = None
            if req is not None:
                req.fail(V.HttpError(
                    V.HTTP_UNAVAILABLE,
                    "session degraded to the slot KV path mid-stream "
                    "(kv_page_alloc latched) — retry"))
        self._pool_tree = None  # free the pool before the slot cache
        self._tok[:] = 0
        self._col[:] = 0
        self._keys[:] = 0
        self._slot_left[:] = 0
        LMServingSession._init_decode_path(self)
        obs_xray.release("kv-cache", ("kv", self.name, id(self)))
        obs_xray.register("kv-cache", ("kv", self.name, id(self)),
                          self._cache_bytes, name=self.name,
                          slots=self.slots, cacheLen=self.cache_len,
                          degraded=True)
        obs_export.log_event("serving", "kv-degrade", model=self.name,
                             streak=self._alloc_fault_streak)
        obs_incidents.trigger("serving:kv-degrade", model=self.name,
                              streak=self._alloc_fault_streak)

    def _degrade_to_bf16(self, reason: str) -> None:
        """Latched ``kv_quant`` fault or drift-gate breach: drop the
        quantized plane and rebuild the SAME paged machinery over
        exact bf16 pages and weights — one rung down the quantization
        ladder (the ``kv_page_alloc`` ladder above can still take it
        the rest of the way to the slot path). In-flight quantized
        streams fail with a retryable 503 and the pool, prefix cache
        and block tables rebuild from scratch, so stale quantized
        state can never leak into the exact path."""
        if self.kv_dtype == "bf16" and self.weights_dtype == "bf16":
            return
        from_kv, from_w = self.kv_dtype, self.weights_dtype
        for slot in range(self.slots):
            req = self._slot_req[slot]
            self._slot_req[slot] = None
            self._slot_out[slot] = []
            self._slot_pages[slot] = []
            self._slot_tenant[slot] = None
            if req is not None:
                req.fail(V.HttpError(
                    V.HTTP_UNAVAILABLE,
                    f"session degraded to bf16 serving mid-stream "
                    f"({reason}) — retry"))
        self._tok[:] = 0
        self._col[:] = 0
        self._keys[:] = 0
        self._slot_left[:] = 0
        self.kv_dtype = "bf16"
        self._pool_tree = None  # free the int8 pool before the bf16 one
        if self.weights_dtype != "bf16":
            import jax

            self.weights_dtype = "bf16"
            self._serve_params = self._quantize_params("bf16")
            self._params_entry.release()
            obs_xray.release("serving-params", self._params_pin_key)
            self._params_entry = self._pin_params()
            self._param_bytes = int(sum(
                a.nbytes for a in
                jax.tree_util.tree_leaves(self._serve_params)))
        # rebuild the paged decode path over exact dtypes, preserving
        # the host-side accounting the rebuild would otherwise reset
        saved = (self._tenant_latency, self._tenant_requests,
                 self._adhoc_tenants, self._last_drift,
                 self._drift_parts, self._drift_probes)
        PagedLMServingSession._init_decode_path(self)
        (self._tenant_latency, self._tenant_requests,
         self._adhoc_tenants, self._last_drift,
         self._drift_parts, self._drift_probes) = saved
        obs_xray.release("kv-cache", ("kv", self.name, id(self)))
        obs_xray.register("kv-cache", ("kv", self.name, id(self)),
                          self._cache_bytes, name=self.name,
                          slots=self.slots, cacheLen=self.cache_len,
                          pages=self.n_pages, dtype=self.kv_dtype)
        health_lib.record("quantDegrades")
        obs_export.log_event("serving", "quant-degrade",
                             model=self.name, reason=reason,
                             fromKv=from_kv, fromWeights=from_w)
        obs_incidents.trigger("serving:quant-degrade",
                              model=self.name, reason=reason,
                              fromKv=from_kv, fromWeights=from_w)

    # -- quantization quality gate ------------------------------------
    def _maybe_probe_drift(self, force: bool = False) -> None:
        """Measure quantized-vs-exact drift on the held probe batch
        and walk the degrade ladder on breach. No-op for fully-exact
        sessions; never raises (a broken probe must not kill the
        worker — it logs and the next probe retries)."""
        self._steps_since_probe = 0
        if self._degraded or (self.kv_dtype == "bf16"
                              and self.weights_dtype == "bf16"):
            return
        try:
            drift, parts = self._measure_drift()
        except Exception as exc:  # noqa: BLE001
            obs_export.log_event("serving", "drift-probe-error",
                                 model=self.name, error=str(exc))
            return
        self._last_drift = drift
        self._drift_parts = parts
        self._drift_probes += 1
        from learningorchestra_tpu.observability import slo as obs_slo

        obs_slo.set_gauge("servingDrift", drift)
        limit = float(getattr(self._ctx.config,
                              "serve_drift_max", 0.05) or 0.0)
        if limit > 0 and drift > limit:
            health_lib.record("driftBreaches")
            self._degrade_to_bf16(
                f"probe drift {drift:.4f} > "
                f"LO_SERVE_DRIFT_MAX={limit:g}")

    def _measure_drift(self) -> Tuple[float, Dict[str, float]]:
        """Quantized-vs-exact relative L1 drift, per component:

        - ``kv``: one paged decode-attention step over a held random
          KV probe, int8 pools + fused dequant vs the exact bf16
          gather (pure ops — no session state is touched);
        - ``weights``: the session's compiled prefill over a held
          probe prompt, quantized pinned params vs the fp32/bf16
          master tree, compared on the final logit row.

        The probe batch is deterministic (seeded) so repeated probes
        measure quantization, not sampling noise."""
        import jax
        import jax.numpy as jnp

        from learningorchestra_tpu.ops import attention as attn_ops

        parts: Dict[str, float] = {}
        rng = np.random.default_rng(0)

        def rel(a, b):
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            return float(np.mean(np.abs(a - b)) /
                         (np.mean(np.abs(a)) + 1e-9))

        if self.kv_dtype == "int8":
            leaf = next(a for a in
                        jax.tree_util.tree_leaves(self._pool_tree)
                        if getattr(a, "ndim", 0) == 4)
            _, pl, kv, d = leaf.shape
            heads = int(getattr(self._model, "n_heads", kv) or kv)
            n_probe = 4
            kp = jnp.asarray(rng.normal(
                size=(n_probe, pl, kv, d)).astype(np.float32))
            vp = jnp.asarray(rng.normal(
                size=(n_probe, pl, kv, d)).astype(np.float32))
            bt = jnp.arange(n_probe, dtype=jnp.int32)[None, :]
            col = jnp.asarray([n_probe * pl - 1], jnp.int32)
            q = jnp.asarray(rng.normal(
                size=(1, 1, heads, d)).astype(np.float32))
            exact = attn_ops.paged_decode_attention(q, kp, vp, bt, col)
            kq, ks = attn_ops.quantize_kv_pages(kp)
            vq, vs = attn_ops.quantize_kv_pages(vp)
            quant = attn_ops.quantized_paged_decode_attention(
                q, kq, ks, vq, vs, bt, col)
            parts["kv"] = rel(exact, quant)
        if self.weights_dtype != "bf16":
            probe_len = max(1, min(8, self.cache_len - 1))
            prompt = rng.integers(
                1, int(self._model.vocab_size),
                size=(1, probe_len)).astype(np.int32)
            prefill = self._pprefill_for(probe_len)
            key = jax.random.PRNGKey(0)
            _, exact_logits, _ = prefill(
                self._model.params, jnp.asarray(prompt), key)
            _, quant_logits, _ = prefill(
                self._serve_params, jnp.asarray(prompt), key)
            parts["weights"] = rel(exact_logits, quant_logits)
        drift = max(parts.values()) if parts else 0.0
        return drift, parts

    def stats(self) -> Dict[str, Any]:
        out = super().stats()
        tenants: Dict[str, Any] = {}
        names = set(self.pool.tenants()) | set(self._tenant_latency)
        for t in sorted(names):
            tracker = self._tenant_latency.get(t)
            tenants[t] = {
                "weight": self._weight(t),
                "pages": self.pool.tenant_pages(t),
                "requests": self._tenant_requests.get(t, 0),
                "latency": tracker.snapshot() if tracker else
                {"count": 0, "p50Ms": 0.0, "p99Ms": 0.0},
            }
        kv = self.pool.stats()
        kv["mode"] = "slot-degraded" if self._degraded else "paged"
        # true bytes resident per token of KV capacity (int8 pages +
        # their scale pool, or the bf16 pool) — feeds the
        # lo_serving_kv_bytes_per_token gauge
        denom = (self.slots * self.cache_len if self._degraded
                 else self.n_pages * self.page_len)
        kv["bytesPerToken"] = round(
            self._cache_bytes / float(max(1, denom)), 3)
        prefix = self.prefix.stats()
        prefix["prefillsSkipped"] = self.prefills_skipped
        kv["prefix"] = prefix
        kv["tenants"] = tenants
        out["kv"] = kv
        if self._last_drift is not None:
            out["drift"] = {
                "value": round(self._last_drift, 6),
                "parts": {k: round(v, 6)
                          for k, v in self._drift_parts.items()},
                "probes": self._drift_probes,
                "max": float(getattr(self._ctx.config,
                                     "serve_drift_max", 0.05) or 0.0),
            }
        if self._draft_name:
            out["spec"] = {
                "draft": self._draft_name,
                "specK": self._spec_k,
                "steps": self.spec_steps,
                "acceptedTokensPerStep": round(
                    self.spec_accepted_total /
                    max(1, self.spec_slot_steps), 4),
                "acceptedTokensTotal": self.spec_accepted_total,
                "active": self._spec_on(),
            }
        return out

    def perf_stats(self) -> Dict[str, Any]:
        out = super().perf_stats()
        if out and self._draft_name and self.spec_slot_steps:
            out["acceptedTokensPerStep"] = round(
                self.spec_accepted_total / self.spec_slot_steps, 4)
        return out


class DisaggLMServingSession(PagedLMServingSession):
    """Disaggregated prefill/decode serving (``LO_SERVE_DISAGG=1`` or
    per-session ``disagg: true``, docs/SERVING.md "Disaggregated
    serving & speculative decoding").

    A dedicated PREFILL worker thread pops admitted prompts off the
    queue, runs :meth:`_prepare` (quota + page funding + the prefill
    forward) and publishes the finished handoff record — its KV pages
    pinned by an extra publish incref — onto a ready queue. The DECODE
    worker (the inherited session thread) adopts records into free
    slots via :meth:`_install` between decode iterations, so a burst
    of long prompts never stalls in-flight token streams: decode
    iterations keep their cadence while prefill compute overlaps on
    the other thread. Pages are handed off by reference counting,
    never copied.

    Lease placement: when the serving fleet has capacity for two
    grants (``LO_MESH_LEASES >= 2``, the ``preempt`` policy, and a
    mesh of >= 2 devices), the session runs split: the device line is
    carved into DISJOINT sub-slices — prefill takes
    ``prefillDevices`` (default half the mesh) as its OWN
    ``ServingLease`` (role ``prefill``) through the same fair queue,
    and the decode lease refits onto the remainder before params pin.
    Disjointness is what lets both grants be live at once (a
    ``footprint=None`` grant is a full-mesh gang, and two gangs can
    only ping-pong). Otherwise the session runs "colocated": both
    workers share the decode lease, and the overlap comes from the
    GIL dropping during XLA compute.

    Thread contract: the device pool tree (and the draft cache) are
    DONATED buffers — only the decode thread ever mutates them.
    _prepare touches host-side refcounts (pool, prefix cache — both
    internally locked) and runs non-donating prefill kernels, so the
    two workers never race a donation. Degrades latch on the decode
    thread: the prefill worker only ever *requests* one via
    ``_degrade_pending``.

    A latched ``kv_page_handoff`` fault collapses the session to
    FUSED mode (``disagg.mode = "fused-degraded"``): in-flight
    streams fail with a retryable 503, published-but-unadopted
    records are drained with every page reference restored, an
    incident bundle fires, and all later requests serve through the
    inherited fused machinery — one rung down, never an outage, never
    a corrupted stream.
    """

    def __init__(self, name: str, ctx, lease: ServingLease, model,
                 slots: int, cache_len: int, temperature: float,
                 top_k: Optional[int], top_p: Optional[float],
                 page_len: int, n_pages: int,
                 tenant_weights: Optional[Dict[str, float]] = None,
                 kv_dtype: str = "bf16",
                 weights_dtype: str = "bf16",
                 draft_model=None, draft_name: str = "",
                 spec_k: int = 4,
                 prefill_devices: Optional[int] = None):
        # handoff state first: super().__init__ reaches _publishes()
        # through _prepare only after start(), but keep construction
        # order obviously safe
        self._ready: Deque[Dict[str, Any]] = collections.deque()
        self._handoff_cv = locks.make_condition("serving.handoff")
        self._degrade_pending: Optional[Tuple[str, str]] = None
        self._handoff_fault_streak = 0
        self.handoffs_total = 0
        self._prefill_lease: Optional[ServingLease] = None
        self._prefill_thread: Optional[threading.Thread] = None
        self.disagg_mode = "colocated"
        slices = ctx.jobs.slice_lease
        total = slices.total_devices() \
            if getattr(slices, "capacity", 1) >= 2 else 1
        if total >= 2 and lease.policy == "preempt":
            # true split: carve the device line into DISJOINT
            # sub-slices — footprint=None is a full-mesh gang grant,
            # and two gangs can never be live at once, so a
            # full-mesh prefill holder would wedge the decode
            # re-acquire forever. Prefill takes prefillDevices
            # (default: half the mesh); the decode lease refits from
            # its create-time full-mesh grant onto the remainder
            # BEFORE super().__init__ pins params, so placement is
            # final by the time buffers land. The prefill lease
            # itself is acquired lazily INSIDE the worker thread —
            # acquiring here would serialize create behind a
            # contended fleet.
            pre = min(int(prefill_devices) if prefill_devices
                      else max(1, total // 2), total - 1)
            lease.refit({"devices": total - pre})
            self._prefill_lease = ServingLease(
                slices, pool="serving", policy="preempt",
                footprint={"devices": pre}, role="prefill")
            self.disagg_mode = "split"
        super().__init__(name, ctx, lease, model, slots, cache_len,
                         temperature, top_k, top_p, page_len, n_pages,
                         tenant_weights, kv_dtype=kv_dtype,
                         weights_dtype=weights_dtype,
                         draft_model=draft_model,
                         draft_name=draft_name, spec_k=spec_k)
        lease.set_role("decode")
        self._prefill_thread = threading.Thread(
            target=self._prefill_run,
            name=f"serving-{name}-prefill", daemon=True)

    def start(self) -> None:
        super().start()
        self._prefill_thread.start()

    # -- mode ----------------------------------------------------------
    def _fused(self) -> bool:
        return self._degraded or self.disagg_mode == "fused-degraded"

    def _publishes(self) -> bool:
        return not self._fused()

    def _note_handoff_fault(self) -> None:
        self._handoff_fault_streak += 1
        if self._handoff_fault_streak >= self._DEGRADE_AFTER and \
                self._degrade_pending is None and not self._fused():
            self._degrade_pending = (
                "fused", "kv_page_handoff fault latched")

    def _note_handoff_ok(self) -> None:
        self._handoff_fault_streak = 0

    # -- prefill worker ------------------------------------------------
    def _prefill_run(self) -> None:
        acquired = False
        try:
            while True:
                with self._cv:
                    if self._closed or self._fused():
                        break
                    req = None
                    if self._degrade_pending is None and \
                            self._queue and \
                            len(self._ready) < self.slots:
                        # backpressure: at most `slots` records in
                        # flight, so a prompt flood cannot fund pages
                        # faster than decode retires them
                        req = self._pop_next()
                    if req is None:
                        self._cv.wait(timeout=_IDLE_TICK_SECONDS)
                if req is None:
                    if acquired:
                        # never camp on the slice while idle: a gang
                        # batch job (every device) can only run once
                        # BOTH serving workers yield, and an idle
                        # prefill holder would block it forever
                        self._prefill_lease.maybe_yield()
                    continue
                req.popped_at = time.monotonic()
                if self._prefill_lease is not None:
                    if not acquired:
                        self._prefill_lease.acquire()
                        acquired = True
                    self._prefill_lease.maybe_yield()
                try:
                    rec = self._prepare(req)
                except V.HttpError as exc:
                    req.fail(exc)
                    continue
                except Exception as exc:  # noqa: BLE001
                    req.fail(V.HttpError(
                        V.HTTP_UNAVAILABLE,
                        f"prefill failed: {exc}"))
                    continue
                publish = False
                with self._handoff_cv:
                    # mode is written under this lock by
                    # _collapse_to_fused, so a record can never slip
                    # into _ready after the drain
                    if not self._fused():
                        self._ready.append(rec)
                        self.handoffs_total += 1
                        publish = True
                if not publish:
                    self._discard_record(rec, V.HttpError(
                        V.HTTP_UNAVAILABLE,
                        "session collapsed to fused prefill+decode — "
                        "retry"))
                    continue
                with self._cv:
                    self._cv.notify_all()
        finally:
            if acquired:
                self._prefill_lease.release()

    # -- decode worker -------------------------------------------------
    def _have_work(self) -> bool:
        if self._fused():
            return super()._have_work()
        return (bool(self._ready)
                or self._degrade_pending is not None
                or any(r is not None for r in self._slot_req))

    def _serve_once(self) -> bool:
        pending = self._degrade_pending
        if pending is not None:
            self._degrade_pending = None
            kind, reason = pending
            if kind == "bf16":
                PagedLMServingSession._degrade_to_bf16(self, reason)
            else:
                if not self._fused():
                    self._collapse_to_fused(reason)
                if kind == "slot":
                    PagedLMServingSession._degrade_to_slot(self)
        if self._fused():
            return super()._serve_once()
        did = self._adopt_ready()
        active = self._active_slots()
        if not active:
            return did
        self._decode_round(active)
        return True

    def _adopt_ready(self) -> bool:
        """Move published handoff records into free slots (decode
        thread). Adoption decrefs the publish hold — from here the
        stream owns its pages exactly like a fused admission."""
        did = False
        while True:
            with self._handoff_cv:
                if not self._ready:
                    break
                rec = self._ready.popleft()
            free = [i for i, r in enumerate(self._slot_req)
                    if r is None]
            if not free:
                with self._handoff_cv:
                    self._ready.appendleft(rec)
                break
            try:
                self._install(free[0], rec)
                did = True
            except V.HttpError as exc:
                rec["req"].fail(exc)
            except Exception as exc:  # noqa: BLE001
                rec["req"].fail(V.HttpError(
                    V.HTTP_UNAVAILABLE,
                    f"prefill install failed: {exc}"))
        return did

    # -- degrade -------------------------------------------------------
    def _degrade_to_slot(self) -> None:
        if threading.current_thread() is self._prefill_thread:
            if self._degrade_pending is None:
                self._degrade_pending = (
                    "slot", "kv_page_alloc latched")
            return
        if not self._fused():
            self._collapse_to_fused("kv_page_alloc latched")
        super()._degrade_to_slot()

    def _degrade_to_bf16(self, reason: str) -> None:
        if threading.current_thread() is self._prefill_thread:
            # the rebuild swaps the donated pool tree — decode-thread
            # work; the prefill worker pauses until it lands
            if self._degrade_pending is None:
                self._degrade_pending = ("bf16", reason)
            return
        super()._degrade_to_bf16(reason)

    def _collapse_to_fused(self, reason: str) -> None:
        """Latched handoff fault (or a slot degrade beneath it): stop
        disaggregating. Decode thread only."""
        with self._handoff_cv:
            if self.disagg_mode == "fused-degraded":
                return
            self.disagg_mode = "fused-degraded"
        for slot in range(self.slots):
            req = self._slot_req[slot]
            if req is None:
                continue
            pages = self._slot_pages[slot]
            if pages:
                self.pool.decref(pages, self._slot_tenant[slot])
            self._slot_req[slot] = None
            self._slot_out[slot] = []
            self._slot_left[slot] = 0
            self._slot_pages[slot] = []
            self._slot_tenant[slot] = None
            self._bt[slot, :] = 0
            req.fail(V.HttpError(
                V.HTTP_UNAVAILABLE,
                f"session collapsed to fused prefill+decode "
                f"mid-stream ({reason}) — retry"))
        self._drain_ready(V.HttpError(
            V.HTTP_UNAVAILABLE,
            f"prefill worker degraded ({reason}) — retry"))
        obs_export.log_event("serving", "handoff-degrade",
                             model=self.name, reason=reason,
                             streak=self._handoff_fault_streak)
        obs_incidents.trigger("serving:handoff-degrade",
                              model=self.name, reason=reason)

    def _drain_ready(self, error: V.HttpError) -> None:
        while True:
            with self._handoff_cv:
                if not self._ready:
                    return
                rec = self._ready.popleft()
            self._discard_record(rec, error)

    def _discard_record(self, rec: Dict[str, Any],
                        error: V.HttpError) -> None:
        """Release every page reference a published record owns (the
        publish hold AND the stream refs) and fail its request — the
        free count must come back exactly to where a normal
        admit+retire would have left it."""
        if rec.get("published"):
            self.pool.decref(rec["row"])
        if rec["row"]:
            self.pool.decref(rec["row"], rec["tenant"])
        if rec["donorTail"] is not None:
            self.pool.decref([rec["donorTail"]])
        rec["req"].fail(error)

    def close(self) -> None:
        super().close()
        thread = self._prefill_thread
        if thread is not None and thread.is_alive():
            with self._cv:
                self._cv.notify_all()
            thread.join(timeout=30.0)
        self._drain_ready(V.HttpError(
            V.HTTP_UNAVAILABLE,
            f"serving session {self.name} was deleted"))
        if self._prefill_lease is not None:
            self._prefill_lease.release()  # idempotent

    def stats(self) -> Dict[str, Any]:
        out = super().stats()
        with self._handoff_cv:
            qlen = len(self._ready)
        leases: Dict[str, Any] = {"decode": self._lease.stats()}
        if self._prefill_lease is not None:
            leases["prefill"] = self._prefill_lease.stats()
        out["disagg"] = {
            "mode": self.disagg_mode,
            "handoffsTotal": self.handoffs_total,
            "handoffQueue": qlen,
            "handoffFaultStreak": self._handoff_fault_streak,
            "leases": leases,
        }
        return out


class BucketServingSession(_SessionBase):
    """Shape-bucketed micro-batcher for ``predict``-style models.

    Queued requests aggregate for up to ``LO_SERVE_MAX_WAIT_MS`` (or
    until the largest bucket fills), the stacked rows pad to the
    smallest precompiled bucket >= n, and ONE ``predict`` call serves
    the whole burst through the PR-3 executable cache — so a warm
    request never traces, never touches the catalog, and never waits
    on the job queue."""

    kind = "predict"

    def __init__(self, name: str, ctx, lease: ServingLease, instance):
        super().__init__(name, ctx, lease)
        self._instance = instance
        buckets = sorted({int(b) for b in
                          str(ctx.config.serve_buckets).split(",") if b})
        self.buckets = [b for b in buckets if b > 0] or [1]
        self._max_wait = float(ctx.config.serve_max_wait_ms) / 1e3
        self.predicts_total = 0
        self.rows_total = 0
        self._last_fill: Optional[float] = None
        # fill-weighted goodput accounting: useful rows vs padded
        # bucket capacity, and the device time spent producing them
        self._predict_seconds = 0.0
        self._fill_rows_sum = 0
        self._fill_bucket_sum = 0

    def validate_request(self, payload: Dict[str, Any]) -> None:
        x = payload.get("x")
        if not isinstance(x, (list, tuple)) or not x:
            raise V.HttpError(
                V.HTTP_NOT_ACCEPTABLE,
                f"{V.MESSAGE_INVALID_FIELD}: x must be a non-empty "
                f"list of feature rows")

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _serve_once(self) -> bool:
        # gather a burst: first request opens the window, then wait up
        # to max_wait for co-travelers (bounded by the largest bucket)
        limit = self.buckets[-1]
        batch: List[_Request] = []
        rows = 0
        deadline = None
        while True:
            with self._cv:
                while self._queue and rows < limit:
                    req = self._queue.popleft()
                    req.popped_at = time.monotonic()
                    n = len(req.payload["x"])
                    batch.append(req)
                    rows += n
                if not batch:
                    return False
                if rows >= limit:
                    break
                if deadline is None:
                    deadline = time.monotonic() + self._max_wait
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cv.wait(timeout=remaining)
                if not self._queue:
                    break
        try:
            stacked = np.concatenate(
                [np.asarray(r.payload["x"]) for r in batch], axis=0)
        except ValueError as exc:
            for req in batch:
                req.fail(V.HttpError(
                    V.HTTP_NOT_ACCEPTABLE,
                    f"{V.MESSAGE_INVALID_FIELD}: rows do not stack "
                    f"({exc})"))
            return True
        n = stacked.shape[0]
        bucket = self._bucket_for(n)
        if bucket > n:
            # pad the batch dim with row 0 so the compiled bucket shape
            # is hit exactly; padded rows are sliced off below
            pad = np.repeat(stacked[:1], bucket - n, axis=0)
            stacked = np.concatenate([stacked, pad], axis=0)
        predict_t0 = time.monotonic()
        try:
            out = np.asarray(self._instance.predict(stacked))
        except Exception as exc:  # noqa: BLE001
            for req in batch:
                req.fail(V.HttpError(V.HTTP_UNAVAILABLE,
                                     f"predict failed: {exc}"))
            return True
        predict_t1 = time.monotonic()
        self.predicts_total += 1
        self.rows_total += n
        self._last_fill = round(n / bucket, 4)
        self._predict_seconds += predict_t1 - predict_t0
        self._fill_rows_sum += n
        self._fill_bucket_sum += bucket
        offset = 0
        for req in batch:
            k = len(req.payload["x"])
            req.stages.append(("batchForm", req.popped_at, predict_t0,
                               {"rows": k}))
            req.stages.append(("predict", predict_t0, predict_t1,
                               {"bucket": bucket, "batchRows": n}))
            req.finish({"predictions": out[offset:offset + k].tolist(),
                        "bucket": bucket})
            offset += k
        return True

    def _batch_fill(self) -> Optional[float]:
        return self._last_fill

    def perf_stats(self) -> Dict[str, Any]:
        if not self.predicts_total or self._predict_seconds <= 0:
            return {}
        n = self._n_chips()
        rps = self._fill_rows_sum / self._predict_seconds
        return {
            "predictsTotal": self.predicts_total,
            "rowsPerSec": round(rps, 2),
            "rowsPerSecPerChip": round(rps / n, 3),
            # fill-weighted goodput: useful rows over padded capacity
            "goodputFrac": round(
                self._fill_rows_sum / max(1, self._fill_bucket_sum), 4),
        }

    def stats(self) -> Dict[str, Any]:
        out = super().stats()
        out.update({
            "buckets": self.buckets,
            "predictsTotal": self.predicts_total,
            "rowsTotal": self.rows_total,
        })
        return out


class ServingManager:
    """Session registry + REST verbs (create/predict/stats/delete).

    One session per model name; sessions share the JobManager's
    SliceLease allocator through ``ServingLease`` handles so resident
    serving and batch gang jobs contend in one fair queue."""

    def __init__(self, ctx):
        self._ctx = ctx
        self._sessions: Dict[str, _SessionBase] = {}
        self._lock = locks.make_lock("serving.manager")

    # -- verbs ---------------------------------------------------------
    def create(self, model_name: str, body: Dict[str, Any]) -> Dict[str, Any]:
        body = body or {}
        with self._lock:
            if model_name in self._sessions:
                raise V.HttpError(
                    V.HTTP_CONFLICT,
                    f"{V.MESSAGE_DUPLICATE_FILE}: serving session for "
                    f"{model_name} already exists")
        type_string = self._ctx.params.artifact_type(model_name)
        if type_string is None:
            raise V.HttpError(V.HTTP_NOT_FOUND,
                              f"{V.MESSAGE_NONEXISTENT_FILE}: "
                              f"{model_name}")
        instance = self._ctx.artifacts.load(model_name, type_string)
        kind = body.get("type")
        if kind is None:
            kind = "lm" if hasattr(instance, "serve_fns") else "predict"
        if kind not in ("lm", "predict"):
            raise V.HttpError(
                V.HTTP_NOT_ACCEPTABLE,
                f"{V.MESSAGE_INVALID_FIELD}: type must be 'lm' or "
                f"'predict', got {kind!r}")
        footprint = None
        devices = V.valid_slice_devices(body.get(V.SLICE_DEVICES_FIELD))
        if devices is not None:
            footprint = {"devices": devices}
        lease = ServingLease(
            self._ctx.jobs.slice_lease, pool="serving",
            policy=self._ctx.config.serve_lease_policy,
            footprint=footprint)
        lease.acquire()
        try:
            session = self._build_session(model_name, instance, kind,
                                          body, lease)
        except BaseException:
            lease.release()
            raise
        session.start()
        with self._lock:
            if model_name in self._sessions:  # lost a create race
                session.close()
                raise V.HttpError(
                    V.HTTP_CONFLICT,
                    f"{V.MESSAGE_DUPLICATE_FILE}: serving session for "
                    f"{model_name} already exists")
            self._sessions[model_name] = session
        obs_export.log_event("serving", "create", model=model_name,
                             sessionKind=kind)
        return session.stats()

    def _build_session(self, model_name: str, instance: Any, kind: str,
                       body: Dict[str, Any],
                       lease: ServingLease) -> _SessionBase:
        if kind == "lm":
            if not hasattr(instance, "serve_fns"):
                raise V.HttpError(
                    V.HTTP_NOT_ACCEPTABLE,
                    f"{V.MESSAGE_INVALID_FIELD}: {model_name} is not a "
                    f"language model (no decode cache support)")
            slots = V.valid_positive_int(
                body.get("maxSlots"), "maxSlots",
                default=self._ctx.config.serve_max_batch)
            cache_len = V.valid_positive_int(
                body.get("cacheLen"), "cacheLen",
                default=int(instance.max_len))
            cache_len = min(cache_len, int(instance.max_len))
            temperature, top_k, top_p = V.valid_sampling(body)
            if top_k is not None and top_k >= instance.vocab_size:
                top_k = None
            cfg = self._ctx.config
            kv_mode = str(body.get("kv") or cfg.serve_kv or "slot")
            if kv_mode not in ("slot", "paged"):
                raise V.HttpError(
                    V.HTTP_NOT_ACCEPTABLE,
                    f"{V.MESSAGE_INVALID_FIELD}: kv must be 'slot' or "
                    f"'paged', got {kv_mode!r}")
            # quantized serving knobs (docs/SERVING.md "Quantized
            # serving"): per-session request fields override the
            # config defaults; both validate at the door
            kv_dtype = V.valid_choice(
                body.get("kvDtype"), "kvDtype", ("bf16", "int8"),
                default=str(getattr(cfg, "serve_kv_dtype", "bf16")
                            or "bf16"))
            weights_dtype = V.valid_choice(
                body.get("weights"), "weights",
                ("bf16", "int8", "fp8"),
                default=str(getattr(cfg, "serve_weights", "bf16")
                            or "bf16"))
            if kv_mode != "paged" and kv_dtype != "bf16" and \
                    body.get("kvDtype") is not None:
                raise V.HttpError(
                    V.HTTP_NOT_ACCEPTABLE,
                    f"{V.MESSAGE_INVALID_FIELD}: kvDtype={kv_dtype!r} "
                    f"needs the paged KV path (kv='paged') — the slot "
                    f"cache is bf16-only")
            if kv_mode == "paged" and \
                    hasattr(instance, "serve_fns_paged"):
                page_len = V.valid_positive_int(
                    body.get("pageLen"), "pageLen",
                    default=int(cfg.serve_page_len))
                # paged bookkeeping wants cache_len on a page
                # boundary (block tables hold whole pages)
                cache_len = max(
                    page_len, (cache_len // page_len) * page_len)
                pages_per = cache_len // page_len
                # LO_SERVE_PAGES=0 auto-sizes the pool to the slot
                # cache's HBM budget (slots x pages-per-stream, plus
                # the reserved trash page), so paged and slot
                # sessions compare at equal KV bytes
                n_pages = V.valid_positive_int(
                    body.get("pages"), "pages",
                    default=int(cfg.serve_pages)
                    or slots * pages_per + 1)
                n_pages = max(n_pages, pages_per + 1)
                disagg = self._want_disagg(body)
                draft_model, draft_name, spec_k = self._load_draft(
                    body, instance, cache_len)
                weights = parse_tenant_weights(
                    cfg.serve_tenant_weights)
                if disagg:
                    prefill_devices = V.valid_slice_devices(
                        body.get("prefillDevices"))
                    if isinstance(prefill_devices, dict):
                        prefill_devices = prefill_devices.get("max")
                    return DisaggLMServingSession(
                        model_name, self._ctx, lease, instance,
                        slots, cache_len, temperature, top_k, top_p,
                        page_len, n_pages, weights,
                        kv_dtype=kv_dtype,
                        weights_dtype=weights_dtype,
                        draft_model=draft_model,
                        draft_name=draft_name, spec_k=spec_k,
                        prefill_devices=prefill_devices)
                return PagedLMServingSession(
                    model_name, self._ctx, lease, instance, slots,
                    cache_len, temperature, top_k, top_p, page_len,
                    n_pages, weights,
                    kv_dtype=kv_dtype, weights_dtype=weights_dtype,
                    draft_model=draft_model, draft_name=draft_name,
                    spec_k=spec_k)
            if body.get("disagg") or body.get("draft"):
                raise V.HttpError(
                    V.HTTP_NOT_ACCEPTABLE,
                    f"{V.MESSAGE_INVALID_FIELD}: disagg/draft need "
                    f"the paged KV path (kv='paged') — the slot "
                    f"cache has no page handoff or verify step")
            return LMServingSession(
                model_name, self._ctx, lease, instance, slots,
                cache_len, temperature, top_k, top_p,
                weights_dtype=weights_dtype)
        if not hasattr(instance, "predict"):
            raise V.HttpError(
                V.HTTP_NOT_ACCEPTABLE,
                f"{V.MESSAGE_INVALID_FIELD}: {model_name} has no "
                f"predict method")
        return BucketServingSession(model_name, self._ctx, lease,
                                    instance)

    def _want_disagg(self, body: Dict[str, Any]) -> bool:
        """Per-session ``disagg`` field overrides the
        ``LO_SERVE_DISAGG`` config default; must be a JSON bool."""
        raw = body.get("disagg")
        if raw is None:
            return str(getattr(self._ctx.config, "serve_disagg", "0")
                       or "0").strip().lower() in ("1", "true",
                                                   "yes", "on")
        if not isinstance(raw, bool):
            raise V.HttpError(
                V.HTTP_NOT_ACCEPTABLE,
                f"{V.MESSAGE_INVALID_FIELD}: disagg must be a "
                f"boolean, got {raw!r}")
        return raw

    def _load_draft(self, body: Dict[str, Any], instance: Any,
                    cache_len: int):
        """Resolve the speculative-decoding draft model (per-session
        ``draft`` field, else ``LO_SERVE_DRAFT``): a second fitted LM
        artifact that must share the target's vocabulary and cover
        the session's cache length. Returns
        ``(draft_model|None, draft_name, spec_k)``."""
        cfg = self._ctx.config
        raw = body.get("draft")
        if raw is None:
            raw = str(getattr(cfg, "serve_draft", "") or "")
        if not raw:
            return None, "", 4
        if not isinstance(raw, str):
            raise V.HttpError(
                V.HTTP_NOT_ACCEPTABLE,
                f"{V.MESSAGE_INVALID_FIELD}: draft must be a model "
                f"name string, got {raw!r}")
        spec_k = V.valid_positive_int(
            body.get("specK"), "specK",
            default=int(getattr(cfg, "serve_spec_k", 4) or 4))
        type_string = self._ctx.params.artifact_type(raw)
        if type_string is None:
            raise V.HttpError(
                V.HTTP_NOT_FOUND,
                f"{V.MESSAGE_NONEXISTENT_FILE}: draft model {raw}")
        draft = self._ctx.artifacts.load(raw, type_string)
        if not hasattr(draft, "serve_fns_draft"):
            raise V.HttpError(
                V.HTTP_NOT_ACCEPTABLE,
                f"{V.MESSAGE_INVALID_FIELD}: draft {raw} is not a "
                f"language model (no propose support)")
        if int(draft.vocab_size) != int(instance.vocab_size):
            raise V.HttpError(
                V.HTTP_NOT_ACCEPTABLE,
                f"{V.MESSAGE_INVALID_FIELD}: draft vocab "
                f"({draft.vocab_size}) must match the target's "
                f"({instance.vocab_size}) — acceptance sampling "
                f"compares their distributions token-for-token")
        if int(draft.max_len) < int(cache_len):
            raise V.HttpError(
                V.HTTP_NOT_ACCEPTABLE,
                f"{V.MESSAGE_INVALID_FIELD}: draft maxLen "
                f"({draft.max_len}) must cover cacheLen "
                f"({cache_len})")
        return draft, raw, spec_k

    def predict(self, model_name: str,
                body: Dict[str, Any]) -> Dict[str, Any]:
        session = self._get(model_name)
        body = body or {}
        session.validate_request(body)
        timeout = V.valid_timeout(body.get(V.TIMEOUT_FIELD))
        return session.submit(body, timeout=timeout)

    def _get(self, model_name: str) -> _SessionBase:
        with self._lock:
            session = self._sessions.get(model_name)
        if session is None:
            raise V.HttpError(
                V.HTTP_NOT_FOUND,
                f"{V.MESSAGE_NONEXISTENT_FILE}: no serving session "
                f"for {model_name}")
        return session

    def session_stats(self, model_name: str) -> Dict[str, Any]:
        return self._get(model_name).stats()

    def list_sessions(self) -> List[Dict[str, Any]]:
        with self._lock:
            sessions = list(self._sessions.values())
        return [s.stats() for s in sessions]

    def delete(self, model_name: str) -> Dict[str, Any]:
        with self._lock:
            session = self._sessions.pop(model_name, None)
        if session is None:
            raise V.HttpError(
                V.HTTP_NOT_FOUND,
                f"{V.MESSAGE_NONEXISTENT_FILE}: no serving session "
                f"for {model_name}")
        final = session.stats()
        session.close()
        final["deleted"] = True
        obs_export.log_event("serving", "delete", model=model_name)
        return final

    # -- observability / lifecycle ------------------------------------
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            sessions = list(self._sessions.values())
        per = [s.stats() for s in sessions]
        out = {
            "sessions": len(per),
            "requestsTotal": sum(p["requestsTotal"] for p in per),
            "rejectedTotal": sum(p["rejectedTotal"] for p in per),
            "tokensTotal": sum(p.get("tokensTotal", 0) for p in per),
            "leaseYields": sum(p["lease"].get("yields", 0)
                               for p in per),
            "bySession": per,
        }
        # fleet goodput roll-up (each session's per-chip rate is
        # already normalized by its own grant)
        perf_blocks = [p.get("perf") or {} for p in per]
        agg = {
            "decodeTokensPerSec": round(sum(
                b.get("decodeTokensPerSec", 0.0)
                for b in perf_blocks), 2),
            "decodeTokensPerSecPerChip": round(sum(
                b.get("decodeTokensPerSecPerChip", 0.0)
                for b in perf_blocks), 3),
            "rowsPerSecPerChip": round(sum(
                b.get("rowsPerSecPerChip", 0.0)
                for b in perf_blocks), 3),
        }
        if any(v for v in agg.values()):
            out["perf"] = agg
        # paged-KV roll-up for /metrics and the cluster monitor rings
        kv_blocks = [p["kv"] for p in per if p.get("kv")]
        if kv_blocks:
            out["kv"] = {
                "pagesTotal": sum(b["pagesTotal"] for b in kv_blocks),
                "pagesFree": sum(b["pagesFree"] for b in kv_blocks),
                "pagesShared": sum(
                    b["pagesShared"] for b in kv_blocks),
                "allocFailures": sum(
                    b["allocFailures"] for b in kv_blocks),
                "prefillsSkipped": sum(
                    b["prefix"]["prefillsSkipped"]
                    for b in kv_blocks),
            }
        return out

    def perf_report(self, model_name: str) -> Optional[Dict[str, Any]]:
        """Roofline/goodput report for one live session, served by
        ``GET /observability/perf/{name}``; None if no session holds
        the name (the route then falls back to train-job reports)."""
        with self._lock:
            session = self._sessions.get(model_name)
        if session is None:
            return None
        out = {
            "kind": "serving",
            "model": model_name,
            "sessionKind": session.kind,
            "batchFill": session._batch_fill(),
            "perf": session.perf_stats(),
        }
        # quantized sessions carry their dtypes + latest drift probe
        # so the perf report shows WHAT is being measured, not just
        # how fast it runs
        dtypes = {}
        if getattr(session, "weights_dtype", "bf16") != "bf16":
            dtypes["weights"] = session.weights_dtype
        if getattr(session, "kv_dtype", "bf16") != "bf16":
            dtypes["kv"] = session.kv_dtype
        if dtypes:
            out["quantized"] = dtypes
        drift = getattr(session, "_last_drift", None)
        if drift is not None:
            out["drift"] = {
                "value": round(drift, 6),
                "parts": {k: round(v, 6) for k, v in
                          getattr(session, "_drift_parts",
                                  {}).items()},
            }
        return out

    def close(self) -> None:
        with self._lock:
            sessions = list(self._sessions.values())
            self._sessions.clear()
        for session in sessions:
            session.close()
