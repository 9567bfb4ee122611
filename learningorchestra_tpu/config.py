"""Configuration system.

The reference configures everything through env vars baked into
Dockerfiles plus per-image ``Constants`` classes (reference
binary_executor_image/Dockerfile:7-12, constants.py:1-79) — no CLI
flags, no files, no reload. We keep env-var override semantics but add
a single typed config object, an optional JSON config file, and
programmatic overrides, shared by every component.

Env vars use the ``LO_`` prefix: ``LO_HOME``, ``LO_PORT``,
``LO_MESH_SHAPE`` etc.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
from pathlib import Path
from typing import Any, Optional
from learningorchestra_tpu.runtime import locks


@dataclasses.dataclass
class Config:
    """Global framework configuration (one instance per process)."""

    # Storage root: catalog db, parquet datasets, binary artifacts,
    # checkpoints all live under here (replaces the reference's 7
    # shared Docker volumes, docker-compose.yml:325-333).
    home: str = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_HOME", os.path.join(os.getcwd(), ".lo_store")))

    # REST server bind (replaces KrakenD:80 + 9 Flask ports).
    host: str = dataclasses.field(
        default_factory=lambda: os.environ.get("LO_HOST", "127.0.0.1"))
    port: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get("LO_PORT", "5000")))

    # API prefix kept identical to the reference gateway contract.
    api_prefix: str = "/api/learningOrchestra/v1"

    # Job manager.
    max_workers: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get("LO_MAX_WORKERS", "8")))
    # Max concurrent jobs holding the accelerator mesh (a TPU mesh is
    # an exclusive resource, unlike the reference's forgiving threads).
    # At 1 (default): strict whole-mesh serialization. Above 1 the
    # scheduler becomes a SLICE allocator: concurrent jobs are packed
    # onto disjoint device sub-meshes sized by their declared
    # footprint, and footprint-less jobs gang-acquire the full mesh
    # (docs/SCALING.md "Slice scheduling").
    mesh_leases: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get("LO_MESH_LEASES", "1")))
    # Smallest slice the allocator will grant (footprints are rounded
    # up to this many devices).
    slice_min_devices: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_SLICE_MIN_DEVICES", "1")))
    # Anti-starvation bound: a full-mesh (gang) job blocked at its
    # pool head stops smaller jobs from backfilling around it after
    # this many seconds, so releases drain devices toward it. 0 = no
    # freeze (backfill forever).
    slice_aging_seconds: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_SLICE_AGING", "30")))
    # Half-life (seconds) for the fair queue's served mesh-seconds:
    # usage older than a few half-lives stops counting against a
    # pool, so fairness tracks RECENT consumption instead of punishing
    # a pool forever for last week's burst. 0 = no decay (all-time).
    fair_served_half_life_seconds: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_FAIR_SERVED_HALF_LIFE", "600")))
    # Fair-scheduling pool weights, "train=2,tune=1" (unlisted pools
    # weigh 1) — reference fairscheduler.xml ``weight`` parity.
    pool_weights: str = dataclasses.field(
        default_factory=lambda: os.environ.get("LO_POOL_WEIGHTS", ""))
    # Epoch-boundary lease yielding (single-host only). Off = strict
    # FIFO-fair serialization, for HBM-tight concurrent footprints.
    mesh_yield: bool = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_MESH_YIELD", "1") not in ("0", "false", "no"))
    # Defrag-via-migration policy (docs/SCALING.md §7): >0 arms it —
    # when a waiter can't fit AND (fragmentation >= this threshold OR
    # the waiter has aged past LO_SLICE_AGING), the scheduler asks the
    # job manager to checkpoint-migrate the cheapest migratable
    # holder instead of letting the waiter starve. 0 = off.
    slice_defrag: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_SLICE_DEFRAG", "0")))
    # Elastic slice autoscaler (docs/SCALING.md "Elastic
    # autoscaling"): the closed-loop policy thread that shrinks
    # elastic jobs (sliceDevices: {min, max}) under pressure (aged
    # waiters, SLO pages, HBM headroom) and grows them onto freed
    # devices. A no-op while no elastic job runs.
    autoscale: bool = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_AUTOSCALE", "1") not in ("0", "false", "no"))
    autoscale_interval_seconds: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_AUTOSCALE_INTERVAL", "1.0")))
    # Per-job resize retry budget: after this many consecutive failed
    # (rolled-back) resizes the autoscaler dead-letters the job's
    # RESIZE ledger — the job keeps training at its current size.
    autoscale_retries: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_AUTOSCALE_RETRIES", "3")))
    # Exponential backoff (base * 2^attempt, capped, +/-50% jitter)
    # between a job's failed resize and the next attempt — the PR 2
    # retry-taxonomy shape, applied to placement changes.
    autoscale_backoff_seconds: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_AUTOSCALE_BACKOFF", "2.0")))
    autoscale_backoff_max_seconds: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_AUTOSCALE_BACKOFF_MAX", "30")))
    # Bounded wait for the resize re-acquire (services/scheduler.py
    # migrate_point): past it the job rolls back to an old-size slice
    # instead of wedging behind a lease race.
    resize_grant_timeout: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_RESIZE_GRANT_TIMEOUT", "10")))

    # Device mesh defaults: axis names follow the scaling-book
    # convention. Shape 'auto' = 1D data-parallel over all devices.
    mesh_shape: str = dataclasses.field(
        default_factory=lambda: os.environ.get("LO_MESH_SHAPE", "auto"))

    # Training defaults.
    default_batch_size: int = 128
    compute_dtype: str = dataclasses.field(
        default_factory=lambda: os.environ.get("LO_COMPUTE_DTYPE", "bfloat16"))
    # Datasets at or below this size train via the whole-epoch
    # lax.scan fast path (one dispatch per epoch instead of per step);
    # 0 disables.
    scan_fit_max_bytes: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_SCAN_FIT_MAX_BYTES", str(1 << 30))))

    # Ingest pipeline.
    ingest_chunk_rows: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get("LO_INGEST_CHUNK", "65536")))
    ingest_queue_depth: int = 8
    # Device-prefetch pipeline depth: batches staged ahead of the
    # training loop by runtime.data.prefetch_to_device.
    prefetch_buffer: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_PREFETCH_BUFFER", "2")))

    # Function / '#' DSL sandboxing: 'subprocess' (separate process +
    # rlimits + fs/exec/socket audit guard — a real jail),
    # 'restricted' (in-process namespace jail), or 'trusted' (plain
    # exec, reference-equivalent behavior, code_execution.py:169-196).
    sandbox_mode: str = dataclasses.field(
        default_factory=lambda: os.environ.get("LO_SANDBOX", "subprocess"))
    # Per-request escalation ceiling: a Function POST may carry
    # "sandboxMode" up to this trust level (subprocess < restricted <
    # trusted) — the reference's live-object Function flow
    # (code_execution.py:169-196) needs in-process execution. Default
    # EMPTY = no escalation beyond sandbox_mode: the in-process modes
    # are escapable by design (sandbox.py:19-24), so opening them to
    # unauthenticated API callers must be an explicit operator opt-in
    # (LO_SANDBOX_MAX=restricted|trusted).
    sandbox_max_mode: str = dataclasses.field(
        default_factory=lambda: os.environ.get("LO_SANDBOX_MAX", ""))
    # Pre-flight static analysis (analysis/): pipeline shape/dtype
    # inference over submitted specs + AST safety lint of user code,
    # rejecting provably-broken requests with 406 BEFORE a job
    # document or accelerator lease exists. On by default; LO_PREFLIGHT=0
    # restores submit-blind reference behavior (docs/ANALYSIS.md).
    preflight: bool = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_PREFLIGHT", "1") not in ("0", "false", "no"))
    # subprocess-jail resource limits
    sandbox_cpu_seconds: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_SANDBOX_CPU_SECONDS", "600")))
    sandbox_memory_bytes: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_SANDBOX_MEMORY_BYTES", str(8 << 30))))
    sandbox_file_bytes: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_SANDBOX_FILE_BYTES", str(1 << 30))))

    # Failure handling: automatic re-runs of a failed job pipeline
    # (each attempt appends its own execution document; the reference's
    # only analogue is swarm restart_policy, docker-compose.yml:3-6),
    # and deterministic fault injection for testing those paths
    # (services/faults.py; e.g. "artifact_save:2").
    job_max_retries: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get("LO_JOB_RETRIES", "0")))
    # Job lifecycle (docs/LIFECYCLE.md). Default per-job deadline in
    # seconds (0 = none; a request's "timeout" field overrides).
    job_timeout_seconds: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get("LO_JOB_TIMEOUT", "0")))
    # Stall watchdog: a job whose progress heartbeat goes quiet for
    # this long is marked "stalled" (0 disables the watchdog) and, when
    # escalation is on (single-host only), cancelled cooperatively.
    stall_seconds: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_STALL_SECONDS", "300")))
    stall_escalate: bool = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_STALL_ESCALATE", "1") not in ("0", "false", "no"))
    # Exponential backoff between classified-transient retry attempts:
    # base * 2^attempt seconds, capped, with +/-50% jitter.
    retry_backoff_seconds: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_RETRY_BACKOFF", "0.5")))
    retry_backoff_max_seconds: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_RETRY_BACKOFF_MAX", "30")))
    # Training health sentinel defaults (docs/RELIABILITY.md). A
    # request's "healthPolicy" field overrides per job. Action "" /
    # "off" disables the sentinel; "skip" drops non-finite steps
    # on-device; "rollback" restores the last-good checkpoint;
    # "fail" raises NumericalDivergence (the jobs layer's
    # "numerical" error class).
    health_action: str = dataclasses.field(
        default_factory=lambda: os.environ.get("LO_HEALTH_ACTION", ""))
    # epoch mean loss > factor * EMA(loss) counts as a spike
    health_spike_factor: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_HEALTH_SPIKE_FACTOR", "4.0")))
    health_ema_alpha: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_HEALTH_EMA_ALPHA", "0.3")))
    # in-fit rollback budget before the fit fails numerically
    health_max_rollbacks: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_HEALTH_MAX_ROLLBACKS", "2")))
    # epochs after a rollback during which spike checks are suppressed
    health_cooldown_epochs: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_HEALTH_COOLDOWN", "1")))
    # job-level rollback-retries for the "numerical" error class (a
    # re-run of a checkpointed fit IS a rollback to its latest step)
    # before the job dead-letters
    health_retries: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_HEALTH_RETRIES", "1")))
    # Async tiered checkpointing (docs/RELIABILITY.md "Async
    # checkpointing"): train-thread saves become a device->host
    # snapshot + a bounded background commit queue
    # (runtime/async_ckpt.py). Off by default: the sync path is the
    # reference behavior and async trades host memory for stall.
    ckpt_async: bool = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_CKPT_ASYNC", "0") not in ("0", "false", "no", ""))
    # Max commits (host snapshots) in flight before save() blocks.
    ckpt_inflight: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_CKPT_INFLIGHT", "2")))
    # Newest quarantined (corrupt) checkpoint dirs kept as evidence;
    # older ones are deleted so chaos can't fill the disk.
    ckpt_quarantine_keep: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_CKPT_QUARANTINE_KEEP", "4")))
    # Vectorized sweep fusion (docs/PERFORMANCE.md "Sweep fusion").
    # When on, GridSearch/RandomSearch fuse same-architecture sweep
    # points into one compiled vmapped training program; off = every
    # point runs as an independent slice-parallel trial.
    sweep_fusion: bool = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_SWEEP_FUSION", "1") not in ("0", "false", "no"))
    # Early-stop margin for fused sweeps: a config whose EMA validation
    # score trails the cohort best by more than this stops updating
    # (its state frozen by the where-guard mask). 0 disables.
    sweep_earlystop_margin: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_SWEEP_EARLYSTOP_MARGIN", "0")))
    # epochs every config is guaranteed to train before the margin
    # check arms
    sweep_earlystop_min_epochs: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_SWEEP_EARLYSTOP_MIN_EPOCHS", "2")))
    # EMA smoothing for the per-config validation score
    sweep_earlystop_alpha: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_SWEEP_EARLYSTOP_ALPHA", "0.5")))
    # byte budget for the $name DataFrame resolution cache (0 disables)
    param_cache_bytes: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_PARAM_CACHE", str(256 << 20))))
    # Feature-plane cache (docs/PERFORMANCE.md). HBM tier budget:
    # bytes of device memory the arena may hold resident between jobs;
    # -1 = auto (a quarter of one device's memory), 0 disables.
    arena_bytes: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get("LO_ARENA_BYTES", "-1")))
    fault_inject: str = dataclasses.field(
        default_factory=lambda: os.environ.get("LO_FAULT_INJECT", ""))

    # Resident serving plane (docs/SERVING.md). Sessions pin a model
    # in the HBM arena and micro-batch concurrent predict requests.
    # Max in-flight decode slots per LM serving session (the
    # continuous batcher's compiled batch width).
    serve_max_batch: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_SERVE_MAX_BATCH", "8")))
    # Precompiled batch-size buckets for classifier/estimator predict
    # micro-batching ("1,2,4,8,..."): a request burst of n rows pads
    # to the smallest bucket >= n so warm predicts never retrace.
    serve_buckets: str = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_SERVE_BUCKETS", "1,2,4,8,16,32,64"))
    # Admission control: requests queued beyond this bound are
    # rejected with 429 (bounded queue per session).
    serve_queue_depth: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_SERVE_QUEUE", "64")))
    # How long a request may wait for batch aggregation before the
    # batcher dispatches a partial batch (milliseconds).
    serve_max_wait_ms: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_SERVE_MAX_WAIT_MS", "2")))
    # Serving-lease policy: "preempt" (the session periodically yields
    # its slice when batch gang jobs wait — never deadlocks them) or
    # "hold" (the session keeps its slice until deleted).
    serve_lease_policy: str = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_SERVE_LEASE_POLICY", "preempt"))
    # KV-cache layout for LM sessions (docs/SERVING.md "Paged KV"):
    # "slot" preallocates slots x cacheLen per session (the PR-6
    # layout, kept as fallback); "paged" carves one shared HBM page
    # pool into page_len-token pages handed out per stream on demand,
    # with refcounted prefix reuse and per-tenant admission.
    serve_kv: str = dataclasses.field(
        default_factory=lambda: os.environ.get("LO_SERVE_KV", "slot"))
    # Tokens per KV page (paged mode). Small pages waste less memory
    # on short tails; large pages gather fewer, wider HBM reads.
    serve_page_len: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_SERVE_PAGE_LEN", "16")))
    # Page-pool size per paged session. 0 = auto: the page count whose
    # pool matches the slot cache's bytes (slots x cacheLen), so
    # "paged vs slot at equal HBM" is the out-of-the-box comparison.
    serve_pages: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_SERVE_PAGES", "0")))
    # Weighted-fair tenant shares over the page budget and the decode
    # slots ("tenantA:3,tenantB:1"; unlisted tenants weigh 1). An
    # over-quota tenant is rejected with 429 while other tenants'
    # pages stay untouched — one abusive tenant cannot evict or starve
    # another's streams (per-tenant servingP99 SLOs watch the rest).
    serve_tenant_weights: str = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_SERVE_TENANT_WEIGHTS", ""))
    # Quantized serving (docs/SERVING.md "Quantized serving"). KV page
    # dtype for paged LM sessions: "bf16" (exact — the bit-identity
    # path) or "int8" (half the pool bytes per token, ~2x resident
    # streams at fixed HBM; per-page-per-head scales ride in a
    # parallel pool). Per-session override: request field "kvDtype".
    serve_kv_dtype: str = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_SERVE_KV_DTYPE", "bf16"))
    # Serving-weight dtype: "bf16" (serve the master params as-is),
    # "int8" or "fp8" (quantize the session's pinned copy once at
    # create; dequant is fused into the jitted step — master params
    # are untouched for training). Per-session override: "weights".
    serve_weights: str = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_SERVE_WEIGHTS", "bf16"))
    # Quality gate for quantized sessions: max relative logit/output
    # drift (quantized vs exact) on the held probe batch before the
    # session degrades itself back to bf16 pages/weights and fires an
    # incident. Probed at session create and every
    # LO_SERVE_DRIFT_EVERY decode steps.
    serve_drift_max: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_SERVE_DRIFT_MAX", "0.05")))
    serve_drift_every: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_SERVE_DRIFT_EVERY", "256")))
    # Disaggregated serving (docs/SERVING.md "Disaggregated serving &
    # speculative decoding"): run paged LM sessions as a prefill
    # worker + decode worker, each on its own ServingLease, with
    # finished KV pages handed off through the shared pool (refcount
    # publish/adopt — never copied). "1" makes it the default for
    # paged sessions; per-session override: request field "disagg".
    serve_disagg: str = dataclasses.field(
        default_factory=lambda: os.environ.get("LO_SERVE_DISAGG", "0"))
    # Default draft-model artifact for speculative decoding ("" = no
    # speculation). The draft proposes LO_SERVE_SPEC_K greedy tokens
    # per step; the target verifies all of them in ONE paged step with
    # exact acceptance sampling (greedy sessions stay bit-identical to
    # solo decode). Per-session overrides: "draft" and "specK".
    serve_draft: str = dataclasses.field(
        default_factory=lambda: os.environ.get("LO_SERVE_DRAFT", ""))
    serve_spec_k: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_SERVE_SPEC_K", "4")))

    # Gateway behaviors (KrakenD parity, krakend.json:1769-1770):
    # version-revalidated response cache for universal GETs (TTL is a
    # lifetime bound, never a staleness window; 0 disables) and an
    # optional per-request timeout -> 504 (0 = off; the reference
    # proxies with "timeout": "10s").
    get_cache_ttl_seconds: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_GET_CACHE_TTL", "300")))
    request_timeout_seconds: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_REQUEST_TIMEOUT", "0")))
    # Cap on concurrent timed dispatches: each LO_REQUEST_TIMEOUT
    # request runs on its own daemon thread that keeps running after
    # a 504, so without a ceiling slow backends accumulate abandoned
    # threads unboundedly. At the cap new timed requests are rejected
    # 503 (counted as lo_gateway_saturated_total); 0 = uncapped.
    gateway_max_inflight: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_GATEWAY_MAX_INFLIGHT", "64")))

    # Observability.
    log_level: str = dataclasses.field(
        default_factory=lambda: os.environ.get("LO_LOG_LEVEL", "INFO"))
    # Span tracing master switch (docs/OBSERVABILITY.md). Off = every
    # tracer call degrades to a shared no-op (no allocation, no lock).
    trace: bool = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_TRACE", "1") not in ("0", "false", "no"))
    # Spans kept per trace (bounded ring; oldest finished spans drop
    # first once a trace exceeds this).
    trace_ring: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_TRACE_RING", "512")))
    # Per-step training telemetry entries kept per job (ring buffer).
    timeline_ring: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_TIMELINE_RING", "4096")))
    # JSONL lifecycle event log path; empty = off. Appends one JSON
    # object per job/serving lifecycle event, carrying traceIds for
    # offline correlation. Strictly best-effort: a failing log never
    # fails the job.
    event_log: str = dataclasses.field(
        default_factory=lambda: os.environ.get("LO_EVENT_LOG", ""))
    # Size bound on the event log: once the file reaches this many
    # bytes it is rolled to ``<path>.1`` (keep-1 rollover) before the
    # next append, so a long-lived process cannot fill the disk.
    # 0 disables rotation.
    event_log_max_bytes: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_EVENT_LOG_MAX_BYTES", str(64 << 20))))
    # HBM attribution ledger + compiled-artifact X-ray
    # (docs/OBSERVABILITY.md "HBM attribution & X-ray"). Off = every
    # allocation-site registration and compile capture is a no-op.
    xray: bool = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_XRAY", "1") not in ("0", "false", "no"))
    # Transfer sentinel: "" (off), "log" (count implicit host<->device
    # transfers in hot loops + emit events, then proceed) or "fail"
    # (raise — CI mode: an implicit transfer fails the job).
    transfer_guard: str = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_TRANSFER_GUARD", ""))
    # Cluster resource monitor (docs/OBSERVABILITY.md "Cluster
    # monitor"). A background sampler thread collects per-device HBM
    # watermarks, arena occupancy, slice-scheduler
    # occupancy/fragmentation, serving queue depth, job-queue depth
    # and host RSS into bounded time-series rings, and the SLO
    # watchdog evaluates the declared objectives against them.
    monitor: bool = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_MONITOR", "1") not in ("0", "false", "no"))
    monitor_interval_ms: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_MONITOR_INTERVAL_MS", "1000")))
    # samples kept per monitored series (ring buffer)
    monitor_ring: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_MONITOR_RING", "600")))
    # Declarative SLOs (0 / NaN disables an objective). Each is
    # evaluated over fast/slow burn-rate windows; a breach in BOTH
    # windows fires an Alert (page severity for serving latency and
    # HBM headroom, ticket otherwise).
    slo_serving_p99_ms: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_SLO_SERVING_P99_MS", "0")))
    slo_queue_wait_s: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_SLO_QUEUE_WAIT_S", "0")))
    slo_hbm_headroom_frac: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_SLO_HBM_HEADROOM_FRAC", "0")))
    slo_deadletter_rate: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_SLO_DEADLETTER_RATE", "0")))
    # Leak detector: page when unattributed HBM (bytes_in_use minus
    # the X-ray ledger) GROWS by more than this many bytes across both
    # burn-rate windows — sustained growth nobody owns is a leak or an
    # unledgered allocation site. 0 disables.
    slo_unattributed_growth_bytes: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_SLO_UNATTRIBUTED_GROWTH_BYTES", "0")))
    # SLO burn-rate windows, seconds (fast catches an acute breach,
    # slow confirms it is sustained before paging).
    slo_fast_window_s: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_SLO_FAST_WINDOW_S", "10")))
    slo_slow_window_s: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_SLO_SLOW_WINDOW_S", "60")))
    # Closed-loop footprint calibration: prefer a repeat execution's
    # measured peakHbmBytes (safety-margined, clamped to the static
    # estimate's order of magnitude) over the preflight heuristic when
    # sizing its mesh slice (docs/SCALING.md §7).
    footprint_calibrate: bool = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_FOOTPRINT_CALIBRATE", "0") not in ("0", "false", "no"))
    # safety margin multiplied onto the measured peak before it
    # replaces the estimate
    footprint_margin: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_FOOTPRINT_MARGIN", "1.25")))
    # Incident flight recorder (docs/OBSERVABILITY.md "Incidents &
    # flight recorder"). On a failure trigger — an SLO alert firing, a
    # job dead-lettering/stalling/timing out, a health-sentinel
    # rollback — the recorder freezes the in-memory telemetry rings
    # into a durable debug bundle under ``home/incidents/<id>/``.
    # Off = every trigger is a no-op.
    incidents: bool = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "LO_INCIDENTS", "1") not in ("0", "false", "no"))
    # Newest bundles kept on disk; older ones are pruned after each
    # commit so alert storms cannot fill the disk.
    incident_keep: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_INCIDENT_KEEP", "8")))
    # Per-trigger cooldown: a trigger that captured a bundle is muted
    # for this many seconds (manual POST captures bypass it).
    incident_cooldown_s: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_INCIDENT_COOLDOWN_S", "300")))
    # Triggered deep profiling: on a serving-latency page the recorder
    # captures a jax.profiler window of this many seconds into the
    # bundle (skipped when a manual /profile session holds the
    # singleton). 0 disables.
    incident_profile_s: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_INCIDENT_PROFILE_S", "0")))
    # /profile hardening: auto-stop watchdog — a started session that
    # nobody stops is force-stopped after this many seconds (0
    # disables) — and bounded retention of captured profile dirs under
    # ``home/profiles`` (newest kept).
    profile_max_seconds: float = dataclasses.field(
        default_factory=lambda: float(os.environ.get(
            "LO_PROFILE_MAX_SECONDS", "600")))
    profile_keep: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "LO_PROFILE_KEEP", "8")))

    def ensure_dirs(self) -> None:
        for sub in ("datasets", "artifacts", "checkpoints", "tmp"):
            Path(self.home, sub).mkdir(parents=True, exist_ok=True)

    @property
    def datasets_dir(self) -> str:
        return os.path.join(self.home, "datasets")

    @property
    def artifacts_dir(self) -> str:
        return os.path.join(self.home, "artifacts")

    @property
    def checkpoints_dir(self) -> str:
        return os.path.join(self.home, "checkpoints")

    @property
    def catalog_path(self) -> str:
        return os.path.join(self.home, "catalog.sqlite")

    @classmethod
    def from_file(cls, path: str) -> "Config":
        with open(path) as f:
            data = json.load(f)
        cfg = cls()
        for key, value in data.items():
            if not hasattr(cfg, key):
                raise KeyError(f"unknown config key: {key}")
            setattr(cfg, key, value)
        return cfg

    def replace(self, **kwargs: Any) -> "Config":
        return dataclasses.replace(self, **kwargs)


_lock = locks.make_lock("config.global")
_config: Optional[Config] = None


def get_config() -> Config:
    global _config
    with _lock:
        if _config is None:
            _config = Config()
            _config.ensure_dirs()
        return _config


def _reset_mesh() -> None:
    # the default mesh is derived from config.mesh_shape; a config
    # swap must invalidate it or engines keep computing on a stale mesh
    try:
        from learningorchestra_tpu.runtime import mesh as mesh_lib
        mesh_lib.reset_default_mesh()
    except ImportError:  # jax not importable in this context
        pass
    # arena entries are keyed by mesh + dataset version; both are
    # invalid across a config swap
    try:
        from learningorchestra_tpu.runtime import arena as arena_lib
        arena_lib.reset_default_arena()
    except ImportError:
        pass


def set_config(config: Config) -> Config:
    global _config
    with _lock:
        _config = config
        _config.ensure_dirs()
    _reset_mesh()
    return config


def reset_config() -> None:
    global _config
    with _lock:
        _config = None
    _reset_mesh()
