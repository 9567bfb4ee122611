"""The REST control plane: one server, the reference's full URI
contract.

Replaces KrakenD:80 + 9 Flask microservices (reference
krakend.json:1-1773, SURVEY §L1-L2) with a single threaded stdlib HTTP
server. Route table (all under ``/api/learningOrchestra/v1``):

====== ================================== ==============================
verb   path                               handler
====== ================================== ==============================
POST   /dataset/{csv,generic}             DatasetService.create
POST   /model/{tensorflow,scikitlearn,jax} ModelService.create
POST   /{train,tune,evaluate,predict}/{tool} ExecutionService.create
POST   /explore/histogram                 HistogramService.create
POST   /explore/{tool}                    DatabaseExecutorService.create
POST   /transform/projection              ProjectionService.create
POST   /transform/dataType                DataTypeService.create
POST   /transform/{tool}                  DatabaseExecutorService.create
POST   /function/python                   FunctionService.create
POST   /builder/sparkml                   BuilderService.create
PATCH  /{service}/{tool}/{name}           per-service ``update``
GET    /{service}/{tool}                  catalog listing by type
GET    /{service}/{tool}/{name}           universal paged read
                                          (?skip&limit&query, images
                                          for explore plots)
DELETE /{service}/{tool}/{name}           per-service ``delete``
GET    /observe/{name}?seq=N              long-poll change feed
GET    /observability/trace/{name}        span tree (?format=chrome)
GET    /observability/timeline/{name}     per-step training telemetry
POST   /profile {action: start|stop}      jax.profiler trace capture
GET    /profile                           profiler status + trace list
GET    /health                            liveness + topology info
====== ================================== ==============================

Semantics preserved: POST validates synchronously (406/409/404), then
returns **201 with the artifact's future GET URI while the job runs
async**; clients poll ``finished`` in the metadata (reference
server.py:65-71 in every image). The Observe service — client-side
Mongo change streams in the reference (README.md:81) — is served here
directly from the catalog's change feed as long-poll JSON.
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from learningorchestra_tpu import analysis as A
from learningorchestra_tpu.catalog import documents as D
from learningorchestra_tpu.observability import export as obs_export
from learningorchestra_tpu.observability import hist as obs_hist
from learningorchestra_tpu.observability import perf as obs_perf
from learningorchestra_tpu.observability import timeline as obs_timeline
from learningorchestra_tpu.observability import trace as obs_trace
from learningorchestra_tpu.observability import xray as obs_xray
from learningorchestra_tpu.services import validators as V
from learningorchestra_tpu.services.builder_service import BuilderService
from learningorchestra_tpu.services.columnar import (DataTypeService,
                                                     HistogramService,
                                                     ProjectionService)
from learningorchestra_tpu.services.context import ServiceContext
from learningorchestra_tpu.services.database_executor import (
    DatabaseExecutorService)
from learningorchestra_tpu.services.dataset import (DatasetService,
                                                    parse_query_param)
from learningorchestra_tpu.services.execution import ExecutionService
from learningorchestra_tpu.services.function_service import FunctionService
from learningorchestra_tpu.services.model_service import ModelService
from learningorchestra_tpu.runtime import locks

EXECUTION_VERBS = ("train", "tune", "evaluate", "predict")
SERVICES = ("dataset", "model", "transform", "explore", "tune", "train",
            "evaluate", "predict", "builder", "function", "serve")


def escape_label_value(v: Any) -> str:
    """Prometheus exposition-format label-value escaping. Per the
    spec, backslash MUST be escaped first (or the escapes introduced
    for ``"`` and newline would themselves be double-escaped), then
    the double quote, then line feeds."""
    return (str(v).replace("\\", r"\\").replace('"', r'\"')
            .replace("\n", r"\n"))


class Api:
    """Transport-independent dispatch (unit-testable without sockets)."""

    def __init__(self, context: Optional[ServiceContext] = None):
        self.ctx = context or ServiceContext()
        self.dataset = DatasetService(self.ctx)
        self.model = ModelService(self.ctx)
        self.execution = ExecutionService(self.ctx)
        self.dbexec = DatabaseExecutorService(self.ctx)
        self.function = FunctionService(self.ctx)
        self.histogram = HistogramService(self.ctx)
        self.projection = ProjectionService(self.ctx)
        self.datatype = DataTypeService(self.ctx)
        self.builder = BuilderService(self.ctx)
        # jax.profiler singleton owner, shared with the incident
        # flight recorder's triggered-profiling window (context.py)
        self._profiler_gate = self.ctx.profiler_gate
        from learningorchestra_tpu.services.cache import ReadCache

        self.read_cache = ReadCache(
            ttl_seconds=self.ctx.config.get_cache_ttl_seconds)
        # gateway metrics (KrakenD exposes a metrics collector on
        # :8090, krakend.json:1752-1760; here it's first-party)
        self._metrics_lock = locks.make_lock("server.metrics")
        self._started = time.monotonic()
        self._requests: Dict[str, int] = {}
        self._statuses: Dict[str, int] = {}
        self._latency_sum = 0.0
        self._latency_count = 0
        # timed-dispatch accounting (the LO_REQUEST_TIMEOUT path in
        # _Handler._respond spawns a thread per request and abandons
        # it on 504 — without a cap N slow dispatches pile up unseen)
        self._gateway_lock = locks.make_lock("server.gateway")
        self._gateway_inflight = 0
        self._gateway_abandoned_inflight = 0
        self._gateway_abandoned_total = 0
        self._gateway_saturated_total = 0
        self.recover_unfinished()
        # elastic pod recovery: when the guard sees heartbeats resume,
        # requeue checkpointed worker-lost executions automatically
        self.ctx.on_pod_healthy.append(self.recover_worker_lost)

    # ------------------------------------------------------------------
    def recover_unfinished(self) -> Dict[str, list]:
        """Boot-time job durability (beyond the reference, whose
        in-flight jobs are silently lost on restart, README.md:194-198;
        SURVEY §7 step 8 sets the bar at requeue-or-fail):

        - executions (train/tune/evaluate/predict) and functions store
          their full request in metadata, so they are REQUEUED — a
          checkpointed train resumes from its latest checkpoint step;
        - everything else (ingests mid-stream, explore/transform,
          builder) gets a typed ``exception`` execution document so a
          polling client sees a terminal failure instead of a forever-
          False ``finished`` flag.
        """
        requeued, failed = [], []
        for meta in self.ctx.catalog.list_collections():
            if meta.get(D.FINISHED_FIELD):
                continue
            name = meta.get(D.NAME_FIELD)
            type_string = str(meta.get(D.TYPE_FIELD, ""))
            verb = type_string.split("/")[0]
            # a trailing exception document means the job TERMINATED
            # in failure (client already has the error; reference
            # parity keeps finished=False) — only jobs interrupted
            # mid-flight (no terminal record) are recovered, or every
            # restart would re-run failed fits / stack duplicate
            # InterruptedError docs. EXCEPTION: a WorkerLost failure
            # on a REQUEUEABLE job is the pod's fault, not the job's —
            # elastic-recovery policy requeues those here too, or a
            # restart would strand jobs the running server
            # auto-recovers. Non-requeueable worker-lost jobs (model/
            # builder) keep their typed WorkerLost record as-is.
            requeueable = (
                (verb in EXECUTION_VERBS and
                 meta.get(D.METHOD_FIELD) is not None) or
                (verb == "function" and
                 meta.get(D.FUNCTION_FIELD) is not None))
            # shutdownAborted is the same story for a DRAINED server:
            # the job never ran; the doc only exists so the orphan is
            # not silent — requeue it like a mid-flight interruption
            docs = self.ctx.catalog.get_documents(name)
            if docs and docs[-1].get(D.EXCEPTION_FIELD) and \
                    not ((docs[-1].get("workerLost") or
                          docs[-1].get("shutdownAborted"))
                         and requeueable):
                continue
            try:
                if verb in EXECUTION_VERBS and \
                        meta.get(D.METHOD_FIELD) is not None:
                    self._requeue_execution(name, type_string, meta)
                    requeued.append(name)
                elif verb == "function" and \
                        meta.get(D.FUNCTION_FIELD) is not None:
                    from learningorchestra_tpu.services import (
                        function_service as fsvc)

                    # replay under the originally granted mode — but
                    # re-resolve against the CURRENT ceiling, so a
                    # lowered LO_SANDBOX_MAX is honored (failure lands
                    # in the catch below as a typed requeue error)
                    mode = fsvc.resolve_sandbox_mode(
                        self.ctx.config,
                        meta.get(fsvc.SANDBOX_MODE_FIELD))
                    self.function._submit(
                        name, type_string, meta[D.FUNCTION_FIELD],
                        meta.get(D.FUNCTION_PARAMETERS_FIELD) or {},
                        meta.get(D.DESCRIPTION_FIELD, ""), mode=mode,
                        timeout=meta.get(V.TIMEOUT_FIELD))
                    requeued.append(name)
                else:
                    self.ctx.catalog.append_document(
                        name, D.execution_document(
                            meta.get(D.DESCRIPTION_FIELD, ""), None,
                            exception="InterruptedError('job was in "
                                      "flight when the server stopped; "
                                      "resubmit it')"))
                    failed.append(name)
            except Exception as exc:  # noqa: BLE001 — boot must finish
                self.ctx.catalog.append_document(
                    name, D.execution_document(
                        meta.get(D.DESCRIPTION_FIELD, ""), None,
                        exception=f"requeue-on-boot failed: {exc!r}"))
                failed.append(name)
        return {"requeued": requeued, "failed": failed}

    def _requeue_execution(self, name: str, type_string: str,
                           meta: Dict[str, Any],
                           only_if_idle: bool = False) -> None:
        """Shared requeue-from-stored-request used by boot recovery
        and elastic re-form recovery (one place owns the _submit
        signature)."""
        self.execution._submit(
            name, type_string, meta[D.PARENT_NAME_FIELD],
            meta[D.METHOD_FIELD],
            meta.get(D.METHOD_PARAMETERS_FIELD) or {},
            meta.get(D.DESCRIPTION_FIELD, ""),
            only_if_idle=only_if_idle,
            timeout=meta.get(V.TIMEOUT_FIELD),
            footprint=meta.get(A.FOOTPRINT_FIELD),
            health_policy=meta.get(V.HEALTH_POLICY_FIELD))

    def recover_worker_lost(self) -> list:
        """Elastic pod recovery (beyond the reference, whose node loss
        loses the work outright, README.md:194-202): when the pod
        guard reports heartbeats resumed, requeue every unfinished
        execution whose LAST failure was attributed to the pod
        (``workerLost`` — a pre-submit refusal, or a mesh job whose
        collective errored while the pod was degraded). A checkpointed
        train then picks up at its latest checkpoint step with NO server
        restart. Not eligible: jobs whose newest failure is a genuine
        (non-pod) error — re-running those on every degrade/heal flap
        would loop a broken fit forever — and jobs whose original
        thread is still live (the atomic ``only_if_idle`` submit skips
        them; a thread wedged in a dead collective can only be cleared
        by a pod restart, which boot recovery then handles)."""
        requeued = []
        for meta in self.ctx.catalog.list_collections():
            if meta.get(D.FINISHED_FIELD):
                continue
            name = meta.get(D.NAME_FIELD)
            type_string = str(meta.get(D.TYPE_FIELD, ""))
            verb = type_string.split("/")[0]
            if verb not in EXECUTION_VERBS or \
                    meta.get(D.METHOD_FIELD) is None:
                continue
            docs = self.ctx.catalog.get_documents(name)
            exc_docs = [d for d in docs if d.get(D.EXCEPTION_FIELD)]
            if not exc_docs or not exc_docs[-1].get("workerLost"):
                continue
            try:
                self._requeue_execution(name, type_string, meta,
                                        only_if_idle=True)
                requeued.append(name)
            except Exception as exc:  # noqa: BLE001 — recovery must
                # not kill the guard thread; record and move on. The
                # doc keeps the workerLost attribution so a transient
                # requeue error leaves the job retryable by the next
                # heal / the next boot instead of stranding it
                self.ctx.catalog.append_document(
                    name, D.execution_document(
                        meta.get(D.DESCRIPTION_FIELD, ""), None,
                        exception=f"requeue-on-reform failed: {exc!r}",
                        extra={"workerLost": True}))
        if requeued:
            print(f"pod re-form: requeued {len(requeued)} worker-lost "
                  f"job(s): {requeued}", flush=True)
        return requeued

    # ------------------------------------------------------------------
    def dispatch(self, method: str, path: str, params: Dict[str, Any],
                 body: Optional[Dict[str, Any]],
                 record: bool = True) -> Tuple[int, Any, str]:
        """Returns (status, payload, content_type). payload is a dict
        (JSON) or raw bytes when content_type is not JSON.
        ``record=False`` lets a deadline-bound caller own the metrics
        record (otherwise a timed-out request would be counted twice:
        the 504 the client saw AND the late real completion)."""
        t0 = time.monotonic()
        try:
            out = self._route(method, path, params, body)
        except V.HttpError as e:
            payload = {"result": e.message}
            if e.findings:
                payload["analysis"] = e.findings
            out = e.status, payload, "application/json"
        except Exception as e:  # noqa: BLE001
            out = 500, {"result": f"internal error: {e!r}"}, \
                "application/json"
        if record:
            self._record_metrics(method, path, out[0],
                                 time.monotonic() - t0)
        return out

    def _record_metrics(self, method: str, path: str, status: int,
                        seconds: float) -> None:
        prefix = self.ctx.config.api_prefix
        parts = [p for p in path[len(prefix):].split("/") if p] \
            if path.startswith(prefix + "/") else []
        service = parts[0] if parts else path.lstrip("/").split("/")[0] \
            or "root"
        with self._metrics_lock:
            key = f"{method} {service}"
            self._requests[key] = self._requests.get(key, 0) + 1
            sk = str(status)
            self._statuses[sk] = self._statuses.get(sk, 0) + 1
            self._latency_sum += seconds
            self._latency_count += 1
        obs_hist.observe("lo_dispatch_seconds", seconds)

    def metrics(self) -> Dict[str, Any]:
        with self._metrics_lock:
            n = self._latency_count
            out = {
                "uptimeSeconds": round(
                    time.monotonic() - self._started, 3),
                "requestsTotal": n,
                "requestsByRoute": dict(sorted(self._requests.items())),
                "responsesByStatus": dict(sorted(self._statuses.items())),
                "meanDispatchSeconds": round(
                    self._latency_sum / n, 6) if n else None,
                "dispatchSecondsSum": round(self._latency_sum, 6),
            }
        out["jobsRunning"] = self.ctx.jobs.running()
        out["collections"] = len(self.ctx.catalog.list_collections())
        out["getCache"] = self.read_cache.stats()
        out["meshSecondsByPool"] = {
            pool: round(seconds, 3) for pool, seconds in
            sorted(self.ctx.jobs.mesh_served().items())}
        out["jobLifecycle"] = self.ctx.jobs.lifecycle_counters()
        out["meshScheduler"] = self.ctx.jobs.scheduler_stats()
        # live migration between slices (docs/SCALING.md §7)
        out["migrationStats"] = self.ctx.jobs.migration_stats()
        # elastic slice autoscaler (docs/SCALING.md "Elastic
        # autoscaling"); absent when LO_AUTOSCALE=0
        autoscaler = getattr(self.ctx, "autoscaler", None)
        if autoscaler is not None:
            out["autoscaler"] = autoscaler.stats()
        # feature-plane cache tiers (docs/PERFORMANCE.md). Lazy
        # imports: arena/engine stats never initialize a backend.
        out["featureCache"] = self.ctx.features.stats()
        from learningorchestra_tpu.runtime import arena as arena_lib
        from learningorchestra_tpu.runtime import engine as engine_lib
        out["arena"] = arena_lib.get_default_arena().stats()
        out["executableCache"] = engine_lib.executable_cache_stats()
        # training-health sentinel + checkpoint-integrity counters
        # (docs/RELIABILITY.md); health.py is jax-free so this import
        # is always cheap
        from learningorchestra_tpu.runtime import health as health_lib
        out["trainingHealth"] = health_lib.health_stats()
        # resident serving plane (docs/SERVING.md): session counts,
        # admission rejects, decode throughput and p50/p99 latency
        out["serving"] = self.ctx.serving.stats()
        # vectorized sweep fusion (docs/PERFORMANCE.md "Sweep fusion")
        from learningorchestra_tpu.models import sweep as sweep_lib
        out["sweepFusion"] = sweep_lib.fusion_stats()
        # latency histograms (docs/OBSERVABILITY.md): cumulative
        # buckets, same snapshots the Prometheus exposition serializes
        out["latencyHistograms"] = obs_hist.snapshot_all()
        # timed-dispatch gateway counters (docs/OBSERVABILITY.md):
        # in-flight/abandoned dispatch threads and saturation rejects
        with self._gateway_lock:
            out["gateway"] = {
                "inflight": self._gateway_inflight,
                "abandonedInflight": self._gateway_abandoned_inflight,
                "abandonedTotal": self._gateway_abandoned_total,
                "saturatedTotal": self._gateway_saturated_total,
                "maxInflight": self.ctx.config.gateway_max_inflight,
            }
        # roofline perf reports (docs/OBSERVABILITY.md "Roofline &
        # perf reports"): latest per-job window + the platform peaks
        # they measure against
        out["perf"] = {
            "platform": obs_perf.platform_summary(),
            "jobs": obs_perf.latest(),
        }
        # HBM attribution ledger + retrace/transfer sentinels
        # (docs/OBSERVABILITY.md "HBM attribution & X-ray"). Only the
        # jax-free subset — the full report with bytes-in-use lives on
        # GET /observability/memory
        out["xray"] = {
            "enabled": obs_xray.enabled(),
            "owners": obs_xray.by_owner(),
            "attributedBytes": obs_xray.attributed_bytes(),
            "counters": obs_xray.counters(),
        }
        # cluster resource sampler + SLO watchdog (docs/OBSERVABILITY
        # .md "Cluster monitor"); absent when LO_MONITOR=0
        monitor = getattr(self.ctx, "monitor", None)
        if monitor is not None:
            out["cluster"] = monitor.latest()
            watchdog = monitor.watchdog
            if watchdog is not None:
                out["alerts"] = watchdog.firing()
                out["alertsFiring"] = len(out["alerts"])
        # incident flight recorder (docs/OBSERVABILITY.md "Incidents
        # & flight recorder"); absent when LO_INCIDENTS=0
        recorder = getattr(self.ctx, "incidents", None)
        if recorder is not None:
            out["incidents"] = recorder.stats()
        return out

    def metrics_prometheus(self) -> bytes:
        """Prometheus text exposition of :meth:`metrics` (KrakenD's
        collector on :8090 is the reference's version of this,
        krakend.json:1752-1760; text format is what the ecosystem's
        scrapers actually ingest)."""
        # sum and count come from the same metrics() snapshot so
        # rate(sum)/rate(count) stays consistent under load
        m = self.metrics()
        esc = escape_label_value
        # constant build pin (satellite: dashboards and bundles can
        # join every series onto exactly what was running)
        from learningorchestra_tpu.observability import \
            incidents as obs_incidents
        info = obs_incidents.build_info()
        lines = [
            "# TYPE lo_build_info gauge",
            f'lo_build_info{{version="{esc(info["version"])}"'
            f',jax_version="{esc(info["jaxVersion"])}"'
            f',backend="{esc(info["backend"])}"'
            f',device_kind="{esc(info["deviceKind"])}"}} 1',
            "# TYPE lo_uptime_seconds gauge",
            f"lo_uptime_seconds {m['uptimeSeconds']}",
            "# TYPE lo_requests_total counter",
        ]
        for route, n in m["requestsByRoute"].items():
            lines.append(
                f'lo_requests_total{{route="{esc(route)}"}} {n}')
        lines.append("# TYPE lo_responses_total counter")
        for status, n in m["responsesByStatus"].items():
            lines.append(
                f'lo_responses_total{{status="{esc(status)}"}} {n}')
        # lo_dispatch_seconds / lo_lease_wait_seconds moved from
        # sum+count summaries to full histograms — emitted with every
        # other latency histogram at the end of this exposition
        lines += [
            "# TYPE lo_jobs_running gauge",
            f"lo_jobs_running {m['jobsRunning']}",
            "# TYPE lo_collections gauge",
            f"lo_collections {m['collections']}",
            "# TYPE lo_mesh_seconds_total counter",
        ]
        for pool, seconds in m["meshSecondsByPool"].items():
            lines.append(
                f'lo_mesh_seconds_total{{pool="{esc(pool)}"}} {seconds}')
        lines += [
            "# TYPE lo_get_cache_hits_total counter",
            f"lo_get_cache_hits_total {m['getCache']['hits']}",
            "# TYPE lo_get_cache_misses_total counter",
            f"lo_get_cache_misses_total {m['getCache']['misses']}",
            "# TYPE lo_get_cache_entries gauge",
            f"lo_get_cache_entries {m['getCache']['entries']}",
        ]
        feature = m["featureCache"]
        arena = m["arena"]
        exec_cache = m["executableCache"]
        lines += [
            "# TYPE lo_feature_cache_hits_total counter",
            f"lo_feature_cache_hits_total {feature['hits']}",
            "# TYPE lo_feature_cache_misses_total counter",
            f"lo_feature_cache_misses_total {feature['misses']}",
            "# TYPE lo_feature_cache_bytes_in_use gauge",
            f"lo_feature_cache_bytes_in_use {feature['bytesInUse']}",
            "# TYPE lo_arena_bytes_in_use gauge",
            f"lo_arena_bytes_in_use {arena['bytesInUse']}",
            "# TYPE lo_arena_evictions_total counter",
            f"lo_arena_evictions_total {arena['evictions']}",
            "# TYPE lo_arena_hits_total counter",
            f"lo_arena_hits_total {arena['hits']}",
            "# TYPE lo_arena_misses_total counter",
            f"lo_arena_misses_total {arena['misses']}",
            "# TYPE lo_executable_cache_hits_total counter",
            f"lo_executable_cache_hits_total {exec_cache['hits']}",
            "# TYPE lo_executable_cache_misses_total counter",
            f"lo_executable_cache_misses_total {exec_cache['misses']}",
        ]
        lifecycle = m["jobLifecycle"]
        lines += [
            "# TYPE lo_job_retries_total counter",
            f"lo_job_retries_total {lifecycle.get('retries', 0)}",
            "# TYPE lo_jobs_cancelled_total counter",
            f"lo_jobs_cancelled_total {lifecycle.get('cancelled', 0)}",
            "# TYPE lo_jobs_timed_out_total counter",
            f"lo_jobs_timed_out_total {lifecycle.get('timedOut', 0)}",
            "# TYPE lo_jobs_stalled gauge",
            f"lo_jobs_stalled {lifecycle.get('stalled', 0)}",
        ]
        scheduler = m["meshScheduler"]
        lines += [
            "# TYPE lo_lease_wait_seconds_max gauge",
            f"lo_lease_wait_seconds_max "
            f"{scheduler.get('leaseWaitMax', 0.0)}",
            "# TYPE lo_mesh_devices_busy gauge",
            f"lo_mesh_devices_busy {scheduler.get('devicesBusy', 0)}",
            "# TYPE lo_slice_grants_total counter",
        ]
        for pool, n in sorted(
                (scheduler.get("grantsByPool") or {}).items()):
            lines.append(
                f'lo_slice_grants_total{{pool="{esc(pool)}"}} {n}')
        lines += [
            "# TYPE lo_job_numerical_retries_total counter",
            f"lo_job_numerical_retries_total "
            f"{lifecycle.get('numericalRetries', 0)}",
        ]
        training_health = m["trainingHealth"]
        lines += [
            "# TYPE lo_nonfinite_steps_total counter",
            f"lo_nonfinite_steps_total "
            f"{training_health.get('nonfiniteSteps', 0)}",
            "# TYPE lo_rollbacks_total counter",
            f"lo_rollbacks_total {training_health.get('rollbacks', 0)}",
            "# TYPE lo_loss_spikes_total counter",
            f"lo_loss_spikes_total "
            f"{training_health.get('lossSpikes', 0)}",
            "# TYPE lo_checkpoints_quarantined_total counter",
            f"lo_checkpoints_quarantined_total "
            f"{training_health.get('quarantined', 0)}",
            # quantized-serving quality gate (services/serving.py)
            "# TYPE lo_serving_drift_breaches_total counter",
            f"lo_serving_drift_breaches_total "
            f"{training_health.get('driftBreaches', 0)}",
            "# TYPE lo_serving_quant_degrades_total counter",
            f"lo_serving_quant_degrades_total "
            f"{training_health.get('quantDegrades', 0)}",
        ]
        sweep_fusion = m["sweepFusion"]
        lines += [
            "# TYPE lo_sweep_fused_trials_total counter",
            f"lo_sweep_fused_trials_total "
            f"{sweep_fusion.get('fusedTrials', 0)}",
            "# TYPE lo_sweep_cohorts_total counter",
            f"lo_sweep_cohorts_total {sweep_fusion.get('cohorts', 0)}",
            "# TYPE lo_sweep_fallback_trials_total counter",
            f"lo_sweep_fallback_trials_total "
            f"{sweep_fusion.get('fallbackTrials', 0)}",
            "# TYPE lo_sweep_early_stopped_total counter",
            f"lo_sweep_early_stopped_total "
            f"{sweep_fusion.get('earlyStopped', 0)}",
            "# TYPE lo_sweep_trial_errors_total counter",
            f"lo_sweep_trial_errors_total "
            f"{sweep_fusion.get('trialErrors', 0)}",
        ]
        serving = m["serving"]
        lines += [
            "# TYPE lo_serving_sessions gauge",
            f"lo_serving_sessions {serving['sessions']}",
            "# TYPE lo_serving_requests_total counter",
            f"lo_serving_requests_total {serving['requestsTotal']}",
            "# TYPE lo_serving_rejected_total counter",
            f"lo_serving_rejected_total {serving['rejectedTotal']}",
            "# TYPE lo_serving_tokens_total counter",
            f"lo_serving_tokens_total {serving['tokensTotal']}",
            "# TYPE lo_serving_lease_yields_total counter",
            f"lo_serving_lease_yields_total {serving['leaseYields']}",
        ]
        for metric, value_of in (
                ("lo_serving_latency_p50_ms",
                 lambda s: s["latency"]["p50Ms"]),
                ("lo_serving_latency_p99_ms",
                 lambda s: s["latency"]["p99Ms"]),
                ("lo_serving_queue_depth",
                 lambda s: s["queueDepth"])):
            lines.append(f"# TYPE {metric} gauge")
            for sess in serving["bySession"]:
                lines.append(
                    f'{metric}{{model="{esc(sess["model"])}"}} '
                    f'{value_of(sess)}')
        # paged-KV pool state per session (services/serving.py
        # PagedLMServingSession): free/shared pages, prefix reuse and
        # per-tenant page holdings
        # NB: pool size is a gauge, so the metric must not end in
        # _total (the suffix drives the TYPE annotation below)
        for metric, kv_value in (
                ("lo_serving_kv_pages",
                 lambda kv: kv["pagesTotal"]),
                ("lo_serving_kv_pages_free",
                 lambda kv: kv["pagesFree"]),
                ("lo_serving_kv_pages_shared",
                 lambda kv: kv["pagesShared"]),
                ("lo_serving_kv_alloc_failures_total",
                 lambda kv: kv["allocFailures"]),
                ("lo_serving_kv_prefills_skipped_total",
                 lambda kv: kv["prefix"]["prefillsSkipped"]),
                ("lo_serving_kv_pages_reused_total",
                 lambda kv: kv["prefix"]["pagesReused"])):
            rows = [s for s in serving["bySession"] if s.get("kv")]
            if not rows:
                break
            kind = ("counter" if metric.endswith("_total")
                    else "gauge")
            lines.append(f"# TYPE {metric} {kind}")
            for sess in rows:
                lines.append(
                    f'{metric}{{model="{esc(sess["model"])}"}} '
                    f'{kv_value(sess["kv"])}')
        lines_added_tenant = False
        for sess in serving["bySession"]:
            tenants = (sess.get("kv") or {}).get("tenants") or {}
            for tenant, tstats in sorted(tenants.items()):
                if not lines_added_tenant:
                    lines.append(
                        "# TYPE lo_serving_tenant_pages gauge")
                    lines_added_tenant = True
                lines.append(
                    f'lo_serving_tenant_pages{{model='
                    f'"{esc(sess["model"])}",tenant='
                    f'"{esc(tenant)}"}} {tstats["pages"]}')
        # serving goodput (observability/perf): decode tokens/s/chip
        # per LM session — the headline serving-efficiency gauge
        lines.append("# TYPE lo_serving_tokens_per_sec_per_chip gauge")
        for sess in serving["bySession"]:
            tps = (sess.get("perf") or {}).get(
                "decodeTokensPerSecPerChip")
            if tps is not None:
                lines.append(
                    f'lo_serving_tokens_per_sec_per_chip'
                    f'{{model="{esc(sess["model"])}"}} {tps}')
        # quantized serving: true KV bytes per cached token (int8 pool
        # + scale pool funded together, so int8 shows ~2x headroom) and
        # the latest drift-probe value per quantized session
        lines.append("# TYPE lo_serving_kv_bytes_per_token gauge")
        for sess in serving["bySession"]:
            bpt = (sess.get("kv") or {}).get("bytesPerToken")
            if bpt is not None:
                lines.append(
                    f'lo_serving_kv_bytes_per_token'
                    f'{{model="{esc(sess["model"])}"}} {bpt}')
        lines.append("# TYPE lo_serving_drift gauge")
        for sess in serving["bySession"]:
            drift = (sess.get("drift") or {}).get("value")
            if drift is not None:
                lines.append(
                    f'lo_serving_drift'
                    f'{{model="{esc(sess["model"])}"}} {drift}')
        # disaggregated serving + speculative decoding
        # (services/serving.py DisaggLMServingSession / spec path):
        # per-role latency over a CLOSED role set
        # (prefill/decode/draft — bounded cardinality by
        # construction), time-to-first-token, handoff volume and the
        # speculative acceptance rate
        for metric, of_sess in (
                ("lo_serving_ttft_p50_ms",
                 lambda s: (s.get("ttft") or {}).get("p50Ms")),
                ("lo_serving_ttft_p99_ms",
                 lambda s: (s.get("ttft") or {}).get("p99Ms")),
                ("lo_serving_accepted_tokens_per_step",
                 lambda s: (s.get("spec") or {}).get(
                     "acceptedTokensPerStep")),
                ("lo_serving_handoff_queue",
                 lambda s: (s.get("disagg") or {}).get(
                     "handoffQueue"))):
            rows = []
            for sess in serving["bySession"]:
                value = of_sess(sess)
                if value is not None:
                    rows.append((sess["model"], value))
            if rows:
                lines.append(f"# TYPE {metric} gauge")
                for model, value in rows:
                    lines.append(
                        f'{metric}{{model="{esc(model)}"}} {value}')
        rows = []
        for sess in serving["bySession"]:
            handoffs = (sess.get("disagg") or {}).get("handoffsTotal")
            if handoffs is not None:
                rows.append((sess["model"], handoffs))
        if rows:
            lines.append(
                "# TYPE lo_serving_handoffs_total counter")
            for model, value in rows:
                lines.append(
                    f'lo_serving_handoffs_total'
                    f'{{model="{esc(model)}"}} {value}')
        role_rows = []
        for sess in serving["bySession"]:
            for role, tracker in sorted(
                    (sess.get("roles") or {}).items()):
                role_rows.append((sess["model"], role, tracker))
        if role_rows:
            for metric, pkey in (
                    ("lo_serving_role_latency_p50_ms", "p50Ms"),
                    ("lo_serving_role_latency_p99_ms", "p99Ms")):
                lines.append(f"# TYPE {metric} gauge")
                for model, role, tracker in role_rows:
                    lines.append(
                        f'{metric}{{model="{esc(model)}",'
                        f'role="{esc(role)}"}} {tracker[pkey]}')
        # timed-dispatch gateway
        gateway = m["gateway"]
        lines += [
            "# TYPE lo_abandoned_dispatches gauge",
            f"lo_abandoned_dispatches {gateway['abandonedInflight']}",
            "# TYPE lo_abandoned_dispatches_total counter",
            f"lo_abandoned_dispatches_total "
            f"{gateway['abandonedTotal']}",
            "# TYPE lo_gateway_inflight gauge",
            f"lo_gateway_inflight {gateway['inflight']}",
            "# TYPE lo_gateway_saturated_total counter",
            f"lo_gateway_saturated_total {gateway['saturatedTotal']}",
        ]
        # roofline gauges per train job (observability/perf); absent
        # until a job records a steady-state window
        perf_jobs = (m.get("perf") or {}).get("jobs") or {}
        for metric, key in (("lo_mfu", "mfu"),
                            ("lo_tflops_per_chip",
                             "tflopsPerSecPerChip"),
                            ("lo_hbm_bw_util_frac", "hbmBwUtil")):
            rows = [(job, rep[key]) for job, rep in perf_jobs.items()
                    if rep.get(key) is not None]
            if rows:
                lines.append(f"# TYPE {metric} gauge")
                for job, value in rows:
                    lines.append(
                        f'{metric}{{job="{esc(job)}"}} {value}')
        # X-ray HBM attribution + sentinels (observability/xray): the
        # per-owner ledger gauge family and the retrace / implicit-
        # transfer counters
        xr = m.get("xray") or {}
        owners = xr.get("owners") or {}
        if owners:
            lines.append("# TYPE lo_hbm_attributed_bytes gauge")
            for owner, nbytes in sorted(owners.items()):
                lines.append(
                    f'lo_hbm_attributed_bytes{{owner="{esc(owner)}"}} '
                    f'{nbytes}')
        xr_counters = xr.get("counters") or {}
        lines += [
            "# TYPE lo_retraces_total counter",
            f"lo_retraces_total {xr_counters.get('retraces', 0)}",
            "# TYPE lo_implicit_transfers_total counter",
            f"lo_implicit_transfers_total "
            f"{xr_counters.get('implicitTransfers', 0)}",
        ]
        # cluster monitor + SLO watchdog gauges (absent when
        # LO_MONITOR=0, so scrapers see the series disappear rather
        # than freeze at the last value)
        cluster = m.get("cluster")
        if cluster:
            hbm = cluster.get("hbm") or {}
            sched = cluster.get("scheduler") or {}
            serving_sample = cluster.get("serving") or {}
            xray_sample = cluster.get("xray") or {}
            for metric, value in (
                    ("lo_hbm_bytes_in_use", hbm.get("bytesInUse")),
                    ("lo_hbm_peak_bytes_in_use",
                     hbm.get("peakBytesInUse")),
                    ("lo_hbm_headroom_frac", hbm.get("headroomFrac")),
                    ("lo_slice_fragmentation",
                     sched.get("fragmentation")),
                    ("lo_serving_queue_depth_total",
                     serving_sample.get("queueDepth")),
                    ("lo_host_rss_bytes", cluster.get("hostRssBytes")),
                    ("lo_hbm_unattributed_bytes",
                     xray_sample.get("unattributedBytes"))):
                if value is not None:
                    lines.append(f"# TYPE {metric} gauge")
                    lines.append(f"{metric} {value}")
        if "alertsFiring" in m:
            lines += [
                "# TYPE lo_alerts_firing gauge",
                f"lo_alerts_firing {m['alertsFiring']}",
            ]
            if m.get("alerts"):
                lines.append("# TYPE lo_alert_firing gauge")
                for alert in m["alerts"]:
                    lines.append(
                        f'lo_alert_firing{{alert="{esc(alert["name"])}"'
                        f',severity="{esc(alert["severity"])}"}} 1')
        # elastic autoscaler counters (absent when LO_AUTOSCALE=0)
        autoscaler = m.get("autoscaler")
        if autoscaler is not None:
            counters = autoscaler.get("counters") or {}
            lines += [
                "# TYPE lo_autoscaler_resizes_total counter",
                f'lo_autoscaler_resizes_total{{direction="shrink"}} '
                f"{counters.get('shrinksCompleted', 0)}",
                f'lo_autoscaler_resizes_total{{direction="grow"}} '
                f"{counters.get('growsCompleted', 0)}",
                "# TYPE lo_autoscaler_rollbacks_total counter",
                f"lo_autoscaler_rollbacks_total "
                f"{counters.get('rollbacks', 0)}",
                "# TYPE lo_autoscaler_dead_lettered_total counter",
                f"lo_autoscaler_dead_lettered_total "
                f"{counters.get('deadLettered', 0)}",
            ]
        # incident flight recorder (absent when LO_INCIDENTS=0)
        incidents = m.get("incidents")
        if incidents is not None:
            lines.append("# TYPE lo_incidents_total counter")
            for trig, n in sorted(
                    (incidents.get("byTrigger") or {}).items()):
                lines.append(
                    f'lo_incidents_total{{trigger="{esc(trig)}"}} {n}')
            lines += [
                "# TYPE lo_incident_bundles gauge",
                f"lo_incident_bundles {incidents['bundles']}",
                "# TYPE lo_incident_bytes gauge",
                f"lo_incident_bytes {incidents['bytes']}",
            ]
        # latency histograms: lo_dispatch_seconds, lo_lease_wait_...,
        # lo_serving_request_..., lo_compile_..., lo_checkpoint_commit_
        # — cumulative _bucket{le=...}/_sum/_count per the exposition
        # format, sharing the escaper above
        lines.extend(obs_hist.prometheus_lines(esc))
        return ("\n".join(lines) + "\n").encode()

    # ------------------------------------------------------------------
    def _route(self, method: str, path: str, params: Dict[str, Any],
               body: Optional[Dict[str, Any]],
               ) -> Tuple[int, Any, str]:
        prefix = self.ctx.config.api_prefix
        if path == "/health":
            return 200, self._health(), "application/json"
        if path == "/healthz":
            return self._healthz()
        if path == "/metrics":
            if params.get("format") == "prometheus":
                return (200, self.metrics_prometheus(),
                        "text/plain; version=0.0.4; charset=utf-8")
            return 200, self.metrics(), "application/json"
        if not path.startswith(prefix + "/"):
            return 404, {"result": "unknown route"}, "application/json"
        parts = [p for p in path[len(prefix):].split("/") if p]
        if parts and parts[0] == "observe":
            return self._observe(parts, params)
        if parts and parts[0] == "profile":
            return self._profile(method, body or {})
        if parts and parts[0] == "observability":
            return self._observability(method, parts, params,
                                       body or {})
        if parts and parts[0] == "serve":
            # serving sessions address the MODEL in the path (the
            # session IS the resource), so the generic
            # /{service}/{tool}/{name} dispatch doesn't fit
            return self._serve(method, parts, body or {})
        if len(parts) < 2 or parts[0] not in SERVICES:
            return 404, {"result": "unknown route"}, "application/json"
        service, tool = parts[0], parts[1]
        name = "/".join(parts[2:]) if len(parts) > 2 else None

        if method == "GET":
            return self._get(service, tool, name, params)
        if method == "POST":
            if name is not None:
                if name.endswith("/migrate") and \
                        len(name) > len("/migrate"):
                    return self._migrate_run(name[:-len("/migrate")])
                raise V.HttpError(V.HTTP_NOT_ACCEPTABLE,
                                  "POST takes no name in the path")
            return self._post(service, tool, body or {})
        if method == "PATCH":
            if name is None:
                raise V.HttpError(V.HTTP_NOT_ACCEPTABLE, "missing name")
            return self._patch(service, tool, name, body or {})
        if method == "DELETE":
            if name is None:
                raise V.HttpError(V.HTTP_NOT_ACCEPTABLE, "missing name")
            return self._delete(service, tool, name)
        return 405, {"result": "unsupported method"}, "application/json"

    # ------------------------------------------------------------------
    def _observability(self, method: str, parts: list,
                       params: Dict[str, Any],
                       body: Optional[Dict[str, Any]] = None,
                       ) -> Tuple[int, Any, str]:
        """Trace / timeline read surface (docs/OBSERVABILITY.md):

        - ``GET /observability/trace``              known trace ids
        - ``GET /observability/trace/{name}``       span tree JSON
        - ``GET /observability/trace/{name}?format=chrome``
          Chrome/Perfetto ``trace_event`` JSON (drag into ui.perfetto.dev)
        - ``GET /observability/timeline``           jobs with telemetry
        - ``GET /observability/timeline/{name}``    per-step ring +
          percentile summary
        - ``GET /observability/cluster``            resource-sampler
          rings (HBM, arena, slices, queues, RSS)
        - ``GET /observability/alerts``             SLO objectives +
          firing/ resolved alert history
        - ``GET /observability/autoscaler``         elastic-resize
          policy state: counters, last pressure signals, per-job
          backoff/dead-letter ledger (docs/SCALING.md "Elastic
          autoscaling")
        - ``GET /observability/perf``               jobs with perf
          reports + platform peaks
        - ``GET /observability/perf/{name}``        roofline report
          (live serving session, in-process train window, or the
          ``perf`` block stamped on terminal train metadata)
        - ``GET /observability/memory``             HBM attribution
          ledger: per-owner byte totals, bytes-in-use and the
          unattributed remainder (XLA temps / leaks) + sentinel
          counters
        - ``GET /observability/memory/{name}``      ledger rows tagged
          with one job / serving session / model name
        - ``GET /observability/compile/{name}``     compiled-artifact
          X-ray: per-program ``memory_analysis()`` (argument/output/
          temp/code bytes) and ``cost_analysis()`` extracts
        - ``GET  /observability/incidents``          captured debug
          bundles (docs/OBSERVABILITY.md "Incidents & flight
          recorder")
        - ``GET  /observability/incidents/{id}``     bundle manifest
        - ``GET  /observability/incidents/{id}/download``  the whole
          bundle as a tar stream
        - ``POST /observability/incidents``          manual on-demand
          capture (bypasses the trigger cooldown)

        Trace names may contain ``/`` (serving requests are
        ``serve/{model}/{seq}``), so the remaining path joins back up.
        """
        kind = parts[1] if len(parts) > 1 else ""
        if kind == "incidents":
            return self._incidents(method, parts, body or {})
        if method != "GET":
            return (405, {"result": "unsupported method"},
                    "application/json")
        name = "/".join(parts[2:])
        if kind == "trace":
            if not name:
                return (200, {"result": obs_trace.known_traces()},
                        "application/json")
            if params.get("format") == "chrome":
                doc = obs_export.chrome_trace(name)
            else:
                doc = obs_trace.tree(name)
            if doc is None:
                raise V.HttpError(
                    V.HTTP_NOT_FOUND,
                    f"no trace recorded for {name} (job never ran "
                    f"here, trace evicted, or LO_TRACE=0)")
            return 200, doc, "application/json"
        if kind == "timeline":
            if not name:
                return (200, {"result": obs_timeline.known_jobs()},
                        "application/json")
            summary = obs_timeline.summary(name)
            if summary is None:
                raise V.HttpError(
                    V.HTTP_NOT_FOUND,
                    f"no step telemetry recorded for {name}")
            return (200, {"job": name, "summary": summary,
                          "timeline": obs_timeline.entries(name)},
                    "application/json")
        if kind == "perf":
            platform = obs_perf.platform_summary()
            if not name:
                return (200, {"platform": platform,
                              "jobs": obs_perf.known_jobs()},
                        "application/json")
            # resolution order: live serving session -> in-process
            # train registry -> the perf block stamped on terminal
            # train metadata (survives the registry's LRU)
            report = self.ctx.serving.perf_report(name)
            if report is None:
                job = obs_perf.job_report(name)
                if job is not None:
                    report = {"kind": "train", "job": name,
                              "perf": job}
            if report is None:
                meta = self.ctx.catalog.get_metadata(name) or {}
                stamped = meta.get("perf")
                if stamped:
                    report = {"kind": "train", "job": name,
                              "perf": stamped, "terminal": True}
            if report is None:
                raise V.HttpError(
                    V.HTTP_NOT_FOUND,
                    f"no perf report for {name} (job never recorded "
                    f"a steady-state window here, or LO_PERF=0)")
            report["platform"] = platform
            return 200, report, "application/json"
        if kind == "memory":
            report = obs_xray.memory_report(name or None)
            if name and not report["entries"]:
                raise V.HttpError(
                    V.HTTP_NOT_FOUND,
                    f"no ledgered allocations tagged {name} (nothing "
                    f"resident for it right now, or LO_XRAY=0)")
            return 200, report, "application/json"
        if kind == "compile":
            if not name:
                return (200, {"result": obs_xray.known_compiles()},
                        "application/json")
            report = obs_xray.compile_report(name)
            if report is None:
                raise V.HttpError(
                    V.HTTP_NOT_FOUND,
                    f"no compiled-artifact report for {name} (job "
                    f"never compiled a step here, report evicted, or "
                    f"LO_XRAY=0)")
            return 200, report, "application/json"
        if kind == "cluster":
            monitor = getattr(self.ctx, "monitor", None)
            if monitor is None:
                raise V.HttpError(
                    V.HTTP_NOT_FOUND,
                    "cluster monitor disabled (LO_MONITOR=0)")
            return 200, monitor.snapshot(), "application/json"
        if kind == "alerts":
            monitor = getattr(self.ctx, "monitor", None)
            watchdog = getattr(monitor, "watchdog", None)
            if watchdog is None:
                raise V.HttpError(
                    V.HTTP_NOT_FOUND,
                    "SLO watchdog disabled (LO_MONITOR=0)")
            return 200, watchdog.snapshot(), "application/json"
        if kind == "autoscaler":
            autoscaler = getattr(self.ctx, "autoscaler", None)
            if autoscaler is None:
                raise V.HttpError(
                    V.HTTP_NOT_FOUND,
                    "elastic autoscaler disabled (LO_AUTOSCALE=0)")
            doc = autoscaler.stats()
            doc["migration"] = self.ctx.jobs.migration_stats()
            return 200, doc, "application/json"
        return 404, {"result": "unknown route"}, "application/json"

    # ------------------------------------------------------------------
    def _incidents(self, method: str, parts: list,
                   body: Dict[str, Any]) -> Tuple[int, Any, str]:
        """Incident flight-recorder surface (docs/OBSERVABILITY.md
        "Incidents & flight recorder"). Auto captures ride the
        trigger queue; POST here is the synchronous manual path —
        both are serialized by the recorder's commit lock, so they
        are race-safe against each other."""
        recorder = getattr(self.ctx, "incidents", None)
        if recorder is None:
            raise V.HttpError(
                V.HTTP_NOT_FOUND,
                "incident recorder disabled (LO_INCIDENTS=0)")
        if method == "POST":
            if len(parts) != 2:
                return (404, {"result": "unknown route"},
                        "application/json")
            manifest = recorder.capture("manual", body)
            return V.HTTP_CREATED, manifest, "application/json"
        if method != "GET":
            return (405, {"result": "unsupported method"},
                    "application/json")
        if len(parts) == 2:
            return (200, {"result": recorder.list()},
                    "application/json")
        iid = parts[2]
        if len(parts) == 4 and parts[3] == "download":
            data = recorder.tar_bytes(iid)
            if data is None:
                raise V.HttpError(V.HTTP_NOT_FOUND,
                                  f"no incident bundle {iid}")
            return 200, data, "application/x-tar"
        if len(parts) == 3:
            manifest = recorder.manifest(iid)
            if manifest is None:
                raise V.HttpError(V.HTTP_NOT_FOUND,
                                  f"no incident bundle {iid}")
            return 200, manifest, "application/json"
        return 404, {"result": "unknown route"}, "application/json"

    # ------------------------------------------------------------------
    def _serve(self, method: str, parts: list,
               body: Dict[str, Any]) -> Tuple[int, Any, str]:
        """Resident serving plane (docs/SERVING.md):

        - ``POST /serve/{model}``            create a session (201)
        - ``POST /serve/{model}/predict``    synchronous inference
        - ``GET  /serve`` / ``/serve/{model}``  stats
        - ``DELETE /serve/{model}``          teardown
        """
        serving = self.ctx.serving
        if method == "GET":
            if len(parts) == 1:
                return (200, {"result": serving.list_sessions()},
                        "application/json")
            if len(parts) == 2:
                return (200, serving.session_stats(parts[1]),
                        "application/json")
        elif method == "POST":
            if len(parts) == 2:
                return (V.HTTP_CREATED, serving.create(parts[1], body),
                        "application/json")
            if len(parts) == 3 and parts[2] == "predict":
                return (200, serving.predict(parts[1], body),
                        "application/json")
        elif method == "DELETE":
            if len(parts) == 2:
                return (200, serving.delete(parts[1]),
                        "application/json")
        else:
            return (405, {"result": "unsupported method"},
                    "application/json")
        return 404, {"result": "unknown route"}, "application/json"

    # ------------------------------------------------------------------
    def _health(self) -> Dict[str, Any]:
        info: Dict[str, Any] = {"status": "ok",
                                "jobsRunning": self.ctx.jobs.running()}
        from learningorchestra_tpu.runtime import distributed as dist

        # pod liveness FIRST and outside the topology try: a broken
        # distributed runtime is when host_info() is most likely to
        # raise, and that must not mask the degraded status
        failure = dist.pod_failure()
        if failure:
            info["status"] = "degraded"
            info["podFailure"] = failure
        try:
            info.update(dist.host_info())
            info["deviceCount"] = info["globalDevices"]
            info["devicePlatform"] = info["platform"]
        except Exception as e:  # noqa: BLE001
            info["deviceError"] = repr(e)
        return info

    def _healthz(self) -> Tuple[int, Any, str]:
        """Readiness probe (docs/OBSERVABILITY.md "/healthz"): 503
        while the server drains (load balancers stop routing before
        the listener dies) or while any page-severity SLO alert fires;
        200 otherwise. Distinct from ``/health``, which reports
        liveness detail and never changes the status code."""
        monitor = getattr(self.ctx, "monitor", None)
        watchdog = getattr(monitor, "watchdog", None)
        paging = [a for a in watchdog.firing()
                  if a["severity"] == "page"] if watchdog else []
        if self.ctx.draining:
            return (503, {"status": "draining"}, "application/json")
        if paging:
            return (503, {"status": "failing", "alerts": paging},
                    "application/json")
        return 200, {"status": "ok"}, "application/json"

    def _profile(self, method: str, body: Dict[str, Any],
                 ) -> Tuple[int, Any, str]:
        """``POST /profile {"action": "start"|"stop"}`` captures a
        ``jax.profiler`` trace (XLA device activity, HLO timelines —
        view in TensorBoard/Perfetto). ``GET /profile`` lists captured
        traces. The singleton session is owned by the process-wide
        :class:`~..observability.incidents.ProfilerGate` (shared with
        the flight recorder's triggered windows), which arms a
        ``LO_PROFILE_MAX_SECONDS`` auto-stop on every manual start;
        captured dirs under ``home/profiles`` are retention-bounded
        to the ``LO_PROFILE_KEEP`` newest. The reference's only
        profiling surface is the Spark UI + builder fitTime
        (SURVEY §5); this is first-party and covers every jitted
        computation in the process."""
        import os
        import time as time_mod

        from learningorchestra_tpu.observability import \
            incidents as obs_incidents

        gate = self._profiler_gate
        root = os.path.join(self.ctx.config.home, "profiles")
        if method == "GET":
            traces = sorted(os.listdir(root)) \
                if os.path.isdir(root) else []
            doc: Dict[str, Any] = {"active": gate.active() is not None,
                                   "traces": traces}
            auto_stop = gate.last_auto_stop()
            if auto_stop is not None:
                doc["lastAutoStop"] = auto_stop
            return 200, doc, "application/json"
        if method != "POST":
            return 405, {"result": "unsupported method"}, "application/json"
        action = (body.get("action") or "").lower()
        if action == "start":
            trace_dir = os.path.join(
                root,
                f"{time_mod.strftime('%Y%m%d-%H%M%S')}-"
                f"{time_mod.time_ns() % 1_000_000:06d}")
            started = gate.try_start(
                trace_dir,
                max_seconds=float(getattr(
                    self.ctx.config, "profile_max_seconds", 0) or 0))
            if not started:
                raise V.HttpError(V.HTTP_NOT_ACCEPTABLE,
                                  "a trace is already active")
            return 201, {"result": trace_dir}, "application/json"
        if action == "stop":
            # the gate clears its active marker even when stop_trace
            # raises (the raise propagates to the generic 500
            # handler), so a failed stop never wedges later starts
            trace_dir = gate.stop()
            if trace_dir is None:
                raise V.HttpError(V.HTTP_NOT_ACCEPTABLE,
                                  "no active trace")
            n_files = sum(len(fs) for _, _, fs in os.walk(trace_dir))
            obs_incidents.prune_dirs(root, int(getattr(
                self.ctx.config, "profile_keep", 0) or 0))
            return 200, {"result": trace_dir,
                         "files": n_files}, "application/json"
        raise V.HttpError(V.HTTP_NOT_ACCEPTABLE,
                          "action must be 'start' or 'stop'")

    def _post(self, service: str, tool: str, body: Dict[str, Any],
              ) -> Tuple[int, Any, str]:
        if service == "dataset":
            status, payload = self.dataset.create(body, tool)
        elif service == "model":
            status, payload = self.model.create(body, tool)
        elif service in EXECUTION_VERBS:
            status, payload = self.execution.create(body, service, tool)
        elif service == "explore" and tool == "histogram":
            status, payload = self.histogram.create(body, tool)
        elif service == "explore":
            status, payload = self.dbexec.create(body, service, tool)
        elif service == "transform" and tool == "projection":
            status, payload = self.projection.create(body, tool)
        elif service == "transform" and tool == "dataType":
            status, payload = self.datatype.create(body, tool)
        elif service == "transform":
            status, payload = self.dbexec.create(body, service, tool)
        elif service == "function":
            status, payload = self.function.create(body, tool)
        elif service == "builder":
            status, payload = self.builder.create(body, tool)
        else:
            raise V.HttpError(404, "unknown route")
        return status, payload, "application/json"

    def _patch(self, service: str, tool: str, name: str,
               body: Dict[str, Any]) -> Tuple[int, Any, str]:
        if service == "model":
            status, payload = self.model.update(name, body, tool)
        elif service in EXECUTION_VERBS:
            status, payload = self.execution.update(name, body, service,
                                                    tool)
        elif service in ("explore", "transform"):
            status, payload = self.dbexec.update(name, body, service, tool)
        elif service == "function":
            status, payload = self.function.update(name, body, tool)
        else:
            raise V.HttpError(V.HTTP_NOT_ACCEPTABLE,
                              f"PATCH unsupported for {service}")
        return status, payload, "application/json"

    def _delete(self, service: str, tool: str, name: str,
                ) -> Tuple[int, Any, str]:
        # ``DELETE .../{name}/run`` cancels the RUNNING JOB, keeping
        # the collection (safe_name forbids "/", so no real collection
        # can shadow the suffix). The job's cancel token flips and the
        # terminal ``cancelled`` document is written at the next
        # cooperative check (docs/LIFECYCLE.md).
        if name.endswith("/run") and len(name) > len("/run"):
            return self._cancel_run(name[:-len("/run")])
        if service == "dataset":
            status, payload = self.dataset.delete_file(name)
        elif service == "model":
            status, payload = self.model.delete(name, tool)
        elif service in EXECUTION_VERBS:
            status, payload = self.execution.delete(name, service, tool)
        elif service in ("explore", "transform", "function", "builder"):
            status, payload = self.dataset.delete_file(name)
        else:
            raise V.HttpError(404, "unknown route")
        return status, payload, "application/json"

    def _cancel_run(self, name: str) -> Tuple[int, Any, str]:
        if self.ctx.catalog.get_metadata(name) is None:
            raise V.HttpError(V.HTTP_NOT_FOUND,
                              f"{V.MESSAGE_NONEXISTENT_FILE}: {name}")
        if not self.ctx.jobs.cancel(name):
            raise V.HttpError(V.HTTP_NOT_ACCEPTABLE,
                              f"no cancellable job for {name} (already "
                              f"finished or never submitted here)")
        return 200, {"result": f"cancellation requested for {name}"}, \
            "application/json"

    def _migrate_run(self, name: str) -> Tuple[int, Any, str]:
        # ``POST .../{name}/migrate`` asks the RUNNING JOB to move to
        # a fresh slice placement at its next epoch boundary
        # (docs/SCALING.md §7); refused (406) when the job is not a
        # live migratable mesh job — finished, never submitted here,
        # whole-mesh, or multi-host.
        if self.ctx.catalog.get_metadata(name) is None:
            raise V.HttpError(V.HTTP_NOT_FOUND,
                              f"{V.MESSAGE_NONEXISTENT_FILE}: {name}")
        if not self.ctx.jobs.migrate(name):
            raise V.HttpError(V.HTTP_NOT_ACCEPTABLE,
                              f"no migratable job for {name} (not "
                              f"running, not sliced, or multi-host)")
        return 200, {"result": f"migration requested for {name}"}, \
            "application/json"

    def _get(self, service: str, tool: str, name: Optional[str],
             params: Dict[str, Any]) -> Tuple[int, Any, str]:
        now = time.monotonic()
        if name is None:
            # listing: every collection of this type (reference routes
            # list GETs to the dataset reader with ?type=,
            # krakend.json:722-757). Cached against the global change
            # seq — any create/update/delete invalidates.
            if self.read_cache.enabled:
                key = ("list", service, tool)
                version = self.ctx.catalog.latest_seq()
                hit = self.read_cache.get(key, version, now)
                if hit is not None:
                    return hit[0], hit[1], "application/json"
            type_string = D.normalize_type(f"{service}/{tool}")
            payload = {"result": self.ctx.catalog.list_collections(
                type_string)}
            if self.read_cache.enabled:
                self.read_cache.put(key, version, now, 200, payload)
            return 200, payload, "application/json"
        # explore plots are PNGs (reference send_file image/png,
        # database_executor server.py:151-166); paged/queried GETs
        # still read the JSON documents so status polling works
        has_paging = any(k in params for k in ("skip", "limit", "query"))
        if service == "explore" and tool != "histogram" and not has_paging:
            meta = self.ctx.catalog.get_metadata(name)
            if meta is not None and str(
                    meta.get(D.TYPE_FIELD, "")).startswith("explore/"):
                try:
                    png, content_type = self.dbexec.image_response(name)
                    return 200, png, content_type
                except Exception:  # noqa: BLE001 - fall through to JSON
                    pass
        skip = int(params.get("skip", 0) or 0)
        limit = params.get("limit")
        limit = int(limit) if limit not in (None, "") else None
        query = parse_query_param(params.get("query"))
        # universal read, cached per (name, page) against the
        # collection's content version: change-feed seq for docs +
        # parquet file stats for rows (appends bypass the feed). A
        # poller spinning on ?limit=1 stops re-reading sqlite/parquet;
        # the doc append that flips ``finished`` bumps the seq and
        # invalidates (krakend.json:1769 "cache_ttl" parity, made
        # staleness-proof).
        key = ("read", name, skip, limit, params.get("query"))
        if self.read_cache.enabled:
            version = (self.ctx.catalog.collection_seq(name),
                       self.ctx.catalog.dataset_version(name))
            hit = self.read_cache.get(key, version, now)
            if hit is not None:
                return hit[0], hit[1], "application/json"
        status, payload = self.dataset.read_file(
            name, skip=skip, limit=limit, query=query)
        if self.read_cache.enabled:
            self.read_cache.put(key, version, now, status, payload)
        return status, payload, "application/json"

    # ------------------------------------------------------------------
    def _observe(self, parts, params) -> Tuple[int, Any, str]:
        """``GET /observe/{name}?seq=N&timeout=S``: block until the
        collection changes past sequence N, then return the new changes
        + current metadata (the reference's Observe service is a
        client-side Mongo change stream; README.md:81)."""
        if len(parts) < 2:
            return 200, {"result": {"seq": self.ctx.catalog.latest_seq()}}, \
                "application/json"
        name = parts[1]
        seq = int(params.get("seq", 0) or 0)
        timeout = min(float(params.get("timeout", 25) or 25), 120.0)
        # under a gateway deadline the long-poll window clamps to just
        # inside it: the client gets an empty 200 (and re-polls, the
        # normal long-poll idiom) instead of a 504 whose abandoned
        # dispatch would sit in the condition wait for the full window
        gateway = self.ctx.config.request_timeout_seconds
        if gateway > 0:
            timeout = min(timeout, max(0.05, gateway - 0.1))
        changes = self.ctx.catalog.watch(seq, collection=name,
                                         timeout=timeout)
        return 200, {"result": {
            "changes": changes,
            "seq": self.ctx.catalog.latest_seq(),
            "metadata": self.ctx.catalog.get_metadata(name),
        }}, "application/json"


# ----------------------------------------------------------------------
class _Handler(BaseHTTPRequestHandler):
    api: Api = None  # set by make_server
    protocol_version = "HTTP/1.1"

    # quiet the default stderr-per-request logging
    def log_message(self, format, *args):  # noqa: A002
        pass

    def _read_body(self) -> Optional[Dict[str, Any]]:
        length = int(self.headers.get("Content-Length") or 0)
        if not length:
            return None
        raw = self.rfile.read(length)
        try:
            body = json.loads(raw)
        except json.JSONDecodeError:
            return None
        return body if isinstance(body, dict) else None

    def _respond(self, method: str) -> None:
        parsed = urlparse(self.path)
        params = {k: v[0] for k, v in parse_qs(parsed.query).items()}
        body = self._read_body() if method in ("POST", "PATCH") else None
        timeout = self.api.ctx.config.request_timeout_seconds
        if timeout > 0:
            # KrakenD proxies with a per-endpoint "timeout": "10s"
            # (krakend.json:1770): the client gets 504 while the
            # backend call keeps running — same semantics here (the
            # dispatch daemon thread finishes its work; only the
            # response is abandoned). A thread per timed request, not
            # a shared pool: N abandoned slow dispatches must never
            # starve unrelated requests, and daemon threads don't
            # block interpreter exit. Metrics are recorded HERE with
            # the status the client actually saw (record=False below).
            t0 = time.monotonic()
            result: list = []
            done = threading.Event()
            # abandoned dispatches are invisible by construction — the
            # 504 already went out — so they are capped and counted
            # (LO_GATEWAY_MAX_INFLIGHT; lo_abandoned_dispatches on
            # /metrics): at the cap new timed requests get an instant
            # 503 instead of stacking another thread on a slow backend
            api = self.api
            cap = api.ctx.config.gateway_max_inflight
            finished = [False]
            abandoned = [False]
            with api._gateway_lock:
                saturated = cap > 0 and api._gateway_inflight >= cap
                if saturated:
                    api._gateway_saturated_total += 1
                else:
                    api._gateway_inflight += 1
            if saturated:
                status, payload, content_type = (
                    503,
                    {"result": f"gateway saturated ({cap} timed "
                               f"dispatches in flight) — retry with "
                               f"backoff"},
                    "application/json")
                api._record_metrics(method, parsed.path, status,
                                    time.monotonic() - t0)
                self._send(status, payload, content_type)
                return

            def run_dispatch() -> None:
                try:
                    result.append(api.dispatch(
                        method, parsed.path, params, body,
                        record=False))
                    done.set()
                finally:
                    with api._gateway_lock:
                        api._gateway_inflight -= 1
                        finished[0] = True
                        if abandoned[0]:
                            api._gateway_abandoned_inflight -= 1

            threading.Thread(target=run_dispatch, daemon=True,
                             name="lo-gateway").start()
            if done.wait(timeout):
                status, payload, content_type = result[0]
            else:
                with api._gateway_lock:
                    # the dispatch may land between wait() expiring
                    # and this lock — only a still-running one counts
                    # as abandoned (its finally block decrements)
                    if not finished[0]:
                        abandoned[0] = True
                        api._gateway_abandoned_total += 1
                        api._gateway_abandoned_inflight += 1
                status, payload, content_type = (
                    504,
                    {"result": f"request timed out after {timeout:g}s"},
                    "application/json")
            self.api._record_metrics(method, parsed.path, status,
                                     time.monotonic() - t0)
        else:
            status, payload, content_type = self.api.dispatch(
                method, parsed.path, params, body)
        self._send(status, payload, content_type)

    def _send(self, status: int, payload: Any,
              content_type: str) -> None:
        if isinstance(payload, (bytes, bytearray)):
            data = bytes(payload)
        else:
            data = json.dumps(payload).encode()
            content_type = "application/json"
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self):  # noqa: N802
        self._respond("GET")

    def do_POST(self):  # noqa: N802
        self._respond("POST")

    def do_PATCH(self):  # noqa: N802
        self._respond("PATCH")

    def do_DELETE(self):  # noqa: N802
        self._respond("DELETE")


class RestServer:
    """Owns the HTTP server + its ServiceContext."""

    def __init__(self, context: Optional[ServiceContext] = None,
                 host: Optional[str] = None, port: Optional[int] = None):
        self.api = Api(context)
        cfg = self.api.ctx.config
        handler = type("BoundHandler", (_Handler,), {"api": self.api})
        self.httpd = ThreadingHTTPServer(
            (host or cfg.host, cfg.port if port is None else port), handler)
        self._thread: Optional[threading.Thread] = None

    @property
    def address(self) -> Tuple[str, int]:
        return self.httpd.server_address[:2]

    @property
    def base_url(self) -> str:
        host, port = self.address
        return f"http://{host}:{port}"

    def start(self) -> "RestServer":
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True, name="lo-rest")
        self._thread.start()
        return self

    def serve_forever(self) -> None:
        self.httpd.serve_forever()

    def stop(self) -> None:
        # flip /healthz to 503 while the listener still answers, so a
        # load balancer health-checking this node drains it first
        self.api.ctx.begin_drain()
        self.httpd.shutdown()
        self.httpd.server_close()
        self.api.ctx.close()


def main(argv=None) -> None:
    import argparse

    from learningorchestra_tpu.config import Config, get_config, set_config

    parser = argparse.ArgumentParser(
        description="learningOrchestra-TPU REST server")
    parser.add_argument("--host", default=None)
    parser.add_argument("--port", type=int, default=None)
    parser.add_argument("--home", default=None,
                        help="storage root (default LO_HOME or ./.lo_store)")
    parser.add_argument("--config", default=None,
                        help="JSON config file")
    parser.add_argument("--coordinator", default=None,
                        help="host:port of process 0 for multi-host runs "
                             "(default LO_COORDINATOR)")
    parser.add_argument("--num-hosts", type=int, default=None,
                        help="total processes in the pod "
                             "(default LO_NUM_HOSTS)")
    parser.add_argument("--host-id", type=int, default=None,
                        help="this process's index (default LO_HOST_ID)")
    args = parser.parse_args(argv)
    if args.config:
        set_config(Config.from_file(args.config))
    if args.home:
        set_config(get_config().replace(home=args.home))

    from learningorchestra_tpu.runtime import distributed as dist

    multi_host = dist.initialize(coordinator_address=args.coordinator,
                                 num_processes=args.num_hosts,
                                 process_id=args.host_id)
    if multi_host and not dist.is_coordinator():
        # workers never serve REST: they follow the coordinator's job
        # broadcasts so every global-mesh jit has all participants
        info = dist.host_info()
        print(f"learningOrchestra-TPU worker {info['processIndex']}/"
              f"{info['processCount']} following coordinator", flush=True)
        dist.HostBridge().follow(lambda msg: None)
        return

    server = RestServer(host=args.host, port=args.port)
    host, port = server.address
    print(f"learningOrchestra-TPU REST on http://{host}:{port}"
          f"{get_config().api_prefix}", flush=True)

    # SIGTERM (the k8s/systemd stop signal) drains like Ctrl-C: stop
    # accepting requests, then the shutdown path below runs. In-flight
    # jobs left unfinished are requeued by the next boot's
    # recover_unfinished().
    import signal as signal_mod

    def _terminate(signum, frame):  # noqa: ARG001
        raise KeyboardInterrupt

    signal_mod.signal(signal_mod.SIGTERM, _terminate)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        server.stop()
    finally:
        if multi_host:
            import time as time_mod

            # drain in-flight mesh jobs first: a job thread publishing
            # its fan-out after our shutdown broadcast would block on a
            # collective the workers already left
            deadline = time_mod.monotonic() + 60
            while server.api.ctx.jobs.running() and \
                    time_mod.monotonic() < deadline:
                time_mod.sleep(0.25)
            try:
                dist.HostBridge().publish({"op": "shutdown"})
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
            dist.shutdown()


if __name__ == "__main__":
    main()
