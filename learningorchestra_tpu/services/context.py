"""Shared service wiring.

The reference constructs a singleton Database/Metadata/UserRequest/
storage stack at import time in every one of its 9 ``server.py`` files
(e.g. binary_executor_image/server.py:10-21) and shares binaries via
cross-mounted volumes. Here one ``ServiceContext`` owns the catalog,
artifact store, job manager, parameter resolver and (lazily) the JAX
runtime, and every executor takes it by injection — also what lets
tests run fully in-process with a tmp-dir store.
"""

from __future__ import annotations

import os
from typing import Optional

from learningorchestra_tpu.config import Config, get_config
from learningorchestra_tpu.catalog.store import Catalog
from learningorchestra_tpu.catalog.artifacts import ArtifactStore


class ServiceContext:
    def __init__(self, config: Optional[Config] = None,
                 pod_failure_fn=None, force_pod_guard: bool = False):
        from learningorchestra_tpu.runtime import distributed as dist
        from learningorchestra_tpu.services.feature_cache import FeatureCache
        from learningorchestra_tpu.services.jobs import JobManager
        from learningorchestra_tpu.services.params import ParameterResolver
        from learningorchestra_tpu.services.scheduler import \
            parse_pool_weights

        self.config = config or get_config()
        self.config.ensure_dirs()
        self.catalog = Catalog(self.config.catalog_path,
                               self.config.datasets_dir)
        self.artifacts = ArtifactStore(self.config.artifacts_dir)
        self.pod_failure_fn = pod_failure_fn or dist.pod_failure
        self.jobs = JobManager(self.catalog,
                               max_workers=self.config.max_workers,
                               mesh_leases=self.config.mesh_leases,
                               pod_failure_fn=self.pod_failure_fn,
                               pool_weights=parse_pool_weights(
                                   self.config.pool_weights),
                               default_timeout=self.config
                               .job_timeout_seconds,
                               stall_seconds=self.config.stall_seconds,
                               stall_escalate=self.config.stall_escalate,
                               retry_backoff=self.config
                               .retry_backoff_seconds,
                               retry_backoff_max=self.config
                               .retry_backoff_max_seconds,
                               slice_min_devices=self.config
                               .slice_min_devices,
                               slice_aging_seconds=self.config
                               .slice_aging_seconds,
                               served_half_life_seconds=self.config
                               .fair_served_half_life_seconds,
                               numerical_retries=self.config
                               .health_retries,
                               slice_defrag=self.config.slice_defrag)
        # feature-plane cache (docs/PERFORMANCE.md): the host tier all
        # dataset reads route through; shares the $name-cache budget
        self.features = FeatureCache(
            self.catalog, host_bytes=self.config.param_cache_bytes)
        self.params = ParameterResolver(self)
        # resident serving plane (docs/SERVING.md): sessions share the
        # JobManager's slice allocator via ServingLease handles
        from learningorchestra_tpu.services.serving import ServingManager
        self.serving = ServingManager(self)
        wire_compile_cache()
        # callbacks fired by the pod guard when a degraded pod's
        # heartbeats resume (the Api registers worker-lost requeue)
        self.on_pod_healthy: list = []
        self._pod_guard = _start_pod_guard(self, force=force_pod_guard)
        # readiness: /healthz reports 503 while this is set (server
        # shutdown flips it before the listener stops accepting)
        self._draining = False
        # cluster resource sampler + SLO watchdog
        # (docs/OBSERVABILITY.md "Cluster monitor"); LO_MONITOR=0
        # leaves both off
        self.monitor = _start_monitor(self)
        # singleton jax.profiler owner, shared between the manual
        # POST /profile surface and the flight recorder's triggered
        # windows — per-context so test servers stay isolated
        from learningorchestra_tpu.observability.incidents import \
            ProfilerGate
        self.profiler_gate = ProfilerGate()
        # incident flight recorder (docs/OBSERVABILITY.md "Incidents
        # & flight recorder"); LO_INCIDENTS=0 leaves it off. Must come
        # after the monitor so its snapshot collectors resolve.
        self.incidents, self._health_listener = _start_incidents(self)
        # elastic slice autoscaler (docs/SCALING.md "Elastic
        # autoscaling"); LO_AUTOSCALE=0 leaves it off. After the
        # monitor so its watchdog accessor resolves.
        self.autoscaler = _start_autoscaler(self)

    @property
    def draining(self) -> bool:
        return self._draining

    def begin_drain(self) -> None:
        """Flip /healthz to 503 so load balancers stop routing here
        before the listener goes away."""
        self._draining = True

    @property
    def mesh(self):
        """The process-wide device mesh (exclusive accelerator
        resource; jobs lease it through ``jobs.mesh_lease``). Shared
        with the model layer's ``get_default_mesh`` so the context and
        the engines always compute on the same mesh."""
        from learningorchestra_tpu.runtime import mesh as mesh_lib
        return mesh_lib.get_default_mesh()

    def close(self) -> None:
        self._draining = True
        # policy loop first: it latches resize requests on job tokens
        # the shutdown below is about to cancel
        if self.autoscaler is not None:
            self.autoscaler.stop()
        if self.incidents is not None:
            from learningorchestra_tpu.observability import \
                incidents as obs_incidents
            from learningorchestra_tpu.runtime import health as \
                health_lib
            if self._health_listener is not None:
                health_lib.remove_listener(self._health_listener)
            # unhook the process-wide trigger registry only if it
            # still points here (a later context may have replaced it)
            if obs_incidents.get_recorder() is self.incidents:
                obs_incidents.set_recorder(None)
            self.incidents.close()
        if self.monitor is not None:
            self.monitor.stop()
        if self._pod_guard is not None:
            self._pod_guard.set()
        # serving sessions first: they hold leases on the mesh the job
        # manager's shutdown may want to drain
        self.serving.close()
        self.jobs.shutdown()
        self.catalog.close()


def compile_cache_path() -> str:
    """The fixed default location of jax's persistent compilation
    cache: ``<checkout>/.jax_cache``. Fixed because the directory is
    part of the cache key — a path that moves never hits."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def wire_compile_cache() -> Optional[str]:
    """THE compile-cache rule, for every entry point (``lo-server``,
    ``chip_smoke.py``): when ``JAX_COMPILATION_CACHE_DIR`` is set
    jax reads it itself and code sets nothing; otherwise an
    accelerator backend caches under :func:`compile_cache_path` and
    the CPU backend caches nothing
    (tests/conftest.py keeps its own opt-in). Call before the first
    compile. Returns the directory in use, None when the cache is off."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    if jax.default_backend() == "cpu":
        return None
    path = compile_cache_path()
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _start_monitor(ctx: "ServiceContext"):
    """Start the cluster resource sampler + SLO watchdog
    (docs/OBSERVABILITY.md "Cluster monitor"). Collectors close over
    the context's live components; everything is best-effort inside
    the monitor. Returns None when ``LO_MONITOR=0``."""
    if not getattr(ctx.config, "monitor", True):
        return None
    from learningorchestra_tpu.observability.monitor import \
        ClusterMonitor
    from learningorchestra_tpu.observability.slo import SloWatchdog
    from learningorchestra_tpu.runtime import arena as arena_lib

    def arena_stats():
        return arena_lib.get_default_arena().stats()

    def serving_stats():
        s = ctx.serving.stats()
        by = s.get("bySession") or []
        depth = sum(int(v.get("queueDepth") or 0) for v in by)
        fills = [v["batchFill"] for v in by
                 if v.get("batchFill") is not None]
        out = {"queueDepth": depth,
               "batchFill": (round(sum(fills) / len(fills), 4)
                             if fills else None),
               "sessions": len(by),
               "requestsTotal": s.get("requestsTotal"),
               "rejectedTotal": s.get("rejectedTotal")}
        kv = s.get("kv")
        if kv:
            out["kvPagesFree"] = kv.get("pagesFree")
            out["kvPagesShared"] = kv.get("pagesShared")
            out["kvPrefillsSkipped"] = kv.get("prefillsSkipped")
        return out

    def active_trace():
        name = ctx.jobs.active_job()
        if name:
            return name
        for session in ctx.serving.stats().get("bySession") or []:
            return f"serve/{session.get('model')}"
        return None

    monitor = ClusterMonitor(
        interval_seconds=max(
            0.01, float(ctx.config.monitor_interval_ms) / 1000.0),
        ring=ctx.config.monitor_ring,
        scheduler_stats=ctx.jobs.scheduler_stats,
        serving_stats=serving_stats,
        job_stats=ctx.jobs.queue_stats,
        arena_stats=arena_stats,
        watchdog=SloWatchdog(active_trace=active_trace))
    return monitor.start()


def _start_incidents(ctx: "ServiceContext"):
    """Create the incident flight recorder (docs/OBSERVABILITY.md
    "Incidents & flight recorder") and publish it to the process-wide
    trigger registry the failure sites call into. Collectors close
    over the context's live components, like the monitor's. Returns
    ``(recorder, health_listener)`` — both None when
    ``LO_INCIDENTS=0``."""
    if not getattr(ctx.config, "incidents", True):
        return None, None
    from learningorchestra_tpu.observability import \
        incidents as obs_incidents
    from learningorchestra_tpu.runtime import health as health_lib

    def cluster_snapshot():
        return ctx.monitor.snapshot() \
            if ctx.monitor is not None else None

    def alerts_snapshot():
        monitor = ctx.monitor
        watchdog = getattr(monitor, "watchdog", None)
        return watchdog.snapshot() if watchdog is not None else None

    def stats_snapshot():
        from learningorchestra_tpu.runtime import health as hl
        return {"jobLifecycle": ctx.jobs.lifecycle_counters(),
                "meshScheduler": ctx.jobs.scheduler_stats(),
                "jobQueue": ctx.jobs.queue_stats(),
                "serving": ctx.serving.stats(),
                "trainingHealth": hl.health_stats()}

    def active_names():
        names = []
        job = ctx.jobs.active_job()
        if job:
            names.append(job)
        for session in ctx.serving.stats().get("bySession") or []:
            names.append(f"serve/{session.get('model')}")
        return names

    recorder = obs_incidents.FlightRecorder(
        home=ctx.config.home,
        cluster_snapshot=cluster_snapshot,
        alerts_snapshot=alerts_snapshot,
        stats_snapshot=stats_snapshot,
        active_names=active_names,
        profiler_gate=ctx.profiler_gate)
    obs_incidents.set_recorder(recorder)

    def on_health_event(kind: str, n: int) -> None:
        # sentinel interventions: a rollback means a fit restored its
        # last-good checkpoint — exactly the moment the in-memory
        # evidence is about to be overwritten by the resumed epochs
        if kind == "rollbacks":
            obs_incidents.trigger("health:rollback")

    health_lib.add_listener(on_health_event)
    return recorder, on_health_event


def _start_autoscaler(ctx: "ServiceContext"):
    """Start the elastic slice autoscaler policy loop
    (docs/SCALING.md "Elastic autoscaling"). The watchdog accessor is
    late-bound so LO_MONITOR=0 simply leaves the SLO pressure signal
    out (aged-waiter pressure still drives shrinks). Returns None
    when ``LO_AUTOSCALE=0``."""
    if not getattr(ctx.config, "autoscale", True):
        return None
    from learningorchestra_tpu.services.autoscaler import \
        SliceAutoscaler

    def watchdog():
        return getattr(ctx.monitor, "watchdog", None)

    return SliceAutoscaler(
        ctx.jobs, watchdog_fn=watchdog, catalog=ctx.catalog,
        interval_seconds=ctx.config.autoscale_interval_seconds,
        retries=ctx.config.autoscale_retries,
        backoff_seconds=ctx.config.autoscale_backoff_seconds,
        backoff_max_seconds=ctx.config.autoscale_backoff_max_seconds,
    ).start()


def _start_pod_guard(ctx: "ServiceContext", force: bool = False):
    """Coordinator-side watchdog (multi-host only): the moment a
    worker stops heartbeating, every in-flight mesh job gets a typed
    ``WorkerLost`` execution document — clients polling see a terminal
    failure within seconds instead of a silent hang on a collective
    (the reference loses in-flight work on node failure and relies on
    Swarm re-placement, README.md:194-202; surfacing the failure is
    the single-controller equivalent). When heartbeats RESUME, the
    ``ctx.on_pod_healthy`` callbacks fire — that's the elastic
    recovery hook that requeues checkpointed worker-lost jobs with no
    server restart. ``force=True`` starts the guard regardless of
    topology (tests with an injected ``pod_failure_fn``)."""
    import threading
    import traceback

    from learningorchestra_tpu.runtime import distributed as dist

    if not force:
        # only consult jax when the multi-host runtime already formed:
        # touching jax.process_count() here would otherwise initialize
        # the single-host backend and break a later dist.initialize()
        # (the documented order is initialize-then-ServiceContext, as
        # services/server.py main does)
        if not dist.is_initialized():
            return None
        try:
            import jax

            if jax.process_count() <= 1 or jax.process_index() != 0:
                return None
        except Exception:  # noqa: BLE001 — no runtime formed yet
            return None

    stop = threading.Event()

    def guard() -> None:
        reported = False
        while not stop.wait(dist.HEARTBEAT_INTERVAL):
            failure = ctx.pod_failure_fn()
            if failure and not reported:
                reported = True
                n = ctx.jobs.fail_running_mesh_jobs(failure)
                print(f"pod guard: {failure} — marked {n} in-flight "
                      f"mesh job(s) failed", flush=True)
            elif not failure and reported:
                # heartbeats resumed (transient pause or a restarted
                # worker): re-arm, then let the recovery callbacks
                # requeue whatever the loss stranded
                reported = False
                print("pod guard: heartbeats resumed, pod healthy "
                      "again", flush=True)
                for callback in list(ctx.on_pod_healthy):
                    try:
                        callback()
                    except Exception:  # noqa: BLE001 — the guard
                        traceback.print_exc()  # must keep watching

    threading.Thread(target=guard, daemon=True,
                     name="lo-pod-guard").start()
    return stop
