"""Binary execution service: Train / Tune / Evaluate / Predict.

One generic "call method X on stored object Y with kwargs Z" executor
backs four API verbs × two tools, exactly like the reference's
binary_executor_image (8 type strings, constants.py:41-51; POST body
``name``, ``modelName``, ``parentName``, ``description``, ``method``,
``methodParameters``, server.py:23-71).

Semantics preserved (binary_execution.py:118-189):
- validation walks the parent chain to the root model/* metadata to
  resolve the module+class whose methods are being called
  (utils.py:257-276);
- ``methodParameters`` go through the ``$``/``#``/``.`` DSL;
- train/tune results ARE the mutated instance itself
  (binary_execution.py:184-188); evaluate/predict store the returned
  value;
- PATCH re-runs a finished execution against its stored parent with
  new parameters (server.py:74-118);
- every run appends an execution document; failures record
  ``repr(exception)`` and leave ``finished`` False.

TPU-native: when the stored parent is a NeuralModel, ``fit`` /
``evaluate`` / ``predict`` dispatch into the mesh-sharded jit engine
(runtime/engine.py) — the accelerator lease is held for the duration
(jobs.py). sklearn parents run their real methods on host CPU.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from learningorchestra_tpu import analysis as A
from learningorchestra_tpu.catalog import documents as D
from learningorchestra_tpu.observability import trace as obs_trace
from learningorchestra_tpu.services import validators as V

NAME_FIELD = "name"
ANALYSIS_FIELD = "analysis"
MODEL_NAME_FIELD = "modelName"
PARENT_NAME_FIELD = "parentName"
DESCRIPTION_FIELD = "description"
METHOD_FIELD = "method"
METHOD_PARAMETERS_FIELD = "methodParameters"

# verbs whose result is the mutated parent instance
_INSTANCE_RESULT_PREFIXES = ("train/", "tune/")


class ExecutionService:
    def __init__(self, context):
        self._ctx = context
        self._validator = V.RequestValidator(context)

    # ------------------------------------------------------------------
    def root_model_metadata(self, name: str) -> Dict[str, Any]:
        """Walk the parentName chain until a model/* artifact — the
        root whose class defines the callable surface (reference
        utils.py:257-276)."""
        seen = set()
        meta = self._validator.existing(name)
        while not meta[D.TYPE_FIELD].startswith("model/"):
            parent = meta.get(D.PARENT_NAME_FIELD)
            if not parent or parent in seen:
                raise V.HttpError(
                    V.HTTP_NOT_ACCEPTABLE,
                    f"no model root in lineage of: {name}")
            seen.add(parent)
            meta = self._validator.existing(parent)
        return meta

    def _validate_method(self, root_meta: Dict[str, Any], method: str,
                         method_parameters: Dict[str, Any]) -> None:
        cls = self._validator.valid_class(
            root_meta[D.MODULE_PATH_FIELD], root_meta[D.CLASS_FIELD])
        if not isinstance(cls, type):
            # the root was created by a FACTORY (e.g.
            # tensorflow.keras.models.load_model on a SavedModel dir):
            # methods live on the returned instance's class, not the
            # factory — resolve it from the artifact's meta.json
            # (never deserializing weights on the request thread;
            # dill-stored foreign objects fall back to a full load)
            try:
                cls = self._ctx.artifacts.stored_class(
                    root_meta[D.NAME_FIELD], root_meta[D.TYPE_FIELD])
                if cls is None:
                    cls = self._ctx.artifacts.load(
                        root_meta[D.NAME_FIELD],
                        root_meta[D.TYPE_FIELD])
            except V.HttpError:
                raise
            except Exception as exc:  # noqa: BLE001 — a validation
                # failure must be a 406, not a request-thread 500
                raise V.HttpError(
                    V.HTTP_NOT_ACCEPTABLE,
                    f"cannot resolve stored model "
                    f"{root_meta[D.NAME_FIELD]!r}: {exc!r}") from exc
        self._validator.valid_method(cls, method)
        self._validator.valid_method_parameters(
            cls, method, method_parameters)

    # ------------------------------------------------------------------
    def create(self, body: Dict[str, Any], verb: str, tool: str,
               ) -> Tuple[int, Dict[str, Any]]:
        self._validator.required_fields(
            body, [NAME_FIELD, MODEL_NAME_FIELD, METHOD_FIELD,
                   METHOD_PARAMETERS_FIELD])
        name = self._validator.safe_name(body[NAME_FIELD])
        parent_name = body.get(PARENT_NAME_FIELD) or body[MODEL_NAME_FIELD]
        method = body[METHOD_FIELD]
        method_parameters = body[METHOD_PARAMETERS_FIELD] or {}
        description = body.get(DESCRIPTION_FIELD, "")
        timeout = V.valid_timeout(body.get(V.TIMEOUT_FIELD))
        slice_devices = V.valid_slice_devices(
            body.get(V.SLICE_DEVICES_FIELD))
        health_policy = V.valid_health_policy(
            body.get(V.HEALTH_POLICY_FIELD))
        # the trace (id == collection name) starts HERE, on the HTTP
        # thread: submit/validate/preflight spans precede the job
        # root span the worker thread opens later
        with obs_trace.span("submit", trace=name, verb=verb,
                            tool=tool):
            with obs_trace.span("validate"):
                self._validator.not_duplicate(name)
                self._validator.existing_finished(parent_name)
                root_meta = self.root_model_metadata(parent_name)
                self._validate_method(root_meta, method,
                                      method_parameters)
            with obs_trace.span("preflight"):
                analysis = self._preflight(root_meta, method,
                                           method_parameters)
                footprint = self._footprint(root_meta, method,
                                            method_parameters,
                                            slice_devices)
        type_string = D.normalize_type(f"{verb}/{tool}")
        extra = {
            D.PARENT_NAME_FIELD: parent_name,
            D.METHOD_FIELD: method,
            D.METHOD_PARAMETERS_FIELD: method_parameters,
            D.DESCRIPTION_FIELD: description,
        }
        if timeout is not None:
            # stored in metadata so boot/elastic requeues replay the
            # same deadline (server._requeue_execution)
            extra[V.TIMEOUT_FIELD] = timeout
        if health_policy is not None:
            # same boot-replay contract as timeout
            extra[V.HEALTH_POLICY_FIELD] = health_policy
        if analysis:
            extra[ANALYSIS_FIELD] = analysis
        if footprint:
            # the _id:0 record of what the scheduler was told — the
            # "why did my job wait" answer for polling clients
            extra[A.FOOTPRINT_FIELD] = footprint
        self._ctx.catalog.create_collection(name, type_string, extra)
        self._submit(name, type_string, parent_name, method,
                     method_parameters, description, timeout=timeout,
                     footprint=footprint, health_policy=health_policy)
        return V.HTTP_CREATED, {
            "result": f"/api/learningOrchestra/v1/{verb}/{tool}/{name}"}

    def update(self, name: str, body: Dict[str, Any], verb: str, tool: str,
               ) -> Tuple[int, Dict[str, Any]]:
        meta = self._validator.existing(name)
        method = meta[D.METHOD_FIELD]
        method_parameters = body.get(
            METHOD_PARAMETERS_FIELD, meta.get(D.METHOD_PARAMETERS_FIELD)) \
            or {}
        description = body.get(DESCRIPTION_FIELD, "")
        timeout = V.valid_timeout(
            body.get(V.TIMEOUT_FIELD, meta.get(V.TIMEOUT_FIELD)))
        stored_fp = meta.get(A.FOOTPRINT_FIELD) or {}
        # elastic bounds outlive the re-run: a PATCH without an
        # explicit sliceDevices keeps the stored {min, max}, not just
        # the (possibly resized) flat device count
        slice_devices = V.valid_slice_devices(
            body.get(V.SLICE_DEVICES_FIELD,
                     stored_fp.get("elastic") or stored_fp.get("devices")))
        health_policy = V.valid_health_policy(
            body.get(V.HEALTH_POLICY_FIELD,
                     meta.get(V.HEALTH_POLICY_FIELD)))
        parent_name = meta[D.PARENT_NAME_FIELD]
        root_meta = self.root_model_metadata(parent_name)
        self._validate_method(root_meta, method, method_parameters)
        analysis = self._preflight(root_meta, method, method_parameters)
        # re-seed the in-process calibration registry from the prior
        # run's durable measurement, so calibration survives restarts
        if getattr(self._ctx.config, "footprint_calibrate", False) \
                and meta.get("peakHbmBytes"):
            from learningorchestra_tpu.observability import \
                monitor as monitor_lib

            monitor_lib.record_peak(
                f"{root_meta.get(D.NAME_FIELD)}:{method}",
                int(meta["peakHbmBytes"]))
        footprint = self._footprint(root_meta, method, method_parameters,
                                    slice_devices)
        self._ctx.catalog.update_metadata(
            name, {D.METHOD_PARAMETERS_FIELD: method_parameters,
                   ANALYSIS_FIELD: analysis,
                   A.FOOTPRINT_FIELD: footprint,
                   V.TIMEOUT_FIELD: timeout,
                   V.HEALTH_POLICY_FIELD: health_policy,
                   D.FINISHED_FIELD: False})
        self._submit(name, meta[D.TYPE_FIELD], parent_name, method,
                     method_parameters, description, timeout=timeout,
                     footprint=footprint, health_policy=health_policy)
        return V.HTTP_SUCCESS, {
            "result": f"/api/learningOrchestra/v1/{verb}/{tool}/{name}"}

    def delete(self, name: str, verb: str, tool: str,
               ) -> Tuple[int, Dict[str, Any]]:
        import shutil

        meta = self._validator.existing(name)
        self._ctx.catalog.delete_collection(name)
        self._ctx.artifacts.delete(name, meta.get(D.TYPE_FIELD))
        # a stale checkpoint dir would make a future execution reusing
        # this name silently resume from the deleted run
        shutil.rmtree(checkpoint_dir_for(self._ctx, name),
                      ignore_errors=True)
        return V.HTTP_SUCCESS, {"result": f"deleted {name}"}

    # ------------------------------------------------------------------
    def _preflight(self, root_meta: Dict[str, Any], method: str,
                   method_parameters: Dict[str, Any]) -> list:
        """Static shape pre-flight + '#'-DSL lint BEFORE the job
        document exists: a provably-broken spec 406s here and leaves
        no ``finished: False`` orphan. Advisory findings come back for
        the job document."""
        if not self._ctx.config.preflight:
            return []
        findings = A.check_execution(
            self._ctx.catalog, root_meta, method, method_parameters,
            mode=self._ctx.config.sandbox_mode)
        return V.run_preflight(findings)

    def _footprint(self, root_meta: Dict[str, Any], method: str,
                   method_parameters: Dict[str, Any],
                   slice_devices: Optional[int],
                   ) -> Optional[Dict[str, Any]]:
        """The slice-scheduler footprint for this execution: the
        request's explicit ``sliceDevices`` merged over the preflight
        HBM estimate (eval_shape init + lowered-step memory_analysis,
        heuristic fallback). None = no claim; the scheduler
        gang-acquires the full mesh, which is always safe."""
        estimate = None
        if self._ctx.config.preflight:
            estimate = A.estimate_footprint(
                self._ctx.catalog, root_meta, method, method_parameters)
        footprint = dict(estimate) if estimate else {}
        self._calibrate(footprint, root_meta, method)
        if isinstance(slice_devices, dict):
            # elastic bounds: start at max (the job takes what it can
            # and shrinks under pressure — services/autoscaler.py)
            footprint["devices"] = int(slice_devices["max"])
            footprint["elastic"] = {"min": int(slice_devices["min"]),
                                    "max": int(slice_devices["max"])}
        elif slice_devices is not None:
            footprint["devices"] = slice_devices
        return footprint or None

    def _calibrate(self, footprint: Dict[str, Any],
                   root_meta: Dict[str, Any], method: str) -> None:
        """Closed-loop footprint calibration (docs/SCALING.md §7,
        LO_FOOTPRINT_CALIBRATE): when a prior execution of the same
        (model, method) recorded its measured peak HBM
        (``peakHbmBytes`` on the terminal metadata, mirrored into the
        in-process registry), prefer that — with LO_FOOTPRINT_MARGIN
        safety padding, clamped to one order of magnitude around the
        static estimate — over the eval-shape heuristic, which pads
        hardest exactly where it matters most (repeat sweeps of one
        architecture). Always stamps ``calibrationKey`` so the job
        layer knows where to record this run's measured peak."""
        from learningorchestra_tpu.observability import \
            monitor as monitor_lib

        cfg = self._ctx.config
        if not getattr(cfg, "footprint_calibrate", False):
            return
        key = f"{root_meta.get(D.NAME_FIELD)}:{method}"
        footprint["calibrationKey"] = key
        estimate = footprint.get("hbmBytes")
        measured = monitor_lib.measured_peak(key)
        if not measured or not estimate:
            return
        footprint["estimatedHbmBytes"] = int(estimate)
        footprint["hbmBytes"] = monitor_lib.calibrated_hbm_bytes(
            measured, int(estimate),
            float(getattr(cfg, "footprint_margin", 1.25)))
        footprint["estimator"] = "measured-peak"

    def _submit(self, name: str, type_string: str, parent_name: str,
                method: str, method_parameters: Dict[str, Any],
                description: str, only_if_idle: bool = False,
                timeout: Optional[float] = None,
                footprint: Optional[Dict[str, Any]] = None,
                health_policy: Optional[Any] = None) -> None:
        def run():
            _broadcast_to_workers(name, type_string, parent_name, method,
                                  method_parameters, health_policy)
            with obs_trace.span("dataLoad"):
                parent_type = self._ctx.params.artifact_type(
                    parent_name)
                instance = self._ctx.artifacts.load(parent_name,
                                                    parent_type)
                with obs_trace.span("paramsTreat"):
                    treated = self._ctx.params.treat(method_parameters)
            ckpt = _prepare_checkpointer(self._ctx, name, type_string,
                                         treated)
            _inject_epoch_log(self._ctx, name, instance, method, treated)
            _inject_health_policy(self._ctx, instance, method, treated,
                                  health_policy)
            try:
                result = getattr(instance, method)(**treated)
            finally:
                if ckpt is not None:
                    ckpt.close()  # flush async checkpoint writes
            if type_string.startswith(_INSTANCE_RESULT_PREFIXES):
                result = instance  # the fitted object is the artifact
            with obs_trace.span("artifactSave"):
                self._ctx.artifacts.save(result, name, type_string)
            _record_result_shapes(self._ctx, name, result)
            _record_sweep_fusion(self._ctx, name, result)
            summary = summarize_result(result)
            if summary is not None:
                self._ctx.catalog.append_document(name, {"result": summary})
            return result

        self._ctx.jobs.submit(
            name, run, description=description,
            parameters=method_parameters, needs_mesh=True,
            # the executor verb (train/tune/evaluate/predict) is the
            # fair-scheduling pool — per-service FAIR pool parity
            # (reference spark_image/fairscheduler.xml:1-8)
            pool=type_string.split("/", 1)[0],
            only_if_idle=only_if_idle,
            max_retries=self._ctx.config.job_max_retries,
            timeout=timeout, footprint=footprint)


def _record_result_shapes(ctx, name: str, result: Any) -> None:
    """Record the result's static array shapes on the metadata doc so
    later executions referencing ``$name``/``$name.key`` get shape
    pre-flight (analysis/preflight.py). Best-effort: shape metadata
    must never sink a finished job."""
    try:
        shapes = A.result_shapes(result)
        if shapes:
            ctx.catalog.update_metadata(
                name, {A.RESULT_SHAPES_FIELD: shapes})
    except Exception:  # noqa: BLE001
        pass


def _record_sweep_fusion(ctx, name: str, result: Any) -> None:
    """Record how much of a finished sweep the fusion planner claimed
    (``fusedTrials``/``cohorts``/``fallbackTrials``/``earlyStopped``)
    plus any isolated per-trial errors on the job's metadata doc.
    Best-effort, like shape metadata: never sinks a finished job."""
    try:
        updates: Dict[str, Any] = {}
        info = getattr(result, "fusion_info_", None)
        if info:
            updates["sweepFusion"] = dict(info)
        errors = getattr(result, "cv_results_", {}).get("error")
        if errors:
            updates["trialErrors"] = [e for e in errors if e]
        if updates:
            ctx.catalog.update_metadata(name, updates)
    except Exception:  # noqa: BLE001
        pass


def _inject_epoch_log(ctx, name: str, instance: Any, method: str,
                      treated: Dict[str, Any]) -> None:
    """Stream per-epoch training records (loss/accuracy/samplesPerSecond
    and the engine's roofline block — tflopsPerSecPerChip/mfu plus
    gbPerSecPerChip/arithmeticIntensity/hbmBwUtil/boundBy when bytes
    and peaks are known, observability/perf) into the execution's
    documents as they happen, when the target method takes a
    ``log_fn`` (our engine-backed fits do; sklearn methods don't). The
    reference's only perf instrumentation is Builder's post-hoc fitTime
    (builder_image/builder.py:117-122) — live epoch records through the
    universal GET reader are a strict superset."""
    import inspect

    if "log_fn" in treated:
        return
    try:
        params = inspect.signature(getattr(instance, method)).parameters
    except (TypeError, ValueError):
        return
    if "log_fn" not in params:
        return

    seen = {"n": 0}
    health = {"rollbacks": 0, "nonfiniteSteps": 0, "lossSpikes": 0,
              "events": []}

    def log_record(record: Dict[str, Any]) -> None:
        event = record.get("healthEvent")
        if event is not None:
            # sentinel events (runtime/health.py) bypass the throttle —
            # they are rare by construction (bounded by the rollback
            # budget) and the acceptance contract is their presence on
            # the job's metadata document
            health["events"].append(event)
            del health["events"][:-32]
            if "restoredStep" in event:
                health["rollbacks"] += 1
            if event.get("kind") == "spike":
                health["lossSpikes"] += 1
            else:
                health["nonfiniteSteps"] += max(
                    int(event.get("badSteps") or 0), 1)
            try:
                ctx.catalog.append_document(name, {"healthEvent": event})
                ctx.catalog.update_metadata(name, {
                    "rollbacks": health["rollbacks"],
                    "nonfiniteSteps": health["nonfiniteSteps"],
                    "lossSpikes": health["lossSpikes"],
                    "healthEvents": list(health["events"])})
            except Exception:  # noqa: BLE001 — must never sink a fit
                pass
            return
        # bounded stream: every epoch up to 512, then every 16th — a
        # 10k-epoch fit appends ~1.1k docs, not 10k (job-history DoS cap)
        i = seen["n"]
        seen["n"] = i + 1
        if i >= 512 and i % 16 != 0:
            return
        try:
            ctx.catalog.append_document(name, {"epochRecord": record})
        except Exception:  # noqa: BLE001 — logging must never sink a fit
            pass

    treated["log_fn"] = log_record


def _inject_health_policy(ctx, instance: Any, method: str,
                          treated: Dict[str, Any],
                          requested: Optional[Any]) -> None:
    """Arm the engine's training-health sentinel
    (docs/RELIABILITY.md) when the target method takes a
    ``health_policy`` kwarg (engine-backed fits do; sklearn methods
    don't): the request's validated ``healthPolicy`` field merged over
    the ``LO_HEALTH_*`` defaults. No-op when both are off."""
    import inspect

    if "health_policy" in treated:
        return
    try:
        params = inspect.signature(getattr(instance, method)).parameters
    except (TypeError, ValueError):
        return
    if "health_policy" not in params:
        return
    from learningorchestra_tpu.runtime import health as health_lib

    policy = health_lib.resolve_policy(requested, ctx.config)
    if policy is not None:
        treated["health_policy"] = policy


def checkpoint_dir_for(ctx, name: str) -> str:
    import os

    return os.path.join(ctx.config.checkpoints_dir, name)


def _prepare_checkpointer(ctx, name: str, type_string: str,
                          treated: Dict[str, Any]):
    """``"checkpoint": true`` in fit methodParameters enables per-epoch
    step checkpointing under the execution's name; a PATCH re-run of
    the same execution then resumes from the latest step (the engine
    restores before training — beyond the reference, whose failed jobs
    restart from scratch, README.md:194-198).

    Train executions only: a tune sweep runs many concurrent trial
    fits that would collide in one checkpoint manager (and restoring
    trial A's weights into trial B corrupts the sweep)."""
    enabled = treated.pop("checkpoint", False)
    if not type_string.startswith("train/") or not enabled:
        return None
    from learningorchestra_tpu.runtime.async_ckpt import \
        wrap_checkpointer
    from learningorchestra_tpu.runtime.checkpoint import Checkpointer

    # LO_CKPT_ASYNC=1 moves the commit (serialize+hash+fsync) off the
    # train thread onto a background worker; the engine barriers at
    # fit end and before any restore/rollback (docs/RELIABILITY.md)
    ckpt = wrap_checkpointer(Checkpointer(checkpoint_dir_for(ctx, name)),
                             config=ctx.config)
    treated["checkpointer"] = ckpt
    return ckpt


# ----------------------------------------------------------------------
# multi-host fan-out (SURVEY §7 hard part #5: one REST call -> N hosts)
# ----------------------------------------------------------------------
def _broadcast_to_workers(name: str, type_string: str, parent_name: str,
                          method: str,
                          method_parameters: Dict[str, Any],
                          health_policy: Optional[Any] = None) -> None:
    """On a multi-host pod the coordinator publishes every mesh job
    before entering it: the jitted train/eval/predict step runs over
    the GLOBAL mesh, whose collectives need all processes to execute
    the same program. Workers replay the identical method call from
    the shared artifact store (see :func:`replay_method_call`). The
    health policy rides along because sentinel instrumentation changes
    the traced program — a coordinator-only policy would diverge the
    SPMD replay."""
    import jax

    from learningorchestra_tpu.runtime import distributed as dist

    if jax.process_count() <= 1:
        return
    dist.HostBridge().publish({
        "op": "run",
        "target": "learningorchestra_tpu.services.execution:"
                  "replay_method_call",
        "kwargs": {"name": name, "type_string": type_string,
                   "parent_name": parent_name, "method": method,
                   "method_parameters": method_parameters,
                   "health_policy": health_policy}})


_worker_ctx = None


def replay_method_call(name: str, type_string: str, parent_name: str,
                       method: str,
                       method_parameters: Dict[str, Any],
                       health_policy: Optional[Any] = None) -> None:
    """Worker-side twin of the coordinator's pipeline: load the same
    artifact from the shared store, resolve the same parameters, call
    the same method — so every host participates in the global-mesh
    jit (including checkpoint saves).
    Catalog/artifact WRITES stay with the coordinator; the worker's
    copy of the result is discarded."""
    global _worker_ctx
    if _worker_ctx is None:
        from learningorchestra_tpu.services.context import ServiceContext

        _worker_ctx = ServiceContext()
    ctx = _worker_ctx
    parent_type = ctx.params.artifact_type(parent_name)
    instance = ctx.artifacts.load(parent_name, parent_type)
    treated = ctx.params.treat(method_parameters)
    ckpt = _prepare_checkpointer(ctx, name, type_string, treated)
    _inject_health_policy(ctx, instance, method, treated, health_policy)
    try:
        getattr(instance, method)(**treated)
    finally:
        if ckpt is not None:
            ckpt.close()


def summarize_result(result: Any) -> Optional[Any]:
    """A JSON-compatible view of an evaluate/predict result for the
    universal GET reader (the reference leaves results opaque in
    volumes; surfacing them in documents is a strict superset)."""
    import numpy as np

    if result is None or isinstance(result, (bool, int, float, str)):
        return result
    if isinstance(result, dict):
        return {str(k): summarize_result(v) for k, v in result.items()}
    if isinstance(result, (list, tuple)):
        if len(result) > 1000:
            return [summarize_result(v) for v in result[:1000]]
        return [summarize_result(v) for v in result]
    if isinstance(result, np.ndarray):
        flat = result.tolist()
        return flat[:1000] if isinstance(flat, list) and \
            len(flat) > 1000 else flat
    if hasattr(result, "history") and isinstance(
            getattr(result, "history"), (dict, list)):
        return summarize_result(result.history)
    return None
