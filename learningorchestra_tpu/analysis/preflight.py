"""Pipeline pre-flight: static shape/dtype/mesh inference for specs.

Before a submitted model/execution/builder spec gets a job document
and an accelerator lease, walk what the catalog already knows about
its parents and try to *prove* the job would fail. The shape engine
is ``jax.eval_shape`` over ``ShapeDtypeStruct``s reconstructed from
catalog metadata — the SAME ``module.init(rng, x[:1], train=False)``
trace the runtime performs (models/neural.py ``_build_params``), so a
pre-flight rejection is a certain runtime failure, never a guess.

Prime directive: **no false rejections**. Anything the analyzer
cannot positively model — unknown artifact, missing recorded shapes,
non-NeuralModel classes, exotic parameters — is bypassed silently.
Advisory observations (mesh divisibility, TPU hazards in ``#``-DSL
code) come back as warning findings stored on the job document.

Rules emitted here (ids are stable; see docs/ANALYSIS.md):

- ``shape-mismatch`` — error. The traced ``init`` fails on the
  recorded input shapes, a declared ``input`` layer contradicts the
  data, x/y sample counts disagree, or a layer config is structurally
  unusable (missing ``kind``).
- ``unknown-layer`` — error. ``layer_configs`` names a layer kind the
  runtime registry would refuse (proved via the trace, not a list).
- ``mesh-divisibility`` — warning. ``batch_size`` does not divide the
  mesh's data-parallel extent; the feed pads (runtime/data.py), which
  wastes accelerator steps but works.
- plus every code-lint rule, applied to ``#``-DSL strings embedded in
  class/method parameters (they are ``exec``'d at run time).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from learningorchestra_tpu.analysis import code_lint
from learningorchestra_tpu.analysis.findings import (
    Finding,
    SEVERITY_ERROR,
    SEVERITY_WARNING,
)
from learningorchestra_tpu.catalog import documents as D

# metadata key under which executions record their result's array
# shapes (written by function/execution services after artifact save)
RESULT_SHAPES_FIELD = "resultShapes"

# metadata key under which an execution's estimated HBM footprint is
# recorded at submit time (consumed by the slice scheduler and shown
# to clients polling the job document)
FOOTPRINT_FIELD = "footprint"

_NEURAL_MODULE = "learningorchestra_tpu.models"
_NEURAL_CLASSES = ("NeuralModel",)
_DATA_METHODS = ("fit", "evaluate", "predict", "score")


# ----------------------------------------------------------------------
# recording side: turn a live result into storable shape metadata
# ----------------------------------------------------------------------
def result_shapes(obj: Any) -> Optional[Dict[str, Any]]:
    """``{key: {"shape": [...], "dtype": "float32"}}`` for a dict of
    arrays, ``{"": {...}}`` for a bare array — or None when the result
    has no static array shape to record. Unmodelable dict values are
    skipped (their ``$name.key`` refs simply bypass pre-flight)."""

    def one(v: Any) -> Optional[Dict[str, Any]]:
        shape = getattr(v, "shape", None)
        dtype = getattr(v, "dtype", None)
        if shape is None or dtype is None:
            return None
        try:
            return {"shape": [int(s) for s in shape],
                    "dtype": str(np.dtype(dtype))}
        except (TypeError, ValueError):
            return None

    if isinstance(obj, dict):
        out = {k: e for k, e in ((str(k), one(v))
                                 for k, v in obj.items()) if e}
        return out or None
    entry = one(obj)
    return {"": entry} if entry else None


def _ref_struct(catalog: Any, value: Any) -> Optional[Any]:
    """``"$name"``/``"$name.key"`` -> ShapeDtypeStruct from the
    artifact's recorded ``resultShapes``, else None (bypass)."""
    if not isinstance(value, str) or "$" not in value:
        return None
    ref = value.replace("$", "")
    name, key = (ref.split(".", 1) if "." in ref else (ref, ""))
    try:
        meta = catalog.get_metadata(name)
    except Exception:  # noqa: BLE001 — catalog unavailable: bypass
        return None
    shapes = (meta or {}).get(RESULT_SHAPES_FIELD)
    if not isinstance(shapes, dict):
        return None
    entry = shapes.get(key)
    if not isinstance(entry, dict):
        return None
    try:
        import jax

        return jax.ShapeDtypeStruct(
            tuple(int(s) for s in entry["shape"]),
            np.dtype(entry["dtype"]))
    except Exception:  # noqa: BLE001 — malformed record: bypass
        return None


# ----------------------------------------------------------------------
# '#'-DSL lint over parameter trees
# ----------------------------------------------------------------------
def _is_hash_expr(value: Any) -> bool:
    # mirrors ParameterResolver._is_hash: '$' wins over '#'
    return isinstance(value, str) and "$" not in value and "#" in value


def lint_parameter_code(parameters: Optional[Dict[str, Any]],
                        mode: str) -> List[Finding]:
    """Lint every ``#``-DSL expression embedded in a parameter dict
    (they run through the sandbox at execution time). Finding
    locations carry the parameter path instead of a line number."""
    findings: List[Finding] = []
    if not isinstance(parameters, dict):
        return findings

    def visit(value: Any, path: str) -> None:
        if isinstance(value, dict):
            for k, v in value.items():
                visit(v, f"{path}.{k}" if path else str(k))
        elif isinstance(value, list):
            for i, v in enumerate(value):
                visit(v, f"{path}[{i}]")
        elif _is_hash_expr(value):
            code = value.replace("#", "")
            for f in code_lint.lint_code(code, mode=mode,
                                         filename=f"<#{path}>"):
                findings.append(Finding(
                    f.severity, f.rule, path or f.location, f.message))

    visit(parameters, "")
    return findings


# ----------------------------------------------------------------------
# shape engine
# ----------------------------------------------------------------------
def _neural_spec(module_path: Any, class_name: Any,
                 class_parameters: Any) -> Optional[List[Any]]:
    """The layer_configs list iff this spec is a modelable
    NeuralModel; None -> bypass."""
    if module_path != _NEURAL_MODULE or class_name not in _NEURAL_CLASSES:
        return None
    if not isinstance(class_parameters, dict):
        return None
    configs = class_parameters.get("layer_configs")
    if not isinstance(configs, list) or not configs:
        return None
    return configs


def _config_findings(configs: List[Any]) -> List[Finding]:
    """Structural checks that need no shape info: every layer config
    must be a dict with a string ``kind`` (the runtime indexes
    ``cfg["kind"]`` unconditionally)."""
    findings = []
    for i, cfg in enumerate(configs):
        loc = f"classParameters.layer_configs[{i}]"
        if not isinstance(cfg, dict):
            findings.append(Finding(
                SEVERITY_ERROR, "shape-mismatch", loc,
                f"layer config must be a dict, got "
                f"{type(cfg).__name__}"))
        elif not isinstance(cfg.get("kind"), str):
            findings.append(Finding(
                SEVERITY_ERROR, "shape-mismatch", loc,
                "layer config has no 'kind' string"))
    return findings


def _declared_input_shape(configs: List[Any]) -> Optional[Tuple[int, ...]]:
    first = configs[0] if isinstance(configs[0], dict) else {}
    if first.get("kind") == "input":
        shape = first.get("shape") or first.get("input_shape")
        if isinstance(shape, (list, tuple)) and shape and \
                all(isinstance(s, int) for s in shape):
            return tuple(shape)
    return None


def _trace_init(configs: List[Any],
                x_struct: Any) -> Tuple[Optional[Any], Optional[str]]:
    """eval_shape the exact runtime init trace; returns (params
    shape-tree, None) or (None, error message). A None message with a
    None tree means "could not model" (bypass)."""
    try:
        import jax

        from learningorchestra_tpu.models import neural as neural_lib

        model = neural_lib.NeuralModel(layer_configs=list(configs))
        module = model.module
        sample = jax.ShapeDtypeStruct((1,) + tuple(x_struct.shape[1:]),
                                      x_struct.dtype)
        rng = jax.random.PRNGKey(0)
        shapes = jax.eval_shape(
            functools.partial(module.init, train=False), rng, sample)
        return shapes, None
    except (ValueError, TypeError, KeyError, IndexError) as e:
        # the identical trace the runtime runs in _build_params — this
        # failure IS the job's failure, surfaced at submit time
        return None, str(e)
    except Exception:  # noqa: BLE001 — analyzer limitation: bypass
        return None, None


def _language_model_findings(module_path: Any, class_name: Any,
                             class_parameters: Any) -> List[Finding]:
    """A ``LanguageModel`` spec is checked by the constructor itself:
    it builds no parameter, and what it refuses (an unknown setting or
    objective, experts held that the router does not span, a head
    width, block length or attention that do not go together) is what
    the model job would fail on."""
    if module_path != "learningorchestra_tpu.models" or \
            class_name != "LanguageModel" or \
            not isinstance(class_parameters, dict):
        return []
    if any(_is_hash_expr(v) or (isinstance(v, str) and v.startswith("$"))
           for v in class_parameters.values()):
        return []   # resolved at run time: bypass
    try:
        from learningorchestra_tpu.models import LanguageModel

        LanguageModel(**class_parameters)
    except (TypeError, ValueError) as e:
        return [Finding(SEVERITY_ERROR, "language-model-config",
                        "classParameters", str(e))]
    except Exception:  # noqa: BLE001 — analyzer limitation: bypass
        return []
    return []


def check_model(module_path: Any, class_name: Any,
                class_parameters: Any,
                mode: str = "subprocess") -> List[Finding]:
    """Pre-flight a model spec at registration time: lint embedded
    ``#``-DSL code and, for NeuralModel specs, validate the layer
    stack (fully, via the init trace, when an ``input`` layer declares
    the feature shape)."""
    findings = lint_parameter_code(
        class_parameters if isinstance(class_parameters, dict) else None,
        mode)
    findings.extend(_language_model_findings(module_path, class_name,
                                             class_parameters))
    configs = _neural_spec(module_path, class_name, class_parameters)
    if configs is None:
        return findings
    findings.extend(_config_findings(configs))
    if any(f.severity == SEVERITY_ERROR for f in findings):
        return findings
    declared = _declared_input_shape(configs)
    if declared is not None:
        try:
            import jax

            x_struct = jax.ShapeDtypeStruct((1,) + declared, np.float32)
        except Exception:  # noqa: BLE001
            return findings
        _, err = _trace_init(configs, x_struct)
        if err is not None:
            rule = ("unknown-layer" if "unknown layer kind" in err
                    else "shape-mismatch")
            findings.append(Finding(
                SEVERITY_ERROR, rule, "classParameters.layer_configs",
                f"layer stack cannot initialize on declared input "
                f"shape {declared}: {err}"))
    return findings


def _dp_multiple() -> Optional[int]:
    try:
        from learningorchestra_tpu.runtime import mesh as mesh_lib

        mesh = mesh_lib.get_default_mesh()
        return int(mesh_lib.data_parallel_size(mesh))
    except Exception:  # noqa: BLE001 — no devices yet: bypass
        return None


def check_execution(catalog: Any, root_meta: Optional[Dict[str, Any]],
                    method: Any, method_parameters: Any,
                    mode: str = "subprocess") -> List[Finding]:
    """Pre-flight an execution spec at submit time.

    ``root_meta`` is the root model's metadata document (the service
    layer already walks the parent chain to find it). Shape checks
    fire only for NeuralModel roots whose x/y parameters resolve to
    artifacts with recorded ``resultShapes``; everything else bypasses.
    """
    findings = lint_parameter_code(
        method_parameters if isinstance(method_parameters, dict) else None,
        mode)
    if not isinstance(method_parameters, dict) or \
            not isinstance(root_meta, dict) or method not in _DATA_METHODS:
        return findings
    configs = _neural_spec(root_meta.get(D.MODULE_PATH_FIELD),
                           root_meta.get(D.CLASS_FIELD),
                           root_meta.get(D.CLASS_PARAMETERS_FIELD))
    if configs is None:
        return findings
    struct_errs = _config_findings(configs)
    if struct_errs:
        # the model doc is already registered; report against it here
        # too so the execution is stopped before a job doc exists
        return findings + struct_errs

    x_struct = _ref_struct(catalog, method_parameters.get("x"))
    y_struct = _ref_struct(catalog, method_parameters.get("y"))

    if method == "fit" and x_struct is not None and \
            y_struct is not None and x_struct.shape and y_struct.shape \
            and x_struct.shape[0] != y_struct.shape[0]:
        findings.append(Finding(
            SEVERITY_ERROR, "shape-mismatch", "methodParameters.y",
            f"x has {x_struct.shape[0]} samples but y has "
            f"{y_struct.shape[0]}"))

    if x_struct is not None and len(x_struct.shape) >= 2:
        declared = _declared_input_shape(configs)
        if declared is not None and tuple(x_struct.shape[1:]) != declared:
            findings.append(Finding(
                SEVERITY_ERROR, "shape-mismatch", "methodParameters.x",
                f"model declares input shape {declared} but x is "
                f"{tuple(x_struct.shape[1:])} per sample"))
        else:
            _, err = _trace_init(configs, x_struct)
            if err is not None:
                rule = ("unknown-layer" if "unknown layer kind" in err
                        else "shape-mismatch")
                findings.append(Finding(
                    SEVERITY_ERROR, rule, "methodParameters.x",
                    f"layer stack cannot initialize on x of shape "
                    f"{tuple(x_struct.shape)}: {err}"))

    batch = method_parameters.get("batch_size")
    if isinstance(batch, int) and batch > 0:
        dp = _dp_multiple()
        if dp and batch % dp:
            findings.append(Finding(
                SEVERITY_WARNING, "mesh-divisibility",
                "methodParameters.batch_size",
                f"batch_size={batch} is not a multiple of the mesh's "
                f"data-parallel extent {dp}; the feed will zero-pad "
                f"each step (wasted accelerator work)"))
    return findings


# ----------------------------------------------------------------------
# footprint estimation (slice scheduler)
# ----------------------------------------------------------------------
# heuristic fallback multiplier over raw param bytes: params + grads
# + two adam moments all live in HBM during a fit
_OPTIMIZER_MULTIPLIER = 4


def _compiled_init_bytes(configs: List[Any],
                         x_struct: Any) -> Optional[int]:
    """Lower + compile the init step and read XLA's
    ``memory_analysis()`` (argument + output + temp bytes). None on
    backends that don't implement the analysis (notably CPU on some
    jaxlib builds) — callers fall back to the heuristic."""
    try:
        import jax

        from learningorchestra_tpu.models import neural as neural_lib

        model = neural_lib.NeuralModel(layer_configs=list(configs))
        module = model.module
        sample = jax.ShapeDtypeStruct((1,) + tuple(x_struct.shape[1:]),
                                      x_struct.dtype)
        compiled = jax.jit(
            functools.partial(module.init, train=False)).lower(
            jax.random.PRNGKey(0), sample).compile()
        analysis = compiled.memory_analysis()
        if analysis is None:
            return None
        total = sum(int(getattr(analysis, field, 0) or 0) for field in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes"))
        return total or None
    except Exception:  # noqa: BLE001 — estimation is best-effort
        return None


def estimate_footprint(catalog: Any,
                       root_meta: Optional[Dict[str, Any]],
                       method: Any,
                       method_parameters: Any) -> Optional[Dict[str, Any]]:
    """Best-effort HBM footprint for a NeuralModel data method:
    ``{"hbmBytes", "paramBytes", "estimator"}`` where ``estimator`` is
    ``"memory_analysis"`` (XLA measured the lowered init step) or
    ``"heuristic"`` (param bytes × optimizer multiplier + two staged
    batches). None for anything unmodelable — the scheduler then
    gang-acquires the full mesh, which is always safe. Same bypass
    discipline as every other pre-flight check: never wrong, possibly
    absent."""
    if method not in _DATA_METHODS or \
            not isinstance(method_parameters, dict) or \
            not isinstance(root_meta, dict):
        return None
    configs = _neural_spec(root_meta.get(D.MODULE_PATH_FIELD),
                           root_meta.get(D.CLASS_FIELD),
                           root_meta.get(D.CLASS_PARAMETERS_FIELD))
    if configs is None:
        return None
    x_struct = _ref_struct(catalog, method_parameters.get("x"))
    if x_struct is None or len(x_struct.shape) < 2:
        return None
    shapes, _ = _trace_init(configs, x_struct)
    if shapes is None:
        return None
    try:
        import jax

        param_bytes = sum(
            int(np.prod(leaf.shape) or 1) * np.dtype(leaf.dtype).itemsize
            for leaf in jax.tree_util.tree_leaves(shapes))
    except Exception:  # noqa: BLE001 — unmodelable tree: bypass
        return None
    batch = method_parameters.get("batch_size")
    if not isinstance(batch, int) or batch <= 0:
        from learningorchestra_tpu.config import get_config

        batch = get_config().default_batch_size
    feature_bytes = int(np.prod(x_struct.shape[1:]) or 1) * \
        np.dtype(x_struct.dtype).itemsize
    estimate = param_bytes * _OPTIMIZER_MULTIPLIER \
        + 2 * batch * feature_bytes
    estimator = "heuristic"
    measured = _compiled_init_bytes(configs, x_struct)
    if measured:
        # the measured init covers params only; optimizer state and
        # staged batches still come from the model above
        estimate = max(estimate,
                       measured * _OPTIMIZER_MULTIPLIER
                       + 2 * batch * feature_bytes)
        estimator = "memory_analysis"
    return {"hbmBytes": int(estimate), "paramBytes": int(param_bytes),
            "estimator": estimator}


def check_builder(modeling_code: Any,
                  mode: str = "subprocess") -> List[Finding]:
    """Pre-flight a builder spec: its ``modelingCode`` is exec'd in
    the sandbox per classifier, so it gets the full code lint."""
    if not isinstance(modeling_code, str):
        return []
    return code_lint.lint_code(modeling_code, mode=mode,
                               filename="<modelingCode>")
