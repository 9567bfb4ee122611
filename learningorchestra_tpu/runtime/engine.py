"""Training / evaluation / prediction engine.

This is what replaces the reference's hot loop — ``getattr(instance,
"fit")(**kwargs)`` running TensorFlow in-process on one node
(binary_executor_image/binary_execution.py:177-189). The engine:

- compiles ONE jitted train step (donated state, fixed batch shapes)
  and drives it over a prefetched device feed;
- computes in ``bfloat16`` on the MXU with float32 master params in
  the optimizer (mixed precision by default, config-switchable);
- is mesh-native: the batch is sharded over the data axes and params
  follow the sharding rules baked into the state — XLA/GSPMD inserts
  the gradient all-reduce (no hand-written collectives, SURVEY §2.5);
- masks padded tail samples so metrics match unpadded math exactly.
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct

from learningorchestra_tpu.observability import hist as obs_hist
from learningorchestra_tpu.observability import perf as obs_perf
from learningorchestra_tpu.observability import timeline as obs_timeline
from learningorchestra_tpu.observability import trace as obs_trace
from learningorchestra_tpu.observability import xray as obs_xray
from learningorchestra_tpu.runtime import arena as arena_lib
from learningorchestra_tpu.runtime import data as data_lib
from learningorchestra_tpu.runtime import health as health_lib
from learningorchestra_tpu.runtime import mesh as mesh_lib
from learningorchestra_tpu.runtime import preempt
from learningorchestra_tpu.runtime.health import (HealthPolicy,
                                                  NumericalDivergence)
from learningorchestra_tpu.runtime import locks

# "HELT": domain-separates the post-rollback rng stream from the
# original, so a replayed epoch does not redraw the exact dropout/
# shuffle sequence that diverged
_HEALTH_TAG = 0x4845_4C54
# added (x rollback count) to the data-shuffle epoch index after a
# rollback: the replayed epoch sees a fresh permutation, not the one
# that fed the poisoned batch
_ROLLBACK_STRIDE = 100003


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any
    # extra mutable collections (e.g. batch_stats) — empty dict if none
    model_state: Any


Metrics = Dict[str, Tuple[jax.Array, jax.Array]]  # name -> (sum, count)


def _tree_nbytes(tree) -> int:
    """Total leaf bytes of a pytree (ledger accounting)."""
    return sum(int(getattr(x, "nbytes", 0))
               for x in jax.tree_util.tree_leaves(tree))


def default_grad_accum() -> int:
    """Process-wide microbatch-count default (LO_GRAD_ACCUM env)."""
    return max(1, int(os.environ.get("LO_GRAD_ACCUM", "1")))


# ----------------------------------------------------------------------
# In-process executable cache (docs/PERFORMANCE.md). Engines are built
# per fit — the builder constructs a fresh classifier (and Engine) per
# job — so per-instance jitted steps recompile identical programs on
# every repeat job. Engines constructed with a ``cache_key`` share
# their jitted callables here, keyed on everything that changes the
# traced program: (model spec hash, step kind, mesh, sharding,
# donation, compute dtype, grad_accum, step shape qualifiers). Same
# key + same batch shapes -> jax's own C++ dispatch cache hit: zero
# retrace, zero recompile. The jit objects hold no device state, so
# sharing them across threads/jobs is safe.
# ----------------------------------------------------------------------
_EXEC_CACHE: "collections.OrderedDict[Any, Callable]" = \
    collections.OrderedDict()
_EXEC_LOCK = locks.make_lock("engine.executables")
_EXEC_STATS = {"hits": 0, "misses": 0}
_EXEC_CACHE_CAP = 64
# measured per-step (flops, bytes accessed) by executable key: lets a
# warm fit skip the _measure_flops lowering (a full trace) entirely
_FLOPS_CACHE: Dict[Any, Tuple[float, float]] = {}
# compiled-artifact X-ray by the same key: memory_analysis() /
# cost_analysis() extracts captured once per cold executable and
# re-attached to every job name that reuses it (observability/xray)
_XRAY_CACHE: Dict[Any, Dict[str, Any]] = {}


def executable_cache_stats() -> Dict[str, int]:
    with _EXEC_LOCK:
        return {"entries": len(_EXEC_CACHE),
                "hits": _EXEC_STATS["hits"],
                "misses": _EXEC_STATS["misses"]}


def reset_executable_cache() -> None:
    with _EXEC_LOCK:
        _EXEC_CACHE.clear()
        _FLOPS_CACHE.clear()
        _XRAY_CACHE.clear()
        _EXEC_STATS["hits"] = 0
        _EXEC_STATS["misses"] = 0


def resolve_grad_accum(requested: Optional[int],
                       current: int) -> Tuple[int, bool]:
    """Clamp a fit-time ``grad_accum`` override and report whether the
    EFFECTIVE value changed (so callers only rebuild their engine —
    discarding every cached jitted step — on a real change; a clamped
    no-op like 0 -> 1 when already 1 must not recompile)."""
    if requested is None:
        return current, False
    value = max(1, int(requested))
    return value, value != current


class Engine:
    """Generic sharded training engine over (apply_fn, loss_fn).

    ``apply_fn(params, model_state, batch, train, rng) ->
    (outputs, new_model_state)`` and ``loss_fn(outputs, batch, weights)
    -> scalar`` are supplied by the model layer; everything here is
    model-agnostic.
    """

    def __init__(self,
                 apply_fn: Callable,
                 loss_fn: Callable,
                 optimizer: optax.GradientTransformation,
                 mesh=None,
                 metrics: Optional[Dict[str, Callable]] = None,
                 compute_dtype: Any = jnp.bfloat16,
                 donate_state: bool = True,
                 param_rules=None,
                 fsdp: bool = True,
                 batch_sharding=None,
                 predict_transform: Optional[Callable] = None,
                 flops_floor_fn: Optional[Callable] = None,
                 grad_accum: int = 1,
                 cache_key: Any = None,
                 counter_prefixes: Tuple[str, ...] = (),
                 float32_leaves: Tuple[str, ...] = ()):
        self._apply_fn = apply_fn
        # parameters of these names are not cast to the compute dtype
        # (a state-space layer's decay exponents); the model's settings
        # decide them, so the cache key already tells such engines apart
        self._float32_leaves = tuple(float32_leaves)
        # loss-emitted sums whose names start so are COUNTERS of the
        # model (router load, masked positions): like every emitted
        # metric they reach the epoch record as a mean over the steps,
        # and they are also set on the epoch's ``epochEnd`` span
        self._counter_prefixes = tuple(counter_prefixes)
        self._loss_fn = loss_fn
        self._optimizer = optimizer
        self._mesh = mesh
        self._metrics = metrics or {}
        self._compute_dtype = compute_dtype
        self._train_step = None
        self._eval_step = None
        self._predict_step = None
        self._epoch_steps: Dict[Any, Callable] = {}
        self._donate = donate_state
        # (path-regex -> PartitionSpec) rules for TP/FSDP param layout;
        # None = replicate (pure DP)
        self._param_rules = param_rules
        self._fsdp = fsdp
        self._batch_sharding = batch_sharding
        # maps raw apply outputs to the prediction array (models whose
        # apply returns a tuple, e.g. (logits, moe_aux))
        self._predict_transform = predict_transform
        self._step_flops: Optional[float] = None
        # XLA's "bytes accessed" for the same step — the denominator of
        # arithmetic intensity in the roofline block (observability/perf)
        self._step_bytes: Optional[float] = None
        self._flops_key = None
        # analytic lower bound on per-step flops given a batch dict —
        # XLA cost analysis reports ZERO flops for custom calls
        # (pallas_call), so a flash-attention model's MFU would be
        # deflated without it
        self._flops_floor_fn = flops_floor_fn
        # microbatch count per optimizer step: the batch splits into
        # grad_accum sequential microbatches whose gradients average
        # before ONE update — peak activation memory scales with the
        # microbatch, letting memory-bound shapes train at batch sizes
        # HBM could not hold in one pass
        self._grad_accum = max(1, int(grad_accum))
        # hashable identity of the PROGRAM this engine computes: it
        # must uniquely determine apply_fn / loss_fn / optimizer /
        # metrics / predict_transform behavior, because engines with
        # equal keys share jitted steps via _EXEC_CACHE. None opts out
        # (custom callables with no stable identity).
        self._cache_key = cache_key
        # training health sentinel (docs/RELIABILITY.md), set per-fit:
        # the flags are read at TRACE time by _train_step_body, so
        # _health_sig joins every executable cache key and a change
        # drops this instance's cached steps
        self._health_on = False
        self._health_skip = False
        self._health_sig: Optional[tuple] = None
        # spans of the running fit (docs/OBSERVABILITY.md): the span
        # the fit runs under and the ``compile`` spans it has left
        self._fit_anchor: Optional[Tuple[str, int]] = None
        self._builds = 0

    # ------------------------------------------------------------------
    def init_state(self, params, model_state=None) -> TrainState:
        with obs_trace.span("initState"):
            if self._mesh is not None and self._param_rules is not None:
                from learningorchestra_tpu.parallel import \
                    sharding as rules_lib

                shardings = rules_lib.param_shardings(
                    params, self._mesh, self._param_rules, fsdp=self._fsdp)
                params = jax.device_put(params, shardings)
                opt_state = self._init_opt_state_on_mesh(params)
                rep = mesh_lib.replicated(self._mesh)
                return TrainState(
                    step=jax.device_put(jnp.zeros((), jnp.int32), rep),
                    params=params, opt_state=opt_state,
                    model_state=jax.device_put(model_state or {}, rep))
            state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                               opt_state=self._optimizer.init(params),
                               model_state=model_state or {})
            if self._mesh is not None:
                state = jax.device_put(state, mesh_lib.replicated(self._mesh))
            return state

    def _init_opt_state_on_mesh(self, params):
        """Optimizer state for rules-sharded ``params``: jit propagates
        the param shardings into matching leaves (adam mu/nu mirror
        params); leaves that depend on no input (the step ``count``)
        come back on the default device alone, so they are replicated
        over the mesh here. Left there, a checkpoint restore — which
        commits every leaf to its target's sharding — would pin them
        to one device and the next step would refuse the mixed
        placement. On a mesh of ONE device such a leaf already sits on
        every device of the mesh, but as a single-device array: the
        step returns it under the mesh's sharding, the second call's
        argument types then differ from the first's, and the fit built
        its program twice (PERF.md F7). So it is put under the mesh's
        sharding there too."""
        opt_state = jax.jit(self._optimizer.init)(params)
        mesh_devices = set(self._mesh.devices.flat)
        rep = mesh_lib.replicated(self._mesh)

        def on_mesh(x):
            # a tracer (eval_shape of init_state) has no placement
            if isinstance(x, jax.core.Tracer) or (
                    x.sharding.device_set == mesh_devices and not
                    isinstance(x.sharding,
                               jax.sharding.SingleDeviceSharding)):
                return x
            return jax.device_put(x, rep)

        return jax.tree_util.tree_map(on_mesh, opt_state)

    def _cast(self, tree):
        dtype = self._compute_dtype

        def cast_leaf(x):
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
                return x.astype(dtype)
            return x

        if self._float32_leaves:
            return jax.tree_util.tree_map_with_path(
                lambda path, x: x if getattr(path[-1], "key", None)
                in self._float32_leaves else cast_leaf(x), tree)
        return jax.tree_util.tree_map(cast_leaf, tree)

    # ------------------------------------------------------------------
    def _micro_grads(self, params, model_state, batch, rng):
        """Gradients + metric sums for one (micro)batch."""
        weights = batch.get(data_lib.MASK_KEY)

        def loss_of(p):
            # named with the update: PERF.md ranks AdamW and the master
            # weights' cast to the compute dtype as one phase
            with jax.named_scope("optimizer"):
                p = self._cast(p)
            outputs, new_model_state = self._apply_fn(
                p, model_state, self._cast(batch), True, rng)
            res = self._loss_fn(outputs, batch, weights)
            # a loss_fn may return (loss, {metric: (sum, count)}) to
            # emit metrics it already computed — the fused-lm-head
            # loss produces accuracy inside its chunked scan, and
            # recomputing it from outputs would cost a second
            # vocab-width matmul per step
            loss, extra = res if isinstance(res, tuple) else (res, {})
            return loss.astype(jnp.float32), (outputs, new_model_state,
                                              extra)

        (loss, (outputs, new_model_state, extra)), grads = \
            jax.value_and_grad(loss_of, has_aux=True)(params)
        metrics = {"loss": (loss * _total(weights), _total(weights))}
        metrics.update(extra)
        for name, fn in self._metrics.items():
            if name in extra:
                continue  # the loss already emitted this metric
            metrics[name] = fn(outputs, batch, weights)
        return grads, new_model_state, metrics

    def _train_step_body(self, state: TrainState, batch, rng):
        if self._grad_accum > 1:
            grads, new_model_state, metrics = self._accum_grads(
                state, batch, rng)
        else:
            grads, new_model_state, metrics = self._micro_grads(
                state.params, state.model_state, batch, rng)
        bad = None
        if self._health_on:
            # on-device health word (docs/RELIABILITY.md): folded into
            # the metric sums the step already ships, so the sentinel
            # adds no extra host sync — loss finiteness + global
            # grad-norm finiteness, a couple of reductions against a
            # full fwd+bwd
            loss_sum, loss_cnt = metrics["loss"]
            mean_loss = loss_sum.astype(jnp.float32) / \
                jnp.maximum(loss_cnt.astype(jnp.float32), 1e-9)
            bad = jnp.logical_or(~jnp.isfinite(mean_loss),
                                 ~jnp.isfinite(optax.global_norm(grads)))
        with jax.named_scope("optimizer"):
            updates, new_opt = self._optimizer.update(
                grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(step=state.step + 1, params=new_params,
                                  opt_state=new_opt,
                                  model_state=new_model_state)
        if bad is not None:
            if self._health_skip:
                # drop the poisoned update wholesale (params, optimizer
                # moments, batch stats) — the step counter still
                # advances so the rng stream stays aligned — and zero
                # the step's metric contributions so the epoch means
                # the sentinel checks stay finite
                kept = state.replace(step=state.step + 1)
                new_state = jax.tree_util.tree_map(
                    lambda old, new: jnp.where(bad, old, new),
                    kept, new_state)
                metrics = {
                    k: (jnp.where(bad, 0.0, s.astype(jnp.float32)),
                        jnp.where(bad, 0.0, c.astype(jnp.float32)))
                    for k, (s, c) in metrics.items()}
            metrics["_health_bad"] = (bad.astype(jnp.float32),
                                      jnp.asarray(1.0, jnp.float32))
        return new_state, metrics

    def _accum_grads(self, state: TrainState, batch, rng):
        """Sequential microbatch gradient accumulation: the batch
        splits leaf-wise into ``grad_accum`` microbatches scanned with
        a running gradient sum, so peak activation memory is one
        microbatch's. Each micro gradient is the gradient of that
        micro's WEIGHTED-MEAN loss, so the accumulator weights it by
        the micro's weight total and normalizes by the grand total —
        algebraically identical to the single-batch weighted-mean
        step for ANY mask/sample_weight distribution (a micro holding
        only padding contributes zero weight, not a diluting zero
        gradient)."""
        accum = self._grad_accum
        b = jax.tree_util.tree_leaves(batch)[0].shape[0]
        if b % accum:
            raise ValueError(
                f"batch size {b} is not divisible by "
                f"grad_accum={accum}")
        micros = jax.tree_util.tree_map(
            lambda a: a.reshape((accum, b // accum) + a.shape[1:]),
            batch)
        zero_g = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params)

        def body(carry, mb):
            g_acc, ms, i = carry
            grads, ms, metrics = self._micro_grads(
                state.params, ms, mb, jax.random.fold_in(rng, i))
            # the "loss" metric's count IS this micro's weight total
            # (sum of mask*sample_weight, or 1.0 when unweighted)
            w = metrics["loss"][1].astype(jnp.float32)
            g_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32) * w,
                g_acc, grads)
            return (g_acc, ms, i + 1), metrics

        (g_sum, new_model_state, _), metrics = jax.lax.scan(
            body, (zero_g, state.model_state,
                   jnp.zeros((), jnp.int32)), micros)
        w_total = jnp.maximum(
            jnp.sum(metrics["loss"][1].astype(jnp.float32)), 1e-9)
        grads = jax.tree_util.tree_map(lambda g: g / w_total, g_sum)
        # each metric leaf is stacked (accum, ...) sums/counts
        metrics = {k: (jnp.sum(s), jnp.sum(c))
                   for k, (s, c) in metrics.items()}
        return grads, new_model_state, metrics

    def _exec_key(self, kind: str, extra: Tuple = ()):
        if self._cache_key is None:
            return None
        return (self._cache_key, kind, self._mesh, self._batch_sharding,
                self._donate, str(self._compute_dtype), self._grad_accum,
                self._health_sig, extra)

    def _set_health(self, policy: Optional[HealthPolicy]) -> None:
        """Arm/disarm sentinel instrumentation for this fit. The flags
        feed trace-time branches, so a signature change invalidates the
        per-instance jitted steps (the shared cache keys on the
        signature and stays correct either way)."""
        sig = policy.jit_signature() if policy is not None else None
        if sig != self._health_sig:
            self._health_sig = sig
            self._train_step = None
            self._epoch_steps = {}
        self._health_on = policy is not None
        self._health_skip = bool(policy) and policy.action == "skip"

    def _shared_step(self, kind: str, build: Callable[[], Callable],
                     extra: Tuple = ()) -> Callable:
        """The jitted step for ``kind``, shared process-wide when this
        engine carries a cache_key (else built per instance as before).
        ``build`` runs outside the lock; a lost race reuses the first
        insert (discarding an unexecuted jit wrapper is free)."""
        key = self._exec_key(kind, extra)
        if key is None:
            return build()
        with _EXEC_LOCK:
            fn = _EXEC_CACHE.get(key)
            if fn is not None:
                _EXEC_CACHE.move_to_end(key)
                _EXEC_STATS["hits"] += 1
                return fn
            _EXEC_STATS["misses"] += 1
        fn = build()
        with _EXEC_LOCK:
            existing = _EXEC_CACHE.get(key)
            if existing is not None:
                return existing
            _EXEC_CACHE[key] = fn
            while len(_EXEC_CACHE) > _EXEC_CACHE_CAP:
                _EXEC_CACHE.popitem(last=False)
        return fn

    def _build_train_step(self):
        donate = (0,) if self._donate else ()
        return jax.jit(self._train_step_body, donate_argnums=donate)

    def _build_epoch_step(self, steps: int, batch_size: int,
                          shuffle: bool):
        """Whole-epoch fast path: ONE jitted program per epoch that
        shuffles ON DEVICE and lax.scans the train step over the
        batches. The dataset stays resident in HBM across epochs —
        after the first transfer the host link carries nothing, and
        per-step Python dispatch (which dominates small models)
        disappears."""
        n_total = steps * batch_size

        def epoch_fn(state: TrainState, arrays, step_rng, shuffle_rng,
                     epoch_idx):
            if shuffle:
                # shuffle_rng is pre-folded with a constant tag (see
                # _shuffle_rng) so the permutation stream stays distinct
                # from the dropout stream even when batcher.seed equals
                # the step seed (the default for every model class)
                perm = jax.random.permutation(
                    jax.random.fold_in(shuffle_rng, epoch_idx), n_total)
                arrays = jax.tree_util.tree_map(
                    lambda a: jnp.take(a, perm, axis=0), arrays)
            batches = jax.tree_util.tree_map(
                lambda a: a.reshape((steps, batch_size) + a.shape[1:]),
                arrays)

            def step(carry, batch):
                rng = jax.random.fold_in(step_rng, carry.step)
                return self._train_step_body(carry, batch, rng)

            state, metrics = jax.lax.scan(step, state, batches)
            totals = {k: (jnp.sum(s), jnp.sum(c))
                      for k, (s, c) in metrics.items()}
            return state, totals

        donate = (0,) if self._donate else ()
        return jax.jit(epoch_fn, donate_argnums=donate)

    def _build_eval_step(self):
        def step_fn(state: TrainState, batch):
            weights = batch.get(data_lib.MASK_KEY)
            outputs, _ = self._apply_fn(
                self._cast(state.params), state.model_state,
                self._cast(batch), False, None)
            res = self._loss_fn(outputs, batch, weights)
            loss, extra = res if isinstance(res, tuple) else (res, {})
            loss = loss.astype(jnp.float32)
            metrics = {"loss": (loss * _total(weights), _total(weights))}
            metrics.update(extra)
            for name, fn in self._metrics.items():
                if name in extra:
                    continue  # the loss already emitted this metric
                metrics[name] = fn(outputs, batch, weights)
            return metrics

        return jax.jit(step_fn)

    def _build_predict_step(self):
        def step_fn(state: TrainState, batch):
            outputs, _ = self._apply_fn(
                self._cast(state.params), state.model_state,
                self._cast(batch), False, None)
            if self._predict_transform is not None:
                outputs = self._predict_transform(outputs)
            if self._mesh is not None and jax.process_count() > 1:
                # multi-host: replicate so every process can read the
                # full prediction (np.asarray needs addressability)
                outputs = jax.tree_util.tree_map(
                    lambda o: jax.lax.with_sharding_constraint(
                        o, mesh_lib.replicated(self._mesh)), outputs)
            # predictions leave the device in full precision even when
            # compute ran in bfloat16 (downstream softmax/thresholds
            # shouldn't inherit MXU rounding)
            return jax.tree_util.tree_map(
                lambda o: o.astype(jnp.float32)
                if jnp.issubdtype(o.dtype, jnp.floating) else o, outputs)

        return jax.jit(step_fn)

    # ------------------------------------------------------------------
    def _resolve_batch_sharding(self):
        if self._batch_sharding is not None:
            return self._batch_sharding
        if self._mesh is not None:
            return mesh_lib.batch_sharding(self._mesh)
        return None

    def _device_feed(self, batcher: data_lib.ArrayBatcher, epoch: int):
        return data_lib.prefetch_to_device(
            batcher.epoch(epoch), self._resolve_batch_sharding())

    def _roofline_record(self, record: Dict[str, Any], steps: int,
                         dt: float) -> None:
        """Attach the roofline block for ``steps`` steady-state steps
        over ``dt`` seconds: achieved tflops/sec/chip + MFU always,
        plus GB/s/chip, arithmetic intensity, bandwidth utilization and
        boundBy when bytes/peaks are known (observability/perf)."""
        if not self._step_flops or steps <= 0 or dt <= 0:
            return
        n_dev = (self._mesh.size if self._mesh is not None
                 else jax.device_count())
        record.update(obs_perf.roofline(
            self._step_flops, self._step_bytes or 0.0, steps, dt,
            n_dev))

    def _begin_fit(self) -> None:
        self._fit_anchor = obs_trace.current()
        self._builds = 0

    def _call_in(self, ctx, fn: Callable, *args):
        """``fn(*args)`` inside the open ``dispatch`` span ``ctx``. A
        jit call returns when its executable is built and enqueued, so
        when the tracer's listeners counted a build during the call,
        the call's own interval is recorded as a ``compile`` span —
        beside the epochs, not inside one — with what the call added
        to the span's compile attrs, ``executable`` (the n-th building
        call of this fit) and ``cold``/``cacheHit`` (XLA compiled it /
        the persistent compilation cache held it)."""
        before = {k: ctx.attrs.get(k, 0) for k in obs_trace.COMPILE_ATTRS}
        t0 = time.monotonic()
        out = fn(*args)
        if ctx.attrs.get("builds", 0) != before["builds"]:
            t1 = time.monotonic()
            built = {k: round(ctx.attrs[k] - before[k], 6)
                     for k in obs_trace.COMPILE_ATTRS if k in ctx.attrs}
            self._builds += 1
            hit = built.get("cacheHits", 0) > 0
            trace_id, parent = self._fit_anchor
            obs_trace.add("compile", trace_id, t0, t1, parent=parent,
                          cold=not hit, cacheHit=hit,
                          executable=self._builds,
                          epoch=ctx.attrs.get("epoch"), **built)
            obs_hist.observe("lo_compile_seconds", t1 - t0)
        return out

    def _observe_window(self, epoch_span, dt: float,
                        record: Dict[str, Any], bad_steps: int, *,
                        step: int, epoch: int, builds: int) -> None:
        """Close a step-window's bookkeeping: the loss on its (live)
        ``epoch`` span and one timeline ring entry. Reuses values the
        fit loop / health sentinel already pulled to the host — no
        extra device syncs — and is best-effort: it must never sink a
        fit."""
        try:
            if self._fit_anchor is None:
                return
            trace_id = self._fit_anchor[0]
            if record.get("loss") is not None:
                epoch_span.set(loss=round(float(record["loss"]), 6))
            if self._counter_prefixes:
                # called inside the ``epochEnd`` span: its attrs
                obs_trace.annotate(**{
                    k: round(float(v), 3) for k, v in record.items()
                    if k.startswith(self._counter_prefixes)})
            # roofline block (stamped on the record by
            # _roofline_record): rides the same ring entry so the
            # timeline answers "how fast vs the hardware" per window,
            # and keeps the job's latest report queryable after the fit
            # via GET /observability/perf/{name}
            perf_block = {k: record[k] for k in (
                "mfu", "tflopsPerSecPerChip", "gbPerSecPerChip",
                "arithmeticIntensity", "hbmBwUtil", "boundBy")
                if k in record}
            obs_timeline.record(
                trace_id, step=step, dt=dt,
                examples_per_second=record.get(
                    "samplesPerSecond", 0.0),
                loss=record.get("loss"),
                bad_steps=bad_steps if bad_steps else None,
                retrace=builds > 0,
                **perf_block)
            if perf_block:
                obs_perf.record_job(trace_id, dict(
                    perf_block, kind="train", epoch=epoch))
        except Exception:  # noqa: BLE001 — observability is advisory
            pass

    def _measure_flops(self, state, batch, rng, step_fn=None,
                       epoch: int = 0, count_only: bool = False) -> None:
        """Per-step flop + bytes-accessed estimate from the lowered HLO
        (cheap — no compile). Basis for the MFU line and the roofline
        block in every history record. Also feeds the X-ray plane: the
        retrace sentinel sees every (program, batch-signature) pair —
        a warm program under a NEW signature is a recompile — and the
        compiled step's memory/cost analysis is captured once per cold
        executable key for ``GET /observability/compile/{name}``. The
        lowering (a second full trace of the step) is a
        ``measureFlops`` span: a warm fit has none. ``count_only``
        (the scanned path of an engine with a ``flops_floor_fn``) takes
        that function's count and lowers nothing."""
        key = tuple(sorted((k, tuple(v.shape)) for k, v in batch.items()))
        self._note_signature(key)
        if self._step_flops is not None and key == self._flops_key:
            return
        shared_key = self._exec_key("flops", key)
        if shared_key is not None:
            cached = _FLOPS_CACHE.get(shared_key)
            if cached is not None:
                # warm job: reuse the measured value — lowering below
                # is a full trace, exactly what a repeat fit must skip
                self._step_flops, self._step_bytes = cached
                self._flops_key = key
                self._record_compile_xray(_XRAY_CACHE.get(shared_key))
                return
        self._flops_key = key
        with obs_trace.span("measureFlops", epoch=epoch):
            if count_only:
                # the scanned path would trace, lower and build ONE step
                # only to count it (its ``step_fn`` runs nothing). A
                # model that brings its own count is taken at its word:
                # for the expert block that second trace was 22 s, its
                # lowering 8 s and its executable 62 s cold of every
                # job's set-up on the chip (PERF.md section 6, PR 26),
                # and where Pallas kernels run XLA's count is the lower
                # of the two anyway. Bytes and the compile X-ray are
                # not taken on this path.
                try:
                    self._step_flops = float(self._flops_floor_fn(batch))
                except Exception:  # noqa: BLE001 — accounting never sinks a run
                    self._step_flops = 0.0
                self._step_bytes = 0.0
                if shared_key is not None:
                    _FLOPS_CACHE[shared_key] = (self._step_flops, 0.0)
                return
            try:
                fn = step_fn if step_fn is not None else self._train_step
                lowered = fn.lower(state, batch, rng)
                compiled = None
                cost = lowered.cost_analysis()
                if not cost or not cost.get("flops"):
                    # some PJRT backends only report costs on the
                    # compiled executable (one extra compile, once per
                    # batch shape)
                    compiled = lowered.compile()
                    cost = compiled.cost_analysis()
                flops = float(cost.get("flops", 0.0)) if cost else 0.0
                self._step_flops = flops if flops > 0 else 0.0
                bytes_acc = (float(cost.get("bytes accessed", 0.0))
                             if cost else 0.0)
                self._step_bytes = bytes_acc if bytes_acc > 0 else 0.0
                self._capture_xray(shared_key, lowered, compiled, key)
            except Exception:  # noqa: BLE001 — accounting never sinks a run
                self._step_flops = 0.0
                self._step_bytes = 0.0
            if self._flops_floor_fn is not None:
                try:
                    # the floor corrects custom calls' ZERO reported
                    # flops; their bytes ARE counted (operands/results),
                    # so only the flop side is raised
                    floor = float(self._flops_floor_fn(batch))
                    self._step_flops = max(self._step_flops or 0.0, floor)
                except Exception:  # noqa: BLE001
                    pass
        if shared_key is not None and self._step_flops is not None:
            _FLOPS_CACHE[shared_key] = (self._step_flops,
                                        self._step_bytes or 0.0)

    def _program_key(self) -> Any:
        """Shape-free identity of this engine's train program — what
        the retrace sentinel tracks signatures against. Falls back to
        the instance for engines without a shared cache key."""
        return self._exec_key("flops", ()) or ("engine", id(self))

    def _note_signature(self, shape_key: Tuple) -> None:
        try:
            cur = obs_trace.current()
            obs_xray.note_signature(self._program_key(), shape_key,
                                    name=cur[0] if cur else None)
        except Exception:  # noqa: BLE001 — observability is advisory
            pass

    def _capture_xray(self, shared_key, lowered, compiled,
                      shape_key: Tuple) -> None:
        """Extract the compiled step's memory/cost X-ray (one extra
        compile per COLD executable key — warm fits reuse the cached
        extract) and attach it to the current job."""
        if not obs_xray.enabled():
            return
        try:
            if compiled is None:
                compiled = lowered.compile()
            report = {
                "memory": obs_xray.extract_memory_analysis(compiled),
                "cost": (obs_xray.extract_cost_analysis(compiled)
                         or obs_xray.extract_cost_analysis(lowered)),
                "batchShapes": {k: list(s) for k, s in shape_key},
            }
            if shared_key is not None:
                _XRAY_CACHE[shared_key] = report
            self._record_compile_xray(report)
        except Exception:  # noqa: BLE001
            pass

    def _record_compile_xray(self, report) -> None:
        try:
            if report is None or not obs_xray.enabled():
                return
            cur = obs_trace.current()
            if cur is not None:
                obs_xray.record_compile(cur[0], "trainStep", report)
        except Exception:  # noqa: BLE001
            pass

    def _ledger_state(self, state) -> None:
        """Register this engine's placed train state in the HBM ledger
        (owner ``train-state``); the fit wrapper releases it."""
        try:
            cur = obs_trace.current()
            obs_xray.register("train-state", id(self),
                              _tree_nbytes(state),
                              name=cur[0] if cur else None)
        except Exception:  # noqa: BLE001
            pass

    def _should_scan(self, batcher: data_lib.ArrayBatcher) -> bool:
        from learningorchestra_tpu.config import get_config

        limit = get_config().scan_fit_max_bytes
        return limit > 0 and batcher.total_bytes() <= limit and \
            batcher.steps_per_epoch > 1

    # -- health sentinel (docs/RELIABILITY.md) -------------------------
    @staticmethod
    def _new_sentinel() -> Dict[str, Any]:
        """Host-side per-fit sentinel state: EMA of the epoch loss,
        rollback budget used, spike-check cooldown remaining."""
        return {"ema": None, "rollbacks": 0, "cooldown": 0}

    def _health_epoch_end(self, policy: HealthPolicy, sent: Dict[str, Any],
                          epoch: int, bad_steps: int, loss: float,
                          state: TrainState, checkpointer, snapshot,
                          log_fn) -> Tuple[bool, TrainState,
                                           Optional[Dict[str, Any]]]:
        """Epoch-boundary policy check. Returns ``(proceed, state,
        event)``: proceed False means re-run the SAME epoch from the
        rolled-back state; a verdict the policy cannot absorb raises
        :class:`NumericalDivergence`. Runs BEFORE the epoch's
        checkpoint save, so a bad epoch never becomes last-good."""
        verdict = None
        if bad_steps > 0 or not np.isfinite(loss):
            verdict = "nonfinite"
        elif sent["cooldown"] > 0:
            # the EMA is stale relative to freshly-restored params;
            # suppress the spike check while it re-warms
            sent["cooldown"] -= 1
        elif sent["ema"] is not None and \
                loss > policy.spike_factor * max(sent["ema"], 1e-9):
            verdict = "spike"
        if verdict is None:
            sent["ema"] = (loss if sent["ema"] is None else
                           policy.ema_alpha * loss +
                           (1.0 - policy.ema_alpha) * sent["ema"])
            return True, state, None
        if verdict == "nonfinite":
            health_lib.record("nonfiniteSteps", max(bad_steps, 1))
        else:
            health_lib.record("lossSpikes")
        event = {"kind": verdict, "epoch": epoch, "action": policy.action,
                 "badSteps": bad_steps,
                 "loss": loss if np.isfinite(loss) else None,
                 "ema": sent["ema"], "rollbacks": sent["rollbacks"]}
        rolled = None
        if policy.action == "rollback" and \
                sent["rollbacks"] < policy.max_rollbacks:
            if checkpointer is not None and \
                    checkpointer.latest_step() is not None:
                # verified restore: a corrupt latest step quarantines
                # and falls back inside the checkpointer; None means
                # nothing on disk survived verification
                rolled = checkpointer.restore(state)
            if rolled is None and snapshot is not None:
                from learningorchestra_tpu.runtime.checkpoint import \
                    _place_like
                rolled = _place_like(snapshot, state)
            if rolled is not None:
                sent["rollbacks"] += 1
                sent["cooldown"] = policy.cooldown_epochs
                health_lib.record("rollbacks")
                event["rollbacks"] = sent["rollbacks"]
                event["restoredStep"] = int(rolled.step)
        if log_fn is not None:
            try:
                log_fn({"healthEvent": dict(event)})
            except Exception:  # noqa: BLE001 — telemetry must not sink a fit
                pass
        if rolled is not None:
            return False, rolled, event
        if policy.action == "skip":
            # updates were already dropped on-device; a spike cannot be
            # skipped retroactively so it is counted and absorbed into
            # the EMA (or the check would fire every epoch after a
            # genuine level shift)
            if np.isfinite(loss):
                sent["ema"] = (loss if sent["ema"] is None else
                               policy.ema_alpha * loss +
                               (1.0 - policy.ema_alpha) * sent["ema"])
            return True, state, event
        suffix = (f" after {sent['rollbacks']} rollbacks"
                  if policy.action == "rollback" else "")
        raise NumericalDivergence(
            f"epoch {epoch}: {verdict} (badSteps={bad_steps}, "
            f"loss={loss}) under healthPolicy action "
            f"{policy.action!r}{suffix}")

    @staticmethod
    def _pop_bad_steps(sums: Dict[str, Any],
                       counts: Optional[Dict[str, Any]] = None) -> int:
        bad = sums.pop("_health_bad", None)
        if counts is not None:
            counts.pop("_health_bad", None)
        return int(float(bad[0] if isinstance(bad, tuple) else bad)) \
            if bad is not None else 0

    def _save_checkpoint(self, checkpointer, state: TrainState,
                         epoch: int) -> None:
        step = int(state.step)
        checkpointer.save(step, state)
        # the save above may be async (runtime/async_ckpt.py): the
        # sidecar records which step it describes, and resume ignores
        # it unless that exact step is what actually restored (a crash
        # mid-save leaves an older committed step + a newer sidecar —
        # trusting it would skip never-trained epochs)
        if hasattr(checkpointer, "save_meta"):
            checkpointer.save_meta({"step": step, "epochs_done": epoch + 1})

    def _maybe_restore(self, state: TrainState, checkpointer
                       ) -> Tuple[TrainState, bool]:
        """Resume from the newest checkpoint if one exists — this is
        what turns the reference's 'failed jobs are lost, resubmit from
        the parent' story (README.md:194-198) into true mid-training
        resume: a PATCH re-run picks up at the last saved step.

        Returns (state, restored) — the flag lets ``fit`` subtract the
        already-completed epochs from the requested budget only on a
        real resume (plain repeated ``fit`` calls keep accumulating
        epochs, Keras-style)."""
        if checkpointer is None or checkpointer.latest_step() is None:
            return state, False
        try:
            restored = checkpointer.restore(state)
        except (ValueError, KeyError, TypeError) as exc:
            # The targeted restore failed. Decide what that MEANS from
            # the checkpoint's own metadata (structure only, no array
            # reads) rather than the exception text: silently training
            # from scratch on a corrupted read could overwrite the
            # last good checkpoint at the next save.
            import warnings

            migrated, reason = self._restore_params_only(state,
                                                         checkpointer)
            if migrated is not None:
                warnings.warn(
                    f"checkpoint state layout changed "
                    f"({type(exc).__name__}: {exc}); resumed params at "
                    f"step {int(migrated.step)} and rebuilt optimizer "
                    f"state fresh", stacklevel=2)
                return migrated, True
            if reason == "unreadable":
                # the checkpoint itself failed to read: corruption/IO,
                # not drift — propagate rather than risk overwriting
                # the last good save with a from-scratch run
                raise
            warnings.warn(
                f"checkpoint restore failed ({type(exc).__name__}: "
                f"{exc}); state layout changed and params could not "
                f"be migrated — training from scratch instead of "
                f"resuming", stacklevel=2)
            return state, False
        if restored is None:
            return state, False
        return restored, True

    def _restore_params_only(self, state: TrainState, checkpointer
                             ) -> Tuple[Optional[TrainState], str]:
        """Layout-drift migration: graft the checkpoint's params (and
        step / model_state where their structure still matches) onto
        the live state and rebuild opt_state from the optimizer — a
        run whose optimizer pytree drifted resumes with a cold
        optimizer instead of restarting at step 0.

        Returns ``(state, "ok")`` on success, ``(None, reason)``
        otherwise; reason "mismatch" means the params themselves
        drifted (scratch is legitimate), anything else means the
        checkpoint could not be read (the caller should re-raise).
        Only the matching subtrees are restored, so a drifted
        opt_state's stale arrays (2x params for adam) never touch
        host memory."""
        if not (hasattr(checkpointer, "saved_metadata") and
                hasattr(checkpointer, "restore_partial")):
            return None, "unsupported"
        meta = checkpointer.saved_metadata()
        if not isinstance(meta, dict) or "params" not in meta:
            return None, "mismatch"

        def _same_structure(live, saved) -> bool:
            if jax.tree_util.tree_structure(live) != \
                    jax.tree_util.tree_structure(saved):
                return False
            return all(
                tuple(getattr(x, "shape", ())) ==
                tuple(getattr(y, "shape", ()))
                for x, y in zip(jax.tree_util.tree_leaves(live),
                                jax.tree_util.tree_leaves(saved)))

        if not _same_structure(state.params, meta["params"]):
            return None, "mismatch"
        target = {"params": jax.tree_util.tree_map(
            lambda x: np.zeros(x.shape, x.dtype), state.params)}
        if "step" in meta:
            target["step"] = np.zeros(state.step.shape, state.step.dtype)
        graft_model_state = (
            "model_state" in meta and
            jax.tree_util.tree_leaves(state.model_state) and
            _same_structure(state.model_state, meta["model_state"]))
        if graft_model_state:
            target["model_state"] = jax.tree_util.tree_map(
                lambda x: np.zeros(x.shape, x.dtype), state.model_state)
        raw = checkpointer.restore_partial(target)
        if raw is None:
            return None, "unreadable"
        # land each leaf on its live sharding so a TP/FSDP layout
        # survives the migration
        params = jax.tree_util.tree_map(
            lambda cur, new: jax.device_put(
                jnp.asarray(new, cur.dtype), cur.sharding),
            state.params, raw["params"])
        if self._mesh is not None and self._param_rules is not None:
            opt_state = self._init_opt_state_on_mesh(params)
        else:
            opt_state = self._optimizer.init(params)
        step = state.step
        if "step" in raw:
            step = jax.device_put(
                jnp.asarray(raw["step"], state.step.dtype),
                state.step.sharding)
        model_state = state.model_state
        if graft_model_state:
            model_state = jax.tree_util.tree_map(
                lambda cur, new: jax.device_put(
                    jnp.asarray(new, cur.dtype), cur.sharding),
                state.model_state, raw["model_state"])
        return TrainState(step=step, params=params, opt_state=opt_state,
                          model_state=model_state), "ok"

    # -- live migration (docs/SCALING.md §7) ---------------------------
    def _place_state(self, host_state: TrainState) -> TrainState:
        """Land a host-snapshotted train state on the CURRENT mesh,
        mirroring :meth:`init_state` placement: rules-sharded params
        (opt_state leaves follow via a jitted init's shardings) or
        whole-state replication."""
        mesh = self._mesh
        if mesh is None:
            return jax.tree_util.tree_map(jnp.asarray, host_state)
        if self._param_rules is not None:
            from learningorchestra_tpu.parallel import \
                sharding as rules_lib

            shardings = rules_lib.param_shardings(
                host_state.params, mesh, self._param_rules,
                fsdp=self._fsdp)
            params = jax.device_put(host_state.params, shardings)
            ref_opt = self._init_opt_state_on_mesh(params)
            opt_state = jax.tree_util.tree_map(
                lambda h, r: jax.device_put(
                    jnp.asarray(h, r.dtype), r.sharding),
                host_state.opt_state, ref_opt)
            rep = mesh_lib.replicated(mesh)
            return TrainState(
                step=jax.device_put(
                    jnp.asarray(host_state.step, jnp.int32), rep),
                params=params, opt_state=opt_state,
                model_state=jax.device_put(host_state.model_state, rep))
        return jax.device_put(host_state, mesh_lib.replicated(mesh))

    def _land_on_devices(self, host_state: TrainState, devices
                         ) -> TrainState:
        """Swap the thread-local mesh to ``devices`` and re-place a
        host-snapshotted state there. Jitted-step identities key on
        the mesh, so the per-instance handles are dropped and the
        next dispatch re-resolves through the shared cache; an
        explicit batch sharding references the OLD mesh, so it falls
        back to the default data-axes sharding of the new one."""
        new_mesh = mesh_lib.mesh_for_slice(devices)
        mesh_lib.set_current_mesh(new_mesh)
        self._mesh = new_mesh
        self._train_step = None
        self._eval_step = None
        self._predict_step = None
        self._epoch_steps = {}
        self._batch_sharding = None
        state = self._place_state(host_state)
        jax.block_until_ready(state.params)
        return state

    def _maybe_migrate(self, state: TrainState, checkpointer
                       ) -> Tuple[TrainState, bool]:
        """Epoch-boundary live migration (services/migration.py):
        when a migrate request is latched on this job's token, barrier
        any in-flight async checkpoint commits, snapshot train state
        device→host, release the held slice and re-acquire a fresh
        placement through the fair queue, re-point the thread-local
        mesh at the new slice, and re-place the snapshot there.
        Per-step rng derives from the host step counter, so the
        resumed run replays bit-identically. A pending elastic RESIZE
        (services/autoscaler.py) rides the same path with a new
        device count and a failure ladder: any fault inside the
        guarded region — injected chaos, a lease race past the grant
        timeout, an OOM placing state on the target mesh — rolls the
        job back to an old-size slice, keeps training, and fires an
        ``autoscaler:rollback`` incident. Returns
        ``(state, migrated)``."""
        if not preempt.migrate_requested():
            return state, False
        t0 = time.monotonic()
        token = preempt.current_cancel()
        resize_want = token.resize_want if token is not None else None
        old_devices = token.slice_devices if token is not None else None
        if resize_want is None:
            _inject_migration_fault()
        if checkpointer is not None and \
                hasattr(checkpointer, "wait_until_finished"):
            checkpointer.wait_until_finished()
        host_state = to_host(state)
        if resize_want is None:
            performed, new_devices = preempt.perform_migrate()
            if not performed:
                return state, False
            state = self._land_on_devices(host_state, new_devices)
            self._record_migration(t0, new_devices, host_state)
            return state, True
        # -- elastic resize: everything after this point rolls back --
        try:
            _inject_resize_fault()
            performed, new_devices = preempt.perform_migrate()
            if not performed:  # defensive: latch raced away
                token.resize_done(False, old_devices,
                                  error="resize latch lost")
                return state, False
            state = self._land_on_devices(host_state, new_devices)
        except preempt.JobCancelled:
            raise
        except Exception as exc:  # noqa: BLE001 — the failure ladder
            return self._rollback_resize(
                host_state, state, token, old_devices, resize_want,
                exc, t0)
        token.resize_done(True, new_devices)
        self._record_migration(t0, new_devices, host_state,
                               resized_to=len(new_devices)
                               if new_devices is not None else None)
        return state, True

    def _rollback_resize(self, host_state: TrainState,
                         state: TrainState, token, old_devices,
                         resize_want: int, exc: Exception,
                         t0: float) -> Tuple[TrainState, bool]:
        """Failed-resize ladder: restore the job onto an old-size
        slice (or leave it untouched when nothing moved yet), report
        the rollback on the token, and leave incident evidence. The
        job KEEPS TRAINING — the autoscaler applies per-job backoff
        before any retry."""
        error = f"{type(exc).__name__}: {exc}"
        migrated = False
        if token.migrate_pending is not None:
            # fault fired before the slice was released: consume the
            # latch; the live state on the old mesh is still valid
            token.consume_migrate()
        else:
            devices = token.slice_devices
            if devices is not None and old_devices is not None \
                    and len(devices) != len(old_devices):
                # placement failed AFTER the resize grant landed: go
                # back to an old-size slice through the raw migrate
                # point (best-effort — a second race leaves us on
                # whatever grant it restored)
                fn = preempt.migrate_fn()
                if fn is not None:
                    try:
                        fn(len(old_devices))
                    except preempt.JobCancelled:
                        raise
                    except Exception:  # noqa: BLE001 — keep ladder
                        pass
            state = self._land_on_devices(host_state,
                                          token.slice_devices)
            migrated = True
        token.resize_done(False, token.slice_devices, error=error)
        try:
            from learningorchestra_tpu.observability import \
                incidents as obs_incidents

            cur = obs_trace.current()
            obs_incidents.trigger(
                "autoscaler:rollback",
                job=(cur[0] if cur is not None else None),
                error=error, want=int(resize_want),
                oldDevices=(list(old_devices)
                            if old_devices is not None else None),
                restoredDevices=(list(token.slice_devices)
                                 if token.slice_devices is not None
                                 else None),
                step=int(host_state.step))
        except Exception:  # noqa: BLE001 — evidence is best-effort
            pass
        return state, migrated

    def _record_migration(self, t0: float, new_devices, host_state,
                          resized_to=None) -> None:
        end = time.monotonic()
        health_lib.record("migrations")
        try:
            obs_hist.observe("lo_migration_seconds", end - t0)
            cur = obs_trace.current()
            if cur is not None:
                extra = {} if resized_to is None \
                    else {"resizedTo": resized_to}
                obs_trace.add(
                    "migration", cur[0], t0, end, parent=cur[1],
                    devices=(list(new_devices)
                             if new_devices is not None else None),
                    step=int(host_state.step), **extra)
        except Exception:  # noqa: BLE001 — observability is advisory
            pass

    def _fit_scanned(self, state: TrainState,
                     batcher: data_lib.ArrayBatcher, epochs: int,
                     seed: int, checkpointer, log_fn,
                     start_epoch: int = 0,
                     policy: Optional[HealthPolicy] = None,
                     ) -> Tuple[TrainState, List[Dict[str, Any]]]:
        steps = batcher.steps_per_epoch
        bs = batcher.batch_size
        key = (steps, bs, batcher.shuffles)
        epoch_step = self._epoch_steps.get(key)
        if epoch_step is None:
            epoch_step = self._epoch_steps[key] = self._shared_step(
                "epoch",
                lambda: self._build_epoch_step(steps, bs,
                                               batcher.shuffles),
                extra=key)
        base_rng = jax.random.PRNGKey(seed)
        shuffle_rng = _shuffle_rng(batcher.seed)
        # one host->HBM transfer for the whole fit; epochs shuffle in
        # HBM (the host link, not the MXU, is the scarce resource).
        # Batchers carrying a content token keep the staged arrays in
        # the device arena BETWEEN fits: a repeat job (or the next
        # classifier over the same dataset) skips pad+transfer too.
        sharding = self._resolve_batch_sharding()
        token = getattr(batcher, "cache_token", None)
        entry = None

        staged = []

        def stage() -> Dict[str, Any]:
            staged.append(True)
            return {k: data_lib.stage_to_device(v, sharding)
                    for k, v in batcher.padded_arrays().items()}

        with obs_trace.span("stage") as stage_span:
            if token is not None:
                entry = arena_lib.get_default_arena().get_or_put(
                    ("fit_arrays", token, steps, bs, batcher.shuffles,
                     self._mesh, sharding),
                    stage, tags=getattr(batcher, "cache_tags", ()),
                    # slice-scheduled fits budget against their slice's
                    # share of HBM, not the whole arena
                    group=self._mesh,
                    group_fraction=mesh_lib.mesh_fraction(self._mesh))
                device_arrays = entry.arrays
            else:
                device_arrays = stage()
            stage_span.set(bytes=_tree_nbytes(device_arrays),
                           arenaHit=not staged)
        history: List[Dict[str, Any]] = []
        sent = self._new_sentinel()
        # last-good fallback when no checkpoint step exists yet (or
        # none survives verification): one host copy, refreshed after
        # each healthy epoch only when there is no checkpointer
        snapshot = (to_host(state)
                    if policy is not None and policy.action == "rollback"
                    else None)
        try:
            epoch = start_epoch
            while epoch < epochs:
                # lifecycle boundary: honor a deadline/cancel before
                # dispatching the next whole-epoch scan, and publish
                # progress for the stall watchdog
                preempt.check_cancel()
                preempt.heartbeat(epoch=epoch,
                                  rollbacks=sent["rollbacks"])
                t0 = time.perf_counter()
                with obs_trace.span("epoch", epoch=epoch) as epoch_span:
                    if epoch == start_epoch and sent["rollbacks"] == 0:
                        # sliced from the device copy so an arena hit
                        # never re-materializes the padded host arrays
                        one = {k: v[:bs] for k, v in device_arrays.items()}
                        self._measure_flops(
                            state, one, base_rng,
                            step_fn=jax.jit(self._train_step_body),
                            epoch=epoch,
                            count_only=self._flops_floor_fn is not None)
                    arrays_in = device_arrays
                    if _armed_nan():
                        arrays_in = _poison_rows(device_arrays, bs)
                    rb = sent["rollbacks"]
                    step_rng = (base_rng if rb == 0
                                else jax.random.fold_in(
                                    base_rng, _HEALTH_TAG + rb))
                    epoch_idx = jnp.asarray(epoch + rb * _ROLLBACK_STRIDE)
                    # once-per-epoch dispatch: the sentinel wrapper is
                    # off the per-step path, so it is always-on here
                    with obs_trace.span("dispatch", epoch=epoch) as dispatch:
                        state, totals = self._call_in(
                            dispatch, obs_xray.guarded_call, epoch_step,
                            state, arrays_in, step_rng, shuffle_rng,
                            epoch_idx)
                    with obs_trace.span("deviceWait", epoch=epoch):
                        jax.block_until_ready(state.params)
                    dt = time.perf_counter() - t0
                    with obs_trace.span("epochEnd", epoch=epoch):
                        bad_steps = self._pop_bad_steps(totals)
                        record = {k: float(s) / max(float(c), 1e-9)
                                  for k, (s, c) in totals.items()}
                        if policy is not None:
                            proceed, state, event = \
                                self._health_epoch_end(
                                    policy, sent, epoch, bad_steps,
                                    record.get("loss", float("nan")),
                                    state, checkpointer, snapshot,
                                    log_fn)
                            if not proceed:
                                continue  # re-run from last-good
                            if event is not None and bad_steps:
                                record["nonfiniteSteps"] = bad_steps
                            if checkpointer is None and \
                                    policy.action == "rollback":
                                snapshot = to_host(state)
                        record.update(
                            epoch=epoch, epochSeconds=round(dt, 4),
                            samplesPerSecond=round(
                                batcher.num_samples / dt, 2))
                        # compile epoch has no steady-state window in
                        # scan mode; roofline numbers start with the
                        # second epoch
                        if epoch > start_epoch:
                            self._roofline_record(record, steps, dt)
                        self._observe_window(
                            epoch_span, dt, record, bad_steps,
                            step=(epoch + 1) * steps, epoch=epoch,
                            builds=dispatch.attrs.get("builds", 0))
                        history.append(record)
                        if checkpointer is not None:
                            self._save_checkpoint(checkpointer, state,
                                                  epoch)
                        if log_fn is not None:
                            log_fn(record)
                # fair scheduling: offer the mesh lease to waiting
                # jobs of other pools (no-op outside the service
                # layer); the epoch is checkpointed, so the hand-off
                # is durable. Never after the last epoch — a finishing
                # job must not block on re-acquiring a lease it has no
                # more work for.
                epoch += 1
                if epoch < epochs:
                    state, migrated = self._maybe_migrate(
                        state, checkpointer)
                    if migrated:
                        # the job moved slices: everything keyed on
                        # the old mesh re-resolves — batch sharding,
                        # the staged epoch arrays (the old slice's HBM
                        # belongs to someone else now) and the epoch
                        # program
                        sharding = self._resolve_batch_sharding()
                        if entry is not None:
                            entry.release()
                            entry = arena_lib.get_default_arena() \
                                .get_or_put(
                                    ("fit_arrays", token, steps, bs,
                                     batcher.shuffles, self._mesh,
                                     sharding),
                                    stage,
                                    tags=getattr(batcher,
                                                 "cache_tags", ()),
                                    group=self._mesh,
                                    group_fraction=mesh_lib
                                    .mesh_fraction(self._mesh))
                            device_arrays = entry.arrays
                        else:
                            device_arrays = stage()
                        epoch_step = self._epoch_steps.get(key)
                        if epoch_step is None:
                            epoch_step = self._epoch_steps[key] = \
                                self._shared_step(
                                    "epoch",
                                    lambda: self._build_epoch_step(
                                        steps, bs, batcher.shuffles),
                                    extra=key)
                    preempt.maybe_yield()
            # surface any latched async-commit failure on the JOB
            # before it reports success (no-op for the sync class)
            if checkpointer is not None and \
                    hasattr(checkpointer, "wait_until_finished"):
                checkpointer.wait_until_finished()
        finally:
            # the pin must drop on EVERY exit — a JobCancelled /
            # timed-out unwind included (docs/LIFECYCLE.md) — or the
            # entry could never be evicted
            if entry is not None:
                entry.release()
        return state, history

    def fit(self, state: TrainState, batcher: data_lib.ArrayBatcher,
            epochs: int = 1, seed: int = 0,
            checkpointer=None,
            log_fn: Optional[Callable[[Dict[str, Any]], None]] = None,
            scan_batches: Optional[bool] = None,
            health_policy=None,
            ) -> Tuple[TrainState, List[Dict[str, Any]]]:
        """Train ``epochs`` over ``batcher``. Holds the train state's
        X-ray ledger entry (owner ``train-state``) for the duration of
        the fit so ``GET /observability/memory`` can attribute the
        resident state while the job runs."""
        self._ledger_state(state)
        self._begin_fit()
        try:
            return self._fit_impl(state, batcher, epochs=epochs,
                                  seed=seed, checkpointer=checkpointer,
                                  log_fn=log_fn,
                                  scan_batches=scan_batches,
                                  health_policy=health_policy)
        finally:
            obs_xray.release("train-state", id(self))

    def _fit_impl(self, state: TrainState,
                  batcher: data_lib.ArrayBatcher,
                  epochs: int = 1, seed: int = 0,
                  checkpointer=None,
                  log_fn: Optional[Callable[[Dict[str, Any]],
                                            None]] = None,
                  scan_batches: Optional[bool] = None,
                  health_policy=None,
                  ) -> Tuple[TrainState, List[Dict[str, Any]]]:
        policy = health_lib.coerce_policy(health_policy)
        self._set_health(policy)
        state, restored = self._maybe_restore(state, checkpointer)
        # On a real resume the requested ``epochs`` is the TOTAL budget:
        # a PATCH re-run of a crashed job trains only the remainder and
        # a re-run of a finished job is a no-op (not a silent doubling).
        # Completed epochs come from the checkpoint's progress sidecar
        # (robust to a re-run reshaping the feed); the restored step is
        # the fallback for checkpoints written before the sidecar.
        start_epoch = 0
        if restored:
            meta = (checkpointer.load_meta()
                    if hasattr(checkpointer, "load_meta") else None)
            if meta and "epochs_done" in meta and \
                    int(meta.get("step", -1)) == int(state.step):
                start_epoch = min(epochs, int(meta["epochs_done"]))
            else:
                start_epoch = min(
                    epochs,
                    int(state.step) // max(1, batcher.steps_per_epoch))
            if start_epoch >= epochs:
                return state, []
        use_scan = (self._should_scan(batcher) if scan_batches is None
                    else scan_batches)
        if use_scan:
            return self._fit_scanned(state, batcher, epochs, seed,
                                     checkpointer, log_fn,
                                     start_epoch=start_epoch,
                                     policy=policy)
        if self._train_step is None:
            self._train_step = self._shared_step(
                "train", self._build_train_step)
        base_rng = jax.random.PRNGKey(seed)
        history: List[Dict[str, Any]] = []
        sent = self._new_sentinel()
        snapshot = (to_host(state)
                    if policy is not None and policy.action == "rollback"
                    else None)
        # Host-side step counter for the dropout rng: reading
        # ``state.step`` here would sync the host on every step and
        # serialize the prefetch pipeline against device compute. It
        # continues from the restored step, so the per-step rng stream
        # does not replay draws consumed before a crash.
        host_step = int(state.step)
        # transfer sentinel (LO_TRANSFER_GUARD): resolved once per fit
        # so the per-step hot path stays branch-only when disarmed
        guard = obs_xray.transfer_guard_mode()
        epoch = start_epoch
        while epoch < epochs:
            t0 = time.perf_counter()
            # metric accumulation stays on-device (async); one sync at
            # epoch end
            sums: Dict[str, Any] = {}
            counts: Dict[str, Any] = {}
            steps = 0
            rb = sent["rollbacks"]
            # post-rollback the rng stream re-keys and the shuffle
            # cursor jumps, so the replayed epoch does not replay the
            # exact batch order / dropout draws that diverged
            eff_rng = (base_rng if rb == 0 else jax.random.fold_in(
                base_rng, _HEALTH_TAG + rb))
            poison = _armed_nan()
            # MFU must reflect steady-state compute, not XLA compile:
            # on the compile epoch the roofline window starts after the
            # first step completes (one extra sync, once per fit)
            t_steady, steady_steps = t0, 0
            builds = span_steps = 0
            with obs_trace.span("epoch", epoch=epoch) as epoch_span, \
                    contextlib.ExitStack() as spans:
                # a step is too short for a span of its own: the fit's
                # first step is one dispatch (its sync bounds the first
                # build), the rest of the epoch's feed loop another
                dispatch = spans.enter_context(
                    obs_trace.span("dispatch", epoch=epoch))
                for batch in self._device_feed(
                        batcher, epoch + rb * _ROLLBACK_STRIDE):
                    # per-step lifecycle point (dispatch is async, so
                    # this is host-side and nearly free): a cancelled/
                    # expired job stops mid-epoch instead of finishing
                    # it out
                    preempt.check_cancel()
                    preempt.heartbeat(epoch=epoch, step=host_step,
                                      rollbacks=rb)
                    if poison:
                        batch = _poison_batch(batch)
                        poison = False
                    rng = jax.random.fold_in(eff_rng, host_step)
                    host_step += 1
                    if steps == 0 and epoch == start_epoch and rb == 0:
                        self._measure_flops(state, batch, rng,
                                            epoch=epoch)
                    if guard:
                        state, metrics = self._call_in(
                            dispatch, obs_xray.guarded_call,
                            self._train_step, state, batch, rng)
                    else:
                        state, metrics = self._call_in(
                            dispatch, self._train_step, state, batch,
                            rng)
                    steps += 1
                    span_steps += 1
                    if steps == 1 and epoch == start_epoch:
                        dispatch.set(steps=span_steps)
                        builds, span_steps = \
                            dispatch.attrs.get("builds", 0), 0
                        spans.close()
                        with obs_trace.span("deviceWait", epoch=epoch):
                            jax.block_until_ready(metrics)
                        t_steady, steady_steps = time.perf_counter(), -1
                        dispatch = spans.enter_context(
                            obs_trace.span("dispatch", epoch=epoch))
                    for k, (s, c) in metrics.items():
                        sums[k] = sums.get(k, 0) + s
                        counts[k] = counts.get(k, 0) + c
                dispatch.set(steps=span_steps)
                builds += dispatch.attrs.get("builds", 0)
                spans.close()
                with obs_trace.span("deviceWait", epoch=epoch):
                    jax.block_until_ready(state.params)
                now = time.perf_counter()
                dt = now - t0
                with obs_trace.span("epochEnd", epoch=epoch):
                    bad_steps = self._pop_bad_steps(sums, counts)
                    record = {
                        k: float(sums[k]) / max(float(counts[k]), 1e-9)
                        for k in sums}
                    if policy is not None:
                        proceed, state, event = self._health_epoch_end(
                            policy, sent, epoch, bad_steps,
                            record.get("loss", float("nan")), state,
                            checkpointer, snapshot, log_fn)
                        if not proceed:
                            # re-run this epoch from the rolled-back
                            # state; the rng step counter rewinds with
                            # it
                            host_step = int(state.step)
                            continue
                        if event is not None and bad_steps:
                            record["nonfiniteSteps"] = bad_steps
                        if checkpointer is None and \
                                policy.action == "rollback":
                            snapshot = to_host(state)
                    record.update(
                        epoch=epoch, epochSeconds=round(dt, 4),
                        samplesPerSecond=round(
                            batcher.num_samples / dt, 2))
                    steady_steps += steps
                    self._roofline_record(record, steady_steps,
                                          now - t_steady)
                    self._observe_window(
                        epoch_span, dt, record, bad_steps, step=host_step,
                        epoch=epoch, builds=builds)
                    history.append(record)
                    if checkpointer is not None:
                        self._save_checkpoint(checkpointer, state, epoch)
                    if log_fn is not None:
                        log_fn(record)
            epoch += 1
            if epoch < epochs:  # fair scheduling (see _fit_scanned)
                state, migrated = self._maybe_migrate(
                    state, checkpointer)
                if migrated:
                    # per-step path: the train step re-resolves under
                    # the new mesh; the device feed re-reads
                    # _resolve_batch_sharding() every epoch already
                    self._train_step = self._shared_step(
                        "train", self._build_train_step)
                preempt.maybe_yield()
        # surface any latched async-commit failure on the JOB before
        # it reports success (no-op for the sync class)
        if checkpointer is not None and \
                hasattr(checkpointer, "wait_until_finished"):
            checkpointer.wait_until_finished()
        return state, history

    def evaluate(self, state: TrainState, batcher: data_lib.ArrayBatcher,
                 ) -> Dict[str, float]:
        if self._eval_step is None:
            self._eval_step = self._shared_step(
                "eval", self._build_eval_step)
        sums: Dict[str, Any] = {}
        counts: Dict[str, Any] = {}
        for step, batch in enumerate(self._device_feed(batcher, 0)):
            preempt.check_cancel()
            preempt.heartbeat(phase="evaluate", step=step)
            metrics = self._eval_step(state, batch)
            for k, (s, c) in metrics.items():
                sums[k] = sums.get(k, 0) + s
                counts[k] = counts.get(k, 0) + c
        return {k: float(sums[k]) / max(float(counts[k]), 1e-9)
                for k in sums}

    def predict(self, state: TrainState, batcher: data_lib.ArrayBatcher,
                ) -> np.ndarray:
        if self._predict_step is None:
            self._predict_step = self._shared_step(
                "predict", self._build_predict_step)
        outs = []
        for step, batch in enumerate(self._device_feed(batcher, 0)):
            preempt.check_cancel()
            preempt.heartbeat(phase="predict", step=step)
            outs.append(np.asarray(self._predict_step(state, batch)))
        full = np.concatenate(outs, axis=0)
        return full[:batcher.num_samples]  # drop padding


# ----------------------------------------------------------------------
# Vectorized sweep fusion (docs/PERFORMANCE.md "Sweep fusion"): train N
# same-architecture hyperparameter configs in ONE compiled program by
# vmapping the train/eval step over a leading config axis. Counters are
# module-level so a test can assert a fused sweep compiled its epoch
# program exactly once (zero warm retraces across points).
# ----------------------------------------------------------------------
_FUSED_STATS = {"epochTraces": 0}


def fused_epoch_traces() -> int:
    """How many times a fused epoch program has been TRACED process-
    wide (incremented at trace time, not per call): one fused sweep
    cohort must contribute exactly 1."""
    return _FUSED_STATS["epochTraces"]


class FusedEngine(Engine):
    """Config-axis mode of the engine: stacked params/opt_state with a
    leading config dimension, per-config optimizer hyperparameters as
    traced arrays, one vmapped train step shared by every config.

    ``optimizer_factory(hyper)`` rebuilds the optax transformation from
    a dict of scalar hyperparameters INSIDE the traced step (the
    ``inject_hyperparams`` trick without carrying them in opt_state),
    so learning rate / decay / momentum become data instead of
    compile-time constants — N sweep points cost one compile. The
    batch and rng stream are broadcast (in_axes=None): every config
    sees exactly the shuffle order and dropout draws an independent
    trial with the same seed would, which is what makes fused metrics
    match unfused trials. The config axis is sharded over the data
    axes when it divides them (parallel/sharding.py
    ``fused_state_shardings``); the batch is then replicated so each
    device advances its configs on the full batch.
    """

    def __init__(self, *, apply_fn: Callable, loss_fn: Callable,
                 optimizer_factory: Callable[[Dict[str, Any]], Any],
                 hyper: Dict[str, Any], mesh=None,
                 metrics: Optional[Dict[str, Callable]] = None,
                 compute_dtype: Any = jnp.bfloat16,
                 donate_state: bool = True, grad_accum: int = 1,
                 cache_key: Any = None):
        names = tuple(sorted(hyper))
        if not names:
            raise ValueError("fused engine needs hyperparameter arrays")
        self._hyper_names = names
        self._hyper = {k: jnp.asarray(np.asarray(hyper[k], np.float32))
                       for k in names}
        sizes = {int(v.shape[0]) for v in self._hyper.values()}
        if len(sizes) != 1:
            raise ValueError(
                f"hyperparameter arrays disagree on config count: "
                f"{sorted(sizes)}")
        self._n_configs = sizes.pop()
        self._opt_factory = optimizer_factory
        # structure-defining init optimizer: opt_state layout does not
        # depend on the hyperparameter VALUES, only on the kind
        base = optimizer_factory(
            {k: float(np.asarray(hyper[k])[0]) for k in names})
        super().__init__(
            apply_fn=apply_fn, loss_fn=loss_fn, optimizer=base,
            mesh=mesh, metrics=metrics, compute_dtype=compute_dtype,
            donate_state=donate_state, grad_accum=grad_accum,
            # the config axis + hyper names change the traced program,
            # so they extend the shared-cache identity
            cache_key=None if cache_key is None else
            ("fused", cache_key, names, self._n_configs))
        self._fused_epoch_steps: Dict[Any, Callable] = {}
        self._fused_eval = None

    @property
    def n_configs(self) -> int:
        return self._n_configs

    def _config_sharded(self) -> bool:
        if self._mesh is None:
            return False
        dp = mesh_lib.data_parallel_size(self._mesh)
        return dp > 1 and self._n_configs % dp == 0

    def _resolve_batch_sharding(self):
        if self._batch_sharding is not None:
            return self._batch_sharding
        if self._mesh is None:
            return None
        if self._config_sharded():
            # configs own the data axes; the batch is replicated so
            # each device trains its config shard on the full batch
            return mesh_lib.replicated(self._mesh)
        return mesh_lib.batch_sharding(self._mesh)

    # ------------------------------------------------------------------
    def init_fused_state(self, params, model_state=None) -> TrainState:
        """Stack one set of initial params N-ways (every config of a
        fused cohort shares the clone's init seed, exactly like the
        independent trials it replaces) and vmap the optimizer init
        over the stack."""
        n = self._n_configs

        def tile(p):
            p = jnp.asarray(p)
            return jnp.tile(p[None], (n,) + (1,) * p.ndim)

        stacked = jax.tree_util.tree_map(tile, params)
        opt_state = jax.vmap(self._optimizer.init)(stacked)
        state = TrainState(step=jnp.zeros((), jnp.int32), params=stacked,
                           opt_state=opt_state,
                           model_state=jax.tree_util.tree_map(
                               tile, model_state or {}))
        if self._mesh is not None:
            from learningorchestra_tpu.parallel import \
                sharding as rules_lib

            state = jax.device_put(state, rules_lib.fused_state_shardings(
                state, self._mesh, n))
        return state

    def _fused_step_body(self, state: TrainState, hyper, active, batch,
                         rng):
        """One vmapped optimizer step over the config axis. ``active``
        masks early-stopped configs with the health-word where-guard
        pattern (PR 5): a stopped config keeps its old state wholesale
        and contributes zeroed metric sums."""
        def one(params, opt_state, model_state, hp, act):
            if self._grad_accum > 1:
                tmp = TrainState(step=state.step, params=params,
                                 opt_state=opt_state,
                                 model_state=model_state)
                grads, new_ms, metrics = self._accum_grads(tmp, batch, rng)
            else:
                grads, new_ms, metrics = self._micro_grads(
                    params, model_state, batch, rng)
            opt = self._opt_factory(hp)
            updates, new_opt = opt.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            stop = jnp.logical_not(act)
            old = (params, opt_state, model_state)
            new = (new_params, new_opt, new_ms)
            new = jax.tree_util.tree_map(
                lambda o, nv: jnp.where(stop, o, nv), old, new)
            metrics = {
                k: (jnp.where(stop, 0.0, s.astype(jnp.float32)),
                    jnp.where(stop, 0.0, c.astype(jnp.float32)))
                for k, (s, c) in metrics.items()}
            return new, metrics

        hp_stack = tuple(hyper[k] for k in self._hyper_names)

        def one_by_stack(params, opt_state, model_state, hps, act):
            return one(params, opt_state, model_state,
                       dict(zip(self._hyper_names, hps)), act)

        (new_params, new_opt, new_ms), metrics = jax.vmap(one_by_stack)(
            state.params, state.opt_state, state.model_state,
            hp_stack, active)
        new_state = state.replace(step=state.step + 1, params=new_params,
                                  opt_state=new_opt, model_state=new_ms)
        return new_state, metrics

    def _build_fused_epoch_step(self, steps: int, batch_size: int,
                                shuffle: bool):
        """Whole-epoch scan over the vmapped step — the fused twin of
        ``_build_epoch_step``: one dispatch per epoch, one shared
        shuffle permutation, per-config (sum, count) metric totals."""
        n_total = steps * batch_size

        def epoch_fn(state: TrainState, hyper, active, arrays, step_rng,
                     shuffle_rng, epoch_idx):
            # trace-time side effect: each (re)trace of the fused
            # program counts once — the sweep-smoke gate asserts this
            # stays at 1 across all sweep points and warm repeats
            _FUSED_STATS["epochTraces"] += 1
            if shuffle:
                perm = jax.random.permutation(
                    jax.random.fold_in(shuffle_rng, epoch_idx), n_total)
                arrays = jax.tree_util.tree_map(
                    lambda a: jnp.take(a, perm, axis=0), arrays)
            batches = jax.tree_util.tree_map(
                lambda a: a.reshape((steps, batch_size) + a.shape[1:]),
                arrays)

            def step(carry, batch):
                rng = jax.random.fold_in(step_rng, carry.step)
                return self._fused_step_body(carry, hyper, active,
                                             batch, rng)

            state_out, metrics = jax.lax.scan(step, state, batches)
            # sum over the step axis, KEEP the config axis: metrics
            # stay per-config so results unstack into per-trial rows
            totals = {k: (jnp.sum(s, axis=0), jnp.sum(c, axis=0))
                      for k, (s, c) in metrics.items()}
            return state_out, totals

        donate = (0,) if self._donate else ()
        return jax.jit(epoch_fn, donate_argnums=donate)

    def _build_fused_eval_step(self):
        def step_fn(state: TrainState, batch):
            weights = batch.get(data_lib.MASK_KEY)

            def one(params, model_state):
                outputs, _ = self._apply_fn(
                    self._cast(params), model_state, self._cast(batch),
                    False, None)
                res = self._loss_fn(outputs, batch, weights)
                loss, extra = res if isinstance(res, tuple) else (res, {})
                loss = loss.astype(jnp.float32)
                metrics = {"loss": (loss * _total(weights),
                                    _total(weights))}
                metrics.update(extra)
                for name, fn in self._metrics.items():
                    if name in extra:
                        continue
                    metrics[name] = fn(outputs, batch, weights)
                return metrics

            return jax.vmap(one)(state.params, state.model_state)

        return jax.jit(step_fn)

    # ------------------------------------------------------------------
    def fit_fused(self, state: TrainState,
                  batcher: data_lib.ArrayBatcher, epochs: int = 1,
                  seed: int = 0, eval_batcher=None, score_fn=None,
                  earlystop: Optional[Dict[str, Any]] = None,
                  log_fn: Optional[Callable] = None,
                  ) -> Tuple[TrainState, List[Dict[str, Any]],
                             np.ndarray, List[Optional[int]]]:
        """Scan-mode fused fit (ledgers the STACKED cohort state as
        ``train-state`` for its duration). Returns ``(state, history,
        active, stopped_epochs)`` — ``active[i]`` False means config
        ``i`` was early-stopped at ``stopped_epochs[i]`` (its params
        frozen from that epoch on). Early stop needs ``eval_batcher``
        + ``score_fn`` and fires once a config's EMA validation score
        trails the cohort best by more than ``earlystop["margin"]``."""
        self._ledger_state(state)
        self._begin_fit()
        try:
            return self._fit_fused_impl(
                state, batcher, epochs=epochs, seed=seed,
                eval_batcher=eval_batcher, score_fn=score_fn,
                earlystop=earlystop, log_fn=log_fn)
        finally:
            obs_xray.release("train-state", id(self))

    def _fit_fused_impl(self, state: TrainState,
                        batcher: data_lib.ArrayBatcher,
                        epochs: int = 1, seed: int = 0,
                        eval_batcher=None, score_fn=None,
                        earlystop: Optional[Dict[str, Any]] = None,
                        log_fn: Optional[Callable] = None,
                        ) -> Tuple[TrainState, List[Dict[str, Any]],
                                   np.ndarray, List[Optional[int]]]:
        if not self._should_scan(batcher):
            raise FusedSweepUnsupported(
                "dataset exceeds the scan-fit budget "
                "(LO_SCAN_FIT_MAX_BYTES) — fused sweeps require the "
                "whole-epoch scan path")
        n = self._n_configs
        steps = batcher.steps_per_epoch
        bs = batcher.batch_size
        key = (steps, bs, batcher.shuffles)
        epoch_step = self._fused_epoch_steps.get(key)
        if epoch_step is None:
            epoch_step = self._fused_epoch_steps[key] = self._shared_step(
                "fused_epoch",
                lambda: self._build_fused_epoch_step(
                    steps, bs, batcher.shuffles),
                extra=key)
        base_rng = jax.random.PRNGKey(seed)
        shuffle_rng = _shuffle_rng(batcher.seed)
        sharding = self._resolve_batch_sharding()
        with obs_trace.span("stage") as stage_span:
            device_arrays = {
                k: data_lib.stage_to_device(v, sharding)
                for k, v in batcher.padded_arrays().items()}
            stage_span.set(bytes=_tree_nbytes(device_arrays),
                           arenaHit=False)
        active = np.ones(n, bool)
        stopped: List[Optional[int]] = [None] * n
        ema: List[Optional[float]] = [None] * n
        es = dict(earlystop or {})
        es_margin = float(es.get("margin", 0.0) or 0.0)
        es_armed = (es_margin > 0.0 and eval_batcher is not None
                    and score_fn is not None)
        es_min_epochs = max(1, int(es.get("min_epochs", 2)))
        es_alpha = float(es.get("alpha", 0.5))
        history: List[Dict[str, Any]] = []
        for epoch in range(epochs):
            preempt.check_cancel()
            preempt.heartbeat(epoch=epoch, fusedConfigs=n)
            t0 = time.perf_counter()
            with obs_trace.span("epoch", epoch=epoch) as epoch_span:
                active_in = jnp.asarray(active)
                epoch_idx = jnp.asarray(epoch)
                with obs_trace.span("dispatch", epoch=epoch) as dispatch:
                    state, totals = self._call_in(
                        dispatch, epoch_step, state, self._hyper,
                        active_in, device_arrays, base_rng, shuffle_rng,
                        epoch_idx)
                with obs_trace.span("deviceWait", epoch=epoch):
                    jax.block_until_ready(state.params)
                dt = time.perf_counter() - t0
                with obs_trace.span("epochEnd", epoch=epoch):
                    record: Dict[str, Any] = {
                        k: (np.asarray(s, np.float64)
                            / np.maximum(np.asarray(c, np.float64), 1e-9)
                            ).round(6).tolist()
                        for k, (s, c) in totals.items()}
                    record.update(epoch=epoch, epochSeconds=round(dt, 4))
                    self._observe_window(
                        epoch_span, dt, {"epoch": epoch}, 0,
                        step=(epoch + 1) * steps, epoch=epoch,
                        builds=dispatch.attrs.get("builds", 0))
                    history.append(record)
                    if log_fn is not None:
                        log_fn(record)
            if es_armed and epoch + 1 < epochs:
                vals = self.evaluate_fused(state, eval_batcher)
                for i in range(n):
                    if not active[i]:
                        continue
                    score = score_fn(
                        {k: float(v[i]) for k, v in vals.items()})
                    ema[i] = (score if ema[i] is None else
                              es_alpha * score
                              + (1.0 - es_alpha) * ema[i])
                live = [ema[i] for i in range(n) if active[i]]
                best = max(v for v in live if v is not None)
                if epoch + 1 >= es_min_epochs:
                    for i in range(n):
                        if active[i] and ema[i] is not None and \
                                best - ema[i] > es_margin:
                            active[i] = False
                            stopped[i] = epoch + 1
            if epoch + 1 < epochs:
                preempt.maybe_yield()
        return state, history, active, stopped

    def evaluate_fused(self, state: TrainState,
                       batcher: data_lib.ArrayBatcher
                       ) -> Dict[str, np.ndarray]:
        """Per-config metric means: dict of (n_configs,) arrays."""
        if self._fused_eval is None:
            self._fused_eval = self._shared_step(
                "fused_eval", self._build_fused_eval_step)
        sums: Dict[str, Any] = {}
        counts: Dict[str, Any] = {}
        for step, batch in enumerate(self._device_feed(batcher, 0)):
            preempt.check_cancel()
            preempt.heartbeat(phase="evaluate_fused", step=step)
            metrics = self._fused_eval(state, batch)
            for k, (s, c) in metrics.items():
                sums[k] = sums.get(k, 0) + np.asarray(s, np.float64)
                counts[k] = counts.get(k, 0) + np.asarray(c, np.float64)
        return {k: sums[k] / np.maximum(counts[k], 1e-9) for k in sums}


class FusedSweepUnsupported(RuntimeError):
    """The fused sweep path cannot serve this cohort (e.g. the dataset
    exceeds the scan budget) — callers fall back to independent
    trials."""


# The per-chip peak tables moved to observability/perf.py (which adds
# HBM bandwidth and env overrides); re-exported here for back-compat.
_PEAK_FLOPS_BF16 = obs_perf.PEAK_FLOPS_BF16
peak_flops_per_chip = obs_perf.peak_flops_per_chip


def to_host(tree):
    """Device pytree -> host numpy, correct on multi-host pods.

    Replicated or locally-addressable arrays read directly; global
    arrays sharded across other processes go through a jitted identity
    with replicated out_shardings (a compiled all-gather) first.
    """
    def fetch(x):
        if isinstance(x, jax.Array) and not (
                x.is_fully_replicated or x.is_fully_addressable):
            x = _replicator(x.sharding.mesh)(x)
        return np.asarray(x)

    return jax.tree_util.tree_map(fetch, tree)


_REPLICATORS: Dict[Any, Callable] = {}


def _replicator(mesh):
    """One jitted identity-with-replicated-output per mesh, shared by
    every to_host leaf so XLA compiles each gather shape once."""
    fn = _REPLICATORS.get(mesh)
    if fn is None:
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(mesh, PartitionSpec())
        fn = _REPLICATORS[mesh] = jax.jit(lambda a: a, out_shardings=rep)
    return fn


def _nan_key(arrays) -> Optional[str]:
    """Which feed key an armed ``engine_step:nan`` fault poisons: the
    feature array if present, else the first floating non-mask leaf."""
    keys = [k for k, v in arrays.items()
            if k != data_lib.MASK_KEY and hasattr(v, "dtype") and
            jnp.issubdtype(v.dtype, jnp.floating)]
    if "x" in keys:
        return "x"
    return keys[0] if keys else None


def _poison_batch(batch):
    """One whole batch to NaN (per-step path). Multiply-by-NaN keeps
    the leaf's sharding/dtype — a device_put of a fresh array would
    land uncommitted."""
    key = _nan_key(batch)
    if key is None:
        return batch
    out = dict(batch)
    out[key] = out[key] * jnp.asarray(float("nan"), out[key].dtype)
    return out


def _poison_rows(arrays, rows: int):
    """First ``rows`` samples to NaN (scanned path) — a NEW array, the
    arena-cached staging entry is never mutated."""
    key = _nan_key(arrays)
    if key is None:
        return arrays
    out = dict(arrays)
    out[key] = out[key].at[:rows].mul(
        jnp.asarray(float("nan"), out[key].dtype))
    return out


def _inject_migration_fault() -> None:
    """Armed ``migration:*`` chaos fault fires at the top of the
    migration sequence (before any state moved) — an InjectedFault is
    an IOError subclass, so the job's transient-retry path absorbs it
    and the latched migrate request survives to the retry."""
    try:
        from learningorchestra_tpu.services import faults
    except Exception:  # noqa: BLE001
        return
    faults.maybe_inject("migration")


def _inject_resize_fault() -> None:
    """Armed ``autoscale_resize:*`` chaos fault fires inside an
    elastic resize's guarded region (before the slice is released) —
    the engine's rollback ladder keeps the job on its old slice and
    training continues; the autoscaler backs off before retrying
    (docs/RELIABILITY.md "Degradation ladder")."""
    try:
        from learningorchestra_tpu.services import faults
    except Exception:  # noqa: BLE001
        return
    faults.maybe_inject("autoscale_resize")


def _armed_nan() -> bool:
    """Armed ``engine_step:*:nan`` chaos fault? (services/faults.py;
    lazy import keeps runtime free of service-layer module deps)."""
    try:
        from learningorchestra_tpu.services import faults

        return faults.maybe_nan("engine_step")
    except Exception:  # noqa: BLE001
        return False


_SHUFFLE_TAG = 0x5348_5546  # "SHUF": domain-separates permutation keys


def _shuffle_rng(seed: int) -> jax.Array:
    """Shuffle-permutation key stream, domain-separated from the step
    (dropout) stream: ``PRNGKey(seed)`` folded with a constant tag, so
    fold_in(key, epoch) never collides with fold_in(step_key, step)
    even when both seeds are the same integer."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), _SHUFFLE_TAG)


def _total(weights):
    if weights is None:
        return jnp.asarray(1.0, jnp.float32)
    return jnp.sum(weights).astype(jnp.float32)


# ----------------------------------------------------------------------
# standard losses / metrics over (outputs, batch, weights)
# ----------------------------------------------------------------------
def _weighted_mean(values, weights):
    values = values.astype(jnp.float32)
    if weights is None:
        return jnp.mean(values)
    weights = weights.astype(jnp.float32)
    return jnp.sum(values * weights) / jnp.maximum(jnp.sum(weights), 1e-9)


def sparse_softmax_loss(outputs, batch, weights):
    labels = batch["y"].astype(jnp.int32)
    losses = optax.softmax_cross_entropy_with_integer_labels(
        outputs.astype(jnp.float32), labels)
    return _weighted_mean(losses, weights)


def sigmoid_binary_loss(outputs, batch, weights):
    labels = batch["y"].astype(jnp.float32)
    logits = outputs.astype(jnp.float32)
    if logits.ndim == labels.ndim + 1 and logits.shape[-1] == 1:
        logits = logits[..., 0]
    losses = optax.sigmoid_binary_cross_entropy(logits, labels)
    return _weighted_mean(losses, weights)


def mse_loss(outputs, batch, weights):
    preds = outputs.astype(jnp.float32)
    y = batch["y"].astype(jnp.float32)
    if preds.ndim == y.ndim + 1 and preds.shape[-1] == 1:
        preds = preds[..., 0]
    losses = jnp.mean(
        jnp.square(preds - y).reshape(preds.shape[0], -1), axis=-1)
    return _weighted_mean(losses, weights)


def _hard_predictions(outputs, batch):
    """(pred, y) as float32 class ids — argmax for multi-class heads,
    threshold-at-0 for single-logit heads (one decision rule shared by
    accuracy/precision/recall)."""
    logits = outputs.astype(jnp.float32)
    y = batch["y"]
    if logits.ndim >= 2 and logits.shape[-1] > 1:
        pred = jnp.argmax(logits, axis=-1).astype(jnp.float32)
    else:
        if logits.ndim == y.ndim + 1:
            logits = logits[..., 0]
        pred = (logits > 0).astype(jnp.float32)
    return pred, y.astype(jnp.float32)


def accuracy_metric(outputs, batch, weights):
    """Returns (correct_sum, count) for exact masked aggregation."""
    pred, y = _hard_predictions(outputs, batch)
    correct = (pred == y).astype(jnp.float32)
    if weights is None:
        return jnp.sum(correct), jnp.asarray(correct.size, jnp.float32)
    w = weights.astype(jnp.float32)
    return jnp.sum(correct * w), jnp.sum(w)


def _require_binary_head(outputs, metric: str) -> None:
    # shapes are static at trace time, so this raises at compile —
    # class-1-vs-rest on a >2-class head matches neither keras nor any
    # macro/micro average and must not be reported silently
    if outputs.ndim >= 2 and outputs.shape[-1] > 2:
        raise ValueError(
            f"metric {metric!r} is binary (positive = class 1); the "
            f"model head has {outputs.shape[-1]} classes — use "
            f"'accuracy' or a custom metric for multi-class")


def precision_metric(outputs, batch, weights):
    """Binary precision as an exact (sum, count) pair: TP over
    predicted-positive, positive = class 1 (keras Precision default)."""
    _require_binary_head(outputs, "precision")
    pred, y = _hard_predictions(outputs, batch)
    w = (jnp.ones_like(pred) if weights is None
         else weights.astype(jnp.float32))
    pred_pos = (pred == 1.0).astype(jnp.float32) * w
    tp = pred_pos * (y == 1.0).astype(jnp.float32)
    return jnp.sum(tp), jnp.sum(pred_pos)


def recall_metric(outputs, batch, weights):
    """Binary recall as an exact (sum, count) pair: TP over
    actual-positive, positive = class 1 (keras Recall default)."""
    _require_binary_head(outputs, "recall")
    pred, y = _hard_predictions(outputs, batch)
    w = (jnp.ones_like(pred) if weights is None
         else weights.astype(jnp.float32))
    actual_pos = (y == 1.0).astype(jnp.float32) * w
    tp = actual_pos * (pred == 1.0).astype(jnp.float32)
    return jnp.sum(tp), jnp.sum(actual_pos)
