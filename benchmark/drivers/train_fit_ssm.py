"""Driver ``train_fit_ssm``: ``train_fit``'s one ``/train/tensorflow`` fit
job, for a hybrid state-space model (``granitemoehybrid``: Mamba-2
mixers beside attention layers, one tied table).

The window, its two ends, the second job and ``epoch_tie`` are
``train_fit``'s, and its helpers are used as they are (loaded by name:
the harness finds a driver by its file). What differs: the seed's
weights are ``benchmark/weights_granite.py``'s tree
(``harness.install_weights`` hard-wires ``weights.py``, so ``run``'s
warm-up and window loop are ``train_fit``'s copied, as
``train_fit_bd``'s are: PERF.md Q11); the reference is
``benchmark/reference/granite_hybrid.py``, which steps the recurrence a
position at a time. A mix's ``rehearsal`` object carries the tiny
model, since ``rehearsal.json`` holds a dense one.

What ``correct`` compares, each number with ONE job (the mix's
``limits`` names the compared ones and ``limits_from`` the readings each
limit lies between; every other reading is printed and not judged):

* the mathematics of a step: ``loss_epoch0_rel`` (for half of the
  targets left out, and for the gate left out).
* precision: ``loss_epoch1_rel`` (for the fp8 control).
* a leaf left unmoved or moved wrongly: ``change_norm_gap``; the first
  moment: ``mu_norm_gap`` (``train_fit.compare``).
* the executables: ``epoch_tie``, exact.
* the carried state: ``state_rms_gap``, the epoch records' counter
  ``ssmStateRms_l<i>`` (the RMS of the states each Mamba-2 layer holds
  at the rows' end, a mean over the epoch's steps) against the
  reference's, as a share of it, by the worst layer of the FIRST epoch:
  for a state dropped at the chunk boundaries. ``decay_mean_gap`` (the
  counter ``ssmDecayMean_l<i>`` likewise) is printed.

What the run says of itself on standard error, judged by nothing: where
its set-up went (``setup_parts``: the driver's own phases, and what the
accepted set-up readers find in the window job's spans, which
``BENCHMARK.json`` lists with the dense cell alone: PERF.md Q11), and
the longest wait between two epoch records of the window
(``record_gaps``), so that a run the host stalled explains itself
(PERF.md Q15).
"""

from __future__ import annotations

import gc
import json
import statistics
import sys
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness

train_fit = harness.load_module("drivers", "train_fit")
epoch_means = train_fit.epoch_means
token_rows = train_fit.token_rows


def install_weights(server, name: str, type_string: str, seed: int,
                    lm_kwargs: Dict[str, Any]) -> None:
    """``harness.install_weights`` for the ``granitemoehybrid`` tree."""
    from benchmark import weights_granite
    from learningorchestra_tpu.models import LanguageModel

    lm = LanguageModel(**lm_kwargs)
    lm.params = weights_granite.make_tree(seed, lm_kwargs)
    server.ctx.artifacts.save(lm, name, type_string)
    del lm
    gc.collect()


def _norm(a) -> float:
    import jax.numpy as jnp

    return float(jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))))


def read_final_state(server, job: str, seed: int, lm_kwargs) -> Dict:
    """``train_fit.read_final_state`` against ``weights_granite``."""
    import jax

    from benchmark import weights_granite

    inst = server.ctx.jobs.wait(job, timeout=60)
    state = inst._state
    mu = next(s.mu for s in jax.tree_util.tree_leaves(
        state.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
        if hasattr(s, "mu"))
    key = weights_granite.seed_key(seed)
    out: Dict[str, Dict[str, float]] = {"mu_norm": {}, "change_norm": {}}
    for path, shape, kind in weights_granite.leaf_table(lm_kwargs):
        node, m = state.params, mu
        for part in path:
            node, m = node[part], m[part]
        name = "/".join(path)
        out["mu_norm"][name] = _norm(m)
        out["change_norm"][name] = _norm(
            node - weights_granite.make_leaf(key, path, shape, kind))
    for leaf in jax.tree_util.tree_leaves(state):
        leaf.delete()
    inst._state = None
    return out


def record_counters(records: List[Dict[str, Any]], epochs: int,
                    mamba_layers: List[int]) -> Dict[str, Any]:
    """The program's counters of the first ``epochs`` epoch records:
    ``state_rms`` and ``decay_mean`` (epochs, Mamba-2 layers), each a
    mean over the epoch's steps; a record or counter that is missing is
    no number."""
    nan = float("nan")

    def get(i, key):
        return float(records[i].get(key, nan)) if i < len(records) else nan

    return {
        "state_rms": [[get(i, f"ssmStateRms_l{j}") for j in mamba_layers]
                      for i in range(epochs)],
        "decay_mean": [[get(i, f"ssmDecayMean_l{j}") for j in mamba_layers]
                       for i in range(epochs)]}


def reference_counters(ref: Dict[str, Any], epochs: int) -> Dict[str, Any]:
    """The same two from ``granite_hybrid.follow_steps``' result."""
    out = {}
    for name in ("state_rms", "decay_mean"):
        v = np.asarray(ref[name], np.float64)       # (steps, layers)
        out[name] = v.reshape(epochs, -1, v.shape[1]).mean(1).tolist()
    return out


def compare(prog: Dict[str, Any], ref: Dict[str, Any],
            limits: Dict[str, float]):
    """``train_fit.compare`` and what is this cell's own. ``prog`` also
    carries ``counters`` (``record_counters``'s shape)."""
    numbers, readings = train_fit.compare(prog, ref, limits)
    nan = float("nan")
    want = reference_counters(ref, len(prog["losses"]))
    got = prog["counters"]

    def worst(values):
        values = [abs(v) for v in values]
        return nan if any(v != v for v in values) or not values \
            else max(values)

    for name in ("state_rms", "decay_mean"):
        gaps = [[(g - w) / w for g, w in zip(ge, we)]
                for ge, we in zip(got[name], want[name])]
        readings[f"{name}_gaps"] = gaps
        for i, e in enumerate(gaps):
            readings[f"{name}_gap_epoch{i}"] = worst(e)
    # the first epoch's: three updates at most lie before its last step
    readings["state_rms_gap"] = readings["state_rms_gap_epoch0"]
    readings["decay_mean_gap"] = readings["decay_mean_gap_epoch0"]
    for k, v in readings.items():
        if k in limits and k not in numbers:
            numbers[k] = (v, limits[k])
    return numbers, readings


def raw_readings(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """Both sides of ``compare`` as JSON takes them, so that a kept line
    can be judged again under other limits without the chip."""
    return {"prog": prog, "ref": ref}


def in_the_programs_place(alt: Dict[str, Any], epochs: int) -> Dict[str, Any]:
    """``follow_steps``' result as ``compare``'s ``prog``: a variant of
    the reference where the program's readings go."""
    return {"losses": epoch_means(alt["losses"], epochs),
            "mu_norm": alt["mu_norm"], "change_norm": alt["change_norm"],
            "counters": reference_counters(alt, epochs)}


SETUP_READERS = ("compile_count.train", "compile_s.train",
                 "artifact_load_s.train", "fit_setup_s.train")


def setup_parts(phases: Dict[str, float],
                spans: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Where ``setup_s`` went: the driver's phases by its own clock and
    the accepted set-up readers' values on the window job's spans (a
    reader that finds nothing is left out)."""
    reading = {"facts": {"spans": spans}}
    out: Dict[str, Any] = {k: round(v, 3) for k, v in phases.items()}
    for name in SETUP_READERS:
        value = harness.load_module("layer_metrics", name).read(reading)
        if value is not None:
            out[name] = round(value, 3)
    return out


def record_gaps(t_open: float, seen_at: List[float]) -> Dict[str, float]:
    """The waits between the polls that found a new epoch record in the
    window, which opens at one: the longest, the median, and how far
    the longest stands over the median (an epoch's length, plus a poll
    at most, where nothing stalled)."""
    stamps = [t_open] + list(seen_at)
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    if not gaps:
        return {}
    longest, median = max(gaps), statistics.median(gaps)
    return {"record_gap_max_s": longest, "record_gap_median_s": median,
            "record_gap_excess_s": longest - median}


def run(run_ctx) -> Dict[str, Any]:
    p = run_ctx.params
    if run_ctx.rehearsal:
        run_ctx.lm_kwargs = dict(p["language_model"])
    lm_kwargs = run_ctx.lm_kwargs
    seed = run_ctx.seed
    steps, batch, seq = p["steps_per_epoch"], p["batch_size"], p["seq"]
    rows = steps * batch
    mamba_layers = [i for i, t in enumerate(lm_kwargs["layer_types"])
                    if t == "mamba"]
    server = run_ctx.server
    _job_documents = train_fit._job_documents

    # -- set-up --------------------------------------------------------
    phases: Dict[str, float] = {}
    t_run = t_phase = time.monotonic()

    def phase(name: str) -> None:
        nonlocal t_phase
        now = time.monotonic()
        phases[name] = now - t_phase
        t_phase = now

    server.call("POST", "/function/python", {
        "name": "bench_data", "functionParameters": {},
        "function": train_fit.DATA_CODE.format(
            seed=seed, rows=rows, seq=seq, vocab=lm_kwargs["vocab_size"])})
    server.wait_finished("/function/python/bench_data")
    phase("data_s")
    server.call("POST", "/model/tensorflow", {
        "modelName": "bench_model",
        "modulePath": "learningorchestra_tpu.models",
        "class": "LanguageModel", "classParameters": lm_kwargs})
    server.wait_finished("/model/tensorflow/bench_model")
    phase("model_s")
    install_weights(server, "bench_model", "model/tensorflow", seed,
                    lm_kwargs)
    phase("weights_s")
    train_fit._submit_fit(server, "bench_window", p, epochs=1_000_000)
    warm = int(p["warm_epochs"])
    check_epochs = int(p["check_epochs"])
    if check_epochs > warm:
        raise ValueError("check_epochs epochs of the window job are tied "
                         "to the check job's: warm_epochs must cover them")
    poll = float(p["poll_seconds"])
    seen = 0
    deadline = time.monotonic() + 1500
    while seen < warm:
        if time.monotonic() > deadline:
            raise TimeoutError("warm-up epochs never finished")
        time.sleep(poll)
        records, ended = _job_documents(server, "bench_window")
        if ended:
            raise RuntimeError("the window's job ended in its warm-up: "
                               f"{records[-1:]}")
        seen = len(records)

    # -- window (train_fit's rules: both ends are epoch boundaries) ----
    t_open = time.monotonic()
    run_ctx.open_window(t_open)
    phase("fit_to_window_s")
    # imports, the device and the server, before this function
    phases = dict(before_driver_s=run_ctx.setup_s - (t_open - t_run),
                  **phases)
    open_epochs = seen
    seen_at: List[float] = []
    profile = run_ctx.profile
    traced = False
    died = False
    t_give_up = t_open + run_ctx.seconds + train_fit.WINDOW_SLACK_S
    while True:
        time.sleep(poll)
        now = time.monotonic()
        if profile is not None and not traced and \
                now - t_open >= 0.25 * run_ctx.seconds:
            profile.start()
            traced = True
        if profile is not None and traced and profile.t1 == 0.0 and \
                now - profile.t0 >= float(p["trace_seconds"]):
            profile.stop()
        records, ended = _job_documents(server, "bench_window")
        if len(records) > seen:
            seen_at.append(time.monotonic())
            seen = len(records)
            if time.monotonic() - t_open >= run_ctx.seconds:
                break
        if ended or now > t_give_up:
            died = True
            break
    t_close = time.monotonic()
    if profile is not None and traced and profile.t1 == 0.0:
        profile.stop()
    in_window = run_ctx.close_window(t_close)
    server.call("DELETE", "/train/tensorflow/bench_window/run",
                ok=(200, 201, 404, 406, 409) if died else (200, 201))
    train_fit._wait_terminal(server, "bench_window")
    epochs_done = seen - open_epochs
    window_s = t_close - t_open
    tokens = epochs_done * steps * batch * seq
    records = train_fit._epoch_records(server, "bench_window")
    memory = run_ctx.device.memory()

    # -- the checked epochs: the same call again, to its end -----------
    run_ctx.compiles.mark()
    train_fit._submit_fit(server, "bench_check", p, epochs=check_epochs)
    prog: Dict[str, Any] = {"mu_norm": {}, "change_norm": {}}
    try:
        server.wait_finished("/train/tensorflow/bench_check", timeout=900)
        prog = read_final_state(server, "bench_check", seed, lm_kwargs)
    except (RuntimeError, TimeoutError) as e:
        print(f"the check job failed: {e}", file=sys.stderr, flush=True)
    check_compiles = run_ctx.compiles.since()
    check_records = train_fit._epoch_records(server, "bench_check")
    prog["losses"] = train_fit._losses(check_records, check_epochs)
    prog["window_losses"] = train_fit._losses(records, check_epochs)
    prog["counters"] = record_counters(check_records, check_epochs,
                                       mamba_layers)
    window_counters = record_counters(records[open_epochs:seen],
                                      epochs_done, mamba_layers)
    spans = run_ctx.job_spans("bench_window")
    run_ctx.shutdown_program()
    gaps = record_gaps(t_open, seen_at)
    print(f"window: {epochs_done} epochs, {tokens} tokens in "
          f"{window_s:.3f}s = {tokens / window_s:.1f} tokens/s; epoch "
          f"seconds {[r.get('epochSeconds') for r in records[:6]]}; "
          f"peak {memory}; check job {check_compiles}", file=sys.stderr,
          flush=True)
    print("set-up: " + json.dumps(setup_parts(phases, spans)) + "; waits "
          "between epoch records: " + json.dumps(gaps), file=sys.stderr,
          flush=True)

    # -- the plain reference follows the same steps --------------------
    from benchmark.reference import granite_hybrid

    data = token_rows(seed, rows, seq, lm_kwargs["vocab_size"])
    # shuffle is off: every epoch takes the same rows in the same order
    batches = np.concatenate([data.reshape(steps, batch, seq)] * check_epochs)
    t_ref = time.monotonic()
    ref = granite_hybrid.follow_steps(seed, lm_kwargs, run_ctx.eps, batches,
                                      p["optimizer"])
    reference_s = time.monotonic() - t_ref
    numbers, readings = compare(prog, ref, p["limits"])
    print("raw: " + json.dumps(raw_readings(prog, ref)), file=sys.stderr,
          flush=True)
    print("readings: " + json.dumps(readings), file=sys.stderr, flush=True)

    return {
        "attempted": epochs_done * steps + int(died), "failed": int(died),
        "numbers": numbers,
        "end_to_end": {"train_tokens_per_s": tokens / window_s},
        "memory": memory,
        "facts": {
            "window_s": window_s, "tokens": tokens,
            "epochs_in_window": epochs_done, "steps": steps,
            "batch": batch, "seq": seq, "spans": spans,
            "compiles_in_window": in_window["compiles"],
            "reference_s": reference_s,
            "check_job_compiles": check_compiles["compiles"],
            "check_job_cache_misses": check_compiles["cache_misses"],
            "epoch_seconds": [r.get("epochSeconds")
                              for r in records[open_epochs:seen]],
            "program_module": "epoch_fn",
            **gaps,
            "readings": readings,
            # the window's epochs: per epoch and Mamba-2 layer, means
            # over the epoch's steps
            "ssm_counters": window_counters,
        },
    }
