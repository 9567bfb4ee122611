"""Driver ``train_fit_bd``: ``train_fit``'s one ``/train/tensorflow`` fit
job, for a block-diffusion expert model (``sdar_moe``).

The window, its two ends, the second job and ``epoch_tie`` are
``train_fit``'s, and its helpers are used as they are (loaded by name:
the harness finds a driver by its file). What differs: the token ids
stop short of ``MASK`` (the vocabulary's last id); the seed's weights
are ``benchmark/weights_sdar.py``'s tree; the reference is
``benchmark/reference/sdar_moe.py``, which follows the same steps under
the same noise; and two more numbers are compared: ``masked_tie``, the
masked positions of each checked epoch, which must EQUAL the
reference's (the noise is a pure function of seed, step, row and
position), and ``copies_gap``, the held experts' routed copies of each
layer and checked epoch (the program's epoch-record counters) against
the reference's, as a share of them. A mix's ``rehearsal`` object
carries the tiny model, since ``rehearsal.json`` holds a dense one.
"""

from __future__ import annotations

import gc
import json
import sys
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness

train_fit = harness.load_module("drivers", "train_fit")
epoch_means = train_fit.epoch_means
leaf_gaps = train_fit.leaf_gaps

DATA_CODE = """
import numpy as np
rng = np.random.default_rng({seed})
x = rng.integers(1, {mask_id}, size=({rows}, {seq}), dtype=np.int64)
response = {{"x": x.astype(np.int32)}}
"""


def token_rows(seed: int, rows: int, seq: int, vocab: int) -> np.ndarray:
    """The rows the sandboxed function makes: ids 1..vocab-2 (0 is
    padding to the loss, vocab-1 is MASK)."""
    rng = np.random.default_rng(seed)
    return rng.integers(1, vocab - 1, size=(rows, seq),
                        dtype=np.int64).astype(np.int32)


def install_weights(server, name: str, type_string: str, seed: int,
                    lm_kwargs: Dict[str, Any]) -> None:
    """``harness.install_weights`` for the ``sdar_moe`` tree."""
    from benchmark import weights_sdar
    from learningorchestra_tpu.models import LanguageModel

    lm = LanguageModel(**lm_kwargs)
    lm.params = weights_sdar.make_tree(seed, lm_kwargs)
    server.ctx.artifacts.save(lm, name, type_string)
    del lm
    gc.collect()


def read_final_state(server, job: str, seed: int, lm_kwargs) -> Dict:
    """``train_fit.read_final_state`` against ``weights_sdar``, a held
    expert a leaf (``leaf_norms``)."""
    import jax
    import jax.numpy as jnp

    from benchmark import weights_sdar

    inst = server.ctx.jobs.wait(job, timeout=60)
    state = inst._state
    mu = next(s.mu for s in jax.tree_util.tree_leaves(
        state.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
        if hasattr(s, "mu"))
    key = weights_sdar.seed_key(seed)
    out: Dict[str, Dict[str, float]] = {"mu_norm": {}, "change_norm": {}}
    for path, shape, kind in weights_sdar.leaf_table(lm_kwargs):
        node, m = state.params, mu
        for part in path:
            node, m = node[part], m[part]
        name = "/".join(path)
        out["mu_norm"].update(leaf_norms({name: m.astype(jnp.float32)}))
        out["change_norm"].update(leaf_norms({
            name: node.astype(jnp.float32)
            - weights_sdar.make_leaf(key, path, shape, kind)}))
    for leaf in jax.tree_util.tree_leaves(state):
        leaf.delete()
    inst._state = None
    return out


def leaf_norms(flat: Dict[str, Any]) -> Dict[str, float]:
    """``sdar_moe.leaf_norms`` of the program's leaves, on the device:
    a stack of held experts gives one norm an expert, ``a/b/c#j``."""
    import jax.numpy as jnp

    out: Dict[str, float] = {}
    for name, leaf in flat.items():
        if "/experts/" in name:
            norms = jnp.sqrt(jnp.sum(jnp.square(leaf), axis=(1, 2)))
            out.update({f"{name}#{j}": float(n)
                        for j, n in enumerate(norms)})
        else:
            out[name] = float(jnp.sqrt(jnp.sum(jnp.square(leaf))))
    return out


def record_counters(records: List[Dict[str, Any]], epochs: int,
                    layers: int) -> Dict[str, Any]:
    """The program's counters of the first ``epochs`` epoch records:
    ``copies`` (epochs, layers), ``busiest`` (epochs, layers), ``masked``
    (epochs,), each a mean over the epoch's steps; a record or counter
    that is missing is no number."""
    nan = float("nan")

    def get(i, key):
        return float(records[i].get(key, nan)) if i < len(records) else nan

    return {
        "copies": [[get(i, f"moeHeldCopies_l{j}") for j in range(layers)]
                   for i in range(epochs)],
        "busiest": [[get(i, f"moeBusiestCopies_l{j}")
                     for j in range(layers)] for i in range(epochs)],
        "masked": [get(i, "maskedPositions") for i in range(epochs)]}


def reference_counters(ref: Dict[str, Any], epochs: int) -> Dict[str, Any]:
    """The same three from ``sdar_moe.follow_steps``' result."""
    copies = np.asarray(ref["copies"], np.float64)   # (steps, layers, held)
    per = copies.shape[0] // epochs
    by_epoch = copies.reshape(epochs, per, *copies.shape[1:])
    masked = np.asarray(ref["masked"], np.float64).reshape(epochs, per)
    return {"copies": by_epoch.sum(-1).mean(1).tolist(),
            "busiest": by_epoch.max(-1).mean(1).tolist(),
            "masked": masked.mean(1).tolist()}


def compare(prog: Dict[str, Any], ref: Dict[str, Any],
            limits: Dict[str, float]):
    """``train_fit.compare`` and the counters. ``prog`` also carries
    ``counters`` (``record_counters``'s shape)."""
    numbers, readings = train_fit.compare(prog, ref, limits)
    epochs = len(prog["losses"])
    want = reference_counters(ref, epochs)
    got = prog["counters"]
    gaps = [abs(g - w) / w for ge, we in zip(got["copies"], want["copies"])
            for g, w in zip(ge, we)]
    nan = float("nan")
    readings["copies_gap"] = nan if any(g != g for g in gaps) else max(gaps)
    ties = [abs(g - w) for g, w in zip(got["masked"], want["masked"])]
    readings["masked_tie"] = nan if any(t != t for t in ties) else max(ties)
    for k in ("copies_gap", "masked_tie"):
        if k in limits:
            numbers[k] = (readings[k], limits[k])
    return numbers, readings


def run(run_ctx) -> Dict[str, Any]:
    p = run_ctx.params
    if run_ctx.rehearsal:
        run_ctx.lm_kwargs = dict(p["language_model"])
    lm_kwargs = run_ctx.lm_kwargs
    seed = run_ctx.seed
    steps, batch, seq = p["steps_per_epoch"], p["batch_size"], p["seq"]
    rows = steps * batch
    layers = int(lm_kwargs["n_layers"])
    server = run_ctx.server
    _job_documents = train_fit._job_documents

    # -- set-up --------------------------------------------------------
    server.call("POST", "/function/python", {
        "name": "bench_data", "functionParameters": {},
        "function": DATA_CODE.format(
            seed=seed, rows=rows, seq=seq,
            mask_id=lm_kwargs["vocab_size"] - 1)})
    server.wait_finished("/function/python/bench_data")
    server.call("POST", "/model/tensorflow", {
        "modelName": "bench_model",
        "modulePath": "learningorchestra_tpu.models",
        "class": "LanguageModel", "classParameters": lm_kwargs})
    server.wait_finished("/model/tensorflow/bench_model")
    install_weights(server, "bench_model", "model/tensorflow", seed,
                    lm_kwargs)
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
    open_epochs = seen
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
    tokens = epochs_done * steps * batch * seq    # ROW tokens
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
    prog["counters"] = record_counters(check_records, check_epochs, layers)
    window_counters = record_counters(records[open_epochs:seen],
                                      epochs_done, layers)
    spans = run_ctx.job_spans("bench_window")
    run_ctx.shutdown_program()
    print(f"window: {epochs_done} epochs, {tokens} row tokens in "
          f"{window_s:.3f}s = {tokens / window_s:.1f} tokens/s; epoch "
          f"seconds {[r.get('epochSeconds') for r in records[:6]]}; "
          f"peak {memory}; check job {check_compiles}", file=sys.stderr,
          flush=True)

    # -- the plain reference follows the same steps, same noise --------
    from benchmark.reference import sdar_moe

    data = token_rows(seed, rows, seq, lm_kwargs["vocab_size"])
    batches = np.concatenate([data.reshape(steps, batch, seq)] * check_epochs)
    t_ref = time.monotonic()
    ref = sdar_moe.follow_steps(seed, lm_kwargs, run_ctx.eps, batches,
                                p["optimizer"], fit_seed=int(p["fit_seed"]))
    reference_s = time.monotonic() - t_ref
    numbers, readings = compare(prog, ref, p["limits"])
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
            "readings": readings,
            # the window's epochs: per epoch and layer, means over steps
            "moe_counters": window_counters,
        },
    }
