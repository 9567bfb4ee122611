"""Driver ``train_fit``: one ``/train/tensorflow`` fit job, timed by
its epoch records against this process's clock.

Set-up: a sandboxed ``/function/python`` synthesises the token rows
from the seed, ``/model/tensorflow`` creates the model, the seed's
weights are installed as that artifact, and the window's job is
submitted. Its first epochs are the warm-up. The window opens when
the last warm-up epoch's record is seen and closes when the first
record at or past ``--seconds`` is seen: both ends are epoch
boundaries, and the rate is all steps between them over all the time
between them. The job is then cancelled.

A fit dispatches its scanned epoch program under two input signatures
(PERF.md F7): epoch 0 takes the optimizer's state as ``init`` made it,
every later epoch takes it as the program returned it, placed on the
mesh. The window runs the second only. ``correct`` (after the window)
therefore crosses both: a second job, the same call on the same
artifact and rows with ``epochs: check_epochs`` (2), runs to its end,
which is the only way the REST path shows an optimizer's state. Each of
its epochs' losses must equal the window job's loss of the same epoch
exactly, which ties the checked steps to the executables the window
job holds. The plain reference then follows those steps from the seed,
and every epoch's loss, Adam's first moment and the parameters' change
after the last step (both returned by the second executable) are
compared.
"""

from __future__ import annotations

import json
import statistics
import sys
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness

# an epoch is a second or so: a job from which no epoch record has come
# this long past the window's length is lost, and the run goes on to
# its end without it (a run is allowed its window and a minute)
WINDOW_SLACK_S = 60.0
TERMINAL = ("cancelled", "timedOut", "deadLettered", "finished", "failed")

DATA_CODE = """
import numpy as np
rng = np.random.default_rng({seed})
x = rng.integers(1, {vocab}, size=({rows}, {seq}), dtype=np.int64)
response = {{"x": x.astype(np.int32)}}
"""


def token_rows(seed: int, rows: int, seq: int, vocab: int) -> np.ndarray:
    """The rows the sandboxed function makes (ids 1..vocab-1: id 0 is
    padding to the loss), made again here for the reference."""
    rng = np.random.default_rng(seed)
    return rng.integers(1, vocab, size=(rows, seq),
                        dtype=np.int64).astype(np.int32)


def _job_documents(server, job: str):
    """(epoch records so far, whether the job has ended)."""
    body = server.call("GET", f"/train/tensorflow/{job}?limit=100000")
    docs = [d for d in body.get("result") or [] if isinstance(d, dict)]
    meta = body.get("metadata") or {}
    ended = bool(meta.get("finished")) or meta.get("status") in TERMINAL \
        or any(d.get("exception") for d in docs)
    return [d["epochRecord"] for d in docs if "epochRecord" in d], ended


def _epoch_records(server, job: str) -> List[Dict[str, Any]]:
    return _job_documents(server, job)[0]


def _submit_fit(server, job: str, p: Dict[str, Any], epochs: int) -> None:
    params = dict(p["fit"], x="$bench_data.x", batch_size=p["batch_size"],
                  epochs=epochs)
    server.call("POST", "/train/tensorflow", {
        "name": job, "modelName": "bench_model", "method": "fit",
        "methodParameters": params})


def _wait_terminal(server, job: str, timeout: float = 300.0) -> None:
    deadline = time.monotonic() + timeout
    while not _job_documents(server, job)[1]:
        if time.monotonic() > deadline:
            raise TimeoutError(f"job {job} did not end")
        time.sleep(0.1)


def _leaf_norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                        for k in path)
        out[name] = float(jnp.sqrt(jnp.sum(jnp.square(
            leaf.astype(jnp.float32)))))
    return out


def read_final_state(server, job: str, seed: int, lm_kwargs) -> Dict:
    """Per-leaf norms of Adam's first moment and of the parameters'
    change, read from the finished job's own state."""
    import jax
    import jax.numpy as jnp

    from benchmark import weights

    inst = server.ctx.jobs.wait(job, timeout=60)
    state = inst._state
    mu = next(s.mu for s in jax.tree_util.tree_leaves(
        state.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
        if hasattr(s, "mu"))
    key = weights.seed_key(seed)
    change = {}
    for path, shape, kind in weights.leaf_table(lm_kwargs):
        node = state.params
        for part in path:
            node = node[part]
        start = weights.make_leaf(key, path, shape, kind)
        change["/".join(path)] = float(jnp.sqrt(jnp.sum(jnp.square(
            node.astype(jnp.float32) - start))))
    out = {"mu_norm": _leaf_norms(mu), "change_norm": change}
    # the finished job's state (16 bytes a parameter) must not outlive
    # this reading: the reference needs the room
    for leaf in jax.tree_util.tree_leaves(state):
        leaf.delete()
    inst._state = None
    return out


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              floor: float = 0.0, keep=None) -> Dict[str, float]:
    """For each leaf, the gap between its norm in the program and in
    the reference, against the reference's norm of that leaf, or
    ``floor`` where that is larger."""
    return {name: abs(prog[name] - r) / max(r, floor, 1e-30)
            for name, r in ref.items() if keep is None or name in keep}


def worst_gap(prog: Dict[str, float], ref: Dict[str, float],
              keep=None, own: bool = False) -> float:
    """The widest leaf gap. Each leaf is measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger; with ``own`` against its own norm alone, so that a small
    leaf (a norm's scale) is not held to a matrix's."""
    floor = 0.0 if own else statistics.median(ref.values())
    return max(leaf_gaps(prog, ref, floor, keep).values())


def epoch_means(step_losses: List[float], epochs: int) -> List[float]:
    per = len(step_losses) // epochs
    return [statistics.fmean(step_losses[i * per:(i + 1) * per])
            for i in range(epochs)]


def compare(prog: Dict[str, Any], ref: Dict[str, Any],
            limits: Dict[str, float]):
    """(numbers, readings). ``numbers``: what ``correct`` is decided
    on, each beside its limit: every reading that ``limits`` names.
    ``readings``: all of them, and the leaf that read worst.

    ``prog``: ``losses`` (one per checked epoch), ``mu_norm`` and
    ``change_norm`` per leaf, and ``window_losses`` where a window job
    ran the same epochs. ``ref``: ``follow_steps``' result."""
    nan = float("nan")
    epochs = len(prog["losses"])
    ref_losses = epoch_means(ref["losses"], epochs)
    readings = {f"loss_epoch{i}_rel": abs(got - want) / want
                for i, (got, want) in enumerate(zip(prog["losses"],
                                                    ref_losses))}
    # leaves whose gradient is nought to rounding in the reference move
    # under Adam by round-off alone: left out of the change by the rule
    # "first moment under a thousandth of the median leaf's"
    median_mu = statistics.median(ref["mu_norm"].values())
    moved = {k for k, v in ref["mu_norm"].items() if v >= 1e-3 * median_mu}
    worst = {}
    for name, norms, keep in (("mu_norm_gap", "mu_norm", None),
                              ("change_norm_gap", "change_norm", moved)):
        if not prog.get(norms):
            readings[name] = readings[name + "_own"] = nan
            continue
        readings[name] = worst_gap(prog[norms], ref[norms], keep)
        own = leaf_gaps(prog[norms], ref[norms], 0.0, keep)
        worst[name + "_own"] = max(own, key=own.get)
        readings[name + "_own"] = own[worst[name + "_own"]]
    if "window_losses" in prog:
        tie = 0.0 if len(prog["window_losses"]) == epochs else nan
        for a, b in zip(prog["window_losses"], prog["losses"]):
            gap = abs(a - b)
            if gap != gap or gap > tie:  # a loss that is no number stays
                tie = gap
        readings["epoch_tie"] = tie
    numbers = {k: (v, limits[k]) for k, v in readings.items()
               if k in limits}
    return numbers, dict(readings, worst_leaf=worst)


def _losses(records: List[Dict[str, Any]], epochs: int) -> List[float]:
    """The first ``epochs`` epoch losses; one that is missing is no
    number, and fails whatever it is compared with."""
    got = [float(r["loss"]) for r in records[:epochs]]
    return got + [float("nan")] * (epochs - len(got))


def run(run_ctx) -> Dict[str, Any]:
    p = run_ctx.params
    lm_kwargs = run_ctx.lm_kwargs
    seed = run_ctx.seed
    steps, batch, seq = p["steps_per_epoch"], p["batch_size"], p["seq"]
    rows = steps * batch
    server = run_ctx.server

    # -- set-up --------------------------------------------------------
    server.call("POST", "/function/python", {
        "name": "bench_data", "functionParameters": {},
        "function": DATA_CODE.format(seed=seed, rows=rows, seq=seq,
                                     vocab=lm_kwargs["vocab_size"])})
    server.wait_finished("/function/python/bench_data")
    server.call("POST", "/model/tensorflow", {
        "modelName": "bench_model",
        "modulePath": "learningorchestra_tpu.models",
        "class": "LanguageModel", "classParameters": lm_kwargs})
    server.wait_finished("/model/tensorflow/bench_model")
    harness.install_weights(server, "bench_model", "model/tensorflow",
                            seed, lm_kwargs)
    _submit_fit(server, "bench_window", p, epochs=1_000_000)
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

    # -- window --------------------------------------------------------
    t_open = time.monotonic()
    run_ctx.open_window(t_open)
    open_epochs = seen
    profile = run_ctx.profile
    traced = False
    died = False
    t_give_up = t_open + run_ctx.seconds + WINDOW_SLACK_S
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
    _wait_terminal(server, "bench_window")
    epochs_done = seen - open_epochs
    window_s = t_close - t_open
    tokens = epochs_done * steps * batch * seq
    records = _epoch_records(server, "bench_window")
    memory = run_ctx.device.memory()

    # -- the checked epochs: the same call again, to its end -----------
    run_ctx.compiles.mark()
    _submit_fit(server, "bench_check", p, epochs=check_epochs)
    prog: Dict[str, Any] = {"mu_norm": {}, "change_norm": {}}
    try:
        server.wait_finished("/train/tensorflow/bench_check", timeout=900)
        prog = read_final_state(server, "bench_check", seed, lm_kwargs)
    except (RuntimeError, TimeoutError) as e:
        print(f"the check job failed: {e}", file=sys.stderr, flush=True)
    check_compiles = run_ctx.compiles.since()
    prog["losses"] = _losses(_epoch_records(server, "bench_check"),
                             check_epochs)
    prog["window_losses"] = _losses(records, check_epochs)
    spans = run_ctx.job_spans("bench_window")
    run_ctx.shutdown_program()
    print(f"window: {epochs_done} epochs, {tokens} tokens in "
          f"{window_s:.3f}s = {tokens / window_s:.1f} tokens/s; epoch "
          f"seconds {[r.get('epochSeconds') for r in records[:6]]}; "
          f"peak {memory}; check job {check_compiles}", file=sys.stderr,
          flush=True)

    # -- the plain reference follows the same steps --------------------
    from benchmark.reference import decoder

    data = token_rows(seed, rows, seq, lm_kwargs["vocab_size"])
    # shuffle is off: every epoch takes the same rows in the same order
    batches = np.concatenate([data.reshape(steps, batch, seq)] * check_epochs)
    t_ref = time.monotonic()
    ref = decoder.follow_steps(seed, lm_kwargs, run_ctx.eps, batches,
                               p["optimizer"])
    reference_s = time.monotonic() - t_ref
    numbers, readings = compare(prog, ref, p["limits"])
    print("readings: " + json.dumps(readings), file=sys.stderr, flush=True)

    return {
        # the unit of work is a step; a job lost inside the window is
        # one more, attempted and failed
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
        },
    }
