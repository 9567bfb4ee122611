"""Chip smoke: the REST train -> serve path, end to end, on one TPU chip.

``python chip_smoke.py`` starts a real ``RestServer`` in this process
and drives it over HTTP the way a user would: a sandboxed
``/function/python`` synthesises token streams, ``/model/tensorflow``
creates the widest ``LanguageModel`` the repo documents for itself,
``/train/tensorflow`` fits it with ``"checkpoint": true`` and a PATCH
resumes it from the saved step, ``/serve`` answers greedy requests on
the slot and the paged KV layouts (bit-identical to
``LanguageModel.generate``), and ``/observability/perf`` names the
device and its peak. Any phase that raises fails the run.

``python chip_smoke.py --chips 4`` runs ONLY the four-chip phase: the
same LM at seq 2048 for a few steps on one device and on the
``auto`` (dp=4), ``fsdp=2,tp=2`` and ``fsdp=2,sp=2`` (ring, then
Ulysses, over the flash kernel) meshes, comparing per-step losses and
checking every device holds parameter and batch shards.

One process holds the chip. Every earlier stdout line is one JSON
object per phase; the LAST line is
``{"ok": true, "device": {"platform", "kind", "count"}}``. Without an
accelerator the script exits non-zero and prints no result line.
``--size tiny`` is the CPU rehearsal of the control flow (it still
refuses to run without an accelerator unless a test bypasses
``require_accelerator``); numbers printed here are smoke, not
measurements.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

SIZES = {
    # the widest LM the repo documents for itself (266.9M parameters
    # with the 3-matrix MLP): no width or depth cut
    "full": {"model": {"vocab_size": 32000, "d_model": 1024,
                       "n_layers": 12, "n_heads": 16, "d_ff": 4096,
                       "max_len": 1024},
             "n_seq": 64, "batch": 16, "prompt": 32, "new_tokens": 16,
             "mesh_seq": 2048, "mesh_batch": 4},
    # CPU rehearsal of the control flow only
    "tiny": {"model": {"vocab_size": 256, "d_model": 64, "n_layers": 2,
                       "n_heads": 4, "d_ff": 128, "max_len": 64},
             "n_seq": 32, "batch": 8, "prompt": 8, "new_tokens": 4,
             "mesh_seq": 64, "mesh_batch": 4},
}
# per-step loss agreement between a mesh and the one-device run: the
# compute dtype is bf16 (8 mantissa bits) and each mesh sums partial
# products in a different order, so steps agree to ~1e-2 relative
MESH_LOSS_RTOL = 2e-2


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require_accelerator():
    """The first thing the run does: no TPU, no smoke."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, jax found "
                 f"{dev.platform!r} ({dev.device_kind}); nothing ran")
    return dev


class CacheCounter:
    """Counts jax's persistent-compilation-cache hit/miss events."""

    def __init__(self):
        import jax

        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"hits": self.hits, "misses": self.misses}


def _versions() -> dict:
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu", "orbax-checkpoint", "flax"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def _peak_hbm(devices) -> list:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def affine_tokens(rng, n: int, seq: int, vocab: int):
    """Learnable streams: an affine next-token map from a random start
    per sequence."""
    import numpy as np

    start = rng.integers(0, vocab, size=(n, 1))
    return ((start + 97 * np.arange(seq)[None, :]) % vocab).astype(
        np.int32)


def synth_code(n: int, seq: int, vocab: int, seed: int) -> str:
    # affine_tokens as source text: it runs in the sandbox child
    return f"""
import numpy as np
rng = np.random.default_rng({seed})
n, seq, vocab = {n}, {seq}, {vocab}
start = rng.integers(0, vocab, size=(n, 1))
steps = np.arange(seq, dtype=np.int64)[None, :]
x = ((start + 97 * steps) % vocab).astype(np.int32)
response = {{"x": x}}
"""


def wait_finished(tool, name: str, timeout: float) -> dict:
    """Poll like a user does, but fail as soon as the job records an
    exception document instead of waiting the timeout out."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        body = tool.read(name, limit=50)
        if body["metadata"].get("finished"):
            return body["metadata"]
        for doc in body.get("result") or []:
            if isinstance(doc, dict) and doc.get("exception"):
                raise RuntimeError(f"job {name} failed: "
                                   f"{doc['exception']}")
        time.sleep(0.5)
    raise TimeoutError(f"job {name} not finished after {timeout}s")


def _compile_seconds(trace_id: str) -> float:
    """Seconds the job's trace spent in ``compile`` spans so far."""
    from learningorchestra_tpu.observability import trace as obs_trace

    return round(obs_trace.durations_by_name(trace_id).get(
        "compile", 0.0), 2)


def _latest_step(ckpt_dir: str):
    from learningorchestra_tpu.runtime.checkpoint import Checkpointer

    return Checkpointer(ckpt_dir).latest_step()


def _checkpoint_layout(ckpt_dir: str, step: int) -> str:
    names = sorted(os.listdir(os.path.join(ckpt_dir, str(step))))
    if "manifest.json" in names:
        return "msgpack+manifest"
    return "other:" + ",".join(names[:6])


def run_one_chip(size: dict, seed: int, dev) -> None:
    import jax
    import numpy as np

    from learningorchestra_tpu import config as config_mod
    from learningorchestra_tpu.client import Context
    from learningorchestra_tpu.services.server import RestServer

    cache = CacheCounter()
    config_mod.reset_config()
    cfg = config_mod.get_config()

    srv = RestServer(host="127.0.0.1", port=0).start()
    try:
        ctx = Context(srv.base_url, timeout=900.0)
        model_cfg = dict(size["model"], attention="auto")
        seq = model_cfg["max_len"]
        steps_per_epoch = size["n_seq"] // size["batch"]

        # -- function: synthetic tokens in the default sandbox --------
        t0 = time.monotonic()
        ctx.function_python.run_function(
            "smoke_data",
            synth_code(size["n_seq"], seq, model_cfg["vocab_size"], seed))
        wait_finished(ctx.function_python, "smoke_data", 300)
        emit("function", seconds=round(time.monotonic() - t0, 2),
             sandbox_mode=cfg.sandbox_mode)

        # -- model ----------------------------------------------------
        ctx.model_tensorflow.create(
            "smoke_model", "learningorchestra_tpu.models",
            "LanguageModel", model_cfg)
        wait_finished(ctx.model_tensorflow, "smoke_model", 300)
        emit("model", config=model_cfg)

        # -- checkpointed train ---------------------------------------
        fit = {"x": "$smoke_data.x", "batch_size": size["batch"],
               "epochs": 2, "checkpoint": True}
        t0 = time.monotonic()
        ctx.train_tensorflow.run("smoke_train", "smoke_model", "fit", fit)
        wait_finished(ctx.train_tensorflow, "smoke_train", 1000)
        train_s = time.monotonic() - t0
        ckpt_dir = os.path.join(cfg.checkpoints_dir, "smoke_train")
        saved = _latest_step(ckpt_dir)
        if saved != 2 * steps_per_epoch:
            raise RuntimeError(f"checkpoint at step {saved}, expected "
                               f"{2 * steps_per_epoch}")
        lm = srv.api.ctx.artifacts.load("smoke_train", "train/tensorflow")
        losses = [float(h["loss"]) for h in lm.history]
        if not all(np.isfinite(losses)) or not losses[-1] < losses[0]:
            raise RuntimeError(f"train loss not finite and falling: "
                               f"{losses}")
        attention = lm._resolved_attention(seq)
        eng = lm._get_engine()
        # abstract state: lowering must not hold a second copy of the
        # train state on the device while the PATCH re-run trains
        lowered = eng._build_train_step().lower(
            jax.eval_shape(eng.init_state, lm.params),
            {"x": jax.ShapeDtypeStruct((size["batch"], seq), np.int32)},
            jax.random.PRNGKey(0))
        kernel = "tpu_custom_call" in lowered.as_text()
        if dev.platform == "tpu" and not (attention == "flash" and kernel):
            raise RuntimeError(
                f"training took attention={attention!r}, "
                f"tpu_custom_call in step: {kernel}")
        emit("train", seconds=round(train_s, 2), losses=losses,
             compile_seconds=_compile_seconds("smoke_train"),
             attention=attention, tpu_custom_call=kernel,
             checkpoint_step=saved,
             checkpoint_layout=_checkpoint_layout(ckpt_dir, saved),
             cache=cache.snapshot(), peak_hbm_bytes=_peak_hbm([dev]))

        # -- PATCH: resume from the saved step ------------------------
        t0 = time.monotonic()
        ctx.train_tensorflow.update(
            "smoke_train", {"methodParameters": dict(fit, epochs=3)})
        wait_finished(ctx.train_tensorflow, "smoke_train", 1000)
        resumed = _latest_step(ckpt_dir)
        if resumed != 3 * steps_per_epoch:
            raise RuntimeError(f"resume ended at step {resumed}")
        lm = srv.api.ctx.artifacts.load("smoke_train", "train/tensorflow")
        # the re-run's history holds only what it trained: a resume is
        # exactly the third epoch, a restart would be all three
        if [h["epoch"] for h in lm.history] != [2]:
            raise RuntimeError(f"PATCH did not resume at epoch 2: "
                               f"{lm.history}")
        emit("resume", seconds=round(time.monotonic() - t0, 2),
             from_step=saved, to_step=resumed,
             loss=float(lm.history[-1]["loss"]),
             compile_seconds=_compile_seconds("smoke_train"),
             cache=cache.snapshot())

        # -- perf report names the device and its peak ----------------
        perf = ctx.perf("smoke_train")
        plat = perf["platform"]
        if dev.platform == "tpu" and (
                plat.get("deviceKind") != dev.device_kind
                or not plat.get("peakTflopsPerChip")
                or not plat.get("peakHbmGbPerSec")):
            raise RuntimeError(f"perf report lacks device/peak: {plat}")
        emit("perf", platform=plat, report=perf["perf"])

        # -- serve: slot KV then paged KV, greedy == generate ---------
        rng = np.random.default_rng(seed)
        prompts = [affine_tokens(rng, 1, size["prompt"] + 3 * i,
                                 model_cfg["vocab_size"])[0].tolist()
                   for i in range(3)]
        new = size["new_tokens"]
        t0 = time.monotonic()
        want = [[int(t) for t in lm.generate(
            np.asarray([p], np.int32), max_new_tokens=new)[0][len(p):]]
            for p in prompts]
        emit("generate", seconds=round(time.monotonic() - t0, 2))
        for kv in ("slot", "paged"):
            t0 = time.monotonic()
            ctx.serve.create("smoke_train", type="lm", kv=kv)
            got = [ctx.serve.generate("smoke_train", p, max_new_tokens=new)
                   for p in prompts]
            stats = ctx.serve.stats("smoke_train")
            ctx.serve.delete("smoke_train")
            for p, g, w in zip(prompts, got, want):
                if g["tokens"][-new:] != w:
                    raise RuntimeError(
                        f"serve kv={kv} tokens {g['tokens'][-new:]} != "
                        f"generate {w} (prompt len {len(p)})")
            emit("serve", kv=kv, requests=len(got),
                 seconds=round(time.monotonic() - t0, 2),
                 requests_total=stats.get("requestsTotal"),
                 cache=cache.snapshot(), peak_hbm_bytes=_peak_hbm([dev]))
    finally:
        srv.stop()


def run_four_chips(size: dict, seed: int, n_chips: int) -> None:
    import jax
    import numpy as np

    from learningorchestra_tpu import config as config_mod
    from learningorchestra_tpu.models import LanguageModel
    from learningorchestra_tpu.runtime import mesh as mesh_lib
    from learningorchestra_tpu.runtime.checkpoint import Checkpointer

    devices = jax.devices()
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} but jax found "
                 f"{len(devices)} device(s); nothing ran")
    devices = devices[:n_chips]
    config_mod.reset_config()
    seq, batch, steps = size["mesh_seq"], size["mesh_batch"], 3
    vocab = size["model"]["vocab_size"]
    tokens = affine_tokens(np.random.default_rng(seed), batch, seq, vocab)
    cases = [("one", "dp=1", devices[:1], "auto"),
             ("auto", "auto", devices, "auto"),
             ("fsdp_tp", "fsdp=2,tp=2", devices, "auto"),
             ("fsdp_sp_ring", "fsdp=2,sp=2", devices, "ring"),
             ("fsdp_sp_ulysses", "fsdp=2,sp=2", devices, "ulysses")]
    params0 = None
    base = None
    for tag, spec, devs, attention in cases:
        mesh = mesh_lib.build_mesh(spec, devices=devs)
        lm = LanguageModel(**dict(size["model"], max_len=seq,
                                  attention=attention))
        lm.set_mesh(mesh)
        if params0 is None:
            lm._build_params(tokens)
            params0 = jax.tree_util.tree_map(np.asarray, lm.params)
        lm.params = params0
        # "auto" is what every /train takes on a four-chip host:
        # checkpoint it, and resume it below
        ckpt_dir = os.path.join(config_mod.get_config().checkpoints_dir,
                                "smoke_mesh_auto")
        ck = Checkpointer(ckpt_dir) if tag == "auto" else None
        t0 = time.monotonic()
        # one step per epoch -> the epoch losses ARE per-step losses
        hist = lm.fit(tokens, batch_size=batch, epochs=steps,
                      shuffle=False, checkpointer=ck)
        losses = [float(v) for v in hist.history["loss"]]
        seconds = time.monotonic() - t0
        if not all(np.isfinite(losses)):
            raise RuntimeError(f"{tag}: non-finite loss {losses}")
        # every device must hold parameter AND batch shards
        holders = set()
        for leaf in jax.tree_util.tree_leaves(lm._state.params):
            holders |= {s.device for s in leaf.addressable_shards}
        fed = next(iter(lm._get_engine()._device_feed(
            lm._batcher(tokens, batch), 0)))
        feeders = {s.device for s in fed["x"].addressable_shards}
        idle = [str(d) for d in devs
                if d not in holders or d not in feeders]
        if idle:
            raise RuntimeError(f"{tag}: devices without param or batch "
                               f"shards: {idle}")
        peaks = _peak_hbm(devs)
        if devs[0].platform == "tpu" and not all(peaks):
            raise RuntimeError(f"{tag}: a device reports zero "
                               f"peak_bytes_in_use: {peaks}")
        rel = None
        if base is None:
            base = losses
        else:
            rel = [abs(a - b) / abs(b) for a, b in zip(losses, base)]
            if max(rel) > MESH_LOSS_RTOL:
                raise RuntimeError(
                    f"{tag}: per-step loss {losses} vs one device {base} "
                    f"(rel {rel}) beyond {MESH_LOSS_RTOL}")
        resumed = None
        if ck is not None:
            again = LanguageModel(**dict(size["model"], max_len=seq,
                                         attention=attention))
            again.set_mesh(mesh)
            again.fit(tokens, batch_size=batch, epochs=steps + 1,
                      shuffle=False, checkpointer=Checkpointer(ckpt_dir))
            resumed = [h["epoch"] for h in again.history]
            if resumed != [steps]:
                raise RuntimeError(f"{tag}: resume from the checkpoint "
                                   f"ran epochs {resumed}, expected "
                                   f"[{steps}]")
        emit("mesh", case=tag, spec=spec, devices=len(devs),
             resumed_epochs=resumed,
             attention=lm._resolved_attention(seq), seq=seq, batch=batch,
             losses=losses, rel_vs_one=rel, rtol=MESH_LOSS_RTOL,
             param_devices=len(holders), batch_devices=len(feeders),
             batch_shard_shape=list(
                 fed["x"].addressable_shards[0].data.shape),
             peak_hbm_bytes=peaks, seconds=round(seconds, 2))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs ONLY the four-chip mesh phase")
    parser.add_argument("--size", choices=sorted(SIZES), default="full",
                        help="'tiny' rehearses the control flow on CPU")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "chip_smoke"))
    args = parser.parse_args(argv)

    dev = require_accelerator()
    import jax

    from learningorchestra_tpu import native
    from learningorchestra_tpu.runtime import arena
    from learningorchestra_tpu.services.context import wire_compile_cache

    home = os.path.join(os.path.abspath(args.out), "lo_home")
    shutil.rmtree(home, ignore_errors=True)
    os.environ["LO_HOME"] = home
    emit("env", backend=jax.default_backend(), platform=dev.platform,
         device_kind=dev.device_kind, device_count=len(jax.devices()),
         versions=_versions(), lo_home=home,
         compile_cache_dir=wire_compile_cache(),
         # a quarter of the device's bytes_limit; raises on an
         # accelerator that reports none
         arena_auto_budget_bytes=arena._auto_budget(),
         native_core_built=native.get_lib() is not None)
    size = SIZES[args.size]
    try:
        if args.chips == 1:
            run_one_chip(size, args.seed, dev)
            count = len(jax.devices())
        else:
            run_four_chips(size, args.seed, args.chips)
            count = args.chips
    finally:
        # the store holds GBs of checkpoints; what is worth keeping
        # was printed
        shutil.rmtree(home, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
