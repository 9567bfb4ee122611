"""Headline benchmarks through the REST control plane.

Drives the real pipeline — Function (synthetic data, zero-egress) →
Model → Train (→ Evaluate) — through the transport-independent Api
dispatcher for THREE model families, and reports the steady-state
training throughput plus the engine's roofline numbers
(tflops/sec/chip and MFU against the chip's bf16 peak) on whatever
accelerator ``jax.devices()`` offers (one TPU chip under the driver;
CPU locally, where MFU is undefined and omitted):

1. MNIST-CNN   — the BASELINE.json metric (samples/sec/chip via
                 /train); ``vs_baseline`` is measured live against the
                 reference's execution model (in-process CPU training,
                 SURVEY §3.3) via a torch-CPU twin of the same layers.
2. IMDb-LSTM   — BASELINE.md config 3 shape: embedding → LSTM →
                 dense over (n, 200) token sequences.
3. TransformerLM — the north-star MFU workload: decoder-only LM with
                 the Pallas flash-attention kernel on TPU (the path
                 ``attention="auto"`` picks), trained on synthetic
                 token streams.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extra": {...}}

The full self-measured table (per BASELINE.md:33-35) lives in
``extra.models``; ``--write-md PATH`` renders it.

Process contract: the parent process NEVER imports jax — a parent that
touched a backend would hold the chip and every phase child would fail
or hang. Each phase runs in its own subprocess under a hard wall-clock
bound and reports one JSON line naming the ``platform``,
``device_kind`` and ``device_count`` it ran on; a phase that hangs or
crashes is killed and recorded as a structured ``{"error": ...}``
entry while the other phases still report.

No fallback hides the device: a full run first probes (in a child that
exits before the first phase starts) that a fresh process reaches an
accelerator, and when it does not, prints why and exits non-zero with
no report — nothing re-runs on the CPU, no kernel gives way to ``dot``,
no older number is pasted in. A full run in which any phase failed
still prints its report, then exits non-zero. ``--phase X`` under an
explicit ``JAX_PLATFORMS=cpu`` stays for the correctness gates of
``deploy/ci.sh`` and the tests; its result says ``platform: cpu``.
"""

import argparse
import functools
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

EPOCHS = int(os.environ.get("LO_BENCH_CNN_EPOCHS", "4"))
BATCH = int(os.environ.get("LO_BENCH_CNN_BATCH", "256"))
N_SAMPLES = int(os.environ.get("LO_BENCH_CNN_N", "16384"))
IMG = 28
CLASSES = 10

# IMDb-LSTM shape (BASELINE config 3): 200-token reviews, binary label
LSTM_VOCAB = 20000
LSTM_SEQ = 200
LSTM_N = int(os.environ.get("LO_BENCH_LSTM_N", "8192"))
LSTM_BATCH = 128
# 5 epochs: train accuracy crosses 0.97 around epoch 4 on the synth
# IMDb task (measured 0.962 at epoch 3), so the time-to-97% half of
# the BASELINE metric lands; steady-state samples/s is per-epoch and
# unaffected by the count
LSTM_EPOCHS = int(os.environ.get("LO_BENCH_LSTM_EPOCHS", "5"))

# TransformerLM (north-star MFU workload); dimensions are
# env-overridable so the MFU sweep can scale the model to the chip
TLM_VOCAB = int(os.environ.get("LO_BENCH_TLM_VOCAB", "32000"))
TLM_SEQ = int(os.environ.get("LO_BENCH_TLM_SEQ", "512"))
TLM_N = int(os.environ.get("LO_BENCH_TLM_N", "2048"))
TLM_BATCH = int(os.environ.get("LO_BENCH_TLM_BATCH", "16"))
TLM_EPOCHS = int(os.environ.get("LO_BENCH_TLM_EPOCHS", "3"))
TLM_CFG = {"vocab_size": TLM_VOCAB,
           "d_model": int(os.environ.get("LO_BENCH_TLM_D", "512")),
           "n_layers": int(os.environ.get("LO_BENCH_TLM_LAYERS", "8")),
           "n_heads": int(os.environ.get("LO_BENCH_TLM_HEADS", "8")),
           "d_ff": int(os.environ.get("LO_BENCH_TLM_FF", "2048")),
           "max_len": TLM_SEQ}
# optional attention-config sweeps (0 = off/default MHA/full context)
_TLM_KV = int(os.environ.get("LO_BENCH_TLM_KV", "0"))
if _TLM_KV:
    TLM_CFG["n_kv_heads"] = _TLM_KV
_TLM_WINDOW = int(os.environ.get("LO_BENCH_TLM_WINDOW", "0"))
if _TLM_WINDOW:
    TLM_CFG["sliding_window"] = _TLM_WINDOW
# "auto" picks dot vs the Pallas flash kernel by sequence length on
# the chip (seq >= 1024 -> flash); a flash run that fails is an error,
# never retried on "dot"
TLM_ATTENTION = os.environ.get("LO_BENCH_TLM_ATTENTION", "auto")

# per-phase wall-clock bounds (seconds); overridable for local smoke
# runs via LO_BENCH_TIMEOUT_<PHASE>
PHASE_TIMEOUTS = {"cnn": 600, "lstm": 600, "tlm": 900, "proxy": 120,
                  "builder": 600, "builder_mesh": 600,
                  "warm_pipeline": 600, "concurrent_jobs": 600,
                  "flash": 600, "ingest": 600, "gen": 900,
                  "serving": 900, "paged_serving": 900,
                  "quant_serving": 900, "disagg_serving": 900,
                  "sentinel_overhead": 600, "sentinel_chaos": 600,
                  "obs_overhead": 600, "monitor_smoke": 600,
                  "incident_smoke": 600,
                  "sweep_fusion": 900,
                  "ckpt_stall": 300, "migration_smoke": 600,
                  "elastic_smoke": 600,
                  "xray_overhead": 600}

# out-of-core Builder (reference config 4: 10M-row GBT via Spark)
BUILDER_ROWS = int(os.environ.get("LO_BENCH_BUILDER_ROWS", "10000000"))

from __graft_entry__ import FLAGSHIP_CNN_LAYERS as CNN_LAYERS  # noqa: E402


def synth_code() -> str:
    return f"""
import numpy as np
rng = np.random.default_rng(0)
n, img, classes = {N_SAMPLES}, {IMG}, {CLASSES}
y = rng.integers(0, classes, size=n).astype(np.int32)
# class-dependent blobs so accuracy is learnable (sanity), not chance
x = rng.normal(0.0, 0.35, size=(n, img * img)).astype(np.float32)
for c in range(classes):
    x[y == c, c * 64:(c + 1) * 64] += 1.0
response = {{"x": x, "y": y}}
"""


def lstm_synth_code() -> str:
    return f"""
import numpy as np
rng = np.random.default_rng(1)
n, seq, vocab = {LSTM_N}, {LSTM_SEQ}, {LSTM_VOCAB}
x = rng.integers(0, vocab, size=(n, seq)).astype(np.int32)
# sentiment proxy: label from the low-token density in the first half
# (learnable by an RNN, not linearly from any single position)
y = (np.mean(x[:, :seq // 2] < vocab // 4, axis=1) > 0.25).astype(np.int32)
response = {{"x": x, "y": y}}
"""


def tlm_synth_code() -> str:
    return f"""
import numpy as np
rng = np.random.default_rng(2)
n, seq, vocab = {TLM_N}, {TLM_SEQ}, {TLM_VOCAB}
# learnable stream: affine next-token map with random per-sequence
# offsets (next-token accuracy can rise above chance; sanity signal)
start = rng.integers(0, vocab, size=(n, 1))
steps = np.arange(seq, dtype=np.int64)[None, :]
x = ((start + 97 * steps) % vocab).astype(np.int32)
response = {{"x": x}}
"""


def _expect_created(status, body):
    if status != 201:
        raise RuntimeError(f"POST failed: {status} {body}")


def _wait(api, uri, timeout=1800.0):
    name = uri.rstrip("/").split("/")[-1]
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        status, body, _ = api.dispatch("GET", uri, {"limit": "1"}, None)
        if status == 200 and body["metadata"].get("finished"):
            return body["metadata"]
        docs = api.ctx.catalog.get_documents(name)
        errs = [d["exception"] for d in docs if d.get("exception")]
        if errs:
            raise RuntimeError(f"job {name} failed: {errs[0]}")
        time.sleep(0.25)
    raise TimeoutError(f"job never finished: {uri}")


def _steady_stats(history, n_chips):
    """Best steady-state epoch (epoch 0 pays jit compilation) →
    per-chip samples/s + the engine's roofline numbers."""
    steady = [h for h in history[1:]] or history
    best = max(steady, key=lambda h: h.get("samplesPerSecond", 0.0))
    out = {
        "samples_per_sec_per_chip": round(
            best.get("samplesPerSecond", 0.0) / n_chips, 2),
        "epoch_seconds": best.get("epochSeconds"),
    }
    if best.get("tflopsPerSecPerChip") is not None:
        out["tflops_per_sec_per_chip"] = best["tflopsPerSecPerChip"]
    if best.get("mfu") is not None:
        out["mfu"] = best["mfu"]
    # extended roofline block (observability/perf) — present when XLA
    # reported bytes accessed (and peaks are known for the util/bound)
    if best.get("gbPerSecPerChip") is not None:
        out["gb_per_sec_per_chip"] = best["gbPerSecPerChip"]
    if best.get("hbmBwUtil") is not None:
        out["hbm_bw_util_frac"] = best["hbmBwUtil"]
    if best.get("boundBy") is not None:
        out["bound_by"] = best["boundBy"]
    if "loss" in best:
        out["final_loss"] = round(float(best["loss"]), 4)
    if "accuracy" in best:
        out["final_train_accuracy"] = round(float(best["accuracy"]), 4)
    # BASELINE.json metric pair: samples/sec/chip AND time-to-accuracy
    total = 0.0
    for h in history:
        total += float(h.get("epochSeconds", 0) or 0)
        if float(h.get("accuracy", 0) or 0) >= 0.97:
            out["time_to_97pct_train_acc_s"] = round(total, 3)
            break
    return out


def _run_pipeline(api, prefix, tag, fn_code, module_path, class_name,
                  class_params, train_params, evaluate=False):
    """Function → Model → Train (→ Evaluate) under unique names; returns
    (train_history, eval_metrics_or_None)."""
    status, body, _ = api.dispatch("POST", f"{prefix}/function/python", {}, {
        "name": f"{tag}_data", "function": fn_code,
        "functionParameters": {}, "description": f"synthetic {tag} data"})
    _expect_created(status, body)
    _wait(api, body["result"])

    status, body, _ = api.dispatch("POST", f"{prefix}/model/tensorflow", {}, {
        "modelName": f"{tag}_model", "modulePath": module_path,
        "class": class_name, "classParameters": class_params,
        "description": f"bench {tag}"})
    _expect_created(status, body)
    _wait(api, body["result"])

    status, body, _ = api.dispatch("POST", f"{prefix}/train/tensorflow", {}, {
        "name": f"{tag}_train", "modelName": f"{tag}_model", "method": "fit",
        "methodParameters": train_params})
    _expect_created(status, body)
    _wait(api, body["result"])

    model = api.ctx.artifacts.load(f"{tag}_train", "train/tensorflow")
    eval_metrics = None
    if evaluate:
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/evaluate/tensorflow", {}, {
                "name": f"{tag}_eval", "modelName": f"{tag}_train",
                "method": "evaluate",
                "methodParameters": {"x": f"${tag}_data.x",
                                     "y": f"${tag}_data.y"}})
        _expect_created(status, body)
        _wait(api, body["result"])
        eval_metrics = api.ctx.artifacts.load(
            f"{tag}_eval", "evaluate/tensorflow")
    return model.history, eval_metrics


def _make_api():
    from learningorchestra_tpu import config as config_mod
    from learningorchestra_tpu.services.server import Api

    home = tempfile.mkdtemp(prefix="lo_bench_")
    config_mod.set_config(config_mod.Config(home=home))
    return Api(), "/api/learningOrchestra/v1"


def phase_cnn():
    import jax

    api, prefix = _make_api()
    n_chips = len(jax.devices())
    try:
        history, ev = _run_pipeline(
            api, prefix, "cnn", synth_code(),
            "tensorflow.keras.models", "Sequential",
            {"layers": CNN_LAYERS},
            {"x": "$cnn_data.x", "y": "$cnn_data.y",
             "epochs": EPOCHS, "batch_size": BATCH},
            evaluate=True)
    finally:
        api.ctx.jobs.shutdown()
    out = _steady_stats(history, n_chips)
    out["eval_accuracy"] = round(float(ev["accuracy"]), 4)
    out["platform"] = jax.devices()[0].platform
    return out


def phase_lstm():
    import jax

    api, prefix = _make_api()
    n_chips = len(jax.devices())
    try:
        history, ev = _run_pipeline(
            api, prefix, "lstm", lstm_synth_code(),
            "learningorchestra_tpu.models", "NeuralModel",
            {"layer_configs": [
                {"kind": "embedding", "vocab": LSTM_VOCAB, "dim": 128},
                {"kind": "lstm", "units": 128},
                {"kind": "dense", "units": 2, "activation": "softmax"}]},
            {"x": "$lstm_data.x", "y": "$lstm_data.y",
             "epochs": LSTM_EPOCHS, "batch_size": LSTM_BATCH},
            evaluate=True)
    finally:
        api.ctx.jobs.shutdown()
    out = _steady_stats(history, n_chips)
    out["eval_accuracy"] = round(float(ev["accuracy"]), 4)
    out["platform"] = jax.devices()[0].platform
    return out


def phase_tlm():
    import jax

    api, prefix = _make_api()
    n_chips = len(jax.devices())
    try:
        history, _ = _run_pipeline(
            api, prefix, "tlm", tlm_synth_code(),
            "learningorchestra_tpu.models", "LanguageModel",
            dict(TLM_CFG, attention=TLM_ATTENTION),
            {"x": "$tlm_data.x", "epochs": TLM_EPOCHS,
             "batch_size": TLM_BATCH})
    finally:
        api.ctx.jobs.shutdown()
    out = _steady_stats(history, n_chips)
    out["tokens_per_sec_per_chip"] = round(
        out["samples_per_sec_per_chip"] * TLM_SEQ, 2)
    out["attention"] = TLM_ATTENTION
    out["platform"] = jax.devices()[0].platform
    return out


def phase_gen():
    """KV-cache decode throughput: tokens/s for autoregressive
    generation on a trained-shape LM. The whole continuation decodes
    inside one jitted lax.fori_loop (transformer.py _gen_fns), so this
    measures the device decode rate, not host round-trip latency.
    Reference has no generation path at all — this is net-new
    capability evidence; the interesting number is ms/token."""
    import jax
    import numpy as np

    from learningorchestra_tpu.models.transformer import LanguageModel

    cfg = dict(TLM_CFG)
    new_tokens = int(os.environ.get("LO_BENCH_GEN_TOKENS", "256"))
    prompt_len = int(os.environ.get("LO_BENCH_GEN_PROMPT", "64"))
    gen_batch = int(os.environ.get("LO_BENCH_GEN_BATCH", "8"))
    # n_kv_heads override: LO_BENCH_GEN_KV=2 measures the GQA decode
    # win (kv-width cache -> less HBM per token)
    kv = int(os.environ.get("LO_BENCH_GEN_KV", "0"))
    if kv:
        cfg["n_kv_heads"] = kv
    cfg["max_len"] = prompt_len + new_tokens
    lm = LanguageModel(**cfg)
    rng = np.random.default_rng(0)
    seed_tokens = rng.integers(
        1, cfg["vocab_size"], size=(gen_batch * 2, 128)).astype(np.int32)
    lm.fit(seed_tokens, batch_size=gen_batch * 2, epochs=1)
    prompt = rng.integers(1, cfg["vocab_size"],
                          size=(gen_batch, prompt_len)).astype(np.int32)
    # warmup pays the prefill+decode compile; then timed runs
    lm.generate(prompt, max_new_tokens=new_tokens, temperature=0.8,
                top_k=50, seed=0)
    n_runs = 3
    t0 = time.perf_counter()
    for i in range(n_runs):
        out = lm.generate(prompt, max_new_tokens=new_tokens,
                          temperature=0.8, top_k=50, seed=i + 1)
    dt = (time.perf_counter() - t0) / n_runs
    assert out.shape == (gen_batch, prompt_len + new_tokens)
    total_new = gen_batch * new_tokens
    return {
        "decode_tokens_per_sec": round(total_new / dt, 1),
        "decode_ms_per_token_per_seq": round(dt * 1000.0 / new_tokens, 3),
        "batch": gen_batch, "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "n_kv_heads": kv or cfg["n_heads"],
        "platform": jax.devices()[0].platform,
    }


def serve_clf_code() -> str:
    return """
import numpy as np
rng = np.random.default_rng(7)
n, d = 4096, 8
x = rng.normal(size=(n, d)).astype(np.float32)
w = rng.normal(size=(d,))
y = (x @ w > 0).astype(np.int32)
response = {"x": x, "y": y, "xq": x[:8]}
"""


def phase_serving():
    """Resident serving plane (docs/SERVING.md) vs the batch path it
    replaces. LM half: sustained mixed traffic — >= 8 concurrent
    request streams through ONE continuous-batched session — gated
    against the in-phase solo decode baseline measured with the
    lm_decode protocol (batch 2, same model, same process). Classifier
    half: warm shape-bucketed predict p50 vs the full submit->poll job
    path on the same fitted artifact (catalog writes + scheduling +
    artifact load per request vs a resident instance)."""
    import concurrent.futures

    import jax
    import numpy as np

    from learningorchestra_tpu.models.transformer import LanguageModel

    new = int(os.environ.get("LO_BENCH_SERVE_TOKENS", "64"))
    prompt_len = int(os.environ.get("LO_BENCH_SERVE_PROMPT", "32"))
    streams = int(os.environ.get("LO_BENCH_SERVE_STREAMS", "8"))
    reqs = int(os.environ.get("LO_BENCH_SERVE_REQS", "3"))
    api, prefix = _make_api()
    out = {"platform": jax.devices()[0].platform,
           "streams": streams, "requests_per_stream": reqs,
           "prompt_len": prompt_len, "new_tokens": new}
    try:
        # ---- LM solo baseline: the lm_decode protocol (batch 2, whole
        # continuation in one jitted fori_loop) on a serving-sized model
        cfg = dict(TLM_CFG)
        cfg["max_len"] = prompt_len + new
        lm = LanguageModel(**cfg)
        rng = np.random.default_rng(0)
        seed_tokens = rng.integers(
            1, cfg["vocab_size"], size=(4, 128)).astype(np.int32)
        lm.fit(seed_tokens, batch_size=4, epochs=1)
        solo_prompt = rng.integers(
            1, cfg["vocab_size"], size=(2, prompt_len)).astype(np.int32)
        lm.generate(solo_prompt, max_new_tokens=new, temperature=0.8,
                    top_k=50, seed=0)  # pays the compile
        t0 = time.perf_counter()
        for i in range(3):
            lm.generate(solo_prompt, max_new_tokens=new, temperature=0.8,
                        top_k=50, seed=i + 1)
        solo_dt = (time.perf_counter() - t0) / 3
        solo_tps = 2 * new / solo_dt
        out["solo_decode_tokens_per_sec"] = round(solo_tps, 1)

        # ---- LM serving: one session, `streams` concurrent clients
        api.ctx.artifacts.save(lm, "serve_lm", "train/tensorflow")
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/serve/serve_lm", {}, {
                "maxSlots": streams, "cacheLen": prompt_len + new,
                "temperature": 0.8, "topK": 50})
        _expect_created(status, body)
        base_prompt = [int(t) for t in rng.integers(
            1, cfg["vocab_size"], size=prompt_len)]
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/serve/serve_lm/predict", {}, {
                "prompt": base_prompt, "maxNewTokens": new, "seed": 0})
        if status != 200:
            raise RuntimeError(f"serve warmup failed: {status} {body}")

        def _stream(k):
            times = []
            for j in range(reqs):
                t = time.perf_counter()
                s2, b2, _ = api.dispatch(
                    "POST", f"{prefix}/serve/serve_lm/predict", {}, {
                        "prompt": base_prompt, "maxNewTokens": new,
                        "seed": k * 100 + j + 1})
                if s2 != 200:
                    raise RuntimeError(
                        f"serve predict failed: {s2} {b2}")
                times.append(time.perf_counter() - t)
            return times

        lat = []
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(streams) as pool:
            for times in pool.map(_stream, range(streams)):
                lat.extend(times)
        serve_dt = time.perf_counter() - t0
        serve_tps = streams * reqs * new / serve_dt
        lat.sort()
        _, lm_stats, _ = api.dispatch(
            "GET", f"{prefix}/serve/serve_lm", {}, None)
        n_chips = max(1, jax.device_count())
        out.update({
            "decode_tokens_per_sec": round(serve_tps, 1),
            "decode_tokens_per_sec_per_chip": round(
                serve_tps / n_chips, 2),
            "speedup_vs_solo": round(serve_tps / solo_tps, 2),
            "request_p50_ms": round(
                lat[int(0.50 * (len(lat) - 1))] * 1e3, 1),
            "p99_ms": round(lat[int(0.99 * (len(lat) - 1))] * 1e3, 1),
            "lease_yields": lm_stats["lease"].get("yields", 0),
        })
        # session-measured goodput (observability/perf): device-step
        # tokens/s/chip and batch-fill-weighted goodput from the
        # continuous batcher itself (the wall-clock tps above includes
        # queue + HTTP dispatch time)
        session_perf = lm_stats.get("perf") or {}
        for src, dst in (
                ("decodeTokensPerSecPerChip",
                 "session_decode_tokens_per_sec_per_chip"),
                ("goodputFrac", "goodput_frac"),
                ("hbmBwUtil", "decode_hbm_bw_util_frac"),
                ("boundBy", "decode_bound_by")):
            if session_perf.get(src) is not None:
                out[dst] = session_perf[src]
        api.dispatch("DELETE", f"{prefix}/serve/serve_lm", {}, None)

        # ---- classifier: submit->poll job path vs warm serving
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/function/python", {}, {
                "name": "sv_data", "function": serve_clf_code(),
                "functionParameters": {}})
        _expect_created(status, body)
        _wait(api, body["result"])
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/model/tensorflow", {}, {
                "modelName": "sv_model",
                "modulePath": "learningorchestra_tpu.models.estimators",
                "class": "LogisticRegressionJAX",
                "classParameters": {"epochs": 4, "batch_size": 512}})
        _expect_created(status, body)
        _wait(api, body["result"])
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/train/tensorflow", {}, {
                "name": "sv_clf", "modelName": "sv_model",
                "method": "fit",
                "methodParameters": {"x": "$sv_data.x",
                                     "y": "$sv_data.y"}})
        _expect_created(status, body)
        _wait(api, body["result"])

        poll_times = []
        for i in range(5):
            t = time.perf_counter()
            status, body, _ = api.dispatch(
                "POST", f"{prefix}/predict/tensorflow", {}, {
                    "name": f"sv_p{i}", "modelName": "sv_clf",
                    "method": "predict",
                    "methodParameters": {"x": "$sv_data.xq"}})
            _expect_created(status, body)
            _wait(api, body["result"])
            poll_times.append(time.perf_counter() - t)

        status, body, _ = api.dispatch(
            "POST", f"{prefix}/serve/sv_clf", {}, {})
        _expect_created(status, body)
        rows = [[float(v) for v in r]
                for r in rng.normal(size=(8, 8))]
        api.dispatch("POST", f"{prefix}/serve/sv_clf/predict", {},
                     {"x": rows})  # warm
        serve_times = []
        for _ in range(20):
            t = time.perf_counter()
            s2, b2, _ = api.dispatch(
                "POST", f"{prefix}/serve/sv_clf/predict", {},
                {"x": rows})
            if s2 != 200:
                raise RuntimeError(f"clf serve failed: {s2} {b2}")
            serve_times.append(time.perf_counter() - t)
        poll_times.sort()
        serve_times.sort()
        poll_p50 = poll_times[len(poll_times) // 2]
        serve_p50 = serve_times[len(serve_times) // 2]
        out.update({
            "predict_submit_poll_p50_ms": round(poll_p50 * 1e3, 1),
            "predict_serving_p50_ms": round(serve_p50 * 1e3, 2),
            "predict_speedup": round(poll_p50 / serve_p50, 1),
        })
    finally:
        api.ctx.serving.close()
        api.ctx.jobs.shutdown()
    return out


def phase_paged_serving():
    """Paged KV pool vs the contiguous slot cache at the SAME HBM
    budget (docs/SERVING.md "Paged KV serving"). Capacity half:
    identical short-request traffic against (a) a slot session whose
    KV is slots x cacheLen and (b) a paged session holding exactly the
    same page budget with lanes sized to actual token demand; the gate
    is the measured peak of simultaneously-decoding streams (paged
    >= 2x slot at equal memory — paged admission reserves
    ceil(tokens/pageLen) pages, not a whole worst-case slot). QoS
    half: an abusive tenant floods page-heavy requests while a victim
    tenant sends small ones through the same small pool — only the
    bully may be 429'd (its own weighted-fair quota), the victim takes
    zero rejections and its per-tenant servingP99 objective must not
    fire."""
    import concurrent.futures
    import threading

    import jax
    import numpy as np

    from learningorchestra_tpu.models.transformer import LanguageModel

    slots = int(os.environ.get("LO_BENCH_PAGED_SLOTS", "4"))
    cache_len = int(os.environ.get("LO_BENCH_PAGED_CACHE", "64"))
    page_len = int(os.environ.get("LO_BENCH_PAGED_PAGE_LEN", "16"))
    prompt_len = int(os.environ.get("LO_BENCH_PAGED_PROMPT", "8"))
    new = int(os.environ.get("LO_BENCH_PAGED_TOKENS", "8"))
    reqs = int(os.environ.get("LO_BENCH_PAGED_REQS", "4"))
    # per-tenant servingP99 objectives need a nonzero threshold to be
    # evaluable (Config is built from env by _make_api below)
    os.environ.setdefault(
        "LO_SLO_SERVING_P99_MS",
        os.environ.get("LO_BENCH_PAGED_SLO_MS", "5000"))
    api, prefix = _make_api()

    tokens_per_req = prompt_len + new
    pages_per_req = -(-tokens_per_req // page_len)
    # equal HBM: the paged pool gets exactly the slot cache's token
    # budget; its lane count is what that budget admits when a stream
    # only reserves the pages it can actually touch
    budget_pages = slots * cache_len // page_len
    paged_slots = budget_pages // pages_per_req
    out = {"platform": jax.devices()[0].platform,
           "slot_slots": slots, "paged_slots": paged_slots,
           "cache_len": cache_len, "page_len": page_len,
           "budget_pages": budget_pages, "prompt_len": prompt_len,
           "new_tokens": new, "requests_per_stream": reqs}
    try:
        cfg = dict(TLM_CFG)
        cfg["max_len"] = cache_len
        lm = LanguageModel(**cfg)
        rng = np.random.default_rng(0)
        seed_tokens = rng.integers(
            1, cfg["vocab_size"], size=(4, 128)).astype(np.int32)
        lm.fit(seed_tokens, batch_size=4, epochs=1)
        api.ctx.artifacts.save(lm, "paged_lm", "train/tensorflow")

        def _drive(n_clients):
            """n_clients concurrent streams x reqs unique-prompt
            requests each; returns (peak simultaneous active streams,
            wall seconds)."""
            sess = api.ctx.serving._sessions["paged_lm"]
            stop = threading.Event()
            peak = [0]

            def poll():
                while not stop.is_set():
                    active = sum(1 for r in sess._slot_req
                                 if r is not None)
                    if active > peak[0]:
                        peak[0] = active
                    time.sleep(0.0002)

            def client(k):
                for j in range(reqs):
                    prompt = [int(t) for t in np.random.default_rng(
                        1000 + k * 97 + j).integers(
                        1, cfg["vocab_size"], size=prompt_len)]
                    s2, b2, _ = api.dispatch(
                        "POST", f"{prefix}/serve/paged_lm/predict",
                        {}, {"prompt": prompt, "maxNewTokens": new,
                             "seed": k * 100 + j})
                    if s2 != 200:
                        raise RuntimeError(f"predict failed: {s2} {b2}")

            client(0)  # pay the prefill/step compile outside the clock
            poller = threading.Thread(target=poll, daemon=True)
            poller.start()
            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(
                    n_clients) as pool:
                list(pool.map(client, range(1, n_clients + 1)))
            dt = time.perf_counter() - t0
            stop.set()
            poller.join(timeout=5)
            return peak[0], dt

        # ---- slot baseline: slots lanes, each a cache_len reservation
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/serve/paged_lm", {}, {
                "maxSlots": slots, "cacheLen": cache_len,
                "temperature": 0.8, "topK": 50})
        _expect_created(status, body)
        slot_bytes = api.ctx.serving._sessions["paged_lm"]._cache_bytes
        slot_peak, slot_dt = _drive(paged_slots)
        api.dispatch("DELETE", f"{prefix}/serve/paged_lm", {}, None)

        # ---- paged: same page budget (plus the reserved trash page),
        # lanes sized to demand
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/serve/paged_lm", {}, {
                "kv": "paged", "maxSlots": paged_slots,
                "cacheLen": cache_len, "pageLen": page_len,
                "pages": budget_pages + 1,
                "temperature": 0.8, "topK": 50})
        _expect_created(status, body)
        paged_bytes = api.ctx.serving._sessions[
            "paged_lm"]._cache_bytes
        paged_peak, paged_dt = _drive(paged_slots)
        _, pstats, _ = api.dispatch(
            "GET", f"{prefix}/serve/paged_lm", {}, None)
        total_tokens = (paged_slots * reqs) * new
        out.update({
            "slot_kv_bytes": slot_bytes,
            "paged_kv_bytes": paged_bytes,
            "slot_peak_streams": slot_peak,
            "paged_peak_streams": paged_peak,
            "streams_vs_slot": round(paged_peak / max(1, slot_peak), 2),
            "slot_decode_tokens_per_sec": round(
                total_tokens / slot_dt, 1),
            "paged_decode_tokens_per_sec": round(
                total_tokens / paged_dt, 1),
            "prefix_pages_reused":
                pstats["kv"]["prefix"]["pagesReused"],
            "pool_alloc_failures": pstats["kv"]["allocFailures"],
        })
        api.dispatch("DELETE", f"{prefix}/serve/paged_lm", {}, None)

        # ---- QoS chaos: a 12-usable-page pool shared by a bully
        # (3-page requests from 6 threads) and a victim (1-page
        # requests). Weighted-fair quota caps the bully at half the
        # pool; the victim must never be rejected or paged.
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/serve/paged_lm", {}, {
                "kv": "paged", "maxSlots": 8, "cacheLen": cache_len,
                "pageLen": page_len, "pages": 13,
                "temperature": 0.8, "topK": 50})
        _expect_created(status, body)
        bully_new = 3 * page_len - prompt_len  # 3 pages per request
        counts = {"bully": [0, 0], "victim": [0, 0]}  # [ok, rejected]
        lock = threading.Lock()

        def chaos_client(tenant, n, new_toks, k):
            for j in range(n):
                prompt = [int(t) for t in np.random.default_rng(
                    5000 + k * 131 + j).integers(
                    1, cfg["vocab_size"], size=prompt_len)]
                s2, b2, _ = api.dispatch(
                    "POST", f"{prefix}/serve/paged_lm/predict", {}, {
                        "prompt": prompt, "maxNewTokens": new_toks,
                        "seed": k * 100 + j, "tenant": tenant})
                if s2 not in (200, 429):
                    raise RuntimeError(f"{tenant}: {s2} {b2}")
                with lock:
                    counts[tenant][0 if s2 == 200 else 1] += 1

        threads = [threading.Thread(
            target=chaos_client, args=("bully", reqs, bully_new, k))
            for k in range(6)]
        threads += [threading.Thread(
            target=chaos_client, args=("victim", reqs + 2, new, 10 + k))
            for k in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)

        _, cstats, _ = api.dispatch(
            "GET", f"{prefix}/serve/paged_lm", {}, None)
        tenants = cstats["kv"]["tenants"]

        from learningorchestra_tpu.observability.slo import SloWatchdog

        wd = SloWatchdog()
        wd.evaluate()
        firing = [a["name"] for a in wd.firing()]
        out.update({
            "bully_ok": counts["bully"][0],
            "bully_rejected": counts["bully"][1],
            "victim_ok": counts["victim"][0],
            "victim_rejected": counts["victim"][1],
            "bully_p99_ms": tenants.get("bully", {}).get(
                "latency", {}).get("p99Ms"),
            "victim_p99_ms": tenants.get("victim", {}).get(
                "latency", {}).get("p99Ms"),
            "victim_slo_fired": "servingP99:victim" in firing,
            "slo_firing": firing,
        })
    finally:
        api.ctx.serving.close()
        api.ctx.jobs.shutdown()
    return out


def phase_quant_serving():
    """int8 KV pages + int8 weights vs the bf16 paged pool at the SAME
    HBM budget (docs/SERVING.md "Quantized serving"). Capacity half:
    the bf16 session gets the slot cache's page budget; the int8
    session gets however many pages the SAME bytes fund once each page
    is int8 payload + its f32 per-head scale row — near 2x, so at
    equal memory it must hold >= 1.8x the simultaneously-decoding
    streams (page capacity at equal bytes is platform-independent, so
    the gate holds on the CPU fallback too). Quality half: the
    create-time drift probe's value must sit under LO_SERVE_DRIFT_MAX.
    Chaos half: a latched ``kv_quant`` fault must walk the degrade
    ladder — the session rebuilds over exact bf16 pages/weights and
    keeps serving."""
    import concurrent.futures
    import threading

    import jax
    import numpy as np

    from learningorchestra_tpu.models.transformer import LanguageModel
    from learningorchestra_tpu.services import faults

    slots = int(os.environ.get("LO_BENCH_QUANT_SLOTS", "4"))
    cache_len = int(os.environ.get("LO_BENCH_QUANT_CACHE", "64"))
    page_len = int(os.environ.get("LO_BENCH_QUANT_PAGE_LEN", "16"))
    prompt_len = int(os.environ.get("LO_BENCH_QUANT_PROMPT", "8"))
    new = int(os.environ.get("LO_BENCH_QUANT_TOKENS", "8"))
    reqs = int(os.environ.get("LO_BENCH_QUANT_REQS", "2"))
    api, prefix = _make_api()

    tokens_per_req = prompt_len + new
    pages_per_req = -(-tokens_per_req // page_len)
    budget_pages = slots * cache_len // page_len
    n_chips = max(1, jax.device_count())
    out = {"platform": jax.devices()[0].platform,
           "cache_len": cache_len, "page_len": page_len,
           "bf16_pages": budget_pages, "prompt_len": prompt_len,
           "new_tokens": new, "requests_per_stream": reqs}
    try:
        cfg = dict(TLM_CFG)
        cfg["max_len"] = cache_len
        lm = LanguageModel(**cfg)
        rng = np.random.default_rng(0)
        seed_tokens = rng.integers(
            1, cfg["vocab_size"], size=(4, 128)).astype(np.int32)
        lm.fit(seed_tokens, batch_size=4, epochs=1)
        api.ctx.artifacts.save(lm, "quant_lm", "train/tensorflow")

        def _session(n_pages, n_slots, **extra):
            body = {"kv": "paged", "maxSlots": n_slots,
                    "cacheLen": cache_len, "pageLen": page_len,
                    "pages": n_pages, "temperature": 0.8, "topK": 50}
            body.update(extra)
            status, body, _ = api.dispatch(
                "POST", f"{prefix}/serve/quant_lm", {}, body)
            _expect_created(status, body)
            return api.ctx.serving._sessions["quant_lm"]

        def _drive(n_clients):
            """n_clients concurrent streams x reqs unique-prompt
            requests; (peak simultaneous active streams, seconds)."""
            sess = api.ctx.serving._sessions["quant_lm"]
            stop = threading.Event()
            peak = [0]

            def poll():
                while not stop.is_set():
                    active = sum(1 for r in sess._slot_req
                                 if r is not None)
                    if active > peak[0]:
                        peak[0] = active
                    time.sleep(0.0002)

            def client(k):
                for j in range(reqs):
                    prompt = [int(t) for t in np.random.default_rng(
                        9000 + k * 97 + j).integers(
                        1, cfg["vocab_size"], size=prompt_len)]
                    s2, b2, _ = api.dispatch(
                        "POST", f"{prefix}/serve/quant_lm/predict",
                        {}, {"prompt": prompt, "maxNewTokens": new,
                             "seed": k * 100 + j})
                    if s2 != 200:
                        raise RuntimeError(f"predict failed: {s2} {b2}")

            client(0)  # pay the prefill/step compile outside the clock
            poller = threading.Thread(target=poll, daemon=True)
            poller.start()
            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(
                    n_clients) as pool:
                list(pool.map(client, range(1, n_clients + 1)))
            dt = time.perf_counter() - t0
            stop.set()
            poller.join(timeout=5)
            return peak[0], dt

        # ---- bf16 paged baseline at the slot cache's page budget
        bf16_cap = budget_pages // pages_per_req
        sess = _session(budget_pages + 1, bf16_cap)
        bf16_bytes = sess._cache_bytes
        bf16_peak, bf16_dt = _drive(bf16_cap)
        api.dispatch("DELETE", f"{prefix}/serve/quant_lm", {}, None)

        # ---- int8 bytes-per-page probe (payload + scale pools are
        # funded together, so this is the TRUE quantized footprint)
        sess = _session(budget_pages + 1, bf16_cap, kvDtype="int8")
        int8_page_bytes = sess._cache_bytes / (budget_pages + 1)
        api.dispatch("DELETE", f"{prefix}/serve/quant_lm", {}, None)

        # ---- int8 at EQUAL HBM: same bytes, ~2x the pages
        int8_pages = int(bf16_bytes // int8_page_bytes) - 1
        int8_cap = int8_pages // pages_per_req
        sess = _session(int8_pages + 1, int8_cap,
                        kvDtype="int8", weights="int8")
        int8_bytes = sess._cache_bytes
        int8_peak, int8_dt = _drive(int8_cap)
        _, qstats, _ = api.dispatch(
            "GET", f"{prefix}/serve/quant_lm", {}, None)
        api.dispatch("DELETE", f"{prefix}/serve/quant_lm", {}, None)

        bf16_tokens = (bf16_cap * reqs) * new
        int8_tokens = (int8_cap * reqs) * new
        out.update({
            "bf16_kv_bytes": bf16_bytes,
            "int8_kv_bytes": int8_bytes,
            "int8_pages": int8_pages,
            "bf16_peak_streams": bf16_peak,
            "int8_peak_streams": int8_peak,
            "streams_vs_bf16": round(
                int8_peak / max(1, bf16_peak), 2),
            "bf16_decode_tokens_per_sec": round(
                bf16_tokens / bf16_dt, 1),
            "int8_decode_tokens_per_sec": round(
                int8_tokens / int8_dt, 1),
            "bf16_decode_tokens_per_sec_per_chip": round(
                bf16_tokens / bf16_dt / n_chips, 1),
            "int8_decode_tokens_per_sec_per_chip": round(
                int8_tokens / int8_dt / n_chips, 1),
            "kv_bytes_per_token": qstats["kv"].get("bytesPerToken"),
            "weights_dtype": qstats["weights"]["dtype"],
            "drift": (qstats.get("drift") or {}).get("value"),
            "drift_max": (qstats.get("drift") or {}).get("max"),
        })

        # ---- chaos: latched kv_quant fault -> degrade ladder to bf16
        api.ctx.config.fault_inject = "kv_quant:100"
        faults.reset()
        _session(budget_pages + 1, 4, kvDtype="int8", weights="int8")
        prompt = [int(t) for t in np.random.default_rng(
            31).integers(1, cfg["vocab_size"], size=prompt_len)]
        codes = []
        for j in range(5):
            s2, b2, _ = api.dispatch(
                "POST", f"{prefix}/serve/quant_lm/predict", {},
                {"prompt": prompt, "maxNewTokens": new, "seed": j})
            codes.append(s2)
            if s2 == 200:
                break
        _, dstats, _ = api.dispatch(
            "GET", f"{prefix}/serve/quant_lm", {}, None)
        api.ctx.config.fault_inject = ""
        faults.reset()
        out.update({
            "degrade_codes": codes,
            "degrade_fired": (dstats["kv"]["dtype"] == "bf16"
                              and dstats["weights"]["dtype"] == "bf16"
                              and codes[-1] == 200),
        })
        api.dispatch("DELETE", f"{prefix}/serve/quant_lm", {}, None)
    finally:
        api.ctx.serving.close()
        api.ctx.jobs.shutdown()
    return out


def _open_loop_arrivals(submit, rate_hz, duration_s, timeout=300):
    """Open-loop (fixed-rate) request arrivals for the serving phases:
    one submission every 1/rate seconds ON THE WALL CLOCK, each on its
    own thread, regardless of how many are still in flight. The
    closed-loop ThreadPool drivers above only re-issue after a reply,
    so a server stall slows the arrival process itself and the
    measured p99 forgives exactly the stalls a latency gate exists to
    catch (coordinated omission); this driver keeps the offered load
    constant so a burst-induced decode stall surfaces as tail latency
    instead of as a quieter clock. Returns submit()'s results in
    completion order."""
    import threading

    results, lock, threads = [], threading.Lock(), []
    n = max(1, int(rate_hz * duration_s))
    t0 = time.perf_counter()
    for i in range(n):
        delay = t0 + i / rate_hz - time.perf_counter()
        if delay > 0:
            time.sleep(delay)

        def _run(idx=i):
            r = submit(idx)
            with lock:
                results.append(r)

        th = threading.Thread(target=_run, daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=timeout)
    return results


def phase_disagg_serving():
    """Disaggregated prefill/decode workers + speculative decoding
    (docs/SERVING.md "Disaggregated serving & speculative decoding").
    Isolation half: the same open-loop fixed-rate short-request
    traffic is measured three ways — fused with no competing load
    (the no-burst decode-p99 floor), fused while burst clients pump
    long prompts through the same session (prefill runs inside the
    serve loop, so mid-stream decodes stall behind it), and
    disaggregated under the identical mixed load (prefill on its own
    worker publishing finished KV pages by reference). deploy/ci.sh
    gates disagg_burst_decode_p99_ms <= LO_SMOKE_DISAGG_P99_MULT x
    the no-burst floor while the fused arm breaches it. Spec half:
    greedy traffic with and without a small draft model — accepted
    tokens/step and the tokens/s uplift land in the payload. Chaos
    half: a latched ``kv_page_handoff`` fault must restore every page
    reference on each 429, collapse the session to fused with an
    incident, and keep serving through the fused path."""
    import concurrent.futures
    import threading

    import jax
    import numpy as np

    from learningorchestra_tpu.models.transformer import LanguageModel
    from learningorchestra_tpu.services import faults

    slots = int(os.environ.get("LO_BENCH_DISAGG_SLOTS", "4"))
    cache_len = int(os.environ.get("LO_BENCH_DISAGG_CACHE", "128"))
    page_len = int(os.environ.get("LO_BENCH_DISAGG_PAGE_LEN", "16"))
    prompt_len = int(os.environ.get("LO_BENCH_DISAGG_PROMPT", "8"))
    new = int(os.environ.get("LO_BENCH_DISAGG_TOKENS", "8"))
    rate = float(os.environ.get("LO_BENCH_DISAGG_RATE", "6"))
    duration = float(os.environ.get("LO_BENCH_DISAGG_SECONDS", "4"))
    burst_prompt = int(os.environ.get(
        "LO_BENCH_DISAGG_BURST_PROMPT", "120"))
    burst_rate = float(os.environ.get(
        "LO_BENCH_DISAGG_BURST_RATE", "6"))
    # bursts are PURE prefill pressure (one emitted token): the
    # decode-p99 contrast must isolate prefill head-of-line stalls,
    # not dilute the tail with the bursts' own long-context decodes
    burst_new = int(os.environ.get(
        "LO_BENCH_DISAGG_BURST_TOKENS", "1"))
    epochs = int(os.environ.get("LO_BENCH_DISAGG_EPOCHS", "25"))
    spec_k = int(os.environ.get("LO_BENCH_DISAGG_SPEC_K", "3"))
    spec_new = int(os.environ.get("LO_BENCH_DISAGG_SPEC_TOKENS", "16"))
    spec_reqs = int(os.environ.get("LO_BENCH_DISAGG_SPEC_REQS", "3"))
    api, prefix = _make_api()

    pages = slots * (cache_len // page_len)
    out = {"platform": jax.devices()[0].platform,
           "slots": slots, "cache_len": cache_len,
           "page_len": page_len, "pages": pages,
           "prompt_len": prompt_len, "burst_prompt_len": burst_prompt,
           "burst_new_tokens": burst_new,
           "new_tokens": new, "open_loop_rate_hz": rate,
           "burst_rate_hz": burst_rate,
           "open_loop_seconds": duration, "spec_k": spec_k}
    try:
        cfg = dict(TLM_CFG)
        cfg["max_len"] = cache_len
        lm = LanguageModel(**cfg)
        # both models train on a cyclic-successor stream (token t is
        # ALWAYS followed by t % P + 1): each learns the bigram map,
        # so the draft's greedy proposals mostly match the target's
        # argmax and accepted tokens/step measures real speculation
        # instead of two noise models never agreeing
        cyc = 16
        rows = np.asarray(
            [[(off + i) % cyc + 1 for i in range(16)]
             for off in range(64)], np.int32)
        lm.fit(rows, batch_size=16, epochs=epochs)
        api.ctx.artifacts.save(lm, "dlm", "train/tensorflow")
        # small draft for the speculative arm: same vocab + context,
        # a fraction of the target's width/depth, trained on the same
        # stream in a different order (close, not identical)
        dcfg = dict(cfg, d_model=max(32, cfg["d_model"] // 4),
                    n_layers=1, n_heads=2,
                    d_ff=max(64, cfg["d_ff"] // 4))
        draft = LanguageModel(**dcfg)
        draft.fit(rows[::-1].copy(), batch_size=16, epochs=epochs)
        api.ctx.artifacts.save(draft, "dlm_draft", "train/tensorflow")

        def _session(**extra):
            body = {"kv": "paged", "maxSlots": slots,
                    "cacheLen": cache_len, "pageLen": page_len,
                    "pages": pages + 1, "temperature": 0.0}
            body.update(extra)
            status, resp, _ = api.dispatch(
                "POST", f"{prefix}/serve/dlm", {}, body)
            _expect_created(status, resp)
            return api.ctx.serving._sessions["dlm"]

        def _predict(prompt, n_toks, seed):
            s2, _, _ = api.dispatch(
                "POST", f"{prefix}/serve/dlm/predict", {},
                {"prompt": prompt, "maxNewTokens": n_toks,
                 "seed": seed})
            return s2

        def _prompt(seed, length):
            return [int(t) for t in np.random.default_rng(
                seed).integers(1, cfg["vocab_size"], size=length)]

        def _mixed_load(tag, burst):
            """Open-loop short traffic (+ an optional open-loop
            long-prompt burst stream — fixed-rate too, so the burst is
            head-of-line pressure on the serve loop, not raw compute
            saturation) against the live session; reads the per-role
            decode/TTFT tail from its stats."""
            # pay both prefill-shape compiles outside the clock
            _predict(_prompt(1, prompt_len), new, 0)
            if burst:
                _predict(_prompt(2, burst_prompt), burst_new, 0)

            bt = threading.Thread(
                target=lambda: _open_loop_arrivals(
                    lambda j: _predict(
                        _prompt(7000 + j, burst_prompt), burst_new,
                        j),
                    burst_rate, duration),
                daemon=True)
            if burst:
                bt.start()
            codes = _open_loop_arrivals(
                lambda j: _predict(_prompt(100 + j, prompt_len),
                                   new, j),
                rate, duration)
            if burst:
                bt.join(timeout=120)
            _, st, _ = api.dispatch(
                "GET", f"{prefix}/serve/dlm", {}, None)
            roles = st.get("roles", {})
            out.update({
                f"{tag}_decode_p99_ms":
                    roles.get("decode", {}).get("p99Ms"),
                f"{tag}_ttft_p99_ms":
                    (st.get("ttft") or {}).get("p99Ms"),
                f"{tag}_ok": sum(1 for c in codes if c == 200),
                f"{tag}_rejected": sum(1 for c in codes if c == 429),
            })
            return st

        reps = int(os.environ.get("LO_BENCH_DISAGG_REPS", "3"))

        def _arm(tag, burst, **extra):
            """Best-of-``reps`` runs of one arm, a fresh session each
            time. A shared/throttled CI core makes single-shot tail
            latency swing several-fold run to run, and external
            contamination only ever INFLATES the tail — the minimum
            decode p99 is each arm's least-polluted measurement, so
            the fused-breach gate stays mechanism-driven (even its
            best run must breach) and the disagg gate is not failed
            by a noisy neighbor."""
            keys = (f"{tag}_decode_p99_ms", f"{tag}_ttft_p99_ms",
                    f"{tag}_ok", f"{tag}_rejected")
            best = None
            for _ in range(max(1, reps)):
                _session(**extra)
                st = _mixed_load(tag, burst)
                api.dispatch("DELETE", f"{prefix}/serve/dlm", {},
                             None)
                cur = out.get(keys[0])
                if best is None or (cur is not None
                                    and cur < (best[0]
                                               or float("inf"))):
                    best = (cur, {k: out.get(k) for k in keys}, st)
            out.update(best[1])
            return best[2]

        # ---- fused, no competing load: the decode-p99 floor
        _arm("no_burst", burst=False)

        # ---- fused + long-prompt burst: prefill stalls decode
        _arm("fused_burst", burst=True)

        # ---- disaggregated + the identical burst
        dst = _arm("disagg_burst", burst=True, disagg=True)
        out.update({
            "disagg_mode": (dst.get("disagg") or {}).get("mode"),
            "handoffs_total":
                (dst.get("disagg") or {}).get("handoffsTotal"),
            "ttft_p99_ms": out.get("disagg_burst_ttft_p99_ms"),
        })
        api.dispatch("DELETE", f"{prefix}/serve/dlm", {}, None)
        floor = out.get("no_burst_decode_p99_ms") or 0.0
        if floor:
            for tag in ("fused_burst", "disagg_burst"):
                p99 = out.get(f"{tag}_decode_p99_ms")
                if p99 is not None:
                    out[f"{tag}_decode_p99_vs_no_burst"] = round(
                        p99 / floor, 3)

        # ---- speculative decoding: greedy tokens/s without/with the
        # draft (fresh session each so per-role stats don't mix)
        def _spec_drive(tag):
            def client(k):
                for j in range(spec_reqs):
                    # on-pattern prompts (distinct phases): the draft
                    # has a real shot at matching the target's argmax
                    phase = (k * 3 + j) % cyc
                    code = _predict(
                        [(phase + i) % cyc + 1
                         for i in range(prompt_len)],
                        spec_new, k * 100 + j)
                    if code != 200:
                        raise RuntimeError(f"{tag} predict: {code}")

            client(0)  # compile outside the clock
            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(
                    slots) as pool:
                list(pool.map(client, range(1, slots + 1)))
            dt = time.perf_counter() - t0
            _, st, _ = api.dispatch(
                "GET", f"{prefix}/serve/dlm", {}, None)
            return round(slots * spec_reqs * spec_new / dt, 1), st

        _session()
        base_tps, _ = _spec_drive("base")
        api.dispatch("DELETE", f"{prefix}/serve/dlm", {}, None)
        _session(draft="dlm_draft", specK=spec_k)
        spec_tps, sstats = _spec_drive("spec")
        api.dispatch("DELETE", f"{prefix}/serve/dlm", {}, None)
        out.update({
            "base_tokens_per_sec": base_tps,
            "spec_tokens_per_sec": spec_tps,
            "spec_tokens_speedup": round(
                spec_tps / max(1e-9, base_tps), 3),
            "accepted_tokens_per_step": (sstats.get("spec") or {}).get(
                "acceptedTokensPerStep"),
        })

        # ---- chaos: latched kv_page_handoff -> every 429 restores
        # its page references, then the session collapses to fused
        api.ctx.config.fault_inject = "kv_page_handoff:100"
        faults.reset()
        sess = _session(disagg=True)
        free0 = sess.pool.free_count()
        codes = []
        for j in range(3):
            codes.append(_predict(_prompt(40 + j, prompt_len), new, j))
            time.sleep(0.05)
        leak_free = sess.pool.free_count() == free0
        # the latched streak defers a collapse to the decode thread;
        # requests keep 429ing until it lands, then serve fused
        final = None
        for j in range(40):
            final = _predict(_prompt(80 + j, prompt_len), new, j)
            codes.append(final)
            if final == 200:
                break
            time.sleep(0.1)
        _, dstats, _ = api.dispatch(
            "GET", f"{prefix}/serve/dlm", {}, None)
        api.ctx.config.fault_inject = ""
        faults.reset()
        out.update({
            "chaos_codes": codes[:8],
            "chaos_leak_free": leak_free,
            "chaos_degrade_fired": (
                (dstats.get("disagg") or {}).get("mode")
                == "fused-degraded" and final == 200
                and all(c == 429 for c in codes[:3])),
        })
        api.dispatch("DELETE", f"{prefix}/serve/dlm", {}, None)
    finally:
        api.ctx.serving.close()
        api.ctx.jobs.shutdown()
    return out


def _scrub_exc(exc) -> str:
    """One-line, ANSI-free rendering of a phase-internal exception."""
    import re
    text = f"{type(exc).__name__}: {exc}"
    text = re.sub(r"\x1b\[[0-9;]*m", "", text)
    return " ".join(text.split())[:300]


def phase_flash():
    """Kernel micro-bench: Pallas flash attention vs the fused-dot
    oracle, forward AND backward, seq 1k-8k, causal and not (verdict
    round-2 weak #4/#6 — the bwd kernels need on-chip wall-clock
    evidence, not just interpret-mode numerics).

    Timing methodology: each measurement runs ``n_iter`` fwd+bwd
    passes **inside one jit** via ``lax.fori_loop``, chaining each
    iteration's gradients into the next iteration's inputs (so no
    pass can be elided) and returning a scalar that the host reads
    back — the wall-clock therefore brackets the full device
    execution, amortized over n_iter.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from learningorchestra_tpu.ops import attention as attn

    def timed_ms_per_iter(fn, q, k, v, causal, n_iter=8):
        grad = jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v, causal=causal)),
            argnums=(0, 1, 2))

        def body(_, carry):
            q, k, v, acc = carry
            dq, dk, dv = grad(q, k, v)
            # chain grads into the next iteration's operands so XLA
            # cannot hoist or elide any of the n_iter passes
            return (q + 1e-6 * dq, k + 1e-6 * dk, v + 1e-6 * dv,
                    acc + jnp.sum(dq))

        @jax.jit
        def looped(q, k, v):
            init = (q, k, v, jnp.float32(0))
            return jax.lax.fori_loop(0, n_iter, body, init)[3]

        float(looped(q, k, v))  # compile + warm; readback syncs
        t0 = time.perf_counter()
        float(looped(q, k, v))  # scalar readback: full device sync
        return (time.perf_counter() - t0) / n_iter * 1e3

    b, h, d = 4, 8, 64
    results = {}
    seqs = tuple(int(s) for s in os.environ.get(
        "LO_BENCH_FLASH_SEQS", "1024,2048,4096,8192").split(","))
    for seq in seqs:
        for causal in (False, True):
            q, k, v = (
                jnp.asarray(np.random.default_rng(i).normal(
                    size=(b, seq, h, d)).astype(np.float32) * 0.1)
                for i in range(3))
            key = f"seq{seq}_{'causal' if causal else 'full'}"
            # the kernel under test fails the phase when it fails; only
            # the dot ORACLE may be recorded as an error and passed over
            # (its (bh, s, s) scores stop fitting at long sequences)
            entry = {"flash_fwd_bwd_ms": round(timed_ms_per_iter(
                attn.flash_attention, q, k, v, causal), 3)}
            try:
                entry["dot_fwd_bwd_ms"] = round(timed_ms_per_iter(
                    attn.reference_attention, q, k, v, causal), 3)
            except Exception as exc:  # noqa: BLE001 — oracle only
                entry["dot_error"] = _scrub_exc(exc)
            if "dot_fwd_bwd_ms" in entry:
                entry["speedup"] = round(
                    entry["dot_fwd_bwd_ms"] / entry["flash_fwd_bwd_ms"], 3)
            # sliding-window row (causal only): the banded grid should
            # make this ~O(s*W) — the evidence for the clamp-indexed
            # tile iteration
            win = int(os.environ.get("LO_BENCH_FLASH_WINDOW", "0"))
            if causal and win:
                wfn = functools.partial(attn.flash_attention, window=win)
                entry[f"flash_window{win}_fwd_bwd_ms"] = round(
                    timed_ms_per_iter(wfn, q, k, v, True), 3)
            results[key] = entry
    results["platform"] = jax.devices()[0].platform
    return results


def _write_builder_synth(cat, name, rows, seed):
    """Linearly separable 5-feature synthetic dataset, written in
    bounded batches (shared by the streaming and mesh builder
    phases so their data distributions can never diverge)."""
    import numpy as np
    import pyarrow as pa

    w_true = np.array([1.0, -2.0, 0.5, 1.5, -1.0])
    r = np.random.default_rng(seed)
    cat.create_collection(name, "dataset/csv", {})
    with cat.dataset_writer(name) as w:
        left = rows
        while left:
            n = min(left, 262_144)
            x = r.normal(size=(n, 5))
            y = (x @ w_true > 0).astype(np.int64)
            w.write_batch(pa.table({
                **{f"f{i}": x[:, i] for i in range(5)}, "label": y}))
            left -= n
    cat.mark_finished(name)


def phase_builder():
    """BASELINE config 4 (the reference's Spark path): 10M-row
    synthetic binary classification through POST /builder with
    streaming=true — batched Parquet iteration, partial_fit (LR) and
    FULL-DATA first-party histogram boosting (GB: every row trains,
    csrc/locore.cpp lo_hgb_*), bounded RSS. No accelerator involved;
    this measures the out-of-core host data plane."""
    import resource

    api, prefix = _make_api()
    cat = api.ctx.catalog

    test_rows = max(BUILDER_ROWS // 20, 1)
    t_gen = time.perf_counter()
    _write_builder_synth(cat, "b_train", BUILDER_ROWS, 1)
    _write_builder_synth(cat, "b_test", test_rows, 2)
    _write_builder_synth(cat, "b_eval", test_rows, 3)
    gen_seconds = time.perf_counter() - t_gen

    t0 = time.perf_counter()
    status, body, _ = api.dispatch("POST", f"{prefix}/builder/sparkml", {}, {
        "trainDatasetName": "b_train", "testDatasetName": "b_test",
        "evaluationDatasetName": "b_eval",
        "classifiersList": ["LR", "GB"], "streaming": True})
    _expect_created(status, body)
    for uri in body["result"]:
        _wait(api, uri, timeout=540)
    elapsed = time.perf_counter() - t0
    api.ctx.jobs.shutdown()

    out = {"rows": BUILDER_ROWS,
           "pipeline_seconds": round(elapsed, 2),
           "train_rows_per_sec": round(BUILDER_ROWS / elapsed, 2),
           "datagen_seconds": round(gen_seconds, 2),
           "peak_rss_mb": round(
               resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024,
               1)}
    for c in ("LR", "GB"):
        meta = cat.get_metadata(f"b_test{c}")
        out[c.lower()] = {"accuracy": meta.get("accuracy"),
                          "f1": meta.get("f1"),
                          "fitTime": meta.get("fitTime"),
                          "trainedOnSample": meta.get("trainedOnSample")}
    return out


def phase_builder_mesh():
    """Mesh-parallel Builder (SURVEY §7: N models as parallel jobs
    over mesh slices): the SAME in-memory pipeline
    run twice — meshParallel=true (LR+NB as JAX fits on disjoint
    device sub-slices) vs host sklearn threads — so the table carries
    a measured jax-vs-sklearn fit-time row per family."""
    import jax

    rows = int(os.environ.get("LO_BENCH_BUILDER_MESH_ROWS", "2000000"))
    api, prefix = _make_api()
    cat = api.ctx.catalog
    _write_builder_synth(cat, "bm_train", rows, 1)
    _write_builder_synth(cat, "bm_test", rows // 20, 2)
    modeling = (
        "import numpy as np\n"
        "feats = [c for c in training_df.columns"
        " if c not in ('label', '_id')]\n"
        "features_training = (training_df[feats].to_numpy(np.float32),"
        " training_df['label'].to_numpy())\n"
        "features_testing = testing_df[feats].to_numpy(np.float32)\n"
        "features_evaluation = (testing_df[feats].to_numpy(np.float32),"
        " testing_df['label'].to_numpy())\n")

    out = {"rows": rows}
    for label, mesh_parallel in (("mesh", True), ("host", False)):
        t0 = time.perf_counter()
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/builder/sparkml", {}, {
                "trainDatasetName": "bm_train",
                "testDatasetName": "bm_test",
                "evaluationDatasetName": "bm_test",
                "modelingCode": modeling,
                "classifiersList": ["LR", "NB"],
                "meshParallel": mesh_parallel})
        _expect_created(status, body)
        for uri in body["result"]:
            _wait(api, uri, timeout=540)
        elapsed = time.perf_counter() - t0
        entry = {"pipeline_seconds": round(elapsed, 2),
                 "train_rows_per_sec": round(rows / elapsed, 2)}
        for c in ("LR", "NB"):
            meta = cat.get_metadata(f"bm_test{c}")
            entry[c.lower()] = {
                "accuracy": meta.get("accuracy"),
                "fitTime": meta.get("fitTime"),
                "engine": meta.get("engine"),
                "meshDevices": meta.get("meshDevices")}
        out[label] = entry
    api.ctx.jobs.shutdown()
    out["platform"] = jax.devices()[0].platform
    return out


def phase_warm_pipeline():
    """Feature-plane cache effect (docs/PERFORMANCE.md): the SAME
    mesh-parallel builder pipeline run twice on an unchanged dataset.
    The cold run pays Parquet read -> pandas -> numpy -> device_put ->
    trace+compile; the warm run should serve the host tier, the HBM
    arena and the executable cache — the reported deltas are the
    regression guard CI's perf-smoke stage asserts on."""
    import jax

    from learningorchestra_tpu.runtime import arena as arena_lib
    from learningorchestra_tpu.runtime import engine as engine_lib

    rows = int(os.environ.get("LO_BENCH_WARM_ROWS", "200000"))
    api, prefix = _make_api()
    cat = api.ctx.catalog
    _write_builder_synth(cat, "wp_train", rows, 1)
    _write_builder_synth(cat, "wp_test", max(rows // 20, 1), 2)
    modeling = (
        "import numpy as np\n"
        "feats = [c for c in training_df.columns"
        " if c not in ('label', '_id')]\n"
        "features_training = (training_df[feats].to_numpy(np.float32),"
        " training_df['label'].to_numpy())\n"
        "features_testing = testing_df[feats].to_numpy(np.float32)\n"
        "features_evaluation = (testing_df[feats].to_numpy(np.float32),"
        " testing_df['label'].to_numpy())\n")

    out = {"rows": rows}
    for label in ("cold", "warm"):
        t0 = time.perf_counter()
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/builder/sparkml", {}, {
                "trainDatasetName": "wp_train",
                "testDatasetName": "wp_test",
                "evaluationDatasetName": "wp_test",
                "modelingCode": modeling,
                "classifiersList": ["LR", "NB"],
                "meshParallel": True})
        _expect_created(status, body)
        for uri in body["result"]:
            _wait(api, uri, timeout=540)
        elapsed = time.perf_counter() - t0
        out[label] = {
            "pipeline_seconds": round(elapsed, 2),
            "featureCache": api.ctx.features.stats(),
            "arena": arena_lib.get_default_arena().stats(),
            "executableCache": engine_lib.executable_cache_stats()}
    api.ctx.jobs.shutdown()
    out["warm_feature_hits"] = (out["warm"]["featureCache"]["hits"]
                                - out["cold"]["featureCache"]["hits"])
    out["warm_arena_hits"] = (out["warm"]["arena"]["hits"]
                              - out["cold"]["arena"]["hits"])
    out["warm_executable_hits"] = (
        out["warm"]["executableCache"]["hits"]
        - out["cold"]["executableCache"]["hits"])
    out["speedup"] = round(
        out["cold"]["pipeline_seconds"]
        / max(out["warm"]["pipeline_seconds"], 1e-9), 2)
    out["platform"] = jax.devices()[0].platform
    return out


def phase_ingest():
    """Dataset-ingest throughput via POST /dataset/csv (SURVEY §3.1
    calls the reference's per-row insert_one loop "a known throughput
    cliff to beat", database.py:144): rows/sec from file on disk to
    queryable Parquet, via the streamed C++-parsed pipeline."""
    import numpy as np

    rows = int(os.environ.get("LO_BENCH_INGEST_ROWS", "2000000"))
    api, prefix = _make_api()
    path = os.path.join(tempfile.mkdtemp(prefix="lo_ingest_"), "big.csv")
    rng = np.random.default_rng(0)
    t_gen = time.perf_counter()
    with open(path, "w") as f:
        f.write("id,a,b,c,label\n")
        left, i0 = rows, 0
        while left:
            n = min(left, 200_000)
            a = rng.normal(size=n)
            b = rng.normal(size=n)
            c = rng.integers(0, 100, size=n)
            y = (a > 0).astype(np.int64)
            ids = np.arange(i0, i0 + n)
            block = "\n".join(
                f"{i},{x:.6f},{z:.6f},{w},{t}"
                for i, x, z, w, t in zip(ids, a, b, c, y))
            f.write(block + "\n")
            left -= n
            i0 += n
    gen_seconds = time.perf_counter() - t_gen

    t0 = time.perf_counter()
    status, body, _ = api.dispatch("POST", f"{prefix}/dataset/csv", {}, {
        "datasetName": "ingest_bench", "datasetURI": path})
    _expect_created(status, body)
    _wait(api, body["result"], timeout=420)
    elapsed = time.perf_counter() - t0
    n_rows = api.ctx.catalog.count_rows("ingest_bench")
    api.ctx.jobs.shutdown()
    if n_rows != rows:
        return {"error": f"ingest row mismatch: {n_rows} != {rows}"}
    return {"rows": rows,
            "ingest_seconds": round(elapsed, 2),
            "rows_per_sec": round(rows / elapsed, 2),
            "csv_gen_seconds": round(gen_seconds, 2),
            "native_core": _native_available()}


def _native_available() -> bool:
    try:
        from learningorchestra_tpu import native

        return native.available()
    except Exception:  # noqa: BLE001
        return False


def _torch_from_layer_configs(configs):
    """Build the torch twin FROM the shared flagship config so the
    proxy can't drift from the measured model."""
    import torch.nn as tnn

    acts = {"relu": tnn.ReLU, "tanh": tnn.Tanh, "sigmoid": tnn.Sigmoid,
            "gelu": tnn.GELU}

    def act_of(cfg, is_last):
        name = cfg.get("activation")
        if name in (None, "linear"):
            return None
        if is_last and name == "softmax":
            return None  # folded into CrossEntropyLoss, like the jax side
        if name not in acts:
            raise ValueError(f"proxy can't mirror activation {name!r}")
        return acts[name]()

    layers, in_ch, hw, flat = [], 1, IMG, None
    for i, cfg in enumerate(configs):
        kind = cfg["kind"]
        is_last = i == len(configs) - 1
        if kind == "reshape":
            in_ch, hw = cfg["shape"][2], cfg["shape"][0]
        elif kind == "conv2d":
            kernel = tuple(cfg.get("kernel", (3, 3)))
            layers.append(tnn.Conv2d(in_ch, cfg["filters"], kernel,
                                     padding="same"))
            act = act_of(cfg, is_last)
            if act is not None:
                layers.append(act)
            in_ch = cfg["filters"]
        elif kind == "maxpool2d":
            pool = tuple(cfg.get("pool", (2, 2)))
            stride = tuple(cfg.get("strides", pool))
            layers.append(tnn.MaxPool2d(pool, stride))
            hw = (hw - pool[0]) // stride[0] + 1
        elif kind == "flatten":
            layers.append(tnn.Flatten())
            flat = in_ch * hw * hw
        elif kind == "dense":
            layers.append(tnn.Linear(flat, cfg["units"]))
            act = act_of(cfg, is_last)
            if act is not None:
                layers.append(act)
            flat = cfg["units"]
        else:
            raise ValueError(f"proxy can't mirror layer kind {kind!r}")
    return tnn.Sequential(*layers)


def phase_proxy(max_seconds=60.0):
    """The same CNN / batch size on torch-CPU — the reference's
    in-process single-host execution model."""
    import numpy as np
    import torch
    import torch.nn as tnn

    torch.set_num_threads(os.cpu_count() or 4)
    model = _torch_from_layer_configs(CNN_LAYERS)
    opt = torch.optim.Adam(model.parameters(), lr=1e-3)
    loss_fn = tnn.CrossEntropyLoss()
    x = torch.randn(BATCH, 1, IMG, IMG)
    y = torch.from_numpy(
        np.random.default_rng(0).integers(0, CLASSES, BATCH))
    # warmup
    for _ in range(2):
        opt.zero_grad()
        loss_fn(model(x), y).backward()
        opt.step()
    steps = 0
    t0 = time.perf_counter()
    while steps < 30 and time.perf_counter() - t0 < max_seconds:
        opt.zero_grad()
        loss_fn(model(x), y).backward()
        opt.step()
        steps += 1
    dt = time.perf_counter() - t0
    return {"samples_per_sec": round(steps * BATCH / dt, 2)}


def phase_concurrent_jobs():
    """Spatial slice multiplexing (docs/SCALING.md): the same TWO
    small train fits run (a) serialized behind a single full-mesh
    lease (LO_MESH_LEASES=1) and (b) concurrently on disjoint
    half-mesh slices (LO_MESH_LEASES=2 + half-mesh footprints). Each
    configuration runs once unmeasured (compiles both slice
    executables; placement is deterministic so the timed run reuses
    them) and once timed. CI gates on concurrent < 0.75x serialized."""
    import jax
    import numpy as np

    from learningorchestra_tpu import config as config_mod
    from learningorchestra_tpu.catalog import Catalog
    from learningorchestra_tpu.models.estimators import (
        LogisticRegressionJAX,
    )
    from learningorchestra_tpu.services.jobs import JobManager

    total = len(jax.devices())
    if total < 2:
        return {"skipped": f"needs >=2 devices, have {total}"}
    half = total // 2
    rows = int(os.environ.get("LO_BENCH_CONCURRENT_ROWS", "8192"))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(rows, 32)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int64)

    def fit_job():
        LogisticRegressionJAX(epochs=3, batch_size=1024).fit(x, y)
        return "ok"

    def run_round(leases, footprint):
        home = tempfile.mkdtemp(prefix="lo_bench_slice_")
        cfg = config_mod.set_config(
            config_mod.Config(home=home, mesh_leases=leases))
        cat = Catalog(cfg.catalog_path, cfg.datasets_dir)
        jobs = JobManager(cat, max_workers=4, mesh_leases=leases)
        try:
            for batch in ("w", "t"):  # w = warm-up, t = timed
                names = [f"{batch}{i}" for i in (1, 2)]
                for n in names:
                    cat.create_collection(n, "train/tensorflow")
                t0 = time.perf_counter()
                for n in names:
                    jobs.submit(n, fit_job, needs_mesh=True,
                                pool="train", footprint=footprint)
                for n in names:
                    jobs.wait(n, timeout=600)
                elapsed = time.perf_counter() - t0
            return elapsed
        finally:
            jobs.shutdown()
            cat.close()

    serialized = run_round(1, None)
    concurrent = run_round(2, {"devices": half})
    return {"devices_total": total, "slice_devices": half,
            "serialized_seconds": round(serialized, 3),
            "concurrent_seconds": round(concurrent, 3),
            "ratio": round(concurrent / serialized, 3),
            "platform": jax.devices()[0].platform}


def phase_sentinel_overhead():
    """Cost of the armed health sentinel (docs/RELIABILITY.md): the
    same MLP fit with the sentinel off vs ``skip`` (the most
    instrumented variant — health word + on-device drop guard). One
    model per arm keeps both executables warm; repeats interleave so
    host drift taxes both arms equally; min-of-repeats is the
    steady-state number CI gates at < 3% overhead."""
    import jax
    import numpy as np

    from learningorchestra_tpu import config as config_mod
    from learningorchestra_tpu.models.neural import NeuralModel

    home = tempfile.mkdtemp(prefix="lo_bench_health_")
    config_mod.set_config(config_mod.Config(home=home))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8192, 64)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int64)

    def build():
        return NeuralModel([
            {"kind": "dense", "units": 128, "activation": "relu"},
            {"kind": "dense", "units": 128, "activation": "relu"},
            {"kind": "dense", "units": 2, "activation": "softmax"}])

    arms = {"off": (build(), None), "skip": (build(), "skip")}
    for model, policy in arms.values():  # compile warm-up, untimed
        model.fit(x, y, epochs=1, batch_size=256, shuffle=False,
                  health_policy=policy)
    times = {name: [] for name in arms}
    for _ in range(5):
        for name, (model, policy) in arms.items():
            t0 = time.perf_counter()
            model.fit(x, y, epochs=3, batch_size=256, shuffle=False,
                      health_policy=policy)
            times[name].append(time.perf_counter() - t0)
    best = {name: min(ts) for name, ts in times.items()}
    return {"off_seconds": round(best["off"], 4),
            "skip_seconds": round(best["skip"], 4),
            "overhead_ratio": round(best["skip"] / best["off"], 4),
            "platform": jax.devices()[0].platform}


def phase_obs_overhead():
    """Tracer correctness + cost (docs/OBSERVABILITY.md). Two parts:
    (1) one small checkpointed train job through the REST stack must
    leave a span tree holding queue wait, a cold compile, per-epoch
    and checkpointCommit spans plus a per-epoch timeline; (2) the same
    MLP fit timed with the tracer recording (under an open job span)
    vs tracing disabled, interleaved, min-of-repeats — the tracer
    shares the sentinel's < 3% steady-state overhead gate."""
    import jax
    import numpy as np

    from learningorchestra_tpu import config as config_mod
    from learningorchestra_tpu.models.neural import NeuralModel
    from learningorchestra_tpu.observability import (
        timeline as obs_timeline)
    from learningorchestra_tpu.observability import trace as obs_trace

    # -- (1) correctness through the full job path
    api, prefix = _make_api()
    home = api.ctx.config.home
    try:
        _run_pipeline(
            api, prefix, "obs",
            ("import numpy as np\n"
             "rng = np.random.default_rng(0)\n"
             "x = rng.normal(size=(2048, 32)).astype(np.float32)\n"
             "y = (x[:, 0] > 0).astype(np.int32)\n"
             "response = {'x': x, 'y': y}\n"),
            "learningorchestra_tpu.models", "NeuralModel",
            {"layer_configs": [
                {"kind": "dense", "units": 32, "activation": "relu"},
                {"kind": "dense", "units": 2,
                 "activation": "softmax"}]},
            {"x": "$obs_data.x", "y": "$obs_data.y", "epochs": 2,
             "batch_size": 128, "shuffle": False, "checkpoint": True})
        totals = obs_trace.durations_by_name("obs_train")
        spans_present = {k: k in totals for k in
                         ("queueWait", "compile", "epoch",
                          "checkpointCommit")}
        cold_compiles = sum(
            1 for s in obs_trace.spans_of("obs_train")
            if s.name == "compile" and s.attrs.get("cold"))
        tl = obs_timeline.summary("obs_train") or {}
    finally:
        api.ctx.jobs.shutdown()

    # -- (2) steady-state overhead, traced vs LO_TRACE=0
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8192, 64)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int64)
    model = NeuralModel([
        {"kind": "dense", "units": 128, "activation": "relu"},
        {"kind": "dense", "units": 128, "activation": "relu"},
        {"kind": "dense", "units": 2, "activation": "softmax"}])
    model.fit(x, y, epochs=1, batch_size=256, shuffle=False)  # warm-up
    # the timed region must be long enough (~0.5 s) that host
    # scheduler jitter cannot fake a 3% delta between the arms
    times = {"traced": [], "untraced": []}
    for _ in range(5):
        config_mod.set_config(config_mod.Config(home=home, trace=True))
        t0 = time.perf_counter()
        with obs_trace.span("fit", trace="obs_overhead"):
            model.fit(x, y, epochs=18, batch_size=256, shuffle=False)
        times["traced"].append(time.perf_counter() - t0)
        config_mod.set_config(config_mod.Config(home=home,
                                                trace=False))
        t0 = time.perf_counter()
        model.fit(x, y, epochs=18, batch_size=256, shuffle=False)
        times["untraced"].append(time.perf_counter() - t0)
    best = {name: min(ts) for name, ts in times.items()}
    return {"spans_present": spans_present,
            "cold_compiles": cold_compiles,
            "timeline_windows": int(tl.get("windows", 0)),
            "timeline_steps": int(tl.get("steps", 0)),
            "traced_seconds": round(best["traced"], 4),
            "untraced_seconds": round(best["untraced"], 4),
            "overhead_ratio": round(
                best["traced"] / best["untraced"], 4),
            "platform": jax.devices()[0].platform}


def phase_sentinel_chaos():
    """NaN + bit-rot chaos through the full REST stack: an armed
    ``engine_step`` NaN plus a corrupted checkpoint write, under
    healthPolicy rollback. The job must FINISH (rollback-to-last-good,
    quarantine-and-fallback restore), not dead-letter — CI gates on
    exactly that."""
    import jax

    from learningorchestra_tpu import config as config_mod
    from learningorchestra_tpu.runtime import health as health_lib
    from learningorchestra_tpu.services import faults
    from learningorchestra_tpu.services.server import Api

    home = tempfile.mkdtemp(prefix="lo_bench_chaos_")
    config_mod.set_config(config_mod.Config(
        home=home,
        fault_inject="engine_step:1:nan,ckpt_write:1:corrupt:64"))
    faults.reset()
    health_lib.reset_health_stats()
    api = Api()
    prefix = "/api/learningOrchestra/v1"
    try:
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/function/python", {}, {
                "name": "chaos_data", "functionParameters": {},
                "function": ("import numpy as np\n"
                             "rng = np.random.default_rng(0)\n"
                             "x = rng.normal(size=(2048, 32))"
                             ".astype(np.float32)\n"
                             "y = (x[:, 0] > 0).astype(np.int32)\n"
                             "response = {'x': x, 'y': y}\n")})
        _expect_created(status, body)
        _wait(api, body["result"])
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/model/tensorflow", {}, {
                "modelName": "chaos_model",
                "modulePath": "learningorchestra_tpu.models",
                "class": "NeuralModel",
                "classParameters": {"layer_configs": [
                    {"kind": "dense", "units": 32,
                     "activation": "relu"},
                    {"kind": "dense", "units": 2,
                     "activation": "softmax"}]}})
        _expect_created(status, body)
        _wait(api, body["result"])
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/train/tensorflow", {}, {
                "name": "chaos_train", "modelName": "chaos_model",
                "method": "fit",
                "healthPolicy": {"action": "rollback",
                                 "maxRollbacks": 2},
                "methodParameters": {
                    "x": "$chaos_data.x", "y": "$chaos_data.y",
                    "epochs": 4, "batch_size": 128,
                    "shuffle": False, "checkpoint": True}})
        _expect_created(status, body)
        meta = _wait(api, body["result"])
        stats = health_lib.health_stats()
        return {"status": meta.get("status"),
                "finished": bool(meta.get("finished")),
                "rollbacks": int(meta.get("rollbacks", 0)),
                "nonfinite_steps": int(meta.get("nonfiniteSteps", 0)),
                "quarantined": stats["quarantined"],
                "platform": jax.devices()[0].platform}
    finally:
        api.ctx.jobs.shutdown()


def phase_monitor_smoke():
    """Cluster monitor + SLO watchdog end-to-end
    (docs/OBSERVABILITY.md "Cluster monitor, SLOs & alerts"). Two
    parts: (1) chaos — an armed ``serving_step`` latency fault
    inflates request latency through a real resident predict session
    until the watchdog's ``servingP99`` page alert FIRES and
    ``GET /healthz`` flips to 503; clearing the fault must RESOLVE the
    alert and return /healthz to 200 with no restart. (2) sampler
    steady-state cost: the same MLP fit with the monitor ticking every
    50 ms vs monitor stopped, interleaved, min-of-repeats — CI gates
    the ratio at < 1%."""
    import jax
    import numpy as np

    from learningorchestra_tpu import config as config_mod
    from learningorchestra_tpu.models.estimators import \
        LogisticRegressionJAX
    from learningorchestra_tpu.models.neural import NeuralModel
    from learningorchestra_tpu.observability import hist as obs_hist
    from learningorchestra_tpu.services import faults
    from learningorchestra_tpu.services.context import _start_monitor
    from learningorchestra_tpu.services.server import Api

    home = tempfile.mkdtemp(prefix="lo_bench_monitor_")
    config_mod.set_config(config_mod.Config(
        home=home,
        monitor_interval_ms=100.0,
        slo_serving_p99_ms=60.0,
        slo_fast_window_s=1.0,
        slo_slow_window_s=2.0,
        fault_inject="serving_step:1000:latency:0.25"))
    faults.reset()
    obs_hist.reset()
    api = Api()
    prefix = "/api/learningOrchestra/v1"
    out = {"platform": jax.devices()[0].platform}
    try:
        # -- (1) resident predict session over a tiny fitted model
        rng = np.random.default_rng(0)
        x = rng.normal(size=(512, 8)).astype(np.float32)
        y = (x[:, 0] > 0).astype(np.int32)
        clf = LogisticRegressionJAX(epochs=2, batch_size=128)
        clf.fit(x, y)
        api.ctx.artifacts.save(clf, "mon_clf", "train/tensorflow")
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/serve/mon_clf", {}, {})
        _expect_created(status, body)
        rows = [[float(v) for v in r] for r in rng.normal(size=(4, 8))]

        def predict():
            s2, b2, _ = api.dispatch(
                "POST", f"{prefix}/serve/mon_clf/predict", {},
                {"x": rows})
            if s2 != 200:
                raise RuntimeError(
                    f"monitor predict failed: {s2} {b2}")

        watchdog = api.ctx.monitor.watchdog

        def fired():
            return any(a["name"] == "servingP99"
                       for a in watchdog.firing())

        # every predict rides a ~0.25 s injected iteration sleep; the
        # background watchdog must see a >60 ms p99 in the fast AND
        # slow windows and fire the page alert
        deadline = time.monotonic() + 90
        while not fired() and time.monotonic() < deadline:
            predict()
        out["alert_fired"] = fired()
        status, _, _ = api.dispatch("GET", "/healthz", {}, None)
        out["healthz_during"] = status
        firing = [a for a in watchdog.firing()
                  if a["name"] == "servingP99"]
        out["alert_trace"] = firing[0]["trace"] if firing else None

        # clear the fault and stop sending: once the fast window holds
        # no slow observations the alert resolves on its own
        api.ctx.config.fault_inject = ""
        deadline = time.monotonic() + 60
        while fired() and time.monotonic() < deadline:
            time.sleep(0.2)
        out["alert_resolved"] = not fired()
        status, _, _ = api.dispatch("GET", "/healthz", {}, None)
        out["healthz_after"] = status
        api.dispatch("DELETE", f"{prefix}/serve/mon_clf", {}, None)

        # -- (2) sampler overhead: monitored fit vs monitor stopped,
        # at the PRODUCTION sampling rate (1 s tick — a sample itself
        # costs ~0.1 ms; sub-second ticks mostly measure GIL wakeup
        # contention with the CPU dispatch loop, which the deployed
        # default never pays). Fresh monitors per rep so the arms
        # interleave; the ~3 s timed region spans several ticks
        api.ctx.monitor.stop()
        api.ctx.config.monitor_interval_ms = 1000.0
        xb = rng.normal(size=(8192, 64)).astype(np.float32)
        yb = (xb[:, 0] > 0).astype(np.int64)
        model = NeuralModel([
            {"kind": "dense", "units": 128, "activation": "relu"},
            {"kind": "dense", "units": 128, "activation": "relu"},
            {"kind": "dense", "units": 2, "activation": "softmax"}])
        model.fit(xb, yb, epochs=1, batch_size=256,
                  shuffle=False)  # warm-up pays the compile
        times = {"on": [], "off": []}
        for _ in range(5):
            mon = _start_monitor(api.ctx)
            t0 = time.perf_counter()
            model.fit(xb, yb, epochs=60, batch_size=256,
                      shuffle=False)
            times["on"].append(time.perf_counter() - t0)
            mon.stop()
            t0 = time.perf_counter()
            model.fit(xb, yb, epochs=60, batch_size=256,
                      shuffle=False)
            times["off"].append(time.perf_counter() - t0)
        best = {name: min(ts) for name, ts in times.items()}
        out.update({
            "monitored_seconds": round(best["on"], 4),
            "unmonitored_seconds": round(best["off"], 4),
            "overhead_ratio": round(best["on"] / best["off"], 4),
        })
    finally:
        if api.ctx.monitor is not None:
            api.ctx.monitor.stop()
        api.ctx.serving.close()
        api.ctx.jobs.shutdown()
    return out


def phase_incident_smoke():
    """Incident flight recorder end-to-end (docs/OBSERVABILITY.md
    "Incidents & flight recorder"). Three parts: (1) chaos — the same
    armed ``serving_step`` latency fault as monitor_smoke drives a
    real resident predict session until the ``servingP99`` page alert
    fires, and the recorder must AUTO-capture a debug bundle whose
    manifest carries every evidence section, the firing alert context
    and zero collector errors, downloadable through the REST tar
    route; (2) bounds — a re-trigger inside the cooldown is muted and
    ``LO_INCIDENT_KEEP`` retention holds the bundle count; (3)
    steady-state cost: the obs_overhead MLP fit with an idle recorder
    armed vs recorder off, interleaved, min-of-repeats — CI gates the
    ratio at < 3%."""
    import jax
    import numpy as np

    from learningorchestra_tpu import config as config_mod
    from learningorchestra_tpu.models.estimators import \
        LogisticRegressionJAX
    from learningorchestra_tpu.models.neural import NeuralModel
    from learningorchestra_tpu.observability import hist as obs_hist
    from learningorchestra_tpu.observability import \
        incidents as obs_incidents
    from learningorchestra_tpu.runtime import health as health_lib
    from learningorchestra_tpu.services import faults
    from learningorchestra_tpu.services.context import _start_incidents
    from learningorchestra_tpu.services.server import Api

    home = tempfile.mkdtemp(prefix="lo_bench_incident_")
    config_mod.set_config(config_mod.Config(
        home=home,
        monitor_interval_ms=100.0,
        slo_serving_p99_ms=60.0,
        slo_fast_window_s=1.0,
        slo_slow_window_s=2.0,
        fault_inject="serving_step:1000:latency:0.25"))
    faults.reset()
    obs_hist.reset()
    api = Api()
    prefix = "/api/learningOrchestra/v1"
    out = {"platform": jax.devices()[0].platform}
    try:
        recorder = api.ctx.incidents
        # -- (1) resident predict session under the latency fault
        rng = np.random.default_rng(0)
        x = rng.normal(size=(512, 8)).astype(np.float32)
        y = (x[:, 0] > 0).astype(np.int32)
        clf = LogisticRegressionJAX(epochs=2, batch_size=128)
        clf.fit(x, y)
        api.ctx.artifacts.save(clf, "inc_clf", "train/tensorflow")
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/serve/inc_clf", {}, {})
        _expect_created(status, body)
        rows = [[float(v) for v in r] for r in rng.normal(size=(4, 8))]

        def slo_bundles():
            return [b for b in recorder.list()
                    if b["trigger"] == "slo:servingP99"]

        deadline = time.monotonic() + 90
        while not slo_bundles() and time.monotonic() < deadline:
            s2, b2, _ = api.dispatch(
                "POST", f"{prefix}/serve/inc_clf/predict", {},
                {"x": rows})
            if s2 != 200:
                raise RuntimeError(
                    f"incident predict failed: {s2} {b2}")
        bundles = slo_bundles()
        out["incident_captured"] = bool(bundles)
        if bundles:
            iid = bundles[0]["id"]
            manifest = recorder.manifest(iid)
            required = {"cluster.json", "alerts.json", "memory.json",
                        "perf.json", "metrics.json", "eventlog.tail",
                        "config.json", "versions.json"}
            present = set(manifest["files"])
            out["sections_missing"] = sorted(required - present)
            out["manifest_errors"] = len(manifest["errors"])
            out["bundle_bytes"] = manifest["totalBytes"]
            alert = manifest["context"].get("alert") or {}
            out["alert_context_ok"] = \
                alert.get("name") == "servingP99" and \
                alert.get("transition") == "firing"
            out["implicated_serving"] = any(
                t.startswith("serve/") for t in
                manifest["implicated"]["traces"])
            status, blob, ctype = api.dispatch(
                "GET",
                f"{prefix}/observability/incidents/{iid}/download",
                {}, None)
            out["download_ok"] = (status == 200
                                  and ctype == "application/x-tar"
                                  and len(blob) > 0)
            out["download_bytes"] = len(blob)
        # -- (2) bounds: cooldown mutes a re-fire; retention holds
        out["cooldown_muted"] = \
            recorder.trigger("slo:servingP99") is False
        api.ctx.config.incident_keep = 2
        for i in range(3):
            recorder.capture("manual", {"rep": i})
        out["retention_ok"] = len(recorder.list()) <= 2
        api.ctx.config.fault_inject = ""
        api.dispatch("DELETE", f"{prefix}/serve/inc_clf", {}, None)

        # -- (3) recorder steady-state overhead: an armed-but-idle
        # recorder (worker blocked on its queue) vs recorder off,
        # fresh per rep so the arms interleave; the monitor is stopped
        # so only the recorder differs between arms
        api.ctx.monitor.stop()
        health_lib.remove_listener(api.ctx._health_listener)
        obs_incidents.set_recorder(None)
        recorder.close()
        api.ctx.incidents = None
        api.ctx.config.incident_keep = 8
        xb = rng.normal(size=(8192, 64)).astype(np.float32)
        yb = (xb[:, 0] > 0).astype(np.int64)
        model = NeuralModel([
            {"kind": "dense", "units": 128, "activation": "relu"},
            {"kind": "dense", "units": 128, "activation": "relu"},
            {"kind": "dense", "units": 2, "activation": "softmax"}])
        model.fit(xb, yb, epochs=1, batch_size=256,
                  shuffle=False)  # warm-up pays the compile
        times = {"on": [], "off": []}
        for _ in range(5):
            rec, listener = _start_incidents(api.ctx)
            t0 = time.perf_counter()
            model.fit(xb, yb, epochs=60, batch_size=256,
                      shuffle=False)
            times["on"].append(time.perf_counter() - t0)
            health_lib.remove_listener(listener)
            obs_incidents.set_recorder(None)
            rec.close()
            t0 = time.perf_counter()
            model.fit(xb, yb, epochs=60, batch_size=256,
                      shuffle=False)
            times["off"].append(time.perf_counter() - t0)
        best = {name: min(ts) for name, ts in times.items()}
        out.update({
            "recorded_seconds": round(best["on"], 4),
            "unrecorded_seconds": round(best["off"], 4),
            "overhead_ratio": round(best["on"] / best["off"], 4),
        })
    finally:
        if api.ctx.monitor is not None:
            api.ctx.monitor.stop()
        if api.ctx.incidents is not None:
            if obs_incidents.get_recorder() is api.ctx.incidents:
                obs_incidents.set_recorder(None)
            api.ctx.incidents.close()
        api.ctx.serving.close()
        api.ctx.jobs.shutdown()
    return out


def phase_sweep_fusion():
    """Vectorized sweep fusion (docs/PERFORMANCE.md "Sweep fusion"):
    an 8-point learning-rate sweep over an MNIST-shaped MLP, fused
    (one vmapped compiled program for the cohort) vs serial (fusion
    off, one trial at a time — each point paying its own compile and
    dispatch). A second fused run measures warm retraces: the fused
    epoch program must trace exactly once per cohort, so the warm
    delta CI gates on is zero."""
    import jax
    import numpy as np

    from learningorchestra_tpu import config as config_mod
    from learningorchestra_tpu.models.neural import NeuralModel
    from learningorchestra_tpu.models.sweep import GridSearch
    from learningorchestra_tpu.runtime import engine as engine_lib

    rows = int(os.environ.get("LO_BENCH_SWEEP_ROWS", "2048"))
    epochs = int(os.environ.get("LO_BENCH_SWEEP_EPOCHS", "2"))
    home = tempfile.mkdtemp(prefix="lo_bench_sweep_")
    rng = np.random.default_rng(0)
    # MNIST-shaped synthetic blobs: 784 features, 10 separable classes
    y = rng.integers(0, 10, size=rows).astype(np.int32)
    x = rng.normal(size=(rows, 784)).astype(np.float32)
    x[np.arange(rows), y] += 3.0
    grid = {"learning_rate": [3e-4, 5e-4, 1e-3, 2e-3,
                              3e-3, 5e-3, 1e-2, 2e-2]}

    def estimator():
        model = NeuralModel([
            {"kind": "dense", "units": 128, "activation": "relu"},
            {"kind": "dense", "units": 10, "activation": "softmax"}],
            name="sweep_bench")
        model.compile({"kind": "adam", "learning_rate": 1e-3})
        return model

    def run_sweep():
        sweep = GridSearch(estimator(), grid, validation_split=0.2,
                           refit=False)
        t0 = time.perf_counter()
        sweep.fit(x, y, epochs=epochs, batch_size=128)
        return time.perf_counter() - t0, sweep

    config_mod.set_config(config_mod.Config(home=home,
                                            sweep_fusion=True))
    fused_seconds, fused = run_sweep()
    if fused.fusion_info_["fusedTrials"] != len(
            grid["learning_rate"]):
        return {"error": "planner did not fuse the full grid: "
                         f"{fused.fusion_info_}"}
    traces_before = engine_lib.fused_epoch_traces()
    fused_warm_seconds, _ = run_sweep()
    warm_retraces = engine_lib.fused_epoch_traces() - traces_before

    config_mod.set_config(config_mod.Config(home=home,
                                            sweep_fusion=False))
    # serial arm: one trial at a time — the pre-fusion cost model
    # (max_parallel=1 keeps the comparison about fusion, not the
    # sub-slice scheduler)
    serial_sweep = GridSearch(estimator(), grid, validation_split=0.2,
                              max_parallel=1, refit=False)
    t0 = time.perf_counter()
    serial_sweep.fit(x, y, epochs=epochs, batch_size=128)
    serial_seconds = time.perf_counter() - t0

    if fused.best_params_ != serial_sweep.best_params_:
        return {"error": "fused and serial sweeps disagree on the "
                         f"winner: {fused.best_params_} vs "
                         f"{serial_sweep.best_params_}"}
    return {"points": len(grid["learning_rate"]),
            "rows": rows, "epochs": epochs,
            "fused_seconds": round(fused_seconds, 3),
            "fused_warm_seconds": round(fused_warm_seconds, 3),
            "serial_seconds": round(serial_seconds, 3),
            "speedup": round(serial_seconds / fused_seconds, 3),
            "warm_retraces": int(warm_retraces),
            "fused_trials": fused.fusion_info_["fusedTrials"],
            "cohorts": fused.fusion_info_["cohorts"],
            "best_lr": fused.best_params_["learning_rate"],
            "platform": jax.devices()[0].platform}


def phase_ckpt_stall():
    """Train-thread checkpoint stall: synchronous commit vs the async
    tiered manager (docs/RELIABILITY.md "Async checkpointing"). The
    same multi-MB state tree is saved SAVES times; the sync arm pays
    serialize+hash+fsync on the caller thread, the async arm pays only
    the device->host snapshot + enqueue while the background worker
    commits during the (emulated) epoch compute between saves. CI
    gates on stall_ratio < 0.10."""
    import jax
    import numpy as np

    from learningorchestra_tpu import config as config_mod
    from learningorchestra_tpu.runtime.async_ckpt import (
        AsyncCheckpointManager,
    )
    from learningorchestra_tpu.runtime.checkpoint import Checkpointer

    home = tempfile.mkdtemp(prefix="lo_bench_ckpt_")
    config_mod.set_config(config_mod.Config(home=home))
    mb = int(os.environ.get("LO_BENCH_CKPT_MB", "32"))
    saves = int(os.environ.get("LO_BENCH_CKPT_SAVES", "5"))
    leaves = 8
    n = mb * (1 << 20) // 4 // leaves
    rng = np.random.default_rng(0)
    tree = {"step": np.int32(0),
            "params": {f"w{i}": jax.device_put(
                rng.normal(size=(n,)).astype(np.float32))
                for i in range(leaves)}}

    def timed_saves(ckpt, gap):
        stall = 0.0
        for step in range(1, saves + 1):
            t0 = time.perf_counter()
            ckpt.save(step, tree)
            stall += time.perf_counter() - t0
            if gap:
                time.sleep(gap)
        return stall

    sync = Checkpointer(os.path.join(home, "sync"), max_to_keep=2)
    sync.save(0, tree)  # warm-up: first-write/page-cache costs
    sync_stall = timed_saves(sync, 0.0)
    sync.close()
    per_commit = sync_stall / saves

    amgr = AsyncCheckpointManager(
        Checkpointer(os.path.join(home, "async"), max_to_keep=2),
        inflight=2)
    amgr.save(0, tree)  # warm-up
    amgr.wait_until_finished()
    # the gap emulates an epoch of compute the background commit
    # overlaps, sized to the measured commit so the bounded queue's
    # backpressure never engages in the steady state being measured
    async_stall = timed_saves(amgr, per_commit)
    amgr.wait_until_finished()
    amgr.close()

    return {"payload_mb": mb, "saves": saves,
            "sync_stall_seconds": round(sync_stall, 4),
            "async_stall_seconds": round(async_stall, 4),
            "commit_seconds_each": round(per_commit, 4),
            "stall_ratio": round(async_stall / sync_stall, 4),
            "platform": jax.devices()[0].platform}


def phase_migration_smoke():
    """Live migration must be invisible to the math, and defrag must
    place an aged waiter (docs/SCALING.md §7). Part 1 runs the same
    deterministic fit twice through the slice scheduler — untouched vs
    force-migrated mid-fit — and compares final params bit-for-bit.
    Part 2 re-creates the fragmentation scenario (a 6/8-device holder
    starving a 4-device waiter) with LO_SLICE_DEFRAG armed; the
    waiter must land WHILE the holder still runs."""
    import threading

    import jax
    import numpy as np

    from learningorchestra_tpu import config as config_mod
    from learningorchestra_tpu.catalog import Catalog
    from learningorchestra_tpu.runtime import preempt
    from learningorchestra_tpu.services.jobs import JobManager

    total = len(jax.devices())
    if total < 2:
        return {"skipped": f"needs >=2 devices, have {total}"}
    half = total // 2
    home = tempfile.mkdtemp(prefix="lo_bench_mig_")
    cfg = config_mod.set_config(config_mod.Config(home=home))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2048, 32)).astype(np.float32)
    y = (x @ rng.normal(size=(32, 1)).astype(np.float32))[:, 0]

    def fit_job(ckpt_dir, sink):
        import jax.numpy as jnp
        import optax

        from learningorchestra_tpu.runtime import data as data_lib
        from learningorchestra_tpu.runtime import mesh as mesh_lib
        from learningorchestra_tpu.runtime.checkpoint import (
            Checkpointer,
        )
        from learningorchestra_tpu.runtime.engine import (
            Engine, mse_loss, to_host)

        def apply_fn(params, model_state, batch, train, step_rng):
            return batch["x"] @ params["w"], model_state

        def job():
            eng = Engine(apply_fn=apply_fn, loss_fn=mse_loss,
                         optimizer=optax.sgd(0.01),
                         mesh=mesh_lib.current_mesh(),
                         compute_dtype=jnp.float32,
                         donate_state=False)
            state = eng.init_state(
                {"w": jnp.zeros((32,), jnp.float32)})
            batcher = data_lib.ArrayBatcher(
                {"x": x, "y": y}, batch_size=256, seed=3)
            ckpt = Checkpointer(ckpt_dir)
            try:
                state, _ = eng.fit(state, batcher, epochs=6, seed=7,
                                   checkpointer=ckpt,
                                   scan_batches=False)
            finally:
                ckpt.close()
            sink.append(to_host(state))
            return "ok"

        return job

    # part 1: forced migration, bit-identical resume
    cat = Catalog(cfg.catalog_path, cfg.datasets_dir)
    jobs = JobManager(cat, max_workers=4, mesh_leases=2)
    results = {}
    elapsed = {}
    try:
        for tag in ("base", "mig"):
            name = f"mig_{tag}"
            cat.create_collection(name, "train/tensorflow")
            sink = []
            results[tag] = sink
            t0 = time.perf_counter()
            jobs.submit(name, fit_job(os.path.join(home, tag), sink),
                        needs_mesh=True, pool="train",
                        footprint={"devices": half})
            if tag == "mig":
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline:
                    if jobs.migrate(name):
                        break
                    time.sleep(0.02)
            jobs.wait(name, timeout=300)
            elapsed[tag] = time.perf_counter() - t0
        mig_stats = jobs.migration_stats()
    finally:
        jobs.shutdown()
        cat.close()
    base, mig = results["base"][0], results["mig"][0]
    bit_identical = bool(
        int(base.step) == int(mig.step)
        and np.array_equal(np.asarray(base.params["w"]),
                           np.asarray(mig.params["w"])))

    # part 2: defrag-via-migration places an aged waiter
    cat2 = Catalog(os.path.join(home, "cat2.db"),
                   os.path.join(home, "ds2"))
    jobs2 = JobManager(cat2, max_workers=4, mesh_leases=2,
                       slice_aging_seconds=0.3, slice_defrag=0.99)
    stop = threading.Event()
    holder_migrated = threading.Event()

    def holder():
        while not stop.is_set():
            if preempt.migrate_requested():
                performed, _devices = preempt.perform_migrate()
                if performed:
                    holder_migrated.set()
            time.sleep(0.02)
        return "held"

    waiter_placed = False
    big = max(2, (3 * total) // 4)
    try:
        cat2.create_collection("frag_holder", "train/tensorflow")
        cat2.create_collection("frag_waiter", "train/tensorflow")
        jobs2.submit("frag_holder", holder, needs_mesh=True,
                     pool="train", footprint={"devices": big})
        time.sleep(0.2)  # holder claims its slice
        t_defrag = time.perf_counter()
        jobs2.submit("frag_waiter", lambda: "b", needs_mesh=True,
                     pool="train", footprint={"devices": half})
        try:
            waiter_placed = jobs2.wait("frag_waiter",
                                       timeout=60) == "b"
        except Exception:
            waiter_placed = False
        defrag_seconds = time.perf_counter() - t_defrag
        defrag_stats = jobs2.migration_stats()
    finally:
        stop.set()
        try:
            jobs2.wait("frag_holder", timeout=30)
        except Exception:
            pass
        jobs2.shutdown()
        cat2.close()

    return {"devices_total": total, "slice_devices": half,
            "bit_identical": bit_identical,
            "migrations_requested": mig_stats["requested"],
            "base_seconds": round(elapsed["base"], 3),
            "migrated_seconds": round(elapsed["mig"], 3),
            "defrag_placed_waiter": bool(
                waiter_placed and holder_migrated.is_set()),
            "defrag_picks": defrag_stats["defragPicks"],
            "defrag_seconds": round(defrag_seconds, 3),
            "platform": jax.devices()[0].platform}


def phase_elastic_smoke():
    """Elastic autoscaling end-to-end (docs/SCALING.md "Elastic
    autoscaling"). Part 1 runs a mixed elastic/rigid workload vs a
    rigid-only twin: an elastic holder blocks a larger rigid waiter;
    the closed policy loop must SHRINK the holder so the waiter
    overlaps it instead of serializing behind it (makespan
    comparison). Part 2 injects SLO-page pressure (the stubbed
    watchdog stands in for a serving p99 burn) and the victim must
    shrink while it keeps training to completion. Part 3 arms the
    ``autoscale_resize`` fault site: the failed resize must ROLL BACK
    to the old slice and the run must stay bit-identical to an
    untouched rigid twin."""
    import dataclasses
    import threading

    import jax
    import numpy as np

    from learningorchestra_tpu import config as config_mod
    from learningorchestra_tpu.catalog import Catalog
    from learningorchestra_tpu.services import faults
    from learningorchestra_tpu.services.autoscaler import SliceAutoscaler
    from learningorchestra_tpu.services.jobs import JobManager

    total = len(jax.devices())
    if total < 8:
        return {"skipped": f"needs >=8 devices, have {total}"}
    home = tempfile.mkdtemp(prefix="lo_bench_ela_")
    cfg = config_mod.set_config(config_mod.Config(home=home))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2048, 32)).astype(np.float32)
    y = (x @ rng.normal(size=(32, 1)).astype(np.float32))[:, 0]

    def fit_job(ckpt_dir, sink, epochs, batch):
        import jax.numpy as jnp
        import optax

        from learningorchestra_tpu.runtime import data as data_lib
        from learningorchestra_tpu.runtime import mesh as mesh_lib
        from learningorchestra_tpu.runtime.checkpoint import (
            Checkpointer,
        )
        from learningorchestra_tpu.runtime.engine import (
            Engine, mse_loss, to_host)

        def apply_fn(params, model_state, batch_, train, step_rng):
            return batch_["x"] @ params["w"], model_state

        def job():
            eng = Engine(apply_fn=apply_fn, loss_fn=mse_loss,
                         optimizer=optax.sgd(0.01),
                         mesh=mesh_lib.current_mesh(),
                         compute_dtype=jnp.float32,
                         donate_state=False)
            state = eng.init_state(
                {"w": jnp.zeros((32,), jnp.float32)})
            batcher = data_lib.ArrayBatcher(
                {"x": x, "y": y}, batch_size=batch, seed=3)
            ckpt = Checkpointer(ckpt_dir)
            try:
                state, _ = eng.fit(state, batcher, epochs=epochs,
                                   seed=7, checkpointer=ckpt,
                                   scan_batches=False)
            finally:
                ckpt.close()
            sink.append(to_host(state))
            return "ok"

        return job

    elastic_fp = {"devices": 4, "elastic": {"min": 2, "max": 4}}

    # part 1: mixed elastic/rigid vs rigid-only — the waiter (6
    # devices) cannot fit beside the 4-device holder; only a shrink
    # lets it overlap instead of serializing behind the whole holder.
    # The headline is the waiter's COMPLETION LATENCY (submit->done):
    # that is what pressure relief buys; makespan is reported too but
    # not gated (a shrunk holder trades its own throughput for it).
    makespan = {}
    waiter_latency = {}
    overlapped = False
    for mode in ("elastic", "rigid"):
        cat = Catalog(os.path.join(home, f"cat_{mode}.db"),
                      os.path.join(home, f"ds_{mode}"))
        jobs = JobManager(cat, max_workers=4, mesh_leases=2,
                          slice_aging_seconds=0.3)
        scaler = None
        if mode == "elastic":
            scaler = SliceAutoscaler(jobs, interval_seconds=0.1,
                                     backoff_seconds=0.1).start()
        try:
            cat.create_collection("ela_holder", "train/tensorflow")
            cat.create_collection("ela_waiter", "train/tensorflow")
            t0 = time.perf_counter()
            holder_fut = jobs.submit(
                "ela_holder",
                fit_job(os.path.join(home, f"h_{mode}"), [], 200, 256),
                needs_mesh=True, pool="train",
                footprint=(dict(elastic_fp) if mode == "elastic"
                           else {"devices": 4}))
            time.sleep(0.2)  # holder claims its slice
            t_waiter = time.perf_counter()
            jobs.submit(
                "ela_waiter",
                fit_job(os.path.join(home, f"w_{mode}"), [], 5, 192),
                needs_mesh=True, pool="train",
                footprint={"devices": 6})
            jobs.wait("ela_waiter", timeout=240)
            waiter_latency[mode] = time.perf_counter() - t_waiter
            if mode == "elastic":
                overlapped = not holder_fut.done()
                scaler_stats = scaler.stats()["counters"]
            jobs.wait("ela_holder", timeout=240)
            makespan[mode] = time.perf_counter() - t0
        finally:
            if scaler is not None:
                scaler.stop()
            jobs.shutdown()
            cat.close()

    # part 2: page pressure (stub watchdog = a firing serving-p99
    # burn) must shrink the victim while it trains to completion
    class _Paging:
        def page_firing(self):
            return True

    cat2 = Catalog(os.path.join(home, "cat2.db"),
                   os.path.join(home, "ds2"))
    jobs2 = JobManager(cat2, max_workers=4, mesh_leases=2)
    scaler2 = SliceAutoscaler(jobs2, interval_seconds=0.1,
                              backoff_seconds=0.1,
                              watchdog_fn=lambda: _Paging()).start()
    pressure_shrinks = 0
    victim_finished = False
    try:
        cat2.create_collection("ela_victim", "train/tensorflow")
        jobs2.submit("ela_victim",
                     fit_job(os.path.join(home, "victim"), [], 8, 256),
                     needs_mesh=True, pool="train",
                     footprint=dict(elastic_fp))
        victim_finished = jobs2.wait("ela_victim", timeout=240) == "ok"
        token = jobs2._job_info["ela_victim"]["token"]
        pressure_shrinks = token.resizes
    finally:
        scaler2.stop()
        jobs2.shutdown()
        cat2.close()

    # part 3: forced resize fault — rollback must keep the run
    # bit-identical to the untouched rigid twin
    config_mod.set_config(dataclasses.replace(
        cfg, fault_inject="autoscale_resize:1:raise"))
    faults.reset()
    cat3 = Catalog(os.path.join(home, "cat3.db"),
                   os.path.join(home, "ds3"))
    jobs3 = JobManager(cat3, max_workers=4, mesh_leases=2)
    results = {}
    rollbacks = 0
    try:
        for tag in ("base", "chaos"):
            name = f"ela_{tag}"
            cat3.create_collection(name, "train/tensorflow")
            sink = []
            results[tag] = sink
            jobs3.submit(name,
                         fit_job(os.path.join(home, tag), sink, 6, 256),
                         needs_mesh=True, pool="train",
                         footprint=(dict(elastic_fp) if tag == "chaos"
                                    else {"devices": 4}))
            if tag == "chaos":
                token = jobs3._job_info[name]["token"]
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline:
                    if jobs3.request_resize(name, 2):
                        break
                    time.sleep(0.02)
                while time.monotonic() < deadline:
                    if token.resize_rollbacks >= 1:
                        break
                    time.sleep(0.02)
                rollbacks = token.resize_rollbacks
            jobs3.wait(name, timeout=240)
    finally:
        faults.reset()
        config_mod.set_config(cfg)
        jobs3.shutdown()
        cat3.close()
    base, chaos = results["base"][0], results["chaos"][0]
    rollback_bit_identical = bool(
        int(base.step) == int(chaos.step)
        and np.array_equal(np.asarray(base.params["w"]),
                           np.asarray(chaos.params["w"])))

    speedup = (round(makespan["rigid"] / makespan["elastic"], 3)
               if makespan.get("elastic") else None)
    waiter_speedup = (round(waiter_latency["rigid"]
                            / waiter_latency["elastic"], 3)
                      if waiter_latency.get("elastic") else None)
    return {"devices_total": total,
            "elastic_makespan_seconds": round(makespan["elastic"], 3),
            "rigid_makespan_seconds": round(makespan["rigid"], 3),
            "makespan_speedup": speedup,
            "elastic_waiter_seconds": round(waiter_latency["elastic"],
                                            3),
            "rigid_waiter_seconds": round(waiter_latency["rigid"], 3),
            "waiter_latency_speedup": waiter_speedup,
            "waiter_overlapped_holder": bool(overlapped),
            "shrinks_requested": scaler_stats["shrinksRequested"],
            "shrinks_completed": scaler_stats["shrinksCompleted"],
            "pressure_shrinks": int(pressure_shrinks),
            "victim_finished": bool(victim_finished),
            "resize_rollbacks": int(rollbacks),
            "rollback_bit_identical": rollback_bit_identical,
            "platform": jax.devices()[0].platform}


def phase_perf_report():
    """Roofline perf observability end-to-end (docs/OBSERVABILITY.md
    "Roofline & perf reports") plus its cost. Three parts: (1) one
    small train job through the REST stack must leave a
    ``GET /observability/perf/{job}`` roofline report and a timeline
    ``perf`` percentile block; (2) an ACTIVE predict session must
    answer the same route with its live goodput block, and /metrics
    must expose the new gauges; (3) the same MLP fit with LO_PERF=1
    vs LO_PERF=0, interleaved, min-of-repeats — perf tracking shares
    the tracer's and sentinel's < 3% steady-state overhead gate."""
    import jax
    import numpy as np

    from learningorchestra_tpu.models.estimators import \
        LogisticRegressionJAX
    from learningorchestra_tpu.models.neural import NeuralModel
    from learningorchestra_tpu.observability import perf as obs_perf
    from learningorchestra_tpu.observability import (
        timeline as obs_timeline)

    # off-TPU the platform registry has no peaks (MFU is undefined
    # against no roofline) — pin a small synthetic one through the env
    # overrides so the full mfu/hbmBwUtil/boundBy block is exercised
    # on every backend; on a real TPU the spec-sheet table is used
    if jax.devices()[0].platform != "tpu":
        os.environ.setdefault("LO_PEAK_TFLOPS_PER_CHIP", "0.05")
        os.environ.setdefault("LO_PEAK_HBM_GBPS", "1")
    os.environ["LO_PERF"] = "1"
    obs_perf.reset()
    api, prefix = _make_api()
    out = {"platform": jax.devices()[0].platform}
    try:
        # -- (1) train job -> roofline report through REST
        _run_pipeline(
            api, prefix, "perfrep",
            ("import numpy as np\n"
             "rng = np.random.default_rng(0)\n"
             "x = rng.normal(size=(4096, 64)).astype(np.float32)\n"
             "y = (x[:, 0] > 0).astype(np.int32)\n"
             "response = {'x': x, 'y': y}\n"),
            "learningorchestra_tpu.models", "NeuralModel",
            {"layer_configs": [
                {"kind": "dense", "units": 64, "activation": "relu"},
                {"kind": "dense", "units": 2,
                 "activation": "softmax"}]},
            {"x": "$perfrep_data.x", "y": "$perfrep_data.y",
             "epochs": 3, "batch_size": 256, "shuffle": False})
        status, report, _ = api.dispatch(
            "GET", f"{prefix}/observability/perf/perfrep_train",
            {}, None)
        blk = (report or {}).get("perf") or {}
        out["train_report_status"] = status
        out["train_mfu"] = blk.get("mfu")
        out["train_tflops_per_chip"] = blk.get("tflopsPerSecPerChip")
        out["train_gb_per_sec_per_chip"] = blk.get("gbPerSecPerChip")
        out["train_hbm_bw_util_frac"] = blk.get("hbmBwUtil")
        out["train_bound_by"] = blk.get("boundBy")
        out["train_report_ok"] = bool(
            status == 200
            and blk.get("tflopsPerSecPerChip") is not None
            and blk.get("mfu") is not None)
        tl = obs_timeline.summary("perfrep_train") or {}
        tl_perf = tl.get("perf") or {}
        out["timeline_perf_ok"] = bool(
            (tl_perf.get("mfu") or {}).get("p50") is not None)

        # -- (2) active predict session answers the same route live
        rng = np.random.default_rng(0)
        x = rng.normal(size=(512, 8)).astype(np.float32)
        y = (x[:, 0] > 0).astype(np.int32)
        clf = LogisticRegressionJAX(epochs=2, batch_size=128)
        clf.fit(x, y)
        api.ctx.artifacts.save(clf, "perfrep_clf", "train/tensorflow")
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/serve/perfrep_clf", {}, {})
        _expect_created(status, body)
        rows = [[float(v) for v in r]
                for r in rng.normal(size=(8, 8))]
        for _ in range(6):
            s2, b2, _ = api.dispatch(
                "POST", f"{prefix}/serve/perfrep_clf/predict", {},
                {"x": rows})
            if s2 != 200:
                raise RuntimeError(f"perf predict failed: {s2} {b2}")
        status, sreport, _ = api.dispatch(
            "GET", f"{prefix}/observability/perf/perfrep_clf",
            {}, None)
        sperf = (sreport or {}).get("perf") or {}
        out["serving_report_status"] = status
        out["serving_rows_per_sec_per_chip"] = sperf.get(
            "rowsPerSecPerChip")
        out["serving_goodput_frac"] = sperf.get("goodputFrac")
        out["serving_report_ok"] = bool(
            status == 200
            and (sreport or {}).get("kind") == "serving"
            and sperf.get("rowsPerSecPerChip") is not None)
        _, prom, _ = api.dispatch(
            "GET", "/metrics", {"format": "prometheus"}, None)
        text = prom.decode() if isinstance(prom, bytes) else str(prom)
        out["prom_gauges_ok"] = ("lo_mfu{" in text
                                 and "lo_tflops_per_chip{" in text
                                 and "lo_abandoned_dispatches" in text)
        api.dispatch("DELETE", f"{prefix}/serve/perfrep_clf", {}, None)
    finally:
        api.ctx.jobs.shutdown()

    # -- (3) steady-state cost, LO_PERF=1 vs LO_PERF=0. Neither arm
    # runs under a job span, so the tracer/timeline path is off for
    # both; the delta is exactly the extended roofline computation the
    # switch gates. ~1.5 s timed regions so scheduler jitter cannot
    # fake a 3% split between the arms.
    rng = np.random.default_rng(0)
    xb = rng.normal(size=(8192, 64)).astype(np.float32)
    yb = (xb[:, 0] > 0).astype(np.int64)
    model = NeuralModel([
        {"kind": "dense", "units": 128, "activation": "relu"},
        {"kind": "dense", "units": 128, "activation": "relu"},
        {"kind": "dense", "units": 2, "activation": "softmax"}])
    model.fit(xb, yb, epochs=1, batch_size=256, shuffle=False)  # warm
    times = {"on": [], "off": []}
    for _ in range(4):
        os.environ["LO_PERF"] = "1"
        t0 = time.perf_counter()
        model.fit(xb, yb, epochs=12, batch_size=256, shuffle=False)
        times["on"].append(time.perf_counter() - t0)
        os.environ["LO_PERF"] = "0"
        t0 = time.perf_counter()
        model.fit(xb, yb, epochs=12, batch_size=256, shuffle=False)
        times["off"].append(time.perf_counter() - t0)
    os.environ["LO_PERF"] = "1"
    best = {name: min(ts) for name, ts in times.items()}
    out["perf_on_seconds"] = round(best["on"], 4)
    out["perf_off_seconds"] = round(best["off"], 4)
    out["perf_overhead_ratio"] = round(best["on"] / best["off"], 4)
    return out


def phase_xray_overhead():
    """HBM attribution + compiled-artifact X-ray end-to-end
    (docs/OBSERVABILITY.md "HBM attribution & X-ray") plus its cost.
    Four parts: (1) one train job through the REST stack — polled
    mid-flight for its transient ``train-state`` ledger entry — must
    leave a ``GET /observability/compile/{job}`` X-ray; (2) a live LM
    serving session must attribute ``serving-params`` + ``kv-cache``
    and the bare memory route's unattributed fraction must stay sane;
    (3) an in-flight async-checkpoint snapshot must appear as the
    ``snapshot`` owner (host-side) and release on commit, and a forced
    retrace + a forced implicit transfer must each land a counted,
    signature-carrying event; (4) the same MLP fit with LO_XRAY=1 vs
    LO_XRAY=0, interleaved, min-of-repeats — the ledger shares the
    observability stack's < 3% steady-state overhead gate."""
    import threading

    import jax
    import numpy as np

    from learningorchestra_tpu import config as config_mod
    from learningorchestra_tpu.models.neural import NeuralModel
    from learningorchestra_tpu.models.transformer import LanguageModel
    from learningorchestra_tpu.observability import xray as obs_xray
    from learningorchestra_tpu.runtime.async_ckpt import (
        AsyncCheckpointManager)
    from learningorchestra_tpu.runtime.checkpoint import Checkpointer

    os.environ["LO_XRAY"] = "1"
    obs_xray.reset()
    api, prefix = _make_api()
    out = {"platform": jax.devices()[0].platform}
    owners_seen = set()
    try:
        # -- (1) train job; poll the memory route while it runs so the
        # transient train-state registration is observed live
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/function/python", {}, {
                "name": "xray_data", "functionParameters": {},
                "description": "xray bench data", "function": (
                    "import numpy as np\n"
                    "rng = np.random.default_rng(0)\n"
                    "x = rng.normal(size=(2048, 32)).astype("
                    "np.float32)\n"
                    "y = (x[:, 0] > 0).astype(np.int32)\n"
                    "response = {'x': x, 'y': y}\n")})
        _expect_created(status, body)
        _wait(api, body["result"])
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/model/tensorflow", {}, {
                "modelName": "xray_model",
                "modulePath": "learningorchestra_tpu.models",
                "class": "NeuralModel", "description": "xray bench",
                "classParameters": {"layer_configs": [
                    {"kind": "dense", "units": 32,
                     "activation": "relu"},
                    {"kind": "dense", "units": 2,
                     "activation": "softmax"}]}})
        _expect_created(status, body)
        _wait(api, body["result"])
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/train/tensorflow", {}, {
                "name": "xray_train", "modelName": "xray_model",
                "method": "fit", "methodParameters": {
                    "x": "$xray_data.x", "y": "$xray_data.y",
                    "epochs": 6, "batch_size": 64}})
        _expect_created(status, body)
        train_uri = body["result"]
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            owners_seen |= {o for o, n in obs_xray.by_owner().items()
                            if n > 0}
            s2, b2, _ = api.dispatch(
                "GET", train_uri, {"limit": "1"}, None)
            if s2 == 200 and b2["metadata"].get("finished"):
                break
            time.sleep(0.002)
        else:
            raise TimeoutError("xray_train never finished")
        status, rep, _ = api.dispatch(
            "GET", f"{prefix}/observability/compile/xray_train",
            {}, None)
        prog = ((rep or {}).get("programs") or {}).get("trainStep", {})
        out["compile_report_status"] = status
        out["compile_peak_bytes"] = (prog.get("memory") or {}).get(
            "peakBytesEstimate")
        out["compile_report_ok"] = bool(
            status == 200 and out["compile_peak_bytes"])

        # the arena owner rides the feature-token path (builder /
        # repeat-fit staging): a token-carrying fit leaves its staged
        # device arrays resident in the arena between fits
        from learningorchestra_tpu.models.estimators import (
            LogisticRegressionJAX)

        rng = np.random.default_rng(1)
        xa = rng.normal(size=(1024, 16)).astype(np.float32)
        ya = (xa[:, 0] > 0).astype(np.int64)
        clf = LogisticRegressionJAX(epochs=2, batch_size=256)
        clf.feature_token = ("bench", "xray", 1)
        clf.feature_tags = ("xray_bench",)
        clf.fit(xa, ya)

        # -- (2) live LM serving session: params pin + KV slot cache
        lm = LanguageModel(vocab_size=48, d_model=32, n_layers=1,
                           n_heads=2, d_ff=64, max_len=32,
                           attention="dot")
        tokens = rng.integers(1, 48, size=(16, 16)).astype(np.int32)
        lm.fit(tokens, batch_size=16, epochs=1)
        api.ctx.artifacts.save(lm, "xray_lm", "train/tensorflow")
        # the session pins its OWN reloaded copy; drop the local one
        # (params + opt state) so it can't pollute the unattributed
        # remainder the route computes from live arrays on CPU
        del lm
        import gc

        gc.collect()
        status, body, _ = api.dispatch(
            "POST", f"{prefix}/serve/xray_lm", {},
            {"maxSlots": 2, "cacheLen": 32})
        _expect_created(status, body)
        s2, b2, _ = api.dispatch(
            "POST", f"{prefix}/serve/xray_lm/predict", {},
            {"prompt": [1, 2, 3], "maxNewTokens": 4, "seed": 7})
        if s2 != 200:
            raise RuntimeError(f"xray lm predict failed: {s2} {b2}")

        # -- (3) in-flight async-ckpt snapshot, gated so the ledger
        # entry is observable rather than racing the commit
        gate = threading.Event()

        class _GatedCkpt(Checkpointer):
            def _commit_host(self, step, host):
                gate.wait(timeout=60)
                return super()._commit_host(step, host)

        ckpt_dir = tempfile.mkdtemp(prefix="lo_xray_ckpt_")
        mgr = AsyncCheckpointManager(_GatedCkpt(ckpt_dir), inflight=2)
        try:
            mgr.save(1, {"w": np.ones((256, 256), np.float32)})
            owners_seen |= {o for o, n in obs_xray.by_owner().items()
                            if n > 0}
            out["snapshot_ledgered"] = (
                obs_xray.by_owner().get("snapshot", 0) > 0)
        finally:
            gate.set()
            mgr.close()
        out["snapshot_released"] = (
            obs_xray.by_owner().get("snapshot", 0) == 0)

        # the memory route, with the serving session still live
        status, mem, _ = api.dispatch(
            "GET", f"{prefix}/observability/memory", {}, None)
        out["memory_route_status"] = status
        owners_seen |= {o for o, n in (mem or {}).get(
            "owners", {}).items() if n > 0}
        out["owners_seen"] = sorted(owners_seen)
        out["owners_ok"] = {"arena", "train-state", "serving-params",
                            "kv-cache", "snapshot"} <= owners_seen
        in_use = (mem or {}).get("bytesInUse")
        unattr = (mem or {}).get("unattributedBytes")
        out["bytes_in_use"] = in_use
        out["bytes_source"] = (mem or {}).get("bytesSource")
        out["unattributed_bytes"] = unattr
        out["unattributed_frac"] = (
            round(unattr / in_use, 4)
            if in_use and unattr is not None else None)

        # -- forced retrace: same program key, new batch signature
        before = obs_xray.counters()["retraces"]
        xb = np.random.default_rng(0).normal(
            size=(512, 16)).astype(np.float32)
        yb = (xb[:, 0] > 0).astype(np.int64)
        probe = NeuralModel([
            {"kind": "dense", "units": 8, "activation": "relu"},
            {"kind": "dense", "units": 2, "activation": "softmax"}])
        probe.fit(xb, yb, epochs=1, batch_size=64, shuffle=False)
        probe.fit(xb, yb, epochs=1, batch_size=32, shuffle=False)
        out["retraces_counted"] = obs_xray.counters()["retraces"] \
            - before
        events = obs_xray.retrace_events()
        out["retrace_ok"] = bool(
            out["retraces_counted"] >= 1 and events
            and events[-1]["prevSignature"]
            and events[-1]["newSignature"])

        # -- forced implicit transfer under the armed sentinel: a
        # jitted dispatch fed a host numpy array
        before = obs_xray.counters()["implicitTransfers"]
        cfg = config_mod.get_config()
        prior_guard = cfg.transfer_guard
        cfg.transfer_guard = "log"
        try:
            import jax.numpy as jnp

            fn = jax.jit(lambda v: jnp.sum(v * 2.0))
            got = float(obs_xray.guarded_call(
                fn, np.ones(8, np.float32), name="xray_bench"))
        finally:
            cfg.transfer_guard = prior_guard
        tev = obs_xray.transfer_events()
        out["transfers_counted"] = \
            obs_xray.counters()["implicitTransfers"] - before
        out["transfer_ok"] = bool(
            got == 16.0 and out["transfers_counted"] >= 1
            and tev and tev[-1]["signature"])

        api.dispatch("DELETE", f"{prefix}/serve/xray_lm", {}, None)
    finally:
        api.ctx.jobs.shutdown()

    # -- (4) steady-state cost, LO_XRAY=1 vs LO_XRAY=0, interleaved
    # min-of-repeats; neither arm runs under a job span so the delta is
    # exactly the ledger/signature bookkeeping the switch gates
    rng = np.random.default_rng(0)
    xb = rng.normal(size=(8192, 64)).astype(np.float32)
    yb = (xb[:, 0] > 0).astype(np.int64)
    model = NeuralModel([
        {"kind": "dense", "units": 128, "activation": "relu"},
        {"kind": "dense", "units": 128, "activation": "relu"},
        {"kind": "dense", "units": 2, "activation": "softmax"}])
    model.fit(xb, yb, epochs=1, batch_size=256, shuffle=False)  # warm
    times = {"on": [], "off": []}
    for _ in range(4):
        os.environ["LO_XRAY"] = "1"
        t0 = time.perf_counter()
        model.fit(xb, yb, epochs=30, batch_size=256, shuffle=False)
        times["on"].append(time.perf_counter() - t0)
        os.environ["LO_XRAY"] = "0"
        t0 = time.perf_counter()
        model.fit(xb, yb, epochs=30, batch_size=256, shuffle=False)
        times["off"].append(time.perf_counter() - t0)
    os.environ["LO_XRAY"] = "1"
    best = {name: min(ts) for name, ts in times.items()}
    out["xray_on_seconds"] = round(best["on"], 4)
    out["xray_off_seconds"] = round(best["off"], 4)
    out["xray_overhead_ratio"] = round(best["on"] / best["off"], 4)
    return out


PHASES = {"cnn": phase_cnn, "lstm": phase_lstm, "tlm": phase_tlm,
          "proxy": phase_proxy, "builder": phase_builder,
          "builder_mesh": phase_builder_mesh,
          "warm_pipeline": phase_warm_pipeline,
          "concurrent_jobs": phase_concurrent_jobs,
          "flash": phase_flash, "ingest": phase_ingest,
          "gen": phase_gen, "serving": phase_serving,
          "paged_serving": phase_paged_serving,
          "quant_serving": phase_quant_serving,
          "disagg_serving": phase_disagg_serving,
          "sentinel_overhead": phase_sentinel_overhead,
          "sentinel_chaos": phase_sentinel_chaos,
          "obs_overhead": phase_obs_overhead,
          "monitor_smoke": phase_monitor_smoke,
          "incident_smoke": phase_incident_smoke,
          "sweep_fusion": phase_sweep_fusion,
          "ckpt_stall": phase_ckpt_stall,
          "migration_smoke": phase_migration_smoke,
          "elastic_smoke": phase_elastic_smoke,
          "perf_report": phase_perf_report,
          "xray_overhead": phase_xray_overhead}

_RESULT_MARK = "@@LO_BENCH_RESULT@@"


def _trace_breakdown():
    """Compile-vs-run-vs-wait attribution from the span tracer,
    summed over every trace this phase produced (phases run their Api
    in-process, so the tracer rings are right here). This is what
    makes ``builder_10m_streaming`` variance attributable: a slow
    repeat shows up as compile (fresh jit), wait (queue/lease
    contention) or run (actual step time) instead of one opaque
    wall-clock number."""
    from learningorchestra_tpu.observability import trace as obs_trace

    agg = {"compileSeconds": 0.0, "waitSeconds": 0.0,
           "runSeconds": 0.0, "checkpointSeconds": 0.0}
    by_trace = {}
    for tid in obs_trace.known_traces():
        totals = obs_trace.durations_by_name(tid)
        if not totals:
            continue
        c = totals.get("compile", 0.0)
        w = totals.get("queueWait", 0.0) + totals.get("leaseWait", 0.0)
        k = totals.get("checkpointCommit", 0.0)
        # the attempt span (job execution) / request span (serving)
        # covers the whole body; run time is what's left after the
        # compile and checkpoint slices are attributed
        body = totals.get("attempt", totals.get("request", 0.0))
        r = max(0.0, body - c - k)
        by_trace[tid] = {"compileSeconds": round(c, 4),
                         "waitSeconds": round(w, 4),
                         "runSeconds": round(r, 4),
                         "checkpointSeconds": round(k, 4)}
        agg["compileSeconds"] += c
        agg["waitSeconds"] += w
        agg["runSeconds"] += r
        agg["checkpointSeconds"] += k
    if not by_trace:
        return None
    return {"totals": {k: round(v, 4) for k, v in agg.items()},
            "byTrace": dict(sorted(by_trace.items())[:48])}


def _child_main(phase: str) -> int:
    """Run one phase and print its JSON result on a marked line."""
    try:
        # the one compile-cache rule (services/context.py): jax's own
        # JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache
        # on an accelerator, off on the CPU backend
        from learningorchestra_tpu.services.context import \
            wire_compile_cache

        wire_compile_cache()
        result = PHASES[phase]()
        if os.environ.get("LO_BENCH_TRACE") == "1" and \
                isinstance(result, dict):
            try:
                breakdown = _trace_breakdown()
                if breakdown is not None:
                    result["traceBreakdown"] = breakdown
            except Exception:  # noqa: BLE001 — attribution is advisory
                pass
        if isinstance(result, dict):
            import jax

            dev = jax.devices()[0]
            result.setdefault("platform", dev.platform)
            result.setdefault("device_kind", dev.device_kind)
            result.setdefault("device_count", len(jax.devices()))
        print(_RESULT_MARK + json.dumps({"ok": True, "result": result}),
              flush=True)
        return 0
    except BaseException as exc:  # noqa: BLE001 — structured error contract
        print(_RESULT_MARK + json.dumps(
            {"ok": False,
             "error": f"{type(exc).__name__}: {exc}"[:2000]}), flush=True)
        return 1


def _accelerator_probe(timeout: float = 150.0):
    """Bounded probe in a child that exits before the first phase
    starts (the parent stays off jax): does a fresh process reach an
    accelerator? Returns ``(ok, why)``; ``why`` names the device found
    or the reason none was."""
    env_t = os.environ.get("LO_BENCH_TPU_PROBE_SECONDS")
    if env_t:
        timeout = float(env_t)
    code = ("import jax; d = jax.devices()[0]; "
            "print('PROBE', d.platform, '|', d.device_kind, '|', "
            "len(jax.devices()))")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True,
            timeout=timeout, text=True, env=dict(os.environ))
    except subprocess.TimeoutExpired:
        return False, f"backend init exceeded {timeout:.0f}s"
    except OSError as exc:
        return False, f"probe spawn failed: {exc}"
    for line in (proc.stdout or "").splitlines():
        if line.startswith("PROBE "):
            platform = line.split()[1]
            found = line[len("PROBE "):]
            if platform == "cpu":
                return False, f"jax found only the CPU backend ({found})"
            return True, found
    tail = " | ".join((proc.stderr or "").strip().splitlines()[-3:])
    return False, f"probe exited rc={proc.returncode}: {tail}"


def _phase_timeout(phase: str) -> float:
    env = os.environ.get(f"LO_BENCH_TIMEOUT_{phase.upper()}")
    return float(env) if env else float(PHASE_TIMEOUTS.get(phase, 600))


def _run_phase(phase: str, extra_env=None):
    """Run a phase in a killable subprocess; never raises.

    Returns the phase's result dict, or {"error": ...} on
    crash/timeout. The child gets its own process group so a hung jax
    runtime (and anything it spawned) is reliably killed — a lingering
    child holding the TPU would wedge the next phase and the driver.
    """
    timeout = _phase_timeout(phase)
    env = dict(os.environ)
    env.update(extra_env or {})
    try:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--phase", phase],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            env=env, start_new_session=True, text=True)
    except OSError as exc:
        return {"error": f"spawn failed: {exc}"}
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        # SIGTERM first: a graceful exit lets the TPU runtime release
        # the chip (a SIGKILLed holder can wedge the device for many
        # minutes, starving the following phases AND the driver)
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except OSError:
            proc.terminate()
        try:
            proc.communicate(timeout=20)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                proc.kill()
            proc.wait()
        return {"error": f"phase '{phase}' exceeded {timeout:.0f}s "
                         f"wall-clock bound and was killed"}
    for line in reversed(out.splitlines()):
        if line.startswith(_RESULT_MARK):
            try:
                payload = json.loads(line[len(_RESULT_MARK):])
            except ValueError:
                break  # truncated/garbage mark line -> generic error path
            if payload.get("ok"):
                return payload["result"]
            return {"error": payload.get("error", "unknown phase error")}
    tail = (err or out or "").strip().splitlines()[-8:]
    return {"error": f"phase '{phase}' exited rc={proc.returncode} "
                     f"without a result; tail: {' | '.join(tail)}"}


def _median_iqr(vals):
    import statistics

    med = statistics.median(vals)
    if len(vals) >= 2:
        q = statistics.quantiles(vals, n=4, method="inclusive")
        iqr = q[2] - q[0]
    else:
        iqr = 0.0
    return round(med, 3), round(iqr, 3)


def _run_phase_repeated(phase: str, extra_env=None, metrics=()):
    """Run a phase LO_BENCH_REPEATS times (default 3); report the last
    successful run plus a ``repeats`` block carrying median + IQR per
    headline metric. Single-shot numbers on a shared host are
    noise-bound — the spread is the evidence the number is real."""
    n = max(1, int(os.environ.get("LO_BENCH_REPEATS", "3")))
    runs = [_run_phase(phase, extra_env) for _ in range(n)]
    good = [r for r in runs if "error" not in r]
    if not good:
        return runs[-1]
    out = dict(good[-1])
    agg = {}
    for metric in metrics:
        vals = [float(r[metric]) for r in good
                if isinstance(r.get(metric), (int, float))]
        if vals:
            med, iqr = _median_iqr(vals)
            agg[metric] = {"median": med, "iqr": iqr, "n": len(vals),
                           "values": [round(v, 3) for v in vals]}
    out["repeats"] = {"n": n, "successful": len(good), "metrics": agg}
    # --trace mode: keep EVERY repeat's compile/run/wait totals (not
    # just the last run's) so a variance outlier is attributable
    breakdowns = [(r.get("traceBreakdown") or {}).get("totals")
                  for r in runs]
    if any(breakdowns):
        out["repeats"]["traceBreakdowns"] = breakdowns
    return out


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--phase", choices=sorted(PHASES))
    parser.add_argument("--write-md", metavar="PATH",
                        help="also render the results table to PATH")
    parser.add_argument("--trace", action="store_true",
                        help="pull the span tree after each phase and "
                             "report a compile-vs-run-vs-wait "
                             "breakdown per repeat (stored in the "
                             "BENCH json; docs/OBSERVABILITY.md)")
    args = parser.parse_args(argv)
    if args.trace:
        # phase children inherit this and attach traceBreakdown to
        # their result line
        os.environ["LO_BENCH_TRACE"] = "1"
        os.environ.setdefault("LO_TRACE", "1")
    if args.phase:
        return _child_main(args.phase)

    # a measurement path that finds no chip fails; it does not fall
    # back to the CPU (the probe child exits before any phase starts)
    ok, why = _accelerator_probe()
    if not ok:
        print(f"bench: no accelerator, nothing ran: {why}",
              file=sys.stderr)
        return 2
    print(f"bench: accelerator {why}", file=sys.stderr)

    models = {}
    models["mnist_cnn"] = _run_phase("cnn")
    models["imdb_lstm"] = _run_phase("lstm")
    models["transformer_lm"] = _run_phase("tlm")
    models["builder_10m_streaming"] = _run_phase_repeated(
        "builder", metrics=("train_rows_per_sec", "pipeline_seconds"))
    models["builder_mesh_2m"] = _run_phase("builder_mesh")
    models["warm_pipeline"] = _run_phase("warm_pipeline")
    models["csv_ingest"] = _run_phase("ingest")
    models["lm_decode"] = _run_phase("gen")
    models["serving"] = _run_phase_repeated(
        "serving",
        metrics=("decode_tokens_per_sec", "speedup_vs_solo", "p99_ms",
                 "predict_speedup"))
    models["paged_serving"] = _run_phase_repeated(
        "paged_serving",
        metrics=("streams_vs_slot", "paged_peak_streams",
                 "paged_decode_tokens_per_sec", "victim_p99_ms"))
    models["quant_serving"] = _run_phase_repeated(
        "quant_serving",
        metrics=("streams_vs_bf16", "int8_peak_streams",
                 "int8_decode_tokens_per_sec", "drift"))
    models["disagg_serving"] = _run_phase_repeated(
        "disagg_serving",
        metrics=("disagg_burst_decode_p99_ms",
                 "fused_burst_decode_p99_ms",
                 "accepted_tokens_per_step", "spec_tokens_per_sec"))
    models["sweep_fusion"] = _run_phase_repeated(
        "sweep_fusion",
        metrics=("speedup", "fused_seconds", "serial_seconds"))
    models["ckpt_stall"] = _run_phase("ckpt_stall")
    # HBM attribution/X-ray smoke + its steady-state overhead ratio —
    # in the round payload so bench_regress gates the ratio drifting
    models["xray_overhead"] = _run_phase("xray_overhead")
    # these two need a sliceable (multi-device) mesh; on one chip they
    # fail, and the run says so
    models["migration_smoke"] = _run_phase("migration_smoke")
    models["elastic_smoke"] = _run_phase("elastic_smoke")
    flash = _run_phase("flash")
    proxy = _run_phase("proxy")

    if args.trace:
        for tag, res in models.items():
            totals = (res.get("traceBreakdown") or {}).get("totals")
            per_repeat = (res.get("repeats") or {}).get(
                "traceBreakdowns")
            if totals:
                print(f"TRACE {tag}: {json.dumps(totals)}",
                      file=sys.stderr)
            for i, bd in enumerate(per_repeat or []):
                if bd:
                    print(f"TRACE {tag} repeat {i}: {json.dumps(bd)}",
                          file=sys.stderr)

    headline = models["mnist_cnn"].get("samples_per_sec_per_chip")
    baseline = proxy.get("samples_per_sec")
    vs = (round(headline / baseline, 3)
          if headline and baseline else None)
    report = {
        "metric": "mnist_cnn_train_samples_per_sec_per_chip",
        "value": headline,  # None when the cnn phase failed
        "unit": "samples/s",
        "vs_baseline": vs,
        "extra": {
            "accelerator": why,
            "reference_proxy_torch_cpu_samples_per_sec": baseline,
            "models": models,
            "flash_attention_microbench": flash,
            "configs": {
                "mnist_cnn": {"epochs": EPOCHS, "batch_size": BATCH,
                              "n_samples": N_SAMPLES},
                "imdb_lstm": {"epochs": LSTM_EPOCHS,
                              "batch_size": LSTM_BATCH,
                              "n_samples": LSTM_N, "seq_len": LSTM_SEQ,
                              "vocab": LSTM_VOCAB},
                "transformer_lm": dict(TLM_CFG, epochs=TLM_EPOCHS,
                                       batch_size=TLM_BATCH,
                                       n_samples=TLM_N),
            },
        },
    }
    if args.write_md:
        _write_md(args.write_md, report)
    full = json.dumps(report)
    report_path = None
    try:
        with open("bench_report.json", "w") as f:
            f.write(full + "\n")
        report_path = "bench_report.json"
    except OSError as exc:
        print(f"bench_report.json not written: {exc}", file=sys.stderr)
    print(full)
    # a tail capture can truncate the head of the giant full-report
    # line and leave it unparseable — so the LAST line is a compact
    # summary that always survives tail truncation
    tlm = models.get("transformer_lm", {})
    failed = sorted(
        tag for tag, res in dict(models, flash_attention_microbench=flash,
                                 proxy=proxy).items()
        if "error" in res)
    compact = {
        "metric": report["metric"],
        "value": report["value"],
        "unit": report["unit"],
        "vs_baseline": report["vs_baseline"],
        "accelerator": why,
        "failed_phases": failed,
        "transformer_lm_mfu": tlm.get("mfu"),
        "transformer_lm_tflops_per_sec_per_chip":
            tlm.get("tflops_per_sec_per_chip"),
        "serving_speedup_vs_solo":
            models.get("serving", {}).get("speedup_vs_solo"),
        "paged_streams_vs_slot":
            models.get("paged_serving", {}).get("streams_vs_slot"),
        "quant_streams_vs_bf16":
            models.get("quant_serving", {}).get("streams_vs_bf16"),
        "full_report": report_path,
    }
    print(json.dumps(compact))
    # a run in which any phase failed reports, then fails
    return 1 if failed else 0


def _write_md(path, report):
    models = report["extra"]["models"]
    configs = report["extra"]["configs"]
    lines = [
        "# BENCHMARKS — self-measured (BASELINE.md:33-35)",
        "",
        "Measured through the REST control plane (Function → Model → "
        "Train → Evaluate), steady-state epoch (post-compile), per chip.",
        "",
        "| model | platform | samples/s/chip | tflops/s/chip | MFU | "
        "eval acc | time-to-97% | config |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for name, stats in models.items():
        if "error" in stats:
            lines.append(f"| {name} | — | ERROR: {stats['error']} | — | "
                         f"— | — | — | — |")
            continue
        if name == "builder_10m_streaming":
            gb = stats.get("gb", {})
            lines.append(
                f"| {name} (host data plane) | cpu "
                f"| {stats.get('train_rows_per_sec', '—')} rows/s | — | "
                f"— | LR {stats.get('lr', {}).get('accuracy')} / GB "
                f"{gb.get('accuracy')} | — "
                f"| rows={stats.get('rows')}, peak_rss_mb="
                f"{stats.get('peak_rss_mb')}, gb_full_data="
                f"{not gb.get('trainedOnSample', False)} |")
            continue
        if name == "builder_mesh_2m":
            mesh = stats.get("mesh", {})
            host = stats.get("host", {})
            lines.append(
                f"| {name} (LR+NB, mesh vs host) "
                f"| {stats.get('platform', '?')} "
                f"| {mesh.get('train_rows_per_sec', '—')} rows/s "
                f"(host {host.get('train_rows_per_sec', '—')}) | — | — "
                f"| LR {mesh.get('lr', {}).get('accuracy')} "
                f"| — | rows={stats.get('rows')}, jax LR fit="
                f"{mesh.get('lr', {}).get('fitTime')}s vs sklearn "
                f"{host.get('lr', {}).get('fitTime')}s, slices="
                f"{mesh.get('lr', {}).get('meshDevices')}dev |")
            continue
        if name == "serving":
            lines.append(
                f"| {name} (resident plane) "
                f"| {stats.get('platform', '?')} "
                f"| {stats.get('decode_tokens_per_sec', '—')} tok/s "
                f"({stats.get('speedup_vs_solo', '—')}× solo decode) "
                f"| — | — | — | — "
                f"| streams={stats.get('streams')}, "
                f"p99={stats.get('p99_ms')}ms, clf predict p50 "
                f"{stats.get('predict_serving_p50_ms')}ms "
                f"({stats.get('predict_speedup', '—')}× vs "
                f"submit→poll) |")
            continue
        if name == "paged_serving":
            lines.append(
                f"| {name} (paged KV vs slot, equal HBM) "
                f"| {stats.get('platform', '?')} "
                f"| {stats.get('paged_decode_tokens_per_sec', '—')} "
                f"tok/s | — | — | — | — "
                f"| peak streams {stats.get('paged_peak_streams')} vs "
                f"{stats.get('slot_peak_streams')} slot "
                f"({stats.get('streams_vs_slot', '—')}×), victim p99="
                f"{stats.get('victim_p99_ms')}ms, bully 429s="
                f"{stats.get('bully_rejected')} |")
            continue
        if name == "quant_serving":
            lines.append(
                f"| {name} (int8 KV+weights vs bf16, equal HBM) "
                f"| {stats.get('platform', '?')} "
                f"| {stats.get('int8_decode_tokens_per_sec', '—')} "
                f"tok/s | — | — | — | — "
                f"| peak streams {stats.get('int8_peak_streams')} vs "
                f"{stats.get('bf16_peak_streams')} bf16 "
                f"({stats.get('streams_vs_bf16', '—')}×), drift="
                f"{stats.get('drift')}, degrade ladder "
                f"{'ok' if stats.get('degrade_fired') else 'FAILED'} |")
            continue
        if name == "disagg_serving":
            lines.append(
                f"| {name} (prefill/decode split + spec decode) "
                f"| {stats.get('platform', '?')} "
                f"| {stats.get('spec_tokens_per_sec', '—')} tok/s "
                f"({stats.get('spec_tokens_speedup', '—')}× vs "
                f"no-draft) | — | — | — | — "
                f"| decode p99 burst/floor: disagg "
                f"{stats.get('disagg_burst_decode_p99_vs_no_burst')}× "
                f"vs fused "
                f"{stats.get('fused_burst_decode_p99_vs_no_burst')}×, "
                f"acc/step={stats.get('accepted_tokens_per_step')}, "
                f"handoff chaos "
                f"{'ok' if stats.get('chaos_degrade_fired') and stats.get('chaos_leak_free') else 'FAILED'} |")
            continue
        if name == "csv_ingest":
            lines.append(
                f"| {name} (host data plane) | cpu "
                f"| {stats.get('rows_per_sec', '—')} rows/s | — | — | — "
                f"| — | rows={stats.get('rows')}, native_core="
                f"{stats.get('native_core')} |")
            continue
        cfg = configs.get(name, {})
        cfg_s = ", ".join(f"{k}={v}" for k, v in sorted(cfg.items()))
        mfu = stats.get("mfu")
        tta = stats.get("time_to_97pct_train_acc_s")
        lines.append(
            f"| {name} | {stats.get('platform', '?')} "
            f"| {stats.get('samples_per_sec_per_chip', '—')} "
            f"| {stats.get('tflops_per_sec_per_chip', '—')} "
            f"| {f'{mfu:.1%}' if mfu is not None else '—'} "
            f"| {stats.get('eval_accuracy', '—')} "
            f"| {f'{tta}s' if tta is not None else '—'} | {cfg_s} |")
    proxy = report["extra"]["reference_proxy_torch_cpu_samples_per_sec"]
    if proxy:
        lines += ["",
                  f"Reference execution-model proxy (torch-CPU twin of the "
                  f"flagship CNN, in-process fit per SURVEY §3.3): "
                  f"**{proxy} samples/s** → speedup "
                  f"**{report['vs_baseline']}×**."]
    flash = report["extra"].get("flash_attention_microbench") or {}
    rows = [(k, v) for k, v in flash.items() if isinstance(v, dict)]
    if rows:
        lines += ["", "## Flash-attention kernel micro-bench "
                      "(fwd+bwd, b=4 h=8 d=64)",
                  "",
                  f"Platform: {flash.get('platform', '?')}. Pallas "
                  "flash (ops/attention.py) vs fused-dot oracle; ms "
                  "per fwd+bwd step.", "",
                  "| shape | flash ms | dot ms | speedup |",
                  "|---|---|---|---|"]
        for k, v in rows:
            lines.append(
                f"| {k} | {v.get('flash_fwd_bwd_ms', '—')} "
                f"| {v.get('dot_fwd_bwd_ms', v.get('dot_error', '—'))} "
                f"| {v.get('speedup', '—')} |")
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    sys.exit(main())
