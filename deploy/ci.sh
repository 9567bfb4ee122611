#!/usr/bin/env bash
# CI gate: repo self-lint, the tier-1 test suite, then a chaos stage
# that re-runs the fault/lifecycle suites under an injecting
# environment (docs/LIFECYCLE.md).
#
# Usage: deploy/ci.sh            (from anywhere; paths are self-rooted)
# Env:   LO_CI_TIMEOUT        seconds for the tier-1 run (default 870)
#        LO_CI_FULL           1 to also run the FULL suite incl. slow
#                             oracle-parity tests (default 0: tier-1
#                             keeps one parity test per subsystem, see
#                             tests/conftest.py)
#        LO_CI_FULL_TIMEOUT   seconds for the full-suite run (default 3600)
#        LO_CI_CHAOS_TIMEOUT  seconds for the chaos stage (default 300)
#        LO_CI_PERF_TIMEOUT   seconds for the perf-smoke stage (default 600)
#        LO_CI_QUANT_TIMEOUT  seconds for the quant-smoke stage (default 900)

set -euo pipefail

REPO="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
cd "$REPO"

echo "== selflint =="
python scripts/selflint.py

echo "== concurrency-lint: lock-order graph + witness hierarchy =="
# The concurrency pass (analysis/concurrency.py) runs inside selflint;
# this stage re-runs it in --json and fails on any error-severity
# finding, so the machine-readable artifact is in the CI log
# (docs/ANALYSIS.md "Concurrency passes").
LINT_OUT="$(mktemp)"
python scripts/selflint.py --json > "$LINT_OUT" || {
  cat "$LINT_OUT"
  echo "concurrency-lint: error-severity findings" >&2
  exit 1
}
python - "$LINT_OUT" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
counts = doc["counts"]
assert counts["error"] == 0, doc["findings"]
print(f"concurrency-lint: OK ({counts['warning']} waived warning(s))")
EOF

echo "== tier-1 tests =="
TIMEOUT="${LO_CI_TIMEOUT:-870}"
timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly

if [ "${LO_CI_FULL:-0}" = "1" ]; then
  echo "== full suite: slow oracle-parity tier included =="
  # The nightly tier: everything tests/conftest.py demotes to slow
  # (exhaustive oracle-parity sweeps, multi-config kernels) on top of
  # tier-1. The default tier keeps at least one parity test per
  # kernel/parallelism subsystem, so skipping this stage never means
  # zero numerical-correctness coverage.
  FULL_TIMEOUT="${LO_CI_FULL_TIMEOUT:-3600}"
  timeout -k 10 "$FULL_TIMEOUT" env JAX_PLATFORMS=cpu \
      python -m pytest tests/ -q -m 'slow or not slow' \
      --continue-on-collection-errors \
      -p no:cacheprovider -p no:xdist -p no:randomly
fi

echo "== chaos: lifecycle under fault injection =="
# A bounded hang at the job_run site (reclaimed by deadlines/cancel)
# plus a slow artifact store. Tests that arm their own LO_FAULT_INJECT
# override this ambient spec; the point is that the lifecycle suites
# keep passing with chaos in the environment. LO_CKPT_ASYNC=1 routes
# every checkpointed train through the async tiered manager, and the
# async/migration suites ride along — they arm the
# ckpt_async_commit / migration fault sites themselves
# (docs/RELIABILITY.md). LO_LOCK_WITNESS=1 arms the runtime
# lock-order witness in raise mode for the whole stage: any
# out-of-order acquisition under chaos fails the build
# (docs/ANALYSIS.md "Concurrency passes").
CHAOS_TIMEOUT="${LO_CI_CHAOS_TIMEOUT:-300}"
timeout -k 10 "$CHAOS_TIMEOUT" env JAX_PLATFORMS=cpu \
    LO_FAULT_INJECT="job_run:1:hang:0.2,artifact_save:1:latency:0.05" \
    LO_CKPT_ASYNC=1 \
    LO_LOCK_WITNESS=1 \
    python -m pytest tests/test_faults.py tests/test_lifecycle.py \
    tests/test_async_ckpt.py tests/test_migration.py \
    tests/test_autoscaler.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly

echo "== perf-smoke: warm pipeline must hit the feature-plane cache =="
# Runs the builder pipeline twice on one small dataset (bench.py
# warm_pipeline) and asserts the warm run actually reused cached
# state: cache hits > 0 and warm pipeline_seconds <= cold. jax's
# persistent compilation cache stays OFF here: on the CPU backend the
# one cache rule (services/context.py) turns it on only when
# JAX_COMPILATION_CACHE_DIR is set (hazard note: tests/conftest.py).
PERF_TIMEOUT="${LO_CI_PERF_TIMEOUT:-600}"
PERF_OUT="$(mktemp)"
SLICE_OUT="$(mktemp)"
trap 'rm -rf "$PERF_OUT" "$SLICE_OUT"' EXIT
timeout -k 10 "$PERF_TIMEOUT" env JAX_PLATFORMS=cpu \
    LO_COMPUTE_DTYPE=float32 \
    LO_BENCH_WARM_ROWS=20000 \
    python bench.py --phase warm_pipeline | tee "$PERF_OUT"
python - "$PERF_OUT" <<'EOF'
import json, sys

mark = "@@LO_BENCH_RESULT@@"
result = None
for line in reversed(open(sys.argv[1]).read().splitlines()):
    if line.startswith(mark):
        result = json.loads(line[len(mark):])
        break
assert result is not None, "perf-smoke: no bench result line"
assert "error" not in result, f"perf-smoke: phase failed: {result}"
result = result.get("result", result)  # unwrap the ok-envelope
hits = (result["warm_feature_hits"] + result["warm_arena_hits"]
        + result["warm_executable_hits"])
cold = result["cold"]["pipeline_seconds"]
warm = result["warm"]["pipeline_seconds"]
assert hits > 0, f"perf-smoke: warm run hit no caches: {result}"
assert warm <= cold, f"perf-smoke: warm {warm}s slower than cold {cold}s"
print(f"perf-smoke: OK (cold {cold}s, warm {warm}s, {hits} cache hits)")
EOF

echo "== slice-smoke: concurrent half-mesh jobs must beat serialization =="
# Two identical small train jobs on an 8-device CPU mesh: serialized
# behind one full-mesh lease vs concurrent on disjoint 4-device slices
# (bench.py concurrent_jobs). The gate asserts spatial multiplexing
# actually pays: concurrent wall-clock < 0.75x serialized.
SLICE_TIMEOUT="${LO_CI_SLICE_TIMEOUT:-600}"
timeout -k 10 "$SLICE_TIMEOUT" env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    LO_COMPUTE_DTYPE=float32 \
    python bench.py --phase concurrent_jobs | tee "$SLICE_OUT"
python - "$SLICE_OUT" <<'EOF'
import json, sys

mark = "@@LO_BENCH_RESULT@@"
result = None
for line in reversed(open(sys.argv[1]).read().splitlines()):
    if line.startswith(mark):
        result = json.loads(line[len(mark):])
        break
assert result is not None, "slice-smoke: no bench result line"
assert "error" not in result, f"slice-smoke: phase failed: {result}"
result = result.get("result", result)  # unwrap the ok-envelope
assert "skipped" not in result, f"slice-smoke: {result['skipped']}"
serialized = result["serialized_seconds"]
concurrent = result["concurrent_seconds"]
ratio = result["ratio"]
assert ratio < 0.75, (
    f"slice-smoke: concurrent {concurrent}s is not < 0.75x "
    f"serialized {serialized}s (ratio {ratio})")
print(f"slice-smoke: OK (serialized {serialized}s, "
      f"concurrent {concurrent}s, ratio {ratio})")
EOF

echo "== ckpt-stall: async checkpointing must hide the commit =="
# The same multi-MB state saved through the sync Checkpointer vs the
# async tiered manager (bench.py ckpt_stall; docs/RELIABILITY.md
# "Async checkpointing"). The gate asserts the train-thread stall
# under LO_CKPT_ASYNC semantics is < 10% of the synchronous commit
# wall-clock — the snapshot is the only cost the caller pays.
CKPT_TIMEOUT="${LO_CI_CKPT_TIMEOUT:-300}"
CKPT_OUT="$(mktemp)"
MIG_OUT="$(mktemp)"
trap 'rm -rf "$PERF_OUT" "$SLICE_OUT" "$CKPT_OUT" "$MIG_OUT"' EXIT
timeout -k 10 "$CKPT_TIMEOUT" env JAX_PLATFORMS=cpu \
    LO_COMPUTE_DTYPE=float32 \
    python bench.py --phase ckpt_stall | tee "$CKPT_OUT"
python - "$CKPT_OUT" <<'EOF'
import json, sys

mark = "@@LO_BENCH_RESULT@@"
result = None
for line in reversed(open(sys.argv[1]).read().splitlines()):
    if line.startswith(mark):
        result = json.loads(line[len(mark):])
        break
assert result is not None, "ckpt-stall: no bench result line"
assert "error" not in result, f"ckpt-stall: phase failed: {result}"
result = result.get("result", result)  # unwrap the ok-envelope
ratio = result["stall_ratio"]
assert ratio < 0.10, (
    f"ckpt-stall: async stall is {ratio}x the sync commit "
    f"(gate < 0.10x): {result}")
print(f"ckpt-stall: OK (sync {result['sync_stall_seconds']}s, "
      f"async {result['async_stall_seconds']}s over "
      f"{result['saves']} saves of {result['payload_mb']}MB, "
      f"ratio {ratio})")
EOF

echo "== migration-smoke: live migration must not perturb the math =="
# A forced mid-fit migration through the fair queue vs an untouched
# twin run (bench.py migration_smoke; docs/SCALING.md §7). Gates:
#  - the migrated run's final params are BIT-identical to the
#    unmigrated run's (placement must be invisible to the math)
#  - with LO_SLICE_DEFRAG armed, an aged waiter starved by a
#    fragmented holder is placed while the holder still runs
#    (defrag-via-migration actually frees a usable slice)
MIG_TIMEOUT="${LO_CI_MIG_TIMEOUT:-600}"
timeout -k 10 "$MIG_TIMEOUT" env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    LO_COMPUTE_DTYPE=float32 \
    python bench.py --phase migration_smoke | tee "$MIG_OUT"
python - "$MIG_OUT" <<'EOF'
import json, sys

mark = "@@LO_BENCH_RESULT@@"
result = None
for line in reversed(open(sys.argv[1]).read().splitlines()):
    if line.startswith(mark):
        result = json.loads(line[len(mark):])
        break
assert result is not None, "migration-smoke: no bench result line"
assert "error" not in result, f"migration-smoke: phase failed: {result}"
result = result.get("result", result)  # unwrap the ok-envelope
assert "skipped" not in result, f"migration-smoke: {result['skipped']}"
assert result["migrations_requested"] >= 1, (
    f"migration-smoke: no migration was requested: {result}")
assert result["bit_identical"], (
    f"migration-smoke: migrated run diverged from the unmigrated "
    f"twin: {result}")
assert result["defrag_placed_waiter"], (
    f"migration-smoke: defrag did not place the aged waiter: {result}")
print(f"migration-smoke: OK (bit-identical across "
      f"{result['migrations_requested']} migration(s), defrag placed "
      f"the waiter in {result['defrag_seconds']}s via "
      f"{result['defrag_picks']} pick(s))")
EOF

echo "== elastic-smoke: autoscaler must relieve pressure, roll back safely =="
# Elastic autoscaling end-to-end (bench.py elastic_smoke;
# docs/SCALING.md "Elastic autoscaling"). Gates:
#  - an aged rigid waiter starved by an elastic holder lands WHILE
#    the holder still runs (the closed loop shrank it), and its
#    completion latency beats the rigid-only twin's
#  - injected SLO-page pressure shrinks a training victim without
#    killing it (it finishes on the smaller slice)
#  - a resize killed by the armed autoscale_resize fault ROLLS BACK:
#    the run stays bit-identical to an untouched rigid twin
ELASTIC_TIMEOUT="${LO_CI_ELASTIC_TIMEOUT:-600}"
ELASTIC_OUT="$(mktemp)"
trap 'rm -rf "$PERF_OUT" "$SLICE_OUT" "$CKPT_OUT" "$MIG_OUT" "$ELASTIC_OUT"' EXIT
timeout -k 10 "$ELASTIC_TIMEOUT" env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    LO_COMPUTE_DTYPE=float32 \
    python bench.py --phase elastic_smoke | tee "$ELASTIC_OUT"
python - "$ELASTIC_OUT" <<'EOF'
import json, sys

mark = "@@LO_BENCH_RESULT@@"
result = None
for line in reversed(open(sys.argv[1]).read().splitlines()):
    if line.startswith(mark):
        result = json.loads(line[len(mark):])
        break
assert result is not None, "elastic-smoke: no bench result line"
assert "error" not in result, f"elastic-smoke: phase failed: {result}"
result = result.get("result", result)  # unwrap the ok-envelope
assert "skipped" not in result, f"elastic-smoke: {result['skipped']}"
assert result["shrinks_completed"] >= 1, (
    f"elastic-smoke: the closed loop never completed a shrink: "
    f"{result}")
assert result["waiter_overlapped_holder"], (
    f"elastic-smoke: the starved waiter did not overlap the elastic "
    f"holder: {result}")
assert result["waiter_latency_speedup"] > 1.0, (
    f"elastic-smoke: elastic waiter latency did not beat the "
    f"rigid-only twin: {result}")
assert result["pressure_shrinks"] >= 1 and result["victim_finished"], (
    f"elastic-smoke: SLO-page pressure did not shrink a surviving "
    f"victim: {result}")
assert result["resize_rollbacks"] >= 1, (
    f"elastic-smoke: armed autoscale_resize fault never rolled back "
    f"a resize: {result}")
assert result["rollback_bit_identical"], (
    f"elastic-smoke: rolled-back run diverged from the rigid twin: "
    f"{result}")
print(f"elastic-smoke: OK (waiter {result['waiter_latency_speedup']}x "
      f"faster, {result['shrinks_completed']} shrink(s), "
      f"{result['resize_rollbacks']} rollback(s) bit-identical, "
      f"makespan ratio {result['makespan_speedup']})")
EOF

echo "== sentinel-smoke: chaos train must finish via rollback =="
# NaN'd train step + bit-rotted checkpoint write through the full REST
# stack under healthPolicy rollback (bench.py sentinel_chaos): the job
# must reach finished — not deadLettered — with at least one recorded
# rollback (docs/RELIABILITY.md).
SENTINEL_TIMEOUT="${LO_CI_SENTINEL_TIMEOUT:-600}"
CHAOS_OUT="$(mktemp)"
OVERHEAD_OUT="$(mktemp)"
OBS_OUT="$(mktemp)"
SERVE_OUT="$(mktemp)"
PAGED_OUT="$(mktemp)"
QUANT_OUT="$(mktemp)"
DISAGG_OUT="$(mktemp)"
SWEEP_OUT="$(mktemp)"
MONITOR_OUT="$(mktemp)"
INCIDENT_OUT="$(mktemp)"
ROOFLINE_OUT="$(mktemp)"
XRAY_OUT="$(mktemp)"
trap 'rm -rf "$PERF_OUT" "$SLICE_OUT" "$CKPT_OUT" "$MIG_OUT" "$ELASTIC_OUT" "$CHAOS_OUT" "$OVERHEAD_OUT" "$OBS_OUT" "$SERVE_OUT" "$PAGED_OUT" "$QUANT_OUT" "$DISAGG_OUT" "$SWEEP_OUT" "$MONITOR_OUT" "$ROOFLINE_OUT" "$XRAY_OUT"' EXIT
timeout -k 10 "$SENTINEL_TIMEOUT" env JAX_PLATFORMS=cpu \
    LO_COMPUTE_DTYPE=float32 \
    python bench.py --phase sentinel_chaos | tee "$CHAOS_OUT"
python - "$CHAOS_OUT" <<'EOF'
import json, sys

mark = "@@LO_BENCH_RESULT@@"
result = None
for line in reversed(open(sys.argv[1]).read().splitlines()):
    if line.startswith(mark):
        result = json.loads(line[len(mark):])
        break
assert result is not None, "sentinel-smoke: no bench result line"
assert "error" not in result, f"sentinel-smoke: phase failed: {result}"
result = result.get("result", result)  # unwrap the ok-envelope
assert result["finished"], f"sentinel-smoke: job did not finish: {result}"
assert result["status"] == "finished", f"sentinel-smoke: {result}"
assert result["rollbacks"] >= 1, (
    f"sentinel-smoke: no rollback recorded: {result}")
print(f"sentinel-smoke: OK (status {result['status']}, "
      f"{result['rollbacks']} rollback(s), "
      f"{result['nonfinite_steps']} nonfinite step(s))")
EOF

echo "== sentinel-overhead: armed sentinel must cost < 3% =="
# The same MLP fit with the sentinel off vs skip (bench.py
# sentinel_overhead); the armed health word + drop guard must stay
# under a 3% steady-state slowdown.
timeout -k 10 "$SENTINEL_TIMEOUT" env JAX_PLATFORMS=cpu \
    LO_COMPUTE_DTYPE=float32 \
    python bench.py --phase sentinel_overhead | tee "$OVERHEAD_OUT"
python - "$OVERHEAD_OUT" <<'EOF'
import json, sys

mark = "@@LO_BENCH_RESULT@@"
result = None
for line in reversed(open(sys.argv[1]).read().splitlines()):
    if line.startswith(mark):
        result = json.loads(line[len(mark):])
        break
assert result is not None, "sentinel-overhead: no bench result line"
assert "error" not in result, f"sentinel-overhead: phase failed: {result}"
result = result.get("result", result)  # unwrap the ok-envelope
ratio = result["overhead_ratio"]
assert ratio < 1.03, (
    f"sentinel-overhead: armed sentinel costs {ratio}x "
    f"(gate < 1.03x): {result}")
print(f"sentinel-overhead: OK (off {result['off_seconds']}s, "
      f"skip {result['skip_seconds']}s, ratio {ratio})")
EOF

echo "== obs-smoke: traced job must tell its whole story for < 3% =="
# One checkpointed train job through the REST stack (bench.py
# obs_overhead; docs/OBSERVABILITY.md): the span tree must contain
# queue-wait, a COLD compile, per-epoch and checkpointCommit spans
# plus a per-epoch timeline — and the tracer's steady-state cost vs
# LO_TRACE=0 must stay under the same < 3% gate as the sentinel.
OBS_TIMEOUT="${LO_CI_OBS_TIMEOUT:-600}"
timeout -k 10 "$OBS_TIMEOUT" env JAX_PLATFORMS=cpu \
    LO_COMPUTE_DTYPE=float32 \
    python bench.py --phase obs_overhead | tee "$OBS_OUT"
python - "$OBS_OUT" <<'EOF'
import json, sys

mark = "@@LO_BENCH_RESULT@@"
result = None
for line in reversed(open(sys.argv[1]).read().splitlines()):
    if line.startswith(mark):
        result = json.loads(line[len(mark):])
        break
assert result is not None, "obs-smoke: no bench result line"
assert "error" not in result, f"obs-smoke: phase failed: {result}"
result = result.get("result", result)  # unwrap the ok-envelope
missing = [k for k, ok in result["spans_present"].items() if not ok]
assert not missing, f"obs-smoke: spans missing from trace: {missing}"
assert result["cold_compiles"] >= 1, (
    f"obs-smoke: no cold compile span recorded: {result}")
assert result["timeline_windows"] >= 1, (
    f"obs-smoke: empty per-step timeline: {result}")
ratio = result["overhead_ratio"]
assert ratio < 1.03, (
    f"obs-smoke: tracer costs {ratio}x (gate < 1.03x): {result}")
print(f"obs-smoke: OK (all spans present, {result['cold_compiles']} "
      f"cold compile(s), {result['timeline_windows']} timeline "
      f"window(s), overhead {ratio}x)")
EOF

echo "== serving-smoke: resident plane must beat the batch path =="
# One continuous-batched LM session under 8 concurrent streams plus a
# shape-bucketed classifier session (bench.py serving;
# docs/SERVING.md). Gates:
#  - warm serving predict p50 >= 5x lower than the submit->poll job
#    path on the same fitted artifact, and an absolute sustained floor
#    (p50 <= 100ms -> >= 10 req/s warm)
#  - sustained decode tokens/s vs the in-phase solo (batch-2) decode
#    baseline: >= 3x on an accelerator, where decode is HBM-bound and
#    slot batching is nearly free; >= 0.8x (parity floor) on the CPU
#    backend, where the vocab projection is compute-bound and scales
#    linearly with batch. Override with LO_SMOKE_SERVE_DECODE_FLOOR.
SERVE_TIMEOUT="${LO_CI_SERVE_TIMEOUT:-900}"
timeout -k 10 "$SERVE_TIMEOUT" env JAX_PLATFORMS=cpu \
    LO_COMPUTE_DTYPE=float32 \
    LO_BENCH_TLM_D=128 LO_BENCH_TLM_LAYERS=2 LO_BENCH_TLM_SEQ=128 \
    LO_BENCH_SERVE_TOKENS=32 LO_BENCH_SERVE_PROMPT=16 \
    LO_BENCH_SERVE_STREAMS=8 LO_BENCH_SERVE_REQS=2 \
    python bench.py --phase serving | tee "$SERVE_OUT"
python - "$SERVE_OUT" <<'EOF'
import json, os, sys

mark = "@@LO_BENCH_RESULT@@"
result = None
for line in reversed(open(sys.argv[1]).read().splitlines()):
    if line.startswith(mark):
        result = json.loads(line[len(mark):])
        break
assert result is not None, "serving-smoke: no bench result line"
assert "error" not in result, f"serving-smoke: phase failed: {result}"
result = result.get("result", result)  # unwrap the ok-envelope
floor = os.environ.get("LO_SMOKE_SERVE_DECODE_FLOOR")
floor = float(floor) if floor else (
    0.8 if result["platform"] == "cpu" else 3.0)
decode = result["speedup_vs_solo"]
assert decode >= floor, (
    f"serving-smoke: sustained decode {decode}x solo baseline "
    f"(gate >= {floor}x on {result['platform']}): {result}")
pspeed = result["predict_speedup"]
assert pspeed >= 5, (
    f"serving-smoke: warm predict only {pspeed}x faster than "
    f"submit->poll (gate >= 5x): {result}")
p50 = result["predict_serving_p50_ms"]
assert p50 <= 100, (
    f"serving-smoke: warm predict p50 {p50}ms (floor <= 100ms): "
    f"{result}")
print(f"serving-smoke: OK (decode {decode}x solo, "
      f"p99 {result['p99_ms']}ms over {result['streams']} streams, "
      f"clf predict {pspeed}x vs submit->poll, p50 {p50}ms)")
EOF

echo "== paged-smoke: paged KV must beat slot KV at equal HBM =="
# Paged KV pool vs the contiguous slot cache on the SAME page budget,
# plus an abusive-tenant chaos run through one shared pool (bench.py
# paged_serving; docs/SERVING.md "Paged KV serving"). Gates:
#  - peak simultaneously-decoding streams: paged >= 2x slot at equal
#    KV memory (page-granular admission vs worst-case slot
#    reservation). Override with LO_SMOKE_PAGED_STREAMS_FLOOR.
#  - QoS isolation: the bully tenant is rejected at least once (its
#    own weighted-fair quota), the victim tenant takes ZERO 429s and
#    its per-tenant servingP99 objective must not fire.
PAGED_TIMEOUT="${LO_CI_PAGED_TIMEOUT:-900}"
timeout -k 10 "$PAGED_TIMEOUT" env JAX_PLATFORMS=cpu \
    LO_COMPUTE_DTYPE=float32 \
    LO_BENCH_TLM_D=128 LO_BENCH_TLM_LAYERS=2 LO_BENCH_TLM_SEQ=128 \
    LO_BENCH_PAGED_SLO_MS=30000 \
    python bench.py --phase paged_serving | tee "$PAGED_OUT"
python - "$PAGED_OUT" <<'EOF'
import json, os, sys

mark = "@@LO_BENCH_RESULT@@"
result = None
for line in reversed(open(sys.argv[1]).read().splitlines()):
    if line.startswith(mark):
        result = json.loads(line[len(mark):])
        break
assert result is not None, "paged-smoke: no bench result line"
assert "error" not in result, f"paged-smoke: phase failed: {result}"
result = result.get("result", result)  # unwrap the ok-envelope
floor = float(os.environ.get("LO_SMOKE_PAGED_STREAMS_FLOOR", "2.0"))
ratio = result["streams_vs_slot"]
assert ratio >= floor, (
    f"paged-smoke: paged sustained only {ratio}x the slot streams "
    f"at equal HBM (gate >= {floor}x): {result}")
assert result["bully_rejected"] >= 1, (
    f"paged-smoke: abusive tenant was never quota-rejected: {result}")
assert result["victim_rejected"] == 0, (
    f"paged-smoke: victim tenant took "
    f"{result['victim_rejected']} 429s behind the bully: {result}")
assert not result["victim_slo_fired"], (
    f"paged-smoke: the bully paged the victim's servingP99 "
    f"objective: {result}")
print(f"paged-smoke: OK (peak {result['paged_peak_streams']} vs "
      f"{result['slot_peak_streams']} slot streams = {ratio}x at "
      f"equal HBM, bully 429s={result['bully_rejected']}, victim "
      f"429s=0, victim p99 {result['victim_p99_ms']}ms, SLO quiet)")
EOF

echo "== quant-smoke: int8 KV must beat bf16 at equal HBM, gated on quality =="
# Quantized serving plane (bench.py quant_serving; docs/SERVING.md
# "Quantized serving"). Gates:
#  - peak simultaneously-decoding streams: int8 >= 1.8x bf16 at equal
#    pool bytes (int8 payload + f32 scale rows funded together; page
#    capacity at equal bytes holds on CPU and TPU alike). Override
#    with LO_SMOKE_QUANT_STREAMS_FLOOR.
#  - quality: the create-time drift probe sits under
#    LO_SERVE_DRIFT_MAX (the quantized session would have degraded
#    itself otherwise).
#  - chaos: a latched kv_quant fault walks the degrade ladder — 429s
#    then a clean 200 over exact bf16 pages/weights, never a
#    corrupted stream.
QUANT_TIMEOUT="${LO_CI_QUANT_TIMEOUT:-900}"
timeout -k 10 "$QUANT_TIMEOUT" env JAX_PLATFORMS=cpu \
    LO_COMPUTE_DTYPE=float32 \
    LO_BENCH_TLM_D=128 LO_BENCH_TLM_LAYERS=2 LO_BENCH_TLM_SEQ=128 \
    python bench.py --phase quant_serving | tee "$QUANT_OUT"
python - "$QUANT_OUT" <<'EOF'
import json, os, sys

mark = "@@LO_BENCH_RESULT@@"
result = None
for line in reversed(open(sys.argv[1]).read().splitlines()):
    if line.startswith(mark):
        result = json.loads(line[len(mark):])
        break
assert result is not None, "quant-smoke: no bench result line"
assert "error" not in result, f"quant-smoke: phase failed: {result}"
result = result.get("result", result)  # unwrap the ok-envelope
floor = float(os.environ.get("LO_SMOKE_QUANT_STREAMS_FLOOR", "1.8"))
ratio = result["streams_vs_bf16"]
assert ratio >= floor, (
    f"quant-smoke: int8 sustained only {ratio}x the bf16 streams "
    f"at equal HBM (gate >= {floor}x): {result}")
drift, limit = result["drift"], result["drift_max"]
assert drift is not None and drift <= limit, (
    f"quant-smoke: drift probe {drift} exceeds "
    f"LO_SERVE_DRIFT_MAX={limit}: {result}")
assert result["degrade_fired"], (
    f"quant-smoke: latched kv_quant fault did not degrade the "
    f"session to bf16: {result}")
print(f"quant-smoke: OK (peak {result['int8_peak_streams']} vs "
      f"{result['bf16_peak_streams']} bf16 streams = {ratio}x at "
      f"equal HBM, drift {drift} <= {limit}, degrade ladder ok)")
EOF
# the quantized test suite rides under the lock-order witness: the
# degrade ladder rebuilds a live session (pool teardown + arena re-pin
# under the session lock), exactly where an out-of-order acquisition
# would hide (docs/ANALYSIS.md "Concurrency passes")
timeout -k 10 "$QUANT_TIMEOUT" env JAX_PLATFORMS=cpu \
    LO_COMPUTE_DTYPE=float32 \
    LO_LOCK_WITNESS=1 \
    python -m pytest tests/test_ops.py tests/test_serving.py \
    -q -k "quant or drift or degrade" \
    -p no:cacheprovider -p no:xdist -p no:randomly

echo "== disagg-smoke: disagg prefill must shield decode from bursts =="
# Disaggregated prefill/decode + speculative decoding (bench.py
# disagg_serving; docs/SERVING.md "Disaggregated serving &
# speculative decoding"). Gates:
#  - isolation: under the same open-loop mixed load (fixed-rate short
#    requests + long-prompt burst clients), the disaggregated
#    session's decode p99 stays <= LO_SMOKE_DISAGG_P99_MULT (default
#    1.2) x the no-burst floor while the fused session breaches that
#    multiple (prefill runs inside its serve loop).
#  - speculation: accepted tokens/step >= 1 with the draft armed
#    (every verify step emits at least the target's own token).
#  - chaos: a latched kv_page_handoff fault restores every page
#    reference on each 429 (no leak), collapses the session to fused
#    with an incident, and later requests serve through that path.
DISAGG_TIMEOUT="${LO_CI_DISAGG_TIMEOUT:-900}"
# colocated on CPU: forced host "devices" share the same cores, so
# split-lease placement would let burst prefills steal the decode
# arm's compute and invert the contrast (split mechanics are covered
# by tests/test_serving.py under the forced-8-device conftest)
timeout -k 10 "$DISAGG_TIMEOUT" env JAX_PLATFORMS=cpu \
    LO_COMPUTE_DTYPE=float32 \
    LO_BENCH_TLM_D=128 LO_BENCH_TLM_LAYERS=2 LO_BENCH_TLM_SEQ=128 \
    python bench.py --phase disagg_serving | tee "$DISAGG_OUT"
python - "$DISAGG_OUT" <<'EOF'
import json, os, sys

mark = "@@LO_BENCH_RESULT@@"
result = None
for line in reversed(open(sys.argv[1]).read().splitlines()):
    if line.startswith(mark):
        result = json.loads(line[len(mark):])
        break
assert result is not None, "disagg-smoke: no bench result line"
assert "error" not in result, f"disagg-smoke: phase failed: {result}"
result = result.get("result", result)  # unwrap the ok-envelope
mult = float(os.environ.get("LO_SMOKE_DISAGG_P99_MULT", "1.2"))
disagg = result["disagg_burst_decode_p99_vs_no_burst"]
fused = result["fused_burst_decode_p99_vs_no_burst"]
assert disagg is not None and disagg <= mult, (
    f"disagg-smoke: burst traffic inflated the disaggregated decode "
    f"p99 to {disagg}x the no-burst floor (gate <= {mult}x): "
    f"{result}")
assert fused is not None and fused > mult, (
    f"disagg-smoke: the fused contrast arm held {fused}x under the "
    f"same burst (expected > {mult}x — the mixed load is not "
    f"stressing prefill, so the isolation gate proves nothing): "
    f"{result}")
acc = result["accepted_tokens_per_step"]
assert acc is not None and acc >= 1.0, (
    f"disagg-smoke: accepted tokens/step {acc} (a verify step always "
    f"emits at least the target's own token): {result}")
assert result["chaos_leak_free"], (
    f"disagg-smoke: 429'd handoffs leaked page references: {result}")
assert result["chaos_degrade_fired"], (
    f"disagg-smoke: latched kv_page_handoff fault did not collapse "
    f"the session to fused serving: {result}")
print(f"disagg-smoke: OK (decode p99 burst/floor: disagg {disagg}x "
      f"vs fused {fused}x, gate {mult}x; accepted/step {acc}; "
      f"spec {result['spec_tokens_per_sec']} tok/s vs "
      f"{result['base_tokens_per_sec']} base; handoff chaos "
      f"leak-free + degraded)")
EOF
# the disagg + spec suites ride under the lock-order witness: the
# handoff path spans three threads (REST admit -> prefill worker ->
# decode loop) across the handoff/prefix/pool ranks, exactly where an
# out-of-order acquisition would hide
timeout -k 10 "$DISAGG_TIMEOUT" env JAX_PLATFORMS=cpu \
    LO_COMPUTE_DTYPE=float32 \
    LO_LOCK_WITNESS=1 \
    python -m pytest tests/test_serving.py \
    -q -k "disagg or spec" \
    -p no:cacheprovider -p no:xdist -p no:randomly

echo "== sweep-smoke: fused sweep must beat serial trials =="
# An 8-point learning-rate grid over one MLP architecture, fused into
# a single vmapped train program vs the serial one-trial-at-a-time
# path (bench.py sweep_fusion; docs/PERFORMANCE.md "Sweep fusion").
# Gates:
#  - the warm fused run re-traces nothing (warm_retraces == 0): the
#    whole cohort shares ONE compiled epoch program
#  - fused wall-clock vs serial: >= 4x on an accelerator, where the 8
#    serial compiles dominate and the fused step keeps the chip fed;
#    >= 2x on the CPU backend, where XLA:CPU already amortizes small
#    GEMMs so the win is mostly the 7 avoided compiles. Override with
#    LO_SMOKE_SWEEP_FLOOR.
SWEEP_TIMEOUT="${LO_CI_SWEEP_TIMEOUT:-900}"
timeout -k 10 "$SWEEP_TIMEOUT" env JAX_PLATFORMS=cpu \
    LO_COMPUTE_DTYPE=float32 \
    python bench.py --phase sweep_fusion | tee "$SWEEP_OUT"
python - "$SWEEP_OUT" <<'EOF'
import json, os, sys

mark = "@@LO_BENCH_RESULT@@"
result = None
for line in reversed(open(sys.argv[1]).read().splitlines()):
    if line.startswith(mark):
        result = json.loads(line[len(mark):])
        break
assert result is not None, "sweep-smoke: no bench result line"
assert "error" not in result, f"sweep-smoke: phase failed: {result}"
result = result.get("result", result)  # unwrap the ok-envelope
assert result["warm_retraces"] == 0, (
    f"sweep-smoke: warm fused sweep re-traced "
    f"{result['warm_retraces']} epoch program(s) (gate == 0): {result}")
assert result["fused_trials"] == result["points"], (
    f"sweep-smoke: only {result['fused_trials']}/{result['points']} "
    f"trials fused: {result}")
floor = os.environ.get("LO_SMOKE_SWEEP_FLOOR")
floor = float(floor) if floor else (
    2.0 if result["platform"] == "cpu" else 4.0)
speedup = result["speedup"]
assert speedup >= floor, (
    f"sweep-smoke: fused sweep only {speedup}x serial "
    f"(gate >= {floor}x on {result['platform']}): {result}")
print(f"sweep-smoke: OK ({result['points']} points in "
      f"{result['cohorts']} cohort(s), fused {result['fused_seconds']}s "
      f"vs serial {result['serial_seconds']}s, {speedup}x, "
      f"0 warm retraces)")
EOF

echo "== monitor-smoke: SLO watchdog must page, resolve, and cost < 1% =="
# A serving-latency fault injected through a real resident predict
# session (bench.py monitor_smoke; docs/OBSERVABILITY.md "Cluster
# monitor, SLOs & alerts"). Gates:
#  - the servingP99 page alert FIRES while the fault is armed and
#    GET /healthz reports 503
#  - clearing the fault RESOLVES the alert and /healthz returns to
#    200 with no restart
#  - the background sampler at the production tick rate costs < 1%
#    steady-state vs the monitor stopped
MONITOR_TIMEOUT="${LO_CI_MONITOR_TIMEOUT:-600}"
timeout -k 10 "$MONITOR_TIMEOUT" env JAX_PLATFORMS=cpu \
    LO_COMPUTE_DTYPE=float32 \
    python bench.py --phase monitor_smoke | tee "$MONITOR_OUT"
python - "$MONITOR_OUT" <<'EOF'
import json, sys

mark = "@@LO_BENCH_RESULT@@"
result = None
for line in reversed(open(sys.argv[1]).read().splitlines()):
    if line.startswith(mark):
        result = json.loads(line[len(mark):])
        break
assert result is not None, "monitor-smoke: no bench result line"
assert "error" not in result, f"monitor-smoke: phase failed: {result}"
result = result.get("result", result)  # unwrap the ok-envelope
assert result["alert_fired"], (
    f"monitor-smoke: servingP99 never fired under the latency "
    f"fault: {result}")
assert result["healthz_during"] == 503, (
    f"monitor-smoke: /healthz did not report 503 while a page "
    f"alert was firing: {result}")
assert result["alert_resolved"], (
    f"monitor-smoke: servingP99 did not resolve after the fault "
    f"cleared: {result}")
assert result["healthz_after"] == 200, (
    f"monitor-smoke: /healthz did not return to 200: {result}")
ratio = result["overhead_ratio"]
assert ratio < 1.01, (
    f"monitor-smoke: sampler costs {ratio}x (gate < 1.01x): {result}")
print(f"monitor-smoke: OK (alert fired on trace "
      f"{result['alert_trace']}, healthz 503 -> 200, sampler "
      f"overhead {ratio}x)")
EOF

echo "== incident-smoke: a page must auto-capture a bundle, cost < 3% =="
# Incident flight recorder end-to-end (bench.py incident_smoke;
# docs/OBSERVABILITY.md "Incidents & flight recorder"). Gates:
#  - the servingP99 page alert firing under the injected latency
#    fault AUTO-captures a debug bundle carrying every evidence
#    section, the firing alert context and zero collector errors,
#    and the bundle downloads through the REST tar route
#  - a re-trigger inside the cooldown is muted and LO_INCIDENT_KEEP
#    bounds the on-disk bundle count
#  - an armed-but-idle recorder costs < 3% steady-state vs off
INCIDENT_TIMEOUT="${LO_CI_INCIDENT_TIMEOUT:-600}"
timeout -k 10 "$INCIDENT_TIMEOUT" env JAX_PLATFORMS=cpu \
    LO_COMPUTE_DTYPE=float32 \
    python bench.py --phase incident_smoke | tee "$INCIDENT_OUT"
python - "$INCIDENT_OUT" <<'EOF'
import json, sys

mark = "@@LO_BENCH_RESULT@@"
result = None
for line in reversed(open(sys.argv[1]).read().splitlines()):
    if line.startswith(mark):
        result = json.loads(line[len(mark):])
        break
assert result is not None, "incident-smoke: no bench result line"
assert "error" not in result, f"incident-smoke: phase failed: {result}"
result = result.get("result", result)  # unwrap the ok-envelope
assert result["incident_captured"], (
    f"incident-smoke: servingP99 page never auto-captured a "
    f"bundle: {result}")
assert result["sections_missing"] == [], (
    f"incident-smoke: bundle missing evidence sections "
    f"{result['sections_missing']}: {result}")
assert result["manifest_errors"] == 0, (
    f"incident-smoke: bundle collectors errored: {result}")
assert result["alert_context_ok"], (
    f"incident-smoke: manifest lacks the firing alert context: "
    f"{result}")
assert result["download_ok"], (
    f"incident-smoke: REST tar download failed: {result}")
assert result["cooldown_muted"], (
    f"incident-smoke: re-trigger inside the cooldown was not "
    f"muted: {result}")
assert result["retention_ok"], (
    f"incident-smoke: LO_INCIDENT_KEEP did not bound the bundle "
    f"count: {result}")
ratio = result["overhead_ratio"]
assert ratio < 1.03, (
    f"incident-smoke: idle recorder costs {ratio}x "
    f"(gate < 1.03x): {result}")
print(f"incident-smoke: OK (bundle {result['bundle_bytes']} bytes, "
      f"download {result['download_bytes']} bytes, cooldown muted, "
      f"retention bounded, overhead {ratio}x)")
EOF

echo "== roofline-smoke: perf reports must land and cost < 3% =="
# Roofline perf observability end-to-end (bench.py perf_report;
# docs/OBSERVABILITY.md "Roofline & perf reports"). Gates:
#  - a finished train job answers GET /observability/perf/{name} with
#    the full roofline block (mfu, achieved GB/s/chip, bound class)
#    and its timeline carries the per-window perf percentiles
#  - an ACTIVE predict session answers the same route with its live
#    goodput block, and /metrics exposes the lo_mfu /
#    lo_tflops_per_chip / lo_abandoned_dispatches gauges
#  - LO_PERF=1 vs LO_PERF=0 steady-state fit cost stays < 3%
ROOFLINE_TIMEOUT="${LO_CI_ROOFLINE_TIMEOUT:-600}"
timeout -k 10 "$ROOFLINE_TIMEOUT" env JAX_PLATFORMS=cpu \
    LO_COMPUTE_DTYPE=float32 \
    python bench.py --phase perf_report | tee "$ROOFLINE_OUT"
python - "$ROOFLINE_OUT" <<'EOF'
import json, sys

mark = "@@LO_BENCH_RESULT@@"
result = None
for line in reversed(open(sys.argv[1]).read().splitlines()):
    if line.startswith(mark):
        result = json.loads(line[len(mark):])
        break
assert result is not None, "roofline-smoke: no bench result line"
assert "error" not in result, f"roofline-smoke: phase failed: {result}"
result = result.get("result", result)  # unwrap the ok-envelope
assert result["train_report_ok"], (
    f"roofline-smoke: train perf report missing/incomplete: {result}")
assert result["timeline_perf_ok"], (
    f"roofline-smoke: timeline carries no perf block: {result}")
assert result["serving_report_ok"], (
    f"roofline-smoke: live serving perf report missing: {result}")
assert result["prom_gauges_ok"], (
    f"roofline-smoke: /metrics lacks the new gauges: {result}")
ratio = result["perf_overhead_ratio"]
assert ratio < 1.03, (
    f"roofline-smoke: perf tracking costs {ratio}x "
    f"(gate < 1.03x): {result}")
print(f"roofline-smoke: OK (train mfu {result['train_mfu']}, "
      f"bound by {result['train_bound_by']}, serving "
      f"{result['serving_rows_per_sec_per_chip']} rows/s/chip, "
      f"overhead {ratio}x)")
EOF

echo "== xray-smoke: HBM ledger must attribute + cost < 3% =="
# HBM attribution ledger + compiled-artifact X-ray end-to-end
# (bench.py xray_overhead; docs/OBSERVABILITY.md "HBM attribution &
# X-ray"). Gates:
#  - a train+serve workload shows EVERY expected owner in the ledger
#    (arena, train-state, serving-params, kv-cache, snapshot) and the
#    job leaves a GET /observability/compile/{name} X-ray
#  - the bare memory route's unattributed fraction stays < 50% on the
#    CPU backend (live-arrays accounting; XLA temps don't persist)
#  - a forced retrace and a forced implicit transfer each land a
#    counted, signature-carrying event
#  - LO_XRAY=1 vs LO_XRAY=0 steady-state fit cost stays < 3%
XRAY_TIMEOUT="${LO_CI_XRAY_TIMEOUT:-600}"
timeout -k 10 "$XRAY_TIMEOUT" env JAX_PLATFORMS=cpu \
    LO_COMPUTE_DTYPE=float32 \
    python bench.py --phase xray_overhead | tee "$XRAY_OUT"
python - "$XRAY_OUT" <<'EOF'
import json, sys

mark = "@@LO_BENCH_RESULT@@"
result = None
for line in reversed(open(sys.argv[1]).read().splitlines()):
    if line.startswith(mark):
        result = json.loads(line[len(mark):])
        break
assert result is not None, "xray-smoke: no bench result line"
assert "error" not in result, f"xray-smoke: phase failed: {result}"
result = result.get("result", result)  # unwrap the ok-envelope
assert result["owners_ok"], (
    f"xray-smoke: ledger missing expected owners "
    f"(saw {result.get('owners_seen')}): {result}")
assert result["compile_report_ok"], (
    f"xray-smoke: compiled-artifact report missing/incomplete: "
    f"{result}")
assert result["snapshot_ledgered"] and result["snapshot_released"], (
    f"xray-smoke: async-ckpt snapshot not ledgered/released: "
    f"{result}")
frac = result["unattributed_frac"]
assert frac is not None and frac < 0.5, (
    f"xray-smoke: unattributed fraction {frac} (gate < 0.5): "
    f"{result}")
assert result["retrace_ok"], (
    f"xray-smoke: forced retrace left no counted signature event: "
    f"{result}")
assert result["transfer_ok"], (
    f"xray-smoke: forced implicit transfer left no counted event: "
    f"{result}")
ratio = result["xray_overhead_ratio"]
assert ratio < 1.03, (
    f"xray-smoke: ledger costs {ratio}x (gate < 1.03x): {result}")
print(f"xray-smoke: OK (owners {result['owners_seen']}, "
      f"unattributed {frac}, overhead {ratio}x)")
EOF

echo "== bench-regress: newest round must not regress the prior one =="
# IQR-scaled per-metric gate over the committed BENCH_r*.json rounds
# (scripts/bench_regress.py); passes trivially when fewer than two
# rounds carry a parseable extra.models payload.
python scripts/bench_regress.py

echo "== ci: OK =="
