#!/usr/bin/env bash
# CI gate: repo self-lint, the tier-1 test suite, then the suites that
# tier-1 runs unarmed, re-run under an injecting environment and under
# the lock-order witness. Every stage runs tests or a linter; speed is
# measured on the chip by `python3 benchmark/run.py` (BENCHMARK.json),
# never here.
#
# Usage: deploy/ci.sh            (from anywhere; paths are self-rooted)
# Env:   LO_CI_TIMEOUT          seconds for the tier-1 run (default 1470)
#        LO_CI_FULL             1 to also run the FULL suite incl. slow
#                               oracle-parity tests (default 0: tier-1
#                               keeps one parity test per subsystem, see
#                               tests/conftest.py)
#        LO_CI_FULL_TIMEOUT     seconds for the full-suite run (default 3600)
#        LO_CI_CHAOS_TIMEOUT    seconds for each armed stage, chaos and
#                               lock-witness (default 300)

set -euo pipefail

REPO="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
cd "$REPO"

PYTEST_SERIAL=(python -m pytest -q -p no:cacheprovider -p no:xdist -p no:randomly)

echo "== selflint =="
python scripts/selflint.py

echo "== concurrency-lint: lock-order graph + witness hierarchy =="
# The concurrency pass (analysis/concurrency.py) runs inside selflint;
# this stage re-runs it in --json and fails on any error-severity
# finding, so the machine-readable artifact is in the CI log
# (docs/ANALYSIS.md "Concurrency passes").
python scripts/selflint.py --json | python -c '
import json, sys
doc = json.load(sys.stdin)
counts = doc["counts"]
assert counts["error"] == 0, doc["findings"]
print("concurrency-lint: OK (%d waived warning(s))" % counts["warning"])
'

echo "== tier-1 tests =="
# The shape the driver runs: six workers, one test file per worker at
# a time (module-global caches and fixtures stay in one process).
timeout -k 10 "${LO_CI_TIMEOUT:-1470}" env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p xdist -n 6 --dist loadfile -p no:randomly

if [ "${LO_CI_FULL:-0}" = "1" ]; then
  echo "== full suite: slow oracle-parity tier included =="
  # The nightly tier: everything tests/conftest.py demotes to slow
  # (exhaustive oracle-parity sweeps, multi-config kernels) on top of
  # tier-1. The default tier keeps at least one parity test per
  # kernel/parallelism subsystem, so skipping this stage never means
  # zero numerical-correctness coverage.
  timeout -k 10 "${LO_CI_FULL_TIMEOUT:-3600}" env JAX_PLATFORMS=cpu \
      "${PYTEST_SERIAL[@]}" tests/ -m 'slow or not slow' \
      --continue-on-collection-errors
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
timeout -k 10 "${LO_CI_CHAOS_TIMEOUT:-300}" env JAX_PLATFORMS=cpu \
    LO_FAULT_INJECT="job_run:1:hang:0.2,artifact_save:1:latency:0.05" \
    LO_CKPT_ASYNC=1 \
    LO_LOCK_WITNESS=1 \
    "${PYTEST_SERIAL[@]}" tests/test_faults.py tests/test_lifecycle.py \
    tests/test_async_ckpt.py tests/test_migration.py \
    tests/test_autoscaler.py

echo "== lock-witness: serving degrade ladders and the handoff path =="
# Tier-1 does not arm the witness. The quantized degrade ladder
# rebuilds a live session (pool teardown + arena re-pin under the
# session lock), and the disaggregated handoff spans three threads
# (REST admit -> prefill worker -> decode loop) across the
# handoff/prefix/pool ranks: exactly where an out-of-order acquisition
# would hide (docs/ANALYSIS.md "Concurrency passes").
timeout -k 10 "${LO_CI_CHAOS_TIMEOUT:-300}" env JAX_PLATFORMS=cpu \
    LO_COMPUTE_DTYPE=float32 \
    LO_LOCK_WITNESS=1 \
    "${PYTEST_SERIAL[@]}" tests/test_ops.py tests/test_serving.py \
    -k "quant or drift or degrade or disagg or spec"

echo "== ci: OK =="
