"""Without an accelerator, or without the program, a run prints no
result and exits non-zero."""

import os
import shutil
import subprocess
import sys

from bm_runs import ROOT, rehearse

CELL = "internlm2-l4.train-seq4k"


def test_no_accelerator_no_result():
    proc, last = rehearse(CELL)  # no --rehearse: the real size
    assert proc.returncode != 0 and last is None
    assert "needs a TPU" in proc.stderr


def test_benchmark_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    shutil.copytree(os.path.join(ROOT, "tests", "benchmark"),
                    tmp_path / "tests" / "benchmark")
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONPATH")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "1", "--seconds", "1", "--trace", "0", "--rehearse", "tiny"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not proc.stdout.strip()
