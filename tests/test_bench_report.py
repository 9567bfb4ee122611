"""bench.py reporting contract: the table ``--write-md`` renders and
the final compact summary line (a tail capture can truncate the one
giant report line — the compact trailer always survives)."""

import importlib.util
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")

spec = importlib.util.spec_from_file_location("lo_bench", BENCH)
bench = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bench)


def _report():
    return {
        "metric": "mnist_cnn_train_samples_per_sec_per_chip",
        "value": 1234.5, "unit": "samples/s", "vs_baseline": 10.0,
        "extra": {
            "accelerator": "tpu | TPU v5 lite | 1",
            "reference_proxy_torch_cpu_samples_per_sec": 123.4,
            "models": {
                "mnist_cnn": {"platform": "tpu",
                              "samples_per_sec_per_chip": 1234.5,
                              "tflops_per_sec_per_chip": 4.2,
                              "mfu": 0.021, "eval_accuracy": 0.99,
                              "time_to_97pct_train_acc_s": 12.3},
                "imdb_lstm": {"platform": "tpu",
                              "samples_per_sec_per_chip": 45000,
                              "eval_accuracy": 0.99},
                "builder_10m_streaming": {
                    "rows": 10_000_000, "train_rows_per_sec": 100000,
                    "peak_rss_mb": 900,
                    "lr": {"accuracy": 0.999},
                    "gb": {"accuracy": 0.986,
                           "trainedOnSample": False}},
                "csv_ingest": {"rows": 2_000_000,
                               "rows_per_sec": 700000,
                               "native_core": True},
                "broken": {"error": "boom"},
            },
            "flash_attention_microbench": {},
            "configs": {"mnist_cnn": {"epochs": 4}},
        },
    }


def test_write_md_renders_time_to_accuracy_and_full_data_gb(tmp_path):
    path = str(tmp_path / "B.md")
    bench._write_md(path, _report())
    text = open(path).read()
    assert "time-to-97%" in text          # header column
    assert "12.3s" in text                # the cnn row's value
    assert "gb_full_data=True" in text    # reservoir removal is visible
    # every table row has the same column count as the header
    rows = [ln for ln in text.splitlines() if ln.startswith("|")]
    counts = {r.count("|") for r in rows[:8]}
    assert counts == {9}, rows[:8]


def test_compact_summary_is_last_line_and_parses(tmp_path):
    """Run bench.py main with the probe stubbed HEALTHY and every
    phase stubbed out — asserting the LAST stdout line is a compact
    parseable summary regardless of report size."""
    code = f"""
import importlib.util, json, sys
sys.path.insert(0, {REPO!r})  # bench imports __graft_entry__
spec = importlib.util.spec_from_file_location("lo_bench", {BENCH!r})
bench = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bench)
bench._accelerator_probe = lambda: (True, "tpu | TPU v5 lite | 1")
bench._run_phase = lambda phase, env=None: {{"stub": phase,
                                            "x": "y" * 2000}}
sys.exit(bench.main([]))
"""
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=120,
                         cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.strip().splitlines() if ln]
    compact = json.loads(lines[-1])
    assert compact["metric"]
    assert compact["accelerator"] == "tpu | TPU v5 lite | 1"
    assert compact["failed_phases"] == []
    assert compact["unit"] == "samples/s"
    # the full report is the line before, and is larger
    assert len(lines) >= 2 and len(lines[-2]) > len(lines[-1])
