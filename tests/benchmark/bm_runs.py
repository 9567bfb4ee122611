"""Start one rehearsal run of the benchmark in a process of its own
(one CPU device, whatever the test session forces) and read its last
line."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def rehearse(workload, fault="none", seed=2**31 + 11, seconds=2,
             trace=0, extra=(), cwd=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.join(HERE, "bm_launcher.py"), fault,
           "--workload", workload, "--seed", str(seed), "--seconds",
           str(seconds), "--trace", str(trace), *extra]
    proc = subprocess.run(cmd, env=env, cwd=cwd or ROOT,
                          capture_output=True, text=True, timeout=900)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    last = None
    if lines:
        try:
            last = json.loads(lines[-1])
        except json.JSONDecodeError:
            last = None
    return proc, last
