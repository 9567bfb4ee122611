"""The launchers' contract around the chip (chip_smoke.py, bench.py,
the compile-cache rule): no accelerator -> non-zero exit and no result,
no fallback that hides the device, the bench parent stays off jax."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"lo_{name}", os.path.join(REPO, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json_lines(text):
    return [json.loads(ln) for ln in text.strip().splitlines() if ln]


@pytest.mark.parametrize("args", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_chip_smoke_without_accelerator_fails_and_prints_no_result(
        tmp_path, args):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=300, cwd=str(tmp_path),
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_tiny_rehearsal_runs_every_phase(
        tmp_config, tmp_path, monkeypatch, capsys):
    """``--size tiny`` with the platform assertion bypassed HERE (a
    monkeypatch, not a program option): every phase runs, each earlier
    line parses, and the last line is the contract's."""
    import jax

    smoke = _load("chip_smoke")
    monkeypatch.setattr(smoke, "require_accelerator",
                        lambda: jax.devices()[0])
    monkeypatch.setenv("LO_HOME", str(tmp_path / "unused"))
    assert smoke.main(["--size", "tiny", "--out", str(tmp_path)]) == 0
    lines = _json_lines(capsys.readouterr().out)
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    phases = [ln["phase"] for ln in lines[:-1]]
    assert phases == ["env", "function", "model", "train", "resume",
                      "perf", "generate", "serve", "serve"]
    by = {ln["phase"]: ln for ln in lines[:-1]}
    assert by["function"]["sandbox_mode"] == "subprocess"
    assert by["env"]["compile_cache_dir"] is None       # CPU: cache off
    assert by["env"]["arena_auto_budget_bytes"] == 1 << 30  # CPU stand-in
    assert by["train"]["checkpoint_layout"] == "msgpack+manifest"
    assert by["train"]["losses"][-1] < by["train"]["losses"][0]
    assert by["resume"]["from_step"] < by["resume"]["to_step"]
    assert [ln["kv"] for ln in lines if ln.get("phase") == "serve"] == \
        ["slot", "paged"]
    # the store (GBs of checkpoints at full size) is gone afterwards
    assert not os.path.exists(tmp_path / "lo_home")


_BENCH_STUB = r"""
import importlib.util, json, sys
sys.path.insert(0, {repo!r})  # bench imports __graft_entry__
spec = importlib.util.spec_from_file_location(
    "lo_bench", {repo!r} + "/bench.py")
bench = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bench)
bench._accelerator_probe = lambda: {probe!r}
bench._run_phase = lambda phase, env=None: {phase_result}
rc = bench.main([])
print("JAX_IMPORTED", "jax" in sys.modules, file=sys.stderr)
sys.exit(rc)
"""


def _run_bench(tmp_path, probe, phase_result='{"stub": phase}'):
    code = _BENCH_STUB.format(repo=REPO, probe=probe,
                              phase_result=phase_result)
    return subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120,
                          cwd=str(tmp_path))


def test_bench_full_run_without_accelerator_fails_with_no_report(
        tmp_path):
    out = _run_bench(tmp_path, (False, "jax found only the CPU backend"))
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no accelerator" in out.stderr
    assert "only the CPU backend" in out.stderr
    assert not os.path.exists(tmp_path / "bench_report.json")


def test_bench_full_run_with_a_failed_phase_reports_then_fails(
        tmp_path):
    out = _run_bench(
        tmp_path, (True, "tpu | TPU v5 lite | 1"),
        '({"error": "boom"} if phase == "tlm" else {"stub": phase})')
    assert out.returncode != 0
    compact = _json_lines(out.stdout)[-1]
    assert compact["failed_phases"] == ["transformer_lm"]


def test_bench_parent_never_imports_jax(tmp_path):
    """The parent must stay off jax: a parent that touched a backend
    would hold the chip its phase children need."""
    out = _run_bench(tmp_path, (True, "tpu | TPU v5 lite | 1"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "JAX_IMPORTED False" in out.stderr


def test_bench_probe_rejects_the_cpu_backend(monkeypatch):
    """The real probe, in its real child: a process that reaches only
    the CPU backend is 'no accelerator', with the reason."""
    bench = _load("bench")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    ok, why = bench._accelerator_probe()
    assert not ok and "only the CPU backend" in why


def test_compile_cache_rule(monkeypatch):
    import jax

    from learningorchestra_tpu.services import context as ctx_mod

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    # env var set -> jax reads it itself, code sets nothing
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    assert ctx_mod.wire_compile_cache() == "/some/where"
    # unset on the CPU backend -> off
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert ctx_mod.wire_compile_cache() is None
    assert updates == []
    # the fixed default: <checkout>/.jax_cache
    assert ctx_mod.compile_cache_path() == os.path.join(REPO, ".jax_cache")


def test_compile_cache_rule_on_an_accelerator(monkeypatch, tmp_path):
    import jax

    from learningorchestra_tpu.services import context as ctx_mod

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ctx_mod, "compile_cache_path",
                        lambda: str(tmp_path / ".jax_cache"))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = ctx_mod.wire_compile_cache()
    assert path == str(tmp_path / ".jax_cache") and os.path.isdir(path)
    assert updates == [("jax_compilation_cache_dir", path)]


class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform = platform
        self.device_kind = "fake"
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("platform,stats,want", [
    ("tpu", {"bytes_limit": 16 << 30}, 4 << 30),
    ("cpu", None, 1 << 30),          # XLA:CPU reports no limit
    ("tpu", {}, RuntimeError),       # an accelerator must report one
], ids=["tpu_limit", "cpu_stand_in", "tpu_no_limit_is_an_error"])
def test_arena_auto_budget(monkeypatch, platform, stats, want):
    import jax

    from learningorchestra_tpu.runtime import arena

    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_FakeDevice(platform, stats)])
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="bytes_limit"):
            arena._auto_budget()
    else:
        assert arena._auto_budget() == want
