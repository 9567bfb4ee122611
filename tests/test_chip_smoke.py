"""The launcher's contract around the chip (chip_smoke.py, the
compile-cache rule): no accelerator -> non-zero exit and no result,
no fallback that hides the device."""

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
