"""What every cell shares: the device check, the in-process server,
HTTP, the compile counter, the profiler window and the result line.

The system under test is the program's REST surface. This module
starts it (``RestServer`` on 127.0.0.1, ``LO_HOME`` in a per-run
temporary directory) and speaks HTTP to it; the drivers under
``benchmark/drivers/`` decide what is sent.
"""

from __future__ import annotations

import gc
import glob
import http.client
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
API = "/api/learningOrchestra/v1"


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, found by name: a driver or a
    per-layer metric is one file, added without an edit elsewhere."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(workload: str) -> Tuple[Dict[str, Any], Dict[str, Any],
                                      Dict[str, Any], Dict[str, Any]]:
    """(benchmark, cell, configuration file, traffic file) of a cell
    that BENCHMARK.json lists."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        sys.exit(f"benchmark: BENCHMARK.json lists no cell {workload!r}; "
                 f"it has {[w['name'] for w in bench['workloads']]}")
    config = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    return bench, cell, config, traffic


def metrics_of(bench: Dict[str, Any], cell_name: str, group: str,
               ) -> List[Dict[str, Any]]:
    """The metrics of ``group`` that this cell reports."""
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    def in_cell(m):
        return "workloads" not in m or cell_name in m["workloads"]

    if group == "end_to_end":
        return [m for m in bench["end_to_end"] if in_cell(m)]
    return [m for m in bench["per_layer"]
            if in_cell(m) and in_cell(e2e[m["moves"]])]


# ----------------------------------------------------------------------
class Device:
    """The accelerator as JAX reports it. ``require`` is the first
    thing a run does: no chip of a known kind, no run."""

    def __init__(self, platform: str, kind: str, count: int, handle):
        self.platform, self.kind, self.count = platform, kind, count
        self._handle = handle

    @classmethod
    def require(cls, chips: int, rehearsal: bool) -> "Device":
        import jax

        devs = jax.devices()
        d = devs[0]
        if rehearsal:
            return cls(d.platform, d.device_kind, len(devs), d)
        if d.platform != "tpu":
            sys.exit(f"benchmark: needs a TPU, jax found {d.platform!r} "
                     f"({d.device_kind}); nothing ran")
        if len(devs) < chips:
            sys.exit(f"benchmark: the cell needs {chips} chip(s), jax "
                     f"found {len(devs)}; nothing ran")
        from benchmark import work

        work.peaks_for(d.device_kind)  # an unknown kind is an error
        return cls(d.platform, d.device_kind, chips, d)

    def memory(self) -> Dict[str, int]:
        stats = self._handle.memory_stats() or {}
        return {"peak": int(stats.get("peak_bytes_in_use", 0)),
                "limit": int(stats.get("bytes_limit", 0)),
                "in_use": int(stats.get("bytes_in_use", 0))}


class CompileCounter:
    """Counts XLA backend compilations and persistent-cache hits and
    misses through jax's monitoring events. ``mark`` then ``since``
    give what happened inside a window."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)
        self._mark = (0, 0, 0)

    def _dur(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> None:
        self._mark = (self.compiles, self.hits, self.misses)

    def since(self) -> Dict[str, int]:
        c, h, m = self._mark
        return {"compiles": self.compiles - c, "cache_hits": self.hits - h,
                "cache_misses": self.misses - m}


class Http:
    """One keep-alive connection to the in-process server."""

    def __init__(self, address: Tuple[str, int], timeout: float = 900.0):
        self._address = address
        self._timeout = timeout
        self._conn: Optional[http.client.HTTPConnection] = None

    def request(self, method: str, path: str,
                body: Optional[Dict[str, Any]] = None,
                ) -> Tuple[int, Any]:
        data = None if body is None else json.dumps(body).encode()
        headers = {"Content-Type": "application/json"} if data else {}
        for attempt in (0, 1):
            if self._conn is None:
                self._conn = http.client.HTTPConnection(
                    *self._address, timeout=self._timeout)
            try:
                self._conn.request(method, path, body=data, headers=headers)
                resp = self._conn.getresponse()
                raw = resp.read()
                break
            except (http.client.HTTPException, ConnectionError, OSError):
                self.close()
                if attempt:
                    raise
        ctype = resp.getheader("Content-Type") or ""
        return resp.status, (json.loads(raw) if "json" in ctype else raw)

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None


class Server:
    """The program's REST server, in this process, on a free port,
    with its storage in a per-run temporary directory."""

    def __init__(self):
        self.home = tempfile.mkdtemp(prefix="lo_bench_")
        os.environ["LO_HOME"] = self.home
        from learningorchestra_tpu import config as config_mod
        from learningorchestra_tpu.services.server import RestServer

        config_mod.reset_config()
        self.srv = RestServer(host="127.0.0.1", port=0).start()
        self.address = self.srv.address
        self.http = Http(self.address)

    @property
    def ctx(self):
        return self.srv.api.ctx

    def call(self, method: str, path: str, body=None, ok=(200, 201)):
        status, payload = self.http.request(method, API + path, body)
        if status not in ok:
            raise RuntimeError(f"{method} {path} -> {status}: {payload}")
        return payload

    def wait_finished(self, path: str, timeout: float = 600.0) -> Dict:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            body = self.call("GET", f"{path}?limit=50")
            meta = body.get("metadata") or {}
            if meta.get("finished"):
                return meta
            for doc in body.get("result") or []:
                if isinstance(doc, dict) and doc.get("exception"):
                    raise RuntimeError(f"{path} failed: {doc['exception']}")
            time.sleep(0.2)
        raise TimeoutError(f"{path} not finished after {timeout}s")

    def stop(self) -> None:
        self.http.close()
        try:
            self.srv.stop()
        finally:
            shutil.rmtree(self.home, ignore_errors=True)


def install_weights(server: Server, name: str, type_string: str,
                    seed: int, lm_kwargs: Dict[str, Any]) -> None:
    """Put the seed's weights where the program looks for a model:
    the artifact ``name``. No REST route takes weights (PERF.md F4), so
    this one step goes through the artifact store in process; every
    later step is HTTP. The tree is made on the device in one jitted
    call and written the way the program writes its own artifacts."""
    from benchmark import weights
    from learningorchestra_tpu.models import LanguageModel

    lm = LanguageModel(**lm_kwargs)
    lm.params = weights.make_tree(seed, lm_kwargs)
    server.ctx.artifacts.save(lm, name, type_string)
    del lm
    gc.collect()


class Profile:
    """A device trace of part of the window (``--trace 1`` only)."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="lo_bench_profile_")
        self.t0 = self.t1 = 0.0

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(self.dir)
        self.t0 = time.monotonic()

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self.t1 = time.monotonic()

    def newest(self) -> Optional[str]:
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        return max(files, key=os.path.getmtime) if files else None

    def keep(self, keep_dir: str) -> None:
        path = self.newest()
        if path and keep_dir:
            os.makedirs(keep_dir, exist_ok=True)
            shutil.copy(path, keep_dir)

    def reduce(self):
        from benchmark import trace_reduce

        path = self.newest()
        return (trace_reduce.reduce_file(path) or None) if path else None


def free_device_memory() -> None:
    """Drop what the program left on the device before the reference
    runs. The server is stopped and nothing of the program runs again
    in this process, so every live array may go."""
    import jax

    gc.collect()
    before = sum(a.nbytes for a in jax.live_arrays())
    for a in jax.live_arrays():
        a.delete()
    gc.collect()
    stats = jax.devices()[0].memory_stats() or {}
    print(f"freed device arrays: {before} bytes live before, "
          f"{stats.get('bytes_in_use')} in use after", file=sys.stderr,
          flush=True)


def judge(numbers: Dict[str, Tuple[float, float]]) -> Tuple[bool, Dict]:
    """``numbers``: name -> (value, limit). Correct when every value is
    at or under its limit; a value that is not a number fails."""
    out, ok = {}, True
    for name, (value, limit) in numbers.items():
        good = value == value and value <= limit
        ok = ok and good
        # a value that is no number is printed as null: the line stays JSON
        out[name] = {"value": value if value == value else None,
                     "limit": limit}
    return ok, out
