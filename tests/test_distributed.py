"""Multi-host runtime: REAL 2-process jax.distributed formation on the
CPU backend — global device view, a cross-host collective, and a
HostBridge publish/follow round-trip."""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

from conftest import JAX_CACHE_ENV
from learningorchestra_tpu.runtime import distributed as dist


def test_single_host_noop(monkeypatch):
    monkeypatch.delenv("LO_COORDINATOR", raising=False)
    monkeypatch.delenv("LO_NUM_HOSTS", raising=False)
    assert dist.initialize() is False


def test_host_info_single():
    info = dist.host_info()
    assert info["processCount"] == 1
    assert info["processIndex"] == 0
    assert info["globalDevices"] >= 1


_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, "@REPO@")
    from learningorchestra_tpu.runtime import distributed as dist

    ok = dist.initialize(coordinator_address="@COORD@",
                         num_processes=2, process_id=@PID@)
    assert ok
    info = dist.host_info()
    assert info["processCount"] == 2, info
    assert info["globalDevices"] == 4, info

    # cross-host collective over the global mesh
    import jax.numpy as jnp
    from jax.experimental import multihost_utils as mhu
    total = mhu.process_allgather(jnp.asarray([info["processIndex"]]))
    assert sorted(int(x) for x in total.ravel()) == [0, 1], total

    bridge = dist.HostBridge()
    if info["processIndex"] == 0:
        bridge.publish({"op": "custom", "value": 41})
        bridge.publish({"op": "shutdown"})
    else:
        seen = []
        bridge.follow(lambda m: seen.append(m["value"]))
        assert seen == [41], seen
    print("HOST_OK", info["processIndex"])
""")


def test_two_process_formation_and_bridge(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    procs = []
    for pid in range(2):
        script = (_WORKER.replace("@REPO@", "/root/repo")
                  .replace("@COORD@", coord).replace("@PID@", str(pid)))
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            env={"PATH": "/usr/bin:/bin", **JAX_CACHE_ENV}))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append(out.decode(errors="replace"))
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"host {pid} failed:\n{out}"
        assert f"HOST_OK {pid}" in out


_TRAIN = textwrap.dedent("""
    import os, sys, json
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["LO_HOME"] = "@HOME@"
    os.environ["LO_MESH_SHAPE"] = "auto"
    os.environ["LO_COMPUTE_DTYPE"] = "float32"
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, "@REPO@")
    from learningorchestra_tpu.runtime import distributed as dist

    assert dist.initialize(coordinator_address="@COORD@",
                           num_processes=2, process_id=@PID@)
    assert jax.device_count() == 4

    if @PID@ == 0:
        import time
        from learningorchestra_tpu.services.server import Api
        api = Api()
        prefix = "/api/learningOrchestra/v1"

        def wait(uri):
            for _ in range(600):
                st, body, _h = api.dispatch("GET", uri, {"limit": "1"}, None)
                if st == 200 and body["metadata"].get("finished"):
                    return
                docs = api.ctx.catalog.get_documents(
                    uri.rstrip("/").split("/")[-1])
                errs = [d["exception"] for d in docs if d.get("exception")]
                assert not errs, errs
                time.sleep(0.2)
            raise SystemExit("timeout: " + uri)

        st, body, _h = api.dispatch("POST", prefix + "/function/python", {}, {
            "name": "mh_data", "functionParameters": {},
            "function": ("import numpy as np\\n"
                         "rng = np.random.default_rng(0)\\n"
                         "x = rng.normal(size=(32, 8)).astype(np.float32)\\n"
                         "y = (x[:, 0] > 0).astype(np.int32)\\n"
                         "response = {'x': x, 'y': y}\\n")})
        assert st == 201, body
        wait(body["result"])

        st, body, _h = api.dispatch("POST", prefix + "/model/tensorflow", {}, {
            "modelName": "mh_model",
            "modulePath": "learningorchestra_tpu.models",
            "class": "NeuralModel",
            "classParameters": {"layer_configs": [
                {"kind": "dense", "units": 8, "activation": "relu"},
                {"kind": "dense", "units": 2, "activation": "softmax"}]}})
        assert st == 201, body
        wait(body["result"])

        st, body, _h = api.dispatch("POST", prefix + "/train/tensorflow", {}, {
            "name": "mh_train", "modelName": "mh_model", "method": "fit",
            "methodParameters": {"x": "$mh_data.x", "y": "$mh_data.y",
                                 "epochs": 2, "batch_size": 8}})
        assert st == 201, body
        wait(body["result"])
        trained = api.ctx.artifacts.load("mh_train", "train/tensorflow")
        assert trained.history, "no training history"
        dist.HostBridge().publish({"op": "shutdown"})
        api.ctx.jobs.shutdown()
    else:
        dist.HostBridge().follow(lambda m: None)
    print("TRAIN_OK", @PID@)
""")


def test_two_process_entry_point_serves_rest(tmp_path):
    """The packaged launcher (`lo-server` / `python -m
    learningorchestra_tpu`, docs/DEPLOY.md): two processes form a pod
    via CLI flags; the coordinator serves REST and answers /health
    with the pod topology; a /train round-trips over real HTTP."""
    import json
    import shutil
    import time
    import urllib.request

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord_port = s.getsockname()[1]
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        rest_port = s.getsockname()[1]
    home = str(tmp_path / "shared_home")
    env = {**JAX_CACHE_ENV,
           "PATH": "/usr/bin:/bin:/opt/venv/bin",
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
           "PYTHONPATH": "/root/repo",
           "LO_MESH_SHAPE": "auto", "LO_COMPUTE_DTYPE": "float32"}
    launcher = shutil.which("lo-server", path=env["PATH"])
    base_cmd = [launcher] if launcher else \
        [sys.executable, "-m", "learningorchestra_tpu"]
    procs = []
    for pid in range(2):
        procs.append(subprocess.Popen(
            base_cmd + ["--home", home, "--host", "127.0.0.1",
                        "--port", str(rest_port),
                        "--coordinator", f"127.0.0.1:{coord_port}",
                        "--num-hosts", "2", "--host-id", str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env))
    base = f"http://127.0.0.1:{rest_port}"
    api = "/api/learningOrchestra/v1"

    def req(method, path, body=None, timeout=30):
        data = json.dumps(body).encode() if body is not None else None
        r = urllib.request.Request(
            base + path, data=data, method=method,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(r, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())

    try:
        health = None
        deadline = time.time() + 240
        while time.time() < deadline:
            if any(p.poll() is not None for p in procs):
                outs = [p.communicate()[0].decode(errors="replace")
                        for p in procs]
                raise AssertionError(f"a pod process died:\n{outs}")
            try:
                _, health = req("GET", "/health", timeout=5)
                break
            except OSError:
                time.sleep(0.5)
        assert health is not None, "REST never came up"
        assert health["processCount"] == 2, health
        assert health["globalDevices"] == 4, health

        st, body = req("POST", api + "/function/python", {
            "name": "ep_data", "functionParameters": {},
            "function": ("import numpy as np\n"
                         "rng = np.random.default_rng(0)\n"
                         "x = rng.normal(size=(32, 8)).astype"
                         "(np.float32)\n"
                         "y = (x[:, 0] > 0).astype(np.int32)\n"
                         "response = {'x': x, 'y': y}\n")})
        assert st == 201, body

        def poll(uri, timeout=240):
            t0 = time.time()
            while time.time() - t0 < timeout:
                st2, b2 = req("GET", uri + "?limit=1")
                if st2 == 200 and b2["metadata"].get("finished"):
                    return b2
                time.sleep(0.3)
            raise AssertionError(f"timeout polling {uri}")

        poll(body["result"])
        st, body = req("POST", api + "/model/tensorflow", {
            "modelName": "ep_model",
            "modulePath": "learningorchestra_tpu.models",
            "class": "NeuralModel",
            "classParameters": {"layer_configs": [
                {"kind": "dense", "units": 4, "activation": "relu"},
                {"kind": "dense", "units": 2,
                 "activation": "softmax"}]}})
        assert st == 201, body
        poll(body["result"])
        st, body = req("POST", api + "/train/tensorflow", {
            "name": "ep_train", "modelName": "ep_model",
            "method": "fit",
            "methodParameters": {"x": "$ep_data.x", "y": "$ep_data.y",
                                 "epochs": 1, "batch_size": 8}})
        assert st == 201, body
        poll(body["result"])
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.communicate(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
                p.communicate()


def test_two_process_rest_train_replay(tmp_path):
    """A /train REST job on the coordinator fans out to the worker via
    the HostBridge and the fit jits over the GLOBAL 4-device mesh."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    home = str(tmp_path / "shared_home")
    procs = []
    for pid in range(2):
        script = (_TRAIN.replace("@REPO@", "/root/repo")
                  .replace("@COORD@", coord).replace("@PID@", str(pid))
                  .replace("@HOME@", home))
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            env={"PATH": "/usr/bin:/bin", **JAX_CACHE_ENV}))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=540)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append(out.decode(errors="replace"))
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"host {pid} failed:\n{out}"
        assert f"TRAIN_OK {pid}" in out


_GUARD = textwrap.dedent("""
    import os, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["LO_HOME"] = "@HOME@"
    os.environ["LO_MESH_SHAPE"] = "auto"
    os.environ["LO_COMPUTE_DTYPE"] = "float32"
    os.environ["LO_HEARTBEAT_INTERVAL"] = "0.25"
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, "@REPO@")
    from learningorchestra_tpu.runtime import distributed as dist

    assert dist.initialize(coordinator_address="@COORD@",
                           num_processes=2, process_id=@PID@)

    if @PID@ == 1:
        # worker: follow until SIGKILLed by the test
        dist.HostBridge().follow(lambda m: None)
        sys.exit(0)

    from learningorchestra_tpu.services.server import Api
    api = Api()
    prefix = "/api/learningOrchestra/v1"

    st, body, _h = api.dispatch("POST", prefix + "/function/python", {}, {
        "name": "g_data", "functionParameters": {},
        "function": ("import numpy as np\\n"
                     "rng = np.random.default_rng(0)\\n"
                     "x = rng.normal(size=(64, 8)).astype(np.float32)\\n"
                     "y = (x[:, 0] > 0).astype(np.int32)\\n"
                     "response = {'x': x, 'y': y}\\n")})
    assert st == 201, body
    for _ in range(300):
        st, b, _h = api.dispatch("GET", body["result"], {"limit": "1"}, None)
        if st == 200 and b["metadata"].get("finished"):
            break
        time.sleep(0.1)

    st, body, _h = api.dispatch("POST", prefix + "/model/tensorflow", {}, {
        "modelName": "g_model",
        "modulePath": "learningorchestra_tpu.models",
        "class": "NeuralModel",
        "classParameters": {"layer_configs": [
            {"kind": "dense", "units": 8, "activation": "relu"},
            {"kind": "dense", "units": 2, "activation": "softmax"}]}})
    assert st == 201, body
    for _ in range(300):
        st, b, _h = api.dispatch("GET", body["result"], {"limit": "1"}, None)
        if st == 200 and b["metadata"].get("finished"):
            break
        time.sleep(0.1)

    # a long-running mesh job stands in for a train step stuck in a
    # collective: on TPU a dead peer makes collectives HANG (the
    # failure mode the guard exists for); the CPU backend's Gloo
    # errors the thread instead, so a sleep models the hang honestly
    api.ctx.catalog.create_collection("g_stuck", "train/tensorflow")
    api.ctx.jobs.submit("g_stuck", lambda: time.sleep(300),
                        description="stuck mesh step",
                        needs_mesh=True)
    open("@HOME@/train_started", "w").write("1")

    # the pod guard must surface WorkerLost on the in-flight job.
    # NOTE the clock: jax's own coordination service also notices the
    # dead task and FATALLY terminates this process ~10s after the
    # kill (client.h:80) — every assertion below must finish first,
    # which is itself evidence the guard beats the runtime's handling
    deadline = time.time() + 45
    seen = None
    while time.time() < deadline:
        docs = api.ctx.catalog.get_documents("g_stuck")
        lost = [d for d in docs if d.get("exception")
                and "WorkerLost" in d["exception"]]
        if lost:
            seen = lost[0]
            break
        time.sleep(0.1)
    assert seen is not None, "no WorkerLost doc within bound"
    print("GUARD_SAW_LOSS", time.time(), flush=True)

    # /health reports degraded
    health = api._health()
    assert health["status"] == "degraded", health
    assert "podFailure" in health, health

    # new mesh jobs are refused with a terminal typed failure
    st, body, _h = api.dispatch("POST", prefix + "/train/tensorflow", {}, {
        "name": "g_train2", "modelName": "g_model", "method": "fit",
        "methodParameters": {"x": "$g_data.x", "y": "$g_data.y",
                             "epochs": 1, "batch_size": 8}})
    assert st == 201, body
    deadline = time.time() + 8
    refused = False
    while time.time() < deadline:
        docs = api.ctx.catalog.get_documents("g_train2")
        if any(d.get("exception") and "WorkerLost" in d["exception"]
               for d in docs):
            refused = True
            break
        time.sleep(0.1)
    assert refused, "new mesh job was not refused"
    print("GUARD_OK", flush=True)
    # exit before jax's fatal error handler fires, and skip joining
    # the stuck mesh thread
    os._exit(0)
""")


def test_worker_sigkill_reports_failure(tmp_path):
    """SIGKILL one of two pod processes mid-train: the coordinator's
    pod guard marks the in-flight mesh job failed with a typed
    WorkerLost execution document within the heartbeat bound, /health
    reports degraded, and new mesh jobs are refused (round-3 review
    missing #4 — Swarm re-placement parity, reference
    README.md:200-202)."""
    import os
    import time

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    home = str(tmp_path / "guard_home")
    procs = []
    for pid in range(2):
        script = (_GUARD.replace("@REPO@", "/root/repo")
                  .replace("@COORD@", coord).replace("@PID@", str(pid))
                  .replace("@HOME@", home))
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            env={"PATH": "/usr/bin:/bin", **JAX_CACHE_ENV}))

    started = os.path.join(home, "train_started")
    deadline = time.time() + 240
    while time.time() < deadline and not os.path.exists(started):
        if procs[0].poll() is not None:
            out = procs[0].communicate()[0].decode(errors="replace")
            procs[1].kill()
            raise AssertionError(f"coordinator died early:\n{out}")
        time.sleep(0.2)
    assert os.path.exists(started), "train never started"
    time.sleep(1.0)  # let the train enter its first mesh step
    procs[1].kill()  # SIGKILL the worker mid-train

    try:
        out, _ = procs[0].communicate(timeout=120)
    except subprocess.TimeoutExpired:
        procs[0].kill()
        out, _ = procs[0].communicate()
    text = out.decode(errors="replace")
    assert procs[0].returncode == 0, f"coordinator failed:\n{text}"
    assert "GUARD_OK" in text, text


def test_heartbeat_monitor_loss_and_resume():
    """Unit-level liveness semantics: a silent worker is reported
    lost, junk datagrams don't kill the monitor or poison state, and
    resumed heartbeats CLEAR the loss (a transient pause must not
    wedge a healthy pod)."""
    import json as json_mod
    import time

    with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as probe:
        probe.bind(("127.0.0.1", 0))
        addr = probe.getsockname()
    mon = dist.HeartbeatMonitor(addr, expected=[1, 2], timeout=0.6)
    try:
        sender = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)

        def beat(host_id):
            sender.sendto(json_mod.dumps(
                {"hostId": host_id}).encode(), addr)

        # both beating -> healthy
        for _ in range(4):
            beat(1)
            beat(2)
            # junk must be ignored, not fatal
            sender.sendto(b"null", addr)
            sender.sendto(b'{"hostId": "x"}', addr)
            sender.sendto(b'{"hostId": 99}', addr)  # not in expected
            time.sleep(0.1)
        assert mon.lost_workers() == []

        # worker 2 goes silent -> lost within the timeout bound
        deadline = time.time() + 5
        lost = []
        while time.time() < deadline:
            beat(1)
            lost = mon.lost_workers()
            if lost:
                break
            time.sleep(0.1)
        # only assert membership: a scheduler stall on a loaded runner
        # can transiently mark worker 1 too (it recovers below)
        assert 2 in lost, lost

        # worker 2 resumes -> loss clears
        deadline = time.time() + 5
        while time.time() < deadline:
            beat(1)
            beat(2)
            if mon.lost_workers() == []:
                break
            time.sleep(0.1)
        assert mon.lost_workers() == []
        sender.close()
    finally:
        mon.close()
