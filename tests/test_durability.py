"""Job durability: requeue-or-fail on boot + manager hygiene.

The reference loses in-flight jobs on failure — a client polling
``finished`` waits forever and must manually resubmit
(README.md:194-198). SURVEY §7 step 8 sets the rebuild's bar at
requeue-or-fail: on boot, executions/functions whose full request
lives in metadata are re-run (checkpointed trains RESUME from their
latest checkpoint step); everything else gets a typed failure execution
document so pollers see a terminal state.
"""

import os
import subprocess
import sys
import time

from learningorchestra_tpu.catalog import documents as D

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import sys
from learningorchestra_tpu import config as config_mod

config_mod.set_config(config_mod.Config(home=sys.argv[1]))
from learningorchestra_tpu.services.server import Api

api = Api()
P = "/api/learningOrchestra/v1"
s, b, _ = api.dispatch("POST", P + "/function/python", {}, {
    "name": "d_data", "functionParameters": {},
    "function": ("import numpy as np\\n"
                 "rng = np.random.default_rng(0)\\n"
                 "x = rng.normal(size=(64, 8)).astype(np.float32)\\n"
                 "y = (x[:, 0] > 0).astype(np.int32)\\n"
                 "response = {'x': x, 'y': y}\\n")})
assert s == 201, b
api.ctx.jobs.wait("d_data", timeout=120)
s, b, _ = api.dispatch("POST", P + "/model/tensorflow", {}, {
    "modelName": "d_model", "modulePath": "learningorchestra_tpu.models",
    "class": "NeuralModel",
    "classParameters": {"layer_configs": [
        {"kind": "dense", "units": 4, "activation": "relu"},
        {"kind": "dense", "units": 2, "activation": "softmax"}]}})
assert s == 201, b
api.ctx.jobs.wait("d_model", timeout=120)
s, b, _ = api.dispatch("POST", P + "/train/tensorflow", {}, {
    "name": "d_train", "modelName": "d_model", "method": "fit",
    "methodParameters": {"x": "$d_data.x", "y": "$d_data.y",
                         "epochs": 300, "batch_size": 16,
                         "checkpoint": True}})
assert s == 201, b
print("TRAIN_SUBMITTED", flush=True)
import time
time.sleep(600)
"""


def test_kill_and_restart_resumes_checkpointed_train(tmp_path):
    """SIGKILL a server mid-train; a fresh boot on the same home must
    requeue the stranded train, resume it from the latest checkpoint step,
    and finish within the original 300-epoch budget."""
    home = str(tmp_path / "lo_home")
    child_py = tmp_path / "child.py"
    child_py.write_text(_CHILD)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.Popen([sys.executable, str(child_py), home],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, env=env, text=True)
    ckpt_dir = os.path.join(home, "checkpoints", "d_train")
    killed_at_step = None
    try:
        deadline = time.time() + 300
        while time.time() < deadline:
            if proc.poll() is not None:
                raise AssertionError(
                    f"child exited early:\n{proc.stdout.read()}")
            steps = [int(d) for d in os.listdir(ckpt_dir)
                     if d.isdigit()] if os.path.isdir(ckpt_dir) else []
            # mid-training: >= 2 epochs saved, far from the 1200-step end
            if steps and max(steps) >= 8:
                killed_at_step = max(steps)
                break
            time.sleep(0.05)
        assert killed_at_step is not None, "never saw a mid-train ckpt"
        assert killed_at_step < 1200
    finally:
        proc.kill()
        proc.wait()

    # --- restart: fresh Api on the same home -------------------------
    from learningorchestra_tpu import config as config_mod

    config_mod.set_config(config_mod.Config(home=home))
    try:
        from learningorchestra_tpu.services.server import Api

        api = Api()  # recover_unfinished() runs here
        try:
            meta = api.ctx.catalog.get_metadata("d_train")
            assert meta is not None and not meta.get("finished")
            api.ctx.jobs.wait("d_train", timeout=240)
            meta = api.ctx.catalog.get_metadata("d_train")
            assert meta["finished"] is True

            from learningorchestra_tpu.runtime.checkpoint import (
                Checkpointer)

            ck = Checkpointer(os.path.join(home, "checkpoints", "d_train"))
            # resumed, not restarted: budget is 300 epochs x 4 steps
            assert ck.latest_step() == 1200
            ck.close()
            # the trained artifact exists and is loadable
            model = api.ctx.artifacts.load("d_train", "train/tensorflow")
            assert model.history
        finally:
            api.ctx.close()
    finally:
        config_mod.reset_config()


def test_boot_marks_unreplayable_jobs_failed(tmp_config):
    """Collections without a stored request (e.g. an ingest killed
    mid-stream) get a typed InterruptedError execution doc on boot."""
    from learningorchestra_tpu.services.server import Api

    api = Api()
    try:
        api.ctx.catalog.create_collection("stranded", "dataset/csv", {})
        out = api.recover_unfinished()
        assert "stranded" in out["failed"]
        docs = api.ctx.catalog.get_documents("stranded")
        assert any("InterruptedError" in (d.get(D.EXCEPTION_FIELD) or "")
                   for d in docs)
        meta = api.ctx.catalog.get_metadata("stranded")
        assert not meta.get("finished")
    finally:
        api.ctx.close()


def test_boot_skips_terminally_failed_jobs(tmp_config):
    """A job that FAILED (trailing exception doc, finished=False per
    reference parity) is terminal — restarts must not re-run it or
    stack duplicate failure documents."""
    from learningorchestra_tpu.services.server import Api

    api = Api()
    try:
        api.ctx.catalog.create_collection("failed_fn", "function/python", {
            D.FUNCTION_FIELD: "raise ValueError('nope')",
            D.FUNCTION_PARAMETERS_FIELD: {}})
        api.ctx.catalog.append_document(
            "failed_fn", D.execution_document(
                "", None, exception="ValueError('nope')"))
        n0 = len(api.ctx.catalog.get_documents("failed_fn"))
        out = api.recover_unfinished()
        assert "failed_fn" not in out["requeued"]
        assert "failed_fn" not in out["failed"]
        # doc count unchanged: no re-run, no duplicate failure records
        assert len(api.ctx.catalog.get_documents("failed_fn")) == n0
        # and repeat boots of the mark-failed path stay idempotent
        api.ctx.catalog.create_collection("stranded2", "dataset/csv", {})
        assert "stranded2" in api.recover_unfinished()["failed"]
        n_docs = len(api.ctx.catalog.get_documents("stranded2"))
        api.recover_unfinished()
        assert len(api.ctx.catalog.get_documents("stranded2")) == n_docs
    finally:
        api.ctx.close()


def test_job_manager_prunes_completed_futures(tmp_config):
    from learningorchestra_tpu.catalog import Catalog
    from learningorchestra_tpu.services.jobs import JobManager

    cat = Catalog(tmp_config.catalog_path, tmp_config.datasets_dir)
    jobs = JobManager(cat, max_workers=2)
    try:
        for i in range(50):
            name = f"j{i}"
            cat.create_collection(name, "function/python", {})
            jobs.submit(name, lambda: 1)
            jobs.wait(name, timeout=30)
        assert len(jobs._futures) < 10  # pruned, not 50
    finally:
        jobs.shutdown()
        cat.close()


def test_pod_reform_requeues_checkpointed_train(tmp_config):
    """Elastic pod recovery: a train refused while
    the pod is degraded (WorkerLost) requeues AUTOMATICALLY when the
    guard sees heartbeats resume — the checkpointed run finishes, from
    its saved step, with NO server restart."""
    from learningorchestra_tpu.services.context import ServiceContext
    from learningorchestra_tpu.services.server import Api

    state = {"failure": None}
    ctx = ServiceContext(tmp_config,
                         pod_failure_fn=lambda: state["failure"],
                         force_pod_guard=True)
    api = Api(ctx)
    P = "/api/learningOrchestra/v1"
    try:
        s, b, _ = api.dispatch("POST", P + "/function/python", {}, {
            "name": "rf_data", "functionParameters": {},
            "function": ("import numpy as np\n"
                         "rng = np.random.default_rng(0)\n"
                         "x = rng.normal(size=(64, 8)).astype(np.float32)\n"
                         "y = (x[:, 0] > 0).astype(np.int32)\n"
                         "response = {'x': x, 'y': y}\n")})
        assert s == 201, b
        api.ctx.jobs.wait("rf_data", timeout=120)
        s, b, _ = api.dispatch("POST", P + "/model/tensorflow", {}, {
            "modelName": "rf_model",
            "modulePath": "learningorchestra_tpu.models",
            "class": "NeuralModel",
            "classParameters": {"layer_configs": [
                {"kind": "dense", "units": 4, "activation": "relu"},
                {"kind": "dense", "units": 2, "activation": "softmax"}]}})
        assert s == 201, b
        api.ctx.jobs.wait("rf_model", timeout=120)

        # phase 1: healthy pod, checkpointed 2-epoch train completes
        s, b, _ = api.dispatch("POST", P + "/train/tensorflow", {}, {
            "name": "rf_train", "modelName": "rf_model",
            "method": "fit",
            "methodParameters": {"x": "$rf_data.x", "y": "$rf_data.y",
                                 "epochs": 2, "batch_size": 8,
                                 "checkpoint": True}})
        assert s == 201, b
        api.ctx.jobs.wait("rf_train", timeout=240)
        assert api.ctx.catalog.get_metadata(
            "rf_train")[D.FINISHED_FIELD] is True

        # phase 2: pod degrades; a PATCH re-run (total budget 4
        # epochs) is REFUSED with a typed WorkerLost document
        state["failure"] = "worker host(s) [1] stopped heartbeating"
        s, b, _ = api.dispatch("PATCH", P + "/train/tensorflow/rf_train",
                               {}, {"methodParameters": {
                                   "x": "$rf_data.x", "y": "$rf_data.y",
                                   "epochs": 4, "batch_size": 8,
                                   "checkpoint": True}})
        assert s == 200, b
        api.ctx.jobs.wait("rf_train", timeout=120)
        docs = api.ctx.catalog.get_documents("rf_train")
        assert docs[-1].get("workerLost") is True, docs[-1]
        assert api.ctx.catalog.get_metadata(
            "rf_train")[D.FINISHED_FIELD] is False
        # hold the failure window open past the guard's poll interval
        # so it OBSERVES the degraded state (in production a heartbeat
        # loss persists >= the 10x-interval timeout; here it's faked)
        time.sleep(2.5)

        # phase 3: heartbeats resume — the guard requeues the train
        # automatically; it resumes from the epoch-2 checkpoint and
        # finishes WITHOUT any server restart
        state["failure"] = None
        deadline = time.time() + 120
        while time.time() < deadline:
            if api.ctx.catalog.get_metadata(
                    "rf_train").get(D.FINISHED_FIELD):
                break
            time.sleep(0.5)
        meta = api.ctx.catalog.get_metadata("rf_train")
        assert meta[D.FINISHED_FIELD] is True, meta
        docs = api.ctx.catalog.get_documents("rf_train")
        resumed = [d["epochRecord"]["epoch"] for d in docs
                   if "epochRecord" in d]
        # the auto-requeued run trained epochs 2..3 only (resume), on
        # top of phase 1's 0..1
        assert resumed.count(2) == 1 and resumed.count(3) == 1, resumed
        assert resumed.count(0) == 1 and resumed.count(1) == 1, resumed

        # phase 4: a job whose newest failure is a GENUINE error (bad
        # params, healthy pod) must NOT re-run on later degrade/heal
        # flaps — only pod-attributed failures are elastic
        s, b, _ = api.dispatch("PATCH", P + "/train/tensorflow/rf_train",
                               {}, {"methodParameters": {
                                   "x": "$rf_data.x", "y": "$rf_data.y",
                                   "epochs": 6, "batch_size": 8,
                                   "checkpoint": True,
                                   "grad_accum": "not-a-number"}})
        assert s == 200, b
        api.ctx.jobs.wait("rf_train", timeout=120)
        docs = api.ctx.catalog.get_documents("rf_train")
        assert docs[-1].get(D.EXCEPTION_FIELD), docs[-1]
        assert not docs[-1].get("workerLost")
        n_docs = len(docs)
        state["failure"] = "worker host(s) [1] stopped heartbeating"
        time.sleep(2.5)
        state["failure"] = None
        time.sleep(2.5)
        assert len(api.ctx.catalog.get_documents("rf_train")) == n_docs
    finally:
        api.ctx.close()


def test_boot_recovery_requeues_worker_lost(tmp_config):
    """A server RESTART must also requeue worker-lost executions (the
    pod was degraded when the server stopped; at boot it is healthy,
    so the guard never sees a transition) — a workerLost failure doc
    is the pod's fault, not a terminal job failure."""
    from learningorchestra_tpu.services.context import ServiceContext
    from learningorchestra_tpu.services.server import Api

    # server #1: pod degrades right before the train — it is refused
    # with a trailing workerLost doc and stays unfinished
    state = {"failure": None}
    ctx1 = ServiceContext(tmp_config,
                          pod_failure_fn=lambda: state["failure"])
    api1 = Api(ctx1)
    P = "/api/learningOrchestra/v1"
    s, b, _ = api1.dispatch("POST", P + "/function/python", {}, {
        "name": "bl_data", "functionParameters": {},
        "function": ("import numpy as np\n"
                     "rng = np.random.default_rng(0)\n"
                     "x = rng.normal(size=(64, 8)).astype(np.float32)\n"
                     "y = (x[:, 0] > 0).astype(np.int32)\n"
                     "response = {'x': x, 'y': y}\n")})
    assert s == 201, b
    api1.ctx.jobs.wait("bl_data", timeout=120)
    s, b, _ = api1.dispatch("POST", P + "/model/tensorflow", {}, {
        "modelName": "bl_model",
        "modulePath": "learningorchestra_tpu.models",
        "class": "NeuralModel",
        "classParameters": {"layer_configs": [
            {"kind": "dense", "units": 4, "activation": "relu"},
            {"kind": "dense", "units": 2, "activation": "softmax"}]}})
    assert s == 201, b
    api1.ctx.jobs.wait("bl_model", timeout=120)
    state["failure"] = "worker 1 lost"
    s, b, _ = api1.dispatch("POST", P + "/train/tensorflow", {}, {
        "name": "bl_train", "modelName": "bl_model", "method": "fit",
        "methodParameters": {"x": "$bl_data.x", "y": "$bl_data.y",
                             "epochs": 2, "batch_size": 8}})
    assert s == 201, b
    api1.ctx.jobs.wait("bl_train", timeout=120)
    docs = api1.ctx.catalog.get_documents("bl_train")
    assert docs[-1].get("workerLost") is True, docs[-1]
    assert api1.ctx.catalog.get_metadata(
        "bl_train")[D.FINISHED_FIELD] is False
    api1.ctx.close()

    # server #2 (fresh boot, healthy pod): recover_unfinished requeues
    # the worker-lost train instead of treating it as terminal
    api2 = Api()
    try:
        api2.ctx.jobs.wait("bl_train", timeout=240)
        meta = api2.ctx.catalog.get_metadata("bl_train")
        assert meta[D.FINISHED_FIELD] is True, meta
    finally:
        api2.ctx.close()


def test_boot_replays_elastic_slice_bounds(tmp_config):
    """A stored elastic footprint (``sliceDevices: {min, max}``) must
    survive a boot requeue intact: the re-submitted job carries the
    same elastic bounds into the slice scheduler — not a collapsed
    rigid size — so the autoscaler can keep resizing it after a
    restart (docs/SCALING.md "Elastic autoscaling")."""
    from learningorchestra_tpu.services.server import Api

    api = Api()
    try:
        api.ctx.catalog.create_collection(
            "elastic_boot", "train/tensorflow", {
                D.PARENT_NAME_FIELD: "eb_model",
                D.METHOD_FIELD: "fit",
                D.METHOD_PARAMETERS_FIELD: {"x": [[1.0]], "y": [0]},
                "footprint": {"devices": 4,
                              "elastic": {"min": 2, "max": 4}}})
        out = api.recover_unfinished()
        assert "elastic_boot" in out["requeued"], out
        fp = api.ctx.jobs._job_info["elastic_boot"]["footprint"]
        assert fp["elastic"] == {"min": 2, "max": 4}, fp
        assert fp["devices"] == 4
    finally:
        api.ctx.close()
