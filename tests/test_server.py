"""REST API tests over real HTTP: the reference's URI contract,
async-201 + finished-poll, universal reads, observe long-poll.

(Test strategy per SURVEY §4: golden end-to-end pipeline tests against
the REST API with a live server.)
"""

import csv
import json
import time
import urllib.request
import urllib.error

import numpy as np
import pytest

API = "/api/learningOrchestra/v1"


@pytest.fixture()
def server(tmp_config):
    from learningorchestra_tpu.services.server import RestServer

    srv = RestServer(host="127.0.0.1", port=0).start()
    yield srv
    srv.stop()


def _call(server, method, path, body=None, params=""):
    url = f"{server.base_url}{path}{params}"
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    if data:
        req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            raw = resp.read()
            ctype = resp.headers.get("Content-Type", "")
            status = resp.status
    except urllib.error.HTTPError as e:
        raw = e.read()
        ctype = e.headers.get("Content-Type", "")
        status = e.code
    if "json" in ctype:
        return status, json.loads(raw)
    return status, raw


def _poll_finished(server, path, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        status, body = _call(server, "GET", path, params="?limit=1")
        assert status == 200, body
        meta = body["metadata"]
        if meta.get("finished"):
            return meta
        time.sleep(0.1)
    raise AssertionError(f"timeout polling {path}")


@pytest.fixture()
def titanic_csv(tmp_path):
    """Titanic-shaped CSV (the reference's flagship demo pipeline,
    BASELINE config 1)."""
    rng = np.random.default_rng(7)
    rows = []
    for i in range(200):
        pclass = int(rng.integers(1, 4))
        sex = rng.choice(["male", "female"])
        age = round(float(rng.uniform(1, 70)), 1)
        fare = round(float(rng.uniform(5, 200)), 2)
        p = 0.8 if sex == "female" else 0.2
        survived = int(rng.random() < p)
        rows.append([i, survived, pclass, sex, age, fare])
    path = tmp_path / "titanic.csv"
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["pid", "survived", "pclass", "sex", "age", "fare"])
        w.writerows(rows)
    return path


def test_health(server):
    status, body = _call(server, "GET", "/health")
    assert status == 200
    assert body["status"] == "ok"
    assert body.get("deviceCount", 0) >= 1


def test_unknown_route(server):
    status, body = _call(server, "GET", f"{API}/nonsense/x")
    assert status == 404


def test_metrics_endpoint(server):
    """Gateway-metrics parity (KrakenD collector, krakend.json:1752):
    request counters by route/status, latency, job and collection
    gauges."""
    _call(server, "GET", "/health")
    _call(server, "GET", f"{API}/dataset/csv")   # listing (200)
    _call(server, "GET", f"{API}/nonsense/x")    # 404
    status, m = _call(server, "GET", "/metrics")
    assert status == 200
    assert m["requestsTotal"] >= 3
    assert m["requestsByRoute"].get("GET dataset", 0) >= 1
    assert m["responsesByStatus"].get("404", 0) >= 1
    assert m["meanDispatchSeconds"] is not None
    assert m["uptimeSeconds"] > 0
    assert "jobsRunning" in m and "collections" in m
    assert "getCache" in m and "meshSecondsByPool" in m
    status, raw = _call(server, "GET", "/metrics",
                        params="?format=prometheus")
    assert status == 200
    text = raw.decode()
    assert "lo_get_cache_hits_total" in text and \
        "lo_mesh_seconds_total" in text


def test_dataset_rest_roundtrip(server, titanic_csv):
    status, body = _call(server, "POST", f"{API}/dataset/csv", {
        "datasetName": "titanic", "datasetURI": str(titanic_csv)})
    assert status == 201
    assert body["result"] == f"{API}/dataset/csv/titanic"
    meta = _poll_finished(server, body["result"])
    assert meta["rows"] == 200
    assert "survived" in meta["fields"]

    # paged + queried reads
    status, body = _call(server, "GET", f"{API}/dataset/csv/titanic",
                         params="?skip=1&limit=2")
    assert status == 200 and len(body["result"]) == 2
    q = json.dumps({"sex": "female"})
    status, body = _call(
        server, "GET", f"{API}/dataset/csv/titanic",
        params=f"?limit=5&query={urllib.request.quote(q)}")
    assert all(r["sex"] == "female" for r in body["result"])

    # listing by type
    status, body = _call(server, "GET", f"{API}/dataset/csv")
    assert any(m["name"] == "titanic" for m in body["result"])

    # duplicate -> 409
    status, _ = _call(server, "POST", f"{API}/dataset/csv", {
        "datasetName": "titanic", "datasetURI": str(titanic_csv)})
    assert status == 409


def test_titanic_pipeline_over_rest(server, titanic_csv):
    """Dataset -> Function(feature prep) -> Model -> Train -> Evaluate
    -> Predict, entirely through the REST API (reference north-star
    call stack, SURVEY §3.3; BASELINE config 1)."""
    status, body = _call(server, "POST", f"{API}/dataset/csv", {
        "datasetName": "titanic", "datasetURI": str(titanic_csv)})
    assert status == 201
    _poll_finished(server, body["result"])

    prep = (
        "import numpy as np\n"
        "df = titanic\n"
        "x = np.stack([df['pclass'].to_numpy(float),"
        " (df['sex']=='female').to_numpy(float),"
        " df['age'].to_numpy(float)/80.0,"
        " df['fare'].to_numpy(float)/250.0], axis=1)\n"
        "y = df['survived'].to_numpy('int64')\n"
        "response = {'x': x, 'y': y}\n"
    )
    status, body = _call(server, "POST", f"{API}/function/python", {
        "name": "prep", "function": prep,
        "functionParameters": {"titanic": "$titanic"}})
    assert status == 201
    _poll_finished(server, body["result"])

    status, body = _call(server, "POST", f"{API}/model/scikitlearn", {
        "modelName": "lr", "modulePath": "sklearn.linear_model",
        "class": "LogisticRegression",
        "classParameters": {"max_iter": 500}})
    assert status == 201
    _poll_finished(server, body["result"])

    status, body = _call(server, "POST", f"{API}/train/scikitlearn", {
        "name": "lr_t", "modelName": "lr", "method": "fit",
        "methodParameters": {"X": "$prep.x", "y": "$prep.y"}})
    assert status == 201
    _poll_finished(server, body["result"])

    status, body = _call(server, "POST", f"{API}/evaluate/scikitlearn", {
        "name": "lr_e", "modelName": "lr_t", "method": "score",
        "methodParameters": {"X": "$prep.x", "y": "$prep.y"}})
    assert status == 201
    _poll_finished(server, body["result"])
    status, body = _call(server, "GET", f"{API}/evaluate/scikitlearn/lr_e")
    results = [d["result"] for d in body["result"] if "result" in d]
    assert results and results[0] > 0.7

    status, body = _call(server, "POST", f"{API}/predict/scikitlearn", {
        "name": "lr_p", "modelName": "lr_t", "method": "predict",
        "methodParameters": {"X": "$prep.x"}})
    assert status == 201
    _poll_finished(server, body["result"])

    # PATCH re-run with same parent (reference PATCH semantics)
    status, body = _call(server, "PATCH", f"{API}/predict/scikitlearn/lr_p",
                         {"methodParameters": {"X": "$prep.x"}})
    assert status == 200
    _poll_finished(server, f"{API}/predict/scikitlearn/lr_p")

    # DELETE
    status, _ = _call(server, "DELETE", f"{API}/predict/scikitlearn/lr_p")
    assert status == 200
    status, _ = _call(server, "GET", f"{API}/predict/scikitlearn/lr_p")
    assert status == 404


def test_transform_explore_histogram_over_rest(server, titanic_csv):
    status, body = _call(server, "POST", f"{API}/dataset/csv", {
        "datasetName": "t2", "datasetURI": str(titanic_csv)})
    _poll_finished(server, body["result"])

    # projection
    status, body = _call(server, "POST", f"{API}/transform/projection", {
        "inputDatasetName": "t2", "outputDatasetName": "t2_small",
        "names": ["age", "fare"]})
    assert status == 201
    _poll_finished(server, f"{API}/transform/projection/t2_small")

    # histogram
    status, body = _call(server, "POST", f"{API}/explore/histogram", {
        "inputDatasetName": "t2", "outputDatasetName": "t2_hist",
        "names": ["survived"]})
    assert status == 201
    _poll_finished(server, f"{API}/explore/histogram/t2_hist")
    status, body = _call(server, "GET", f"{API}/explore/histogram/t2_hist")
    hist = next(d for d in body["result"] if "survived" in d)
    assert sum(b["count"] for b in hist["survived"]) == 200

    # dataType: survived int -> string
    status, body = _call(server, "POST", f"{API}/transform/dataType", {
        "datasetName": "t2_small", "types": {"age": "string"}})
    assert status == 200
    _poll_finished(server, f"{API}/transform/dataType/t2_small")

    # explore plot (PNG)
    status, body = _call(server, "POST", f"{API}/explore/scikitlearn", {
        "name": "pca2", "modulePath": "sklearn.decomposition",
        "class": "PCA", "classParameters": {"n_components": 2},
        "method": "fit_transform",
        "methodParameters": {"X": "$proj_xy"}})
    assert status == 201
    # stage the numeric matrix it needs, then re-run via PATCH
    # (cheaper than a second function step)
    ctx = server.api.ctx
    df = ctx.catalog.read_dataframe("t2", columns=["age", "fare"])
    ctx.artifacts.save(df.to_numpy(), "proj_xy", "function/python")
    ctx.catalog.create_collection("proj_xy", "function/python")
    ctx.catalog.mark_finished("proj_xy")
    status, _ = _call(server, "PATCH", f"{API}/explore/scikitlearn/pca2",
                      {})
    _poll_finished(server, f"{API}/explore/scikitlearn/pca2")
    status, png = _call(server, "GET", f"{API}/explore/scikitlearn/pca2")
    assert status == 200 and isinstance(png, bytes)
    assert png[:8] == b"\x89PNG\r\n\x1a\n"


def test_builder_over_rest(server, titanic_csv):
    for ds in ("btr", "bte"):
        status, body = _call(server, "POST", f"{API}/dataset/csv", {
            "datasetName": ds, "datasetURI": str(titanic_csv)})
        _poll_finished(server, body["result"])
    code = (
        "import numpy as np\n"
        "def feats(df):\n"
        "    return np.stack([df['pclass'].to_numpy(float),"
        " (df['sex']=='female').to_numpy(float)], axis=1)\n"
        "features_training = (feats(training_df),"
        " training_df['survived'].to_numpy('int64'))\n"
        "features_evaluation = features_training\n"
        "features_testing = feats(testing_df)\n"
    )
    status, body = _call(server, "POST", f"{API}/builder/sparkml", {
        "trainDatasetName": "btr", "testDatasetName": "bte",
        "modelingCode": code, "classifiersList": ["LR", "NB"]})
    assert status == 201
    assert len(body["result"]) == 2
    for uri in body["result"]:
        meta = _poll_finished(server, uri)
        assert meta["accuracy"] > 0.6
        status, rows = _call(server, "GET", uri, params="?skip=1&limit=3")
        assert any("prediction" in r for r in rows["result"])


def test_observe_long_poll(server, titanic_csv):
    import threading

    status, body = _call(server, "GET", f"{API}/observe")
    seq0 = body["result"]["seq"]
    results = {}

    def watcher():
        results["resp"] = _call(
            server, "GET", f"{API}/observe/obs_ds",
            params=f"?seq={seq0}&timeout=30")

    t = threading.Thread(target=watcher)
    t.start()
    time.sleep(0.2)
    _call(server, "POST", f"{API}/dataset/csv", {
        "datasetName": "obs_ds", "datasetURI": str(titanic_csv)})
    t.join(timeout=40)
    assert not t.is_alive()
    status, body = results["resp"]
    assert status == 200
    changes = body["result"]["changes"]
    assert changes and all(c["collection"] == "obs_ds" for c in changes)


def test_tune_grid_search_pipeline(server):
    """/model creates a GridSearch over a $model ref; /tune fit runs
    trial-parallel over mesh sub-slices; results readable via GET."""
    st, body = _call(server, "POST", f"{API}/function/python", body={
        "name": "tune_data", "functionParameters": {},
        "function": ("import numpy as np\n"
                     "rng = np.random.default_rng(0)\n"
                     "x = rng.normal(size=(48, 8)).astype(np.float32)\n"
                     "y = (x[:, 0] > 0).astype(np.int32)\n"
                     "x[:, 1] = y * 2.0\n"
                     "response = {'x': x, 'y': y}\n")})
    assert st == 201, body
    _poll_finished(server, f"{API}/function/python/tune_data")

    st, body = _call(server, "POST", f"{API}/model/tensorflow", body={
        "modelName": "tune_base",
        "modulePath": "learningorchestra_tpu.models",
        "class": "NeuralModel",
        "classParameters": {"layer_configs": [
            {"kind": "dense", "units": 8, "activation": "relu"},
            {"kind": "dense", "units": 2, "activation": "softmax"}]}})
    assert st == 201, body
    _poll_finished(server, f"{API}/model/tensorflow/tune_base")

    st, body = _call(server, "POST", f"{API}/model/tensorflow", body={
        "modelName": "tune_sweep",
        "modulePath": "learningorchestra_tpu.models",
        "class": "GridSearch",
        "classParameters": {"estimator": "$tune_base",
                            "param_grid": {"learning_rate": [0.0001, 0.05]},
                            "validation_split": 0.25}})
    assert st == 201, body
    _poll_finished(server, f"{API}/model/tensorflow/tune_sweep")

    st, body = _call(server, "POST", f"{API}/tune/tensorflow", body={
        "name": "tune_run", "modelName": "tune_sweep", "method": "fit",
        "methodParameters": {"x": "$tune_data.x", "y": "$tune_data.y",
                             "epochs": 4, "batch_size": 8}})
    assert st == 201, body
    meta = _poll_finished(server, f"{API}/tune/tensorflow/tune_run",
                          timeout=300)
    assert meta["finished"]


def _resnet_transfer_tune(server, tmp_path, stage_sizes,
                          learning_rates=(1e-3, 1e-4)):
    """BASELINE config 5 end-to-end: a pretrained ResNet-50 (weights
    loaded from a real npz export, not silent random init) created by
    module path through /model, then a learning-rate sweep through
    /tune — the reference's transfer-learn + GridSearchCV flow.
    ``stage_sizes`` shrinks the bottleneck stages for the fast run
    (same architecture family, ~10x cheaper compile on the CPU test
    backend); the fast run also sweeps ONE learning rate (each trial
    pays a full compile; multi-trial tune mechanics are covered by
    test_tune_grid_search_pipeline on a cheap model)."""
    import os

    from learningorchestra_tpu.models.tf_compat.keras import applications

    # "pretrained" artifact: an exported ResNet-50 weight file
    pre = applications.ResNet50(classes=3, input_shape=(32, 32, 3),
                                stage_sizes=stage_sizes)
    pre._build_params(np.zeros((1, 32, 32, 3), np.float32))
    weights_path = os.path.join(tmp_path, "resnet50_pretrained.npz")
    pre.save_weights(weights_path)

    st, body = _call(server, "POST", f"{API}/function/python", body={
        "name": "rn_data", "functionParameters": {},
        "function": ("import numpy as np\n"
                     "rng = np.random.default_rng(0)\n"
                     "x = rng.normal(size=(12, 32, 32, 3))"
                     ".astype(np.float32)\n"
                     "y = rng.integers(0, 3, size=12).astype(np.int32)\n"
                     "response = {'x': x, 'y': y}\n")})
    assert st == 201, body
    _poll_finished(server, f"{API}/function/python/rn_data")

    st, body = _call(server, "POST", f"{API}/model/tensorflow", body={
        "modelName": "rn_model",
        "modulePath": "tensorflow.keras.applications",
        "class": "ResNet50",
        "classParameters": {"classes": 3, "weights": weights_path,
                            "input_shape": [32, 32, 3],
                            **({"stage_sizes": stage_sizes}
                               if stage_sizes else {})}})
    assert st == 201, body
    _poll_finished(server, f"{API}/model/tensorflow/rn_model", timeout=300)

    st, body = _call(server, "POST", f"{API}/model/tensorflow", body={
        "modelName": "rn_sweep",
        "modulePath": "learningorchestra_tpu.models",
        "class": "GridSearch",
        "classParameters": {"estimator": "$rn_model",
                            "param_grid": {
                                "learning_rate": list(learning_rates)},
                            "validation_split": 0.25}})
    assert st == 201, body
    _poll_finished(server, f"{API}/model/tensorflow/rn_sweep")

    st, body = _call(server, "POST", f"{API}/tune/tensorflow", body={
        "name": "rn_tune", "modelName": "rn_sweep", "method": "fit",
        "methodParameters": {"x": "$rn_data.x", "y": "$rn_data.y",
                             "epochs": 1, "batch_size": 4}})
    assert st == 201, body
    meta = _poll_finished(server, f"{API}/tune/tensorflow/rn_tune",
                          timeout=900)
    assert meta["finished"]
    sweep = server.api.ctx.artifacts.load("rn_tune", "tune/tensorflow")
    assert sweep.best_params_ is not None
    assert len(sweep.cv_results_["params"]) == len(learning_rates)


def test_resnet_transfer_tune_pipeline_fast(server, tmp_path):
    """Shrunken-stages variant ([1, 1, 1, 1] bottlenecks, one sweep
    trial) — the whole REST transfer+tune flow at a fraction of the
    compile cost."""
    _resnet_transfer_tune(server, tmp_path, [1, 1, 1, 1],
                          learning_rates=(1e-3,))


@pytest.mark.slow
def test_resnet50_transfer_tune_pipeline(server, tmp_path):
    """Full-size ResNet-50 (stages 3/4/6/3) — run with ``-m slow``."""
    _resnet_transfer_tune(server, tmp_path, None)


def test_generate_through_predict_verb(server):
    """Token generation is reachable through the reference's generic
    call-method-X-on-stored-object-Y contract: POST /predict with
    method="generate" runs the KV-cache decode loop and the sampled
    ids surface in the execution documents via the universal GET."""
    st, body = _call(server, "POST", f"{API}/function/python", body={
        "name": "gen_data", "functionParameters": {},
        "function": ("import numpy as np\n"
                     "response = {'x': ((np.arange(32*12)"
                     ".reshape(32,12)*7) % 31 + 1).astype('int32')}\n")})
    assert st == 201, body
    _poll_finished(server, f"{API}/function/python/gen_data")
    st, body = _call(server, "POST", f"{API}/model/tensorflow", body={
        "modelName": "gen_lm",
        "modulePath": "learningorchestra_tpu.models",
        "class": "LanguageModel",
        "classParameters": {"vocab_size": 32, "d_model": 16,
                            "n_layers": 1, "n_heads": 2, "max_len": 12,
                            "attention": "dot"}})
    assert st == 201, body
    _poll_finished(server, f"{API}/model/tensorflow/gen_lm")
    st, body = _call(server, "POST", f"{API}/train/tensorflow", body={
        "name": "gen_train", "modelName": "gen_lm", "method": "fit",
        "methodParameters": {"x": "$gen_data.x", "epochs": 1,
                             "batch_size": 16}})
    assert st == 201, body
    _poll_finished(server, f"{API}/train/tensorflow/gen_train",
                   timeout=300)

    st, body = _call(server, "POST", f"{API}/predict/tensorflow", body={
        "name": "gen_out", "modelName": "gen_train",
        "method": "generate",
        "methodParameters": {"prompt": [[1, 2, 3]],
                             "max_new_tokens": 5}})
    assert st == 201, body
    _poll_finished(server, f"{API}/predict/tensorflow/gen_out",
                   timeout=300)
    st, body = _call(server, "GET", f"{API}/predict/tensorflow/gen_out",
                     params="?skip=0&limit=20")
    results = [d["result"] for d in body["result"] if d.get("result")]
    assert results, body
    tokens = results[-1][0]
    assert tokens[:3] == [1, 2, 3] and len(tokens) == 8


def test_train_checkpoint_and_patch_resume(server):
    """checkpoint: true saves per-epoch step dirs under the execution
    name; PATCH re-runs the same execution and resumes from them."""
    import os

    st, body = _call(server, "POST", f"{API}/function/python", body={
        "name": "ck_data", "functionParameters": {},
        "function": ("import numpy as np\n"
                     "rng = np.random.default_rng(0)\n"
                     "x = rng.normal(size=(32, 8)).astype(np.float32)\n"
                     "y = (x[:, 0] > 0).astype(np.int32)\n"
                     "response = {'x': x, 'y': y}\n")})
    assert st == 201, body
    _poll_finished(server, f"{API}/function/python/ck_data")

    st, body = _call(server, "POST", f"{API}/model/tensorflow", body={
        "modelName": "ck_model",
        "modulePath": "learningorchestra_tpu.models",
        "class": "NeuralModel",
        "classParameters": {"layer_configs": [
            {"kind": "dense", "units": 4, "activation": "relu"},
            {"kind": "dense", "units": 2, "activation": "softmax"}]}})
    assert st == 201, body
    _poll_finished(server, f"{API}/model/tensorflow/ck_model")

    st, body = _call(server, "POST", f"{API}/train/tensorflow", body={
        "name": "ck_train", "modelName": "ck_model", "method": "fit",
        "methodParameters": {"x": "$ck_data.x", "y": "$ck_data.y",
                             "epochs": 2, "batch_size": 8,
                             "checkpoint": True}})
    assert st == 201, body
    _poll_finished(server, f"{API}/train/tensorflow/ck_train")

    ckpt_dir = os.path.join(server.api.ctx.config.checkpoints_dir,
                            "ck_train")
    assert os.path.isdir(ckpt_dir) and os.listdir(ckpt_dir)

    from learningorchestra_tpu.runtime.checkpoint import Checkpointer

    ck = Checkpointer(ckpt_dir)
    assert ck.latest_step() == 8  # 2 epochs x 4 steps
    ck.close()

    st, body = _call(server, "PATCH", f"{API}/train/tensorflow/ck_train",
                     body={"methodParameters": {
                         "x": "$ck_data.x", "y": "$ck_data.y",
                         "epochs": 3, "batch_size": 8,
                         "checkpoint": True}})
    assert st == 200, body
    _poll_finished(server, f"{API}/train/tensorflow/ck_train")
    # resumed from step 8 with a TOTAL budget of 3 epochs: 2 already
    # done, so exactly one more epoch runs -> step 12 (a restart from
    # scratch would have left the latest checkpoint at 4; the old
    # overshoot bug would have trained 3 more epochs -> step 20)
    ck = Checkpointer(ckpt_dir)
    assert ck.latest_step() == 12
    ck.close()


def test_profile_trace_capture(server):
    """POST /profile start/stop captures a jax.profiler trace."""
    import jax.numpy as jnp

    st, body = _call(server, "POST", f"{API}/profile",
                     body={"action": "start"})
    assert st == 201, body
    # give the profiler something to record
    (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    st, body = _call(server, "POST", f"{API}/profile",
                     body={"action": "stop"})
    assert st == 200, body
    assert body["files"] > 0
    st, body = _call(server, "GET", f"{API}/profile")
    assert st == 200 and len(body["traces"]) == 1
    # double-stop is a client error, not a crash
    st, body = _call(server, "POST", f"{API}/profile",
                     body={"action": "stop"})
    assert st == 406


def test_metrics_prometheus_exposition(server):
    status, _ = _call(server, "GET", "/health")
    assert status == 200
    import urllib.request
    with urllib.request.urlopen(
            f"{server.base_url}/metrics?format=prometheus") as r:
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        text = r.read().decode()
    assert "lo_uptime_seconds" in text
    assert 'lo_requests_total{route=' in text
    assert "lo_jobs_running" in text
    # every sample line is "name{labels} value" or "name value"
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        assert len(line.rsplit(" ", 1)) == 2, line


def test_savedmodel_import_through_model_service(server, tmp_path):
    """The reference's primary artifact flow over REST: a stock
    tf.keras SavedModel DIRECTORY imported by module path through
    POST /model (``tensorflow.keras.models.load_model`` resolves to
    the tf_compat shim, which reads the bundle with zero tensorflow
    imports), then served for prediction."""
    tfk = pytest.importorskip("tf_keras")
    kl = tfk.layers

    km = tfk.Sequential([
        kl.Dense(6, activation="relu", input_shape=(4,)),
        kl.Dense(2, activation="softmax")])
    x = np.random.default_rng(9).normal(size=(5, 4)).astype(np.float32)
    want = np.asarray(km(x))
    sm_dir = str(tmp_path / "sm_dir")
    km.save(sm_dir, save_format="tf")

    st, body = _call(server, "POST", f"{API}/model/tensorflow", body={
        "modelName": "smi",
        "modulePath": "tensorflow.keras.models",
        "class": "load_model",
        "classParameters": {"path": sm_dir}})
    assert st == 201, body
    _poll_finished(server, f"{API}/model/tensorflow/smi")

    st, body = _call(server, "POST", f"{API}/predict/tensorflow", body={
        "name": "smi_pred", "modelName": "smi", "method": "predict",
        "methodParameters": {"x": x.tolist(), "batch_size": 5}})
    assert st == 201, body
    _poll_finished(server, f"{API}/predict/tensorflow/smi_pred")
    got = np.asarray(server.api.ctx.artifacts.load(
        "smi_pred", "predict/tensorflow"))
    np.testing.assert_allclose(got, want, atol=2e-2)  # bf16 default
