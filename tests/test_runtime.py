"""Runtime tests: mesh specs, batcher padding, prefetch, engine
convergence, checkpoint roundtrips — all on the 8-device CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax


def test_eight_cpu_devices():
    assert len(jax.devices()) == 8


def test_mesh_spec_parse_and_build():
    from learningorchestra_tpu.runtime import mesh as M
    assert M.parse_mesh_spec("dp=2,tp=4") == {"dp": 2, "tp": 4}
    mesh = M.build_mesh("dp=2,tp=4")
    assert mesh.shape == {"dp": 2, "tp": 4}
    mesh = M.build_mesh("dp=-1,tp=2")
    assert mesh.shape == {"dp": 4, "tp": 2}
    auto = M.build_mesh("auto")
    assert auto.shape == {"dp": 8}
    with pytest.raises(ValueError):
        M.build_mesh("dp=3,tp=3")
    assert M.data_parallel_size(mesh) == 4


def test_dcn_mesh_axis():
    """Multi-slice grammar (SURVEY §2.5): a ``dcn`` outer axis models
    pod slices joined over DCN. It must be outermost (slice-contiguous
    device blocks land on the inner ICI axes) and it shards data, so
    the only cross-slice collective is the gradient all-reduce."""
    from learningorchestra_tpu.runtime import mesh as M

    mesh = M.build_mesh("dcn=2,dp=2,tp=2")
    assert mesh.shape == {"dcn": 2, "dp": 2, "tp": 2}
    assert M.data_axes(mesh) == ("dcn", "dp")
    assert M.data_parallel_size(mesh) == 4
    with pytest.raises(ValueError, match="OUTERMOST"):
        M.build_mesh("dp=2,dcn=2,tp=2")


def test_dcn_training_matches_flat_dp(tmp_config):
    """A dcn=2,dp=4 two-slice mesh must train numerically like plain
    dp=8 — params replicate across slices, the batch splits over
    dcn x dp, gradients all-reduce across everything."""
    import optax

    from learningorchestra_tpu.runtime import engine as E
    from learningorchestra_tpu.runtime import mesh as M
    from learningorchestra_tpu.runtime.data import ArrayBatcher

    def apply_fn(params, model_state, batch, train, rng_):
        return batch["x"] @ params["w"], model_state

    x = np.random.default_rng(0).normal(size=(32, 3)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)

    losses = {}
    for spec in ("dp=8", "dcn=2,dp=4"):
        eng = E.Engine(apply_fn, E.mse_loss, optax.sgd(0.1),
                       mesh=M.build_mesh(spec),
                       compute_dtype=jnp.float32)
        st = eng.init_state({"w": jnp.zeros((3, 1))})
        batcher = ArrayBatcher({"x": x, "y": y}, 16, dp_multiple=8)
        _, hist = eng.fit(st, batcher, epochs=2)
        losses[spec] = [h["loss"] for h in hist]
    np.testing.assert_allclose(losses["dp=8"], losses["dcn=2,dp=4"],
                               rtol=1e-5)


def test_batcher_pads_and_masks(tmp_config):
    from learningorchestra_tpu.runtime.data import ArrayBatcher, MASK_KEY
    b = ArrayBatcher({"x": np.arange(10, dtype=np.float32)},
                     batch_size=4, dp_multiple=4)
    batches = list(b.epoch(0))
    assert len(batches) == 3 == b.steps_per_epoch
    last = batches[-1]
    assert last["x"].shape == (4,)
    assert last[MASK_KEY].tolist() == [1, 1, 0, 0]
    # dp_multiple rounds odd batch size up
    b2 = ArrayBatcher({"x": np.zeros(10, np.float32)}, batch_size=3,
                      dp_multiple=4)
    assert b2.batch_size == 4


def test_batcher_shuffle_deterministic(tmp_config):
    from learningorchestra_tpu.runtime.data import ArrayBatcher
    arr = {"x": np.arange(16, dtype=np.float32)}
    b1 = ArrayBatcher(arr, 8, shuffle=True, seed=1)
    b2 = ArrayBatcher(arr, 8, shuffle=True, seed=1)
    e1 = np.concatenate([bb["x"] for bb in b1.epoch(0)])
    e2 = np.concatenate([bb["x"] for bb in b2.epoch(0)])
    assert (e1 == e2).all()
    e3 = np.concatenate([bb["x"] for bb in b1.epoch(1)])
    assert not (e1 == e3).all()


def test_prefetch_propagates_errors(tmp_config):
    from learningorchestra_tpu.runtime.data import prefetch_to_device

    def gen():
        yield {"x": np.zeros(2, np.float32)}
        raise RuntimeError("boom")

    it = prefetch_to_device(gen())
    next(it)
    with pytest.raises(RuntimeError, match="boom"):
        next(it)


def test_engine_fits_linear_regression(tmp_config):
    from learningorchestra_tpu.runtime import engine as E
    from learningorchestra_tpu.runtime.data import ArrayBatcher
    from learningorchestra_tpu.runtime import mesh as M

    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 3)).astype(np.float32)
    w_true = np.array([[2.0], [-1.0], [0.5]], np.float32)
    y = (x @ w_true)[:, 0] + 0.3

    def apply_fn(params, model_state, batch, train, rng_):
        return batch["x"] @ params["w"] + params["b"], model_state

    eng = E.Engine(apply_fn, E.mse_loss, optax.adam(0.1),
                   mesh=M.build_mesh("auto"),
                   compute_dtype=jnp.float32)
    params = {"w": jnp.zeros((3, 1)), "b": jnp.zeros(())}
    state = eng.init_state(params)
    batcher = ArrayBatcher({"x": x, "y": y}, 64, dp_multiple=8)
    state, history = eng.fit(state, batcher, epochs=30)
    assert history[-1]["loss"] < 0.01
    assert history[0]["loss"] > history[-1]["loss"]
    # evaluate + predict agree
    final = eng.evaluate(state, batcher)
    assert final["loss"] < 0.01
    preds = eng.predict(state, batcher)
    assert preds.shape[0] == 256


def test_engine_masks_padding_exactly(tmp_config):
    """Metrics over a ragged dataset must equal unpadded math."""
    from learningorchestra_tpu.runtime import engine as E
    from learningorchestra_tpu.runtime.data import ArrayBatcher
    from learningorchestra_tpu.runtime import mesh as M

    x = np.ones((10, 2), np.float32)
    y = np.array([0, 1] * 5, np.int32)

    def apply_fn(params, model_state, batch, train, rng_):
        return batch["x"] @ params["w"], model_state

    eng = E.Engine(apply_fn, E.sparse_softmax_loss, optax.sgd(0.0),
                   mesh=M.build_mesh("auto"),
                   metrics={"accuracy": E.accuracy_metric},
                   compute_dtype=jnp.float32)
    params = {"w": jnp.array([[1.0, 0.0], [0.0, 0.0]])}
    state = eng.init_state(params)
    # batch=8 -> second batch has 6 padded samples
    res = eng.evaluate(state, ArrayBatcher({"x": x, "y": y}, 8,
                                           dp_multiple=8))
    # model always predicts class 0 => accuracy exactly 0.5
    assert abs(res["accuracy"] - 0.5) < 1e-6


def test_checkpointer_roundtrip(tmp_config, tmp_path):
    from learningorchestra_tpu.runtime.checkpoint import (
        Checkpointer, load_pytree, save_pytree)

    tree = {"a": jnp.arange(4.0), "b": {"c": jnp.ones((2, 2))}}
    ck = Checkpointer(str(tmp_path / "ckpt"))
    ck.save(1, tree)
    ck.save(2, jax.tree_util.tree_map(lambda v: v * 2, tree))
    ck.wait_until_finished()
    assert ck.latest_step() == 2
    restored = ck.restore(tree)
    assert np.allclose(restored["a"], np.arange(4.0) * 2)

    path = str(tmp_path / "tree.msgpack")
    save_pytree(tree, path)
    back = load_pytree(path, tree)
    assert np.allclose(back["b"]["c"], 1.0)


def test_scan_fit_matches_loop_fit(tmp_config):
    """The whole-epoch lax.scan fast path must produce the same
    training math as the per-step loop (same rngs aside)."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from learningorchestra_tpu.runtime import data as data_lib
    from learningorchestra_tpu.runtime import engine as engine_lib
    from learningorchestra_tpu.runtime import mesh as mesh_lib

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)
    w = rng.normal(size=(8, 2)).astype(np.float32) * 0.1

    def apply_fn(params, model_state, batch, train, step_rng):
        return batch["x"] @ params["w"].astype(jnp.float32), model_state

    def make_engine():
        return engine_lib.Engine(
            apply_fn=apply_fn,
            loss_fn=engine_lib.sparse_softmax_loss,
            optimizer=optax.sgd(0.1),
            mesh=mesh_lib.get_default_mesh(),
            metrics={"accuracy": engine_lib.accuracy_metric},
            compute_dtype=jnp.float32)

    results = {}
    for mode in (False, True):
        eng = make_engine()
        state = eng.init_state({"w": w.copy()})
        # shuffle=False: the loop path shuffles on host, the scan path
        # in HBM, so only the unshuffled order is bit-comparable
        batcher = data_lib.ArrayBatcher({"x": x, "y": y}, batch_size=16,
                                        shuffle=False, dp_multiple=8)
        state, hist = eng.fit(state, batcher, epochs=3, seed=7,
                              scan_batches=mode)
        results[mode] = (np.asarray(state.params["w"]),
                         [h["loss"] for h in hist])

    # identical batch order; rng streams differ but the model is
    # dropout-free, so params and losses must match exactly
    np.testing.assert_allclose(results[False][0], results[True][0],
                               atol=1e-6)
    np.testing.assert_allclose(results[False][1], results[True][1],
                               atol=1e-6)


def test_scan_fit_ragged_tail_masked(tmp_config):
    """Padding rows in the scan path must not leak into the loss."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from learningorchestra_tpu.runtime import data as data_lib
    from learningorchestra_tpu.runtime import engine as engine_lib
    from learningorchestra_tpu.runtime import mesh as mesh_lib

    rng = np.random.default_rng(1)
    x = rng.normal(size=(40, 4)).astype(np.float32)  # 40 % 16 != 0
    y = (x[:, 0] > 0).astype(np.int32)

    def apply_fn(params, model_state, batch, train, step_rng):
        return batch["x"] @ params["w"].astype(jnp.float32), model_state

    eng = engine_lib.Engine(
        apply_fn=apply_fn, loss_fn=engine_lib.sparse_softmax_loss,
        optimizer=optax.sgd(0.05), mesh=mesh_lib.get_default_mesh(),
        metrics={"accuracy": engine_lib.accuracy_metric},
        compute_dtype=jnp.float32)
    state = eng.init_state(
        {"w": rng.normal(size=(4, 2)).astype(np.float32)})
    batcher = data_lib.ArrayBatcher({"x": x, "y": y}, batch_size=16,
                                    dp_multiple=8)
    _, hist = eng.fit(state, batcher, epochs=2, scan_batches=True)
    assert all(np.isfinite(h["loss"]) for h in hist)
    assert all(0.0 <= h["accuracy"] <= 1.0 for h in hist)


def test_checkpoint_resume(tmp_config, tmp_path):
    """fit -> checkpoint -> fresh engine resumes from the saved step
    instead of restarting (beyond the reference's lost-job story)."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from learningorchestra_tpu.runtime import checkpoint as ckpt_lib
    from learningorchestra_tpu.runtime import data as data_lib
    from learningorchestra_tpu.runtime import engine as engine_lib
    from learningorchestra_tpu.runtime import mesh as mesh_lib

    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)

    def apply_fn(params, model_state, batch, train, step_rng):
        return batch["x"] @ params["w"].astype(jnp.float32), model_state

    def make():
        eng = engine_lib.Engine(
            apply_fn=apply_fn, loss_fn=engine_lib.sparse_softmax_loss,
            optimizer=optax.sgd(0.05), mesh=mesh_lib.get_default_mesh(),
            compute_dtype=jnp.float32)
        state = eng.init_state(
            {"w": np.zeros((4, 2), np.float32)})
        batcher = data_lib.ArrayBatcher({"x": x, "y": y}, batch_size=8,
                                        dp_multiple=8)
        return eng, state, batcher

    ckpt = ckpt_lib.Checkpointer(str(tmp_path / "ck"))
    eng, state, batcher = make()
    state, _ = eng.fit(state, batcher, epochs=2, checkpointer=ckpt)
    first_steps = int(state.step)
    assert first_steps == 8  # 4 steps/epoch * 2

    # fresh engine + zeroed state: restores, and ``epochs`` is the
    # TOTAL budget — 2 are done, so epochs=3 trains exactly 1 more
    eng2, state2, batcher2 = make()
    state2, hist2 = eng2.fit(state2, batcher2, epochs=3, checkpointer=ckpt)
    assert int(state2.step) == first_steps + 4
    assert [h["epoch"] for h in hist2] == [2]

    # re-running a finished budget is a no-op, not a silent doubling
    eng3, state3, batcher3 = make()
    state3, hist3 = eng3.fit(state3, batcher3, epochs=3, checkpointer=ckpt)
    assert int(state3.step) == first_steps + 4
    assert hist3 == []

    # epoch progress comes from the checkpoint sidecar, so a re-run
    # that RESHAPES the feed (batch_size 8 -> 4, 8 steps/epoch) still
    # counts 3 epochs done: budget 3 stays a no-op even though
    # step(12) // new_steps_per_epoch(8) would miscount as 1
    eng4, state4, _ = make()
    batcher4 = data_lib.ArrayBatcher({"x": x, "y": y}, batch_size=4,
                                     dp_multiple=4)
    state4, hist4 = eng4.fit(state4, batcher4, epochs=3, checkpointer=ckpt)
    assert int(state4.step) == first_steps + 4
    assert hist4 == []
    ckpt.close()


def test_grad_accum_matches_full_batch(tmp_config):
    """grad_accum=4: four sequential microbatches, one optimizer
    update — with uniform micro sizes and no masking the step is
    numerically the full-batch step (mean of micro means == full
    mean), so params and loss sums must match accum=1."""
    from learningorchestra_tpu.runtime import engine as E
    from learningorchestra_tpu.runtime import mesh as M
    from learningorchestra_tpu.runtime.data import ArrayBatcher

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 3)).astype(np.float32)
    w_true = np.array([[2.0], [-1.0], [0.5]], np.float32)
    y = (x @ w_true)[:, 0] + 0.3

    def apply_fn(params, model_state, batch, train, rng_):
        return batch["x"] @ params["w"] + params["b"], model_state

    def run(accum):
        eng = E.Engine(apply_fn, E.mse_loss, optax.sgd(0.1),
                       mesh=M.build_mesh("auto"),
                       compute_dtype=jnp.float32, grad_accum=accum)
        params = {"w": jnp.zeros((3, 1)), "b": jnp.zeros(())}
        state = eng.init_state(params)
        batcher = ArrayBatcher({"x": x, "y": y}, 64, dp_multiple=8)
        state, history = eng.fit(state, batcher, epochs=3)
        return E.to_host(state.params), history

    p1, h1 = run(1)
    p4, h4 = run(4)
    np.testing.assert_allclose(np.asarray(p4["w"]), np.asarray(p1["w"]),
                               atol=1e-5)
    assert abs(h4[-1]["loss"] - h1[-1]["loss"]) < 1e-4


def test_grad_accum_rejects_non_divisible(tmp_config):
    from learningorchestra_tpu.runtime import engine as E
    from learningorchestra_tpu.runtime import mesh as M
    from learningorchestra_tpu.runtime.data import ArrayBatcher

    def apply_fn(params, model_state, batch, train, rng_):
        return batch["x"] @ params["w"], model_state

    eng = E.Engine(apply_fn, E.mse_loss, optax.sgd(0.1),
                   mesh=M.build_mesh("auto"),
                   compute_dtype=jnp.float32, grad_accum=3)
    params = {"w": jnp.zeros((3, 1))}
    state = eng.init_state(params)
    x = np.ones((8, 3), np.float32)
    batcher = ArrayBatcher({"x": x, "y": np.zeros(8, np.float32)}, 8,
                           dp_multiple=8)
    with pytest.raises(ValueError, match="not divisible"):
        eng.fit(state, batcher, epochs=1)


def test_lm_fit_grad_accum_kwarg(tmp_config):
    """REST-reachable surface: fit(grad_accum=2) on a LanguageModel
    trains and microbatching leaves the loss finite."""
    from learningorchestra_tpu.models.transformer import LanguageModel

    lm = LanguageModel(vocab_size=32, d_model=16, n_layers=1,
                       n_heads=2, max_len=12, attention="dot")
    toks = (np.arange(8 * 12).reshape(8, 12) % 31 + 1).astype(np.int32)
    hist = lm.fit(toks, batch_size=8, epochs=1, grad_accum=2)
    assert np.isfinite(hist.history["loss"][0])
    assert lm._accum == 2


def test_grad_accum_noop_override_keeps_engine(tmp_config):
    """fit(grad_accum=0) clamps to 1; when the effective value is
    unchanged the cached engine (and its compiled steps) survives."""
    from learningorchestra_tpu.models.transformer import LanguageModel

    lm = LanguageModel(vocab_size=32, d_model=16, n_layers=1,
                       n_heads=2, max_len=12, attention="dot")
    toks = (np.arange(8 * 12).reshape(8, 12) % 31 + 1).astype(np.int32)
    lm.fit(toks, batch_size=8, epochs=1)
    eng = lm._engine
    lm.fit(toks, batch_size=8, epochs=1, grad_accum=0)
    assert lm._engine is eng


def test_grad_accum_exact_under_skewed_weights(tmp_config):
    """Micro gradients are weighted by their weight totals, so
    accumulation equals the single-batch weighted step even when the
    sample weights land wildly unevenly across microbatches."""
    from learningorchestra_tpu.runtime import engine as E
    from learningorchestra_tpu.runtime import mesh as M
    from learningorchestra_tpu.runtime.data import ArrayBatcher

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 3)).astype(np.float32)
    y = (x @ np.array([[2.0], [-1.0], [0.5]], np.float32))[:, 0]
    w = np.ones(64, np.float32)
    w[:16] = 30.0        # first microbatch dominates
    w[48:] = 0.001       # last microbatch nearly weightless

    def apply_fn(params, model_state, batch, train, rng_):
        return batch["x"] @ params["w"] + params["b"], model_state

    def run(accum):
        eng = E.Engine(apply_fn, E.mse_loss, optax.sgd(0.1),
                       mesh=M.build_mesh("auto"),
                       compute_dtype=jnp.float32, grad_accum=accum)
        params = {"w": jnp.zeros((3, 1)), "b": jnp.zeros(())}
        state = eng.init_state(params)
        batcher = ArrayBatcher({"x": x, "y": y}, 64, dp_multiple=8,
                               sample_weight=w)
        state, history = eng.fit(state, batcher, epochs=2)
        return E.to_host(state.params), history

    p1, h1 = run(1)
    p4, h4 = run(4)
    np.testing.assert_allclose(np.asarray(p4["w"]), np.asarray(p1["w"]),
                               atol=1e-5)
    assert abs(h4[-1]["loss"] - h1[-1]["loss"]) < 1e-4


def test_restore_optimizer_drift_migrates_params(tmp_config, tmp_path):
    """A checkpoint whose OPTIMIZER pytree no longer matches the live
    state (optimizer structure evolved between versions, e.g. adamw
    gaining a decay mask) resumes params-only with a freshly built
    opt_state instead of silently restarting at step 0."""
    from learningorchestra_tpu.runtime import engine as E
    from learningorchestra_tpu.runtime import mesh as M
    from learningorchestra_tpu.runtime.checkpoint import Checkpointer
    from learningorchestra_tpu.runtime.data import ArrayBatcher

    def apply_fn(params, model_state, batch, train, rng_):
        return batch["x"] @ params["w"], model_state

    x = np.random.default_rng(0).normal(size=(16, 3)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    batcher = ArrayBatcher({"x": x, "y": y}, 8, dp_multiple=8)

    # write a checkpoint under one optimizer structure...
    eng1 = E.Engine(apply_fn, E.mse_loss, optax.sgd(0.1),
                    mesh=M.build_mesh("auto"),
                    compute_dtype=jnp.float32)
    st1 = eng1.init_state({"w": jnp.zeros((3, 1))})
    ck = Checkpointer(str(tmp_path / "ck"))
    st1, _ = eng1.fit(st1, batcher, epochs=2, checkpointer=ck)
    trained_w = np.asarray(st1.params["w"])
    trained_step = int(st1.step)

    # ...then resume with a DIFFERENT optimizer state tree: the params
    # graft over, the step continues, and only the remaining budget runs
    eng2 = E.Engine(apply_fn, E.mse_loss, optax.adam(0.1),
                    mesh=M.build_mesh("auto"),
                    compute_dtype=jnp.float32)
    # the migration grafts EXACTLY the trained params (not the live
    # zero-init) before any further training
    probe = eng2.init_state({"w": jnp.zeros((3, 1))})
    with pytest.warns(UserWarning, match="rebuilt optimizer"):
        migrated, was_restored = eng2._maybe_restore(probe, ck)
    assert was_restored and int(migrated.step) == trained_step
    assert not np.allclose(trained_w, 0.0)
    np.testing.assert_allclose(np.asarray(migrated.params["w"]),
                               trained_w)
    st2 = eng2.init_state({"w": jnp.zeros((3, 1))})
    with pytest.warns(UserWarning, match="rebuilt optimizer"):
        st2, history = eng2.fit(st2, batcher, epochs=3, checkpointer=ck)
    assert len(history) == 1  # 2 of 3 epochs already done
    assert int(st2.step) > trained_step


def test_restore_params_drift_trains_from_scratch(tmp_config, tmp_path):
    """When the PARAMS tree itself drifted (different shapes), no
    migration is possible: warn and train from scratch."""
    from learningorchestra_tpu.runtime import engine as E
    from learningorchestra_tpu.runtime import mesh as M
    from learningorchestra_tpu.runtime.checkpoint import Checkpointer
    from learningorchestra_tpu.runtime.data import ArrayBatcher

    x = np.random.default_rng(0).normal(size=(16, 3)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    batcher = ArrayBatcher({"x": x, "y": y}, 8, dp_multiple=8)

    def apply1(params, model_state, batch, train, rng_):
        return batch["x"] @ params["w"], model_state

    eng1 = E.Engine(apply1, E.mse_loss, optax.sgd(0.1),
                    mesh=M.build_mesh("auto"),
                    compute_dtype=jnp.float32)
    st1 = eng1.init_state({"w": jnp.zeros((3, 1))})
    ck = Checkpointer(str(tmp_path / "ck"))
    eng1.fit(st1, batcher, epochs=2, checkpointer=ck)

    def apply2(params, model_state, batch, train, rng_):
        return batch["x"] @ params["w"] + params["b"], model_state

    eng2 = E.Engine(apply2, E.mse_loss, optax.adam(0.1),
                    mesh=M.build_mesh("auto"),
                    compute_dtype=jnp.float32)
    st2 = eng2.init_state({"w": jnp.zeros((3, 1)), "b": jnp.zeros((1,))})
    with pytest.warns(UserWarning, match="training from scratch"):
        _, history = eng2.fit(st2, batcher, epochs=2, checkpointer=ck)
    assert len(history) == 2  # full budget ran fresh
