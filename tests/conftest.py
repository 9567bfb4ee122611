"""Test config: force an 8-device CPU mesh before jax import.

SURVEY §4: the reference has no tests at all; our strategy is unit
tests per component with the JAX CPU backend and
``--xla_force_host_platform_device_count=8`` so all mesh/sharding logic
(DP/TP/PP/SP/EP) is exercised multi-device without a TPU.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# Persisting compiled executables across runs (keyed by HLO hash)
# saves compile time, but on this jaxlib executing XLA:CPU executables
# deserialized from the disk cache intermittently corrupts the glibc
# heap ("corrupted double-linked list" / SIGSEGV in a later jitted
# step), killing the whole pytest process — reproduced ~1-in-3 on
# resume-after-checkpoint workloads and never without the cache. The
# cache is therefore OPT-IN (LO_TEST_COMPILE_CACHE=1) until a jaxlib
# with a fixed deserialization path is in the image.
if os.environ.get("LO_TEST_COMPILE_CACHE", "0") == "1":
    # same placement rule as services/context.py: jax's own variable
    # when set (code then sets nothing), else <checkout>/.jax_cache.
    # Set before jax is imported: jax reads both natively, and so do
    # the children (durability/distributed/cluster server boots).
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache"))
    os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

# pin the CPU backend through jax.config as well: it wins over the
# env var, so the tests stay on the CPU whatever the shell exported
import jax

jax.config.update("jax_platforms", "cpu")

# the exact cache vars, for tests that spawn children with a MINIMAL
# env (everything else inherits os.environ and needs nothing)
JAX_CACHE_ENV = {k: v for k, v in os.environ.items()
                 if k.startswith(("JAX_COMPILATION",
                                  "JAX_PERSISTENT"))}

import pytest


@pytest.fixture()
def tmp_config(tmp_path, monkeypatch):
    """Fresh framework config rooted in a tmp dir."""
    from learningorchestra_tpu import config as config_mod
    cfg = config_mod.Config(home=str(tmp_path / "lo_home"))
    config_mod.set_config(cfg)
    yield cfg
    config_mod.reset_config()


@pytest.fixture(autouse=True)
def _no_shared_executables():
    """Engines of equal programs share their jitted steps process-wide
    (``Engine(cache_key=...)``; ``LanguageModel`` too since PR 26). A
    test that counts builds or spans of a cold fit must not find the
    steps an earlier test of its worker left there."""
    from learningorchestra_tpu.runtime import engine as engine_lib
    engine_lib.reset_executable_cache()
    yield


@pytest.fixture()
def catalog(tmp_config):
    from learningorchestra_tpu.catalog import Catalog
    cat = Catalog(tmp_config.catalog_path, tmp_config.datasets_dir)
    yield cat
    cat.close()


@pytest.fixture()
def artifacts(tmp_config):
    from learningorchestra_tpu.catalog import ArtifactStore
    return ArtifactStore(tmp_config.artifacts_dir)


# ----------------------------------------------------------------------
# Test tiering: the default `pytest -q` run must stay fast on one core
# (the heavy end-to-end/parity tests below dominated a ~12-minute full
# run). They carry the `slow` marker, deselected by addopts; run the
# FULL suite with `pytest -m 'slow or not slow'` (deploy/ci.sh runs it
# as the LO_CI_FULL=1 stage). Durations measured 2026-07-31 (single
# core, --durations=40).
#
# Invariant: the DEFAULT tier keeps at least one oracle-parity test
# per numerical subsystem — flash-attention kernels
# (test_transformer.py::test_gqa_flash_matches_dot_in_module), ring/
# sequence parallelism (test_parallel.py::
# test_ring_flash_grads_match_oracle), pipeline parallelism
# (test_pp_transformer.py::test_1f1b_matches_autodiff_oracle) and the
# grouped-GQA kernel (test_ops.py::
# test_gqa_grouped_kernel_matches_repeat) — so deselecting `slow`
# never means zero numerical-correctness coverage (~35s total,
# re-measured 2026-08-05). Don't re-add those four below without
# moving an equivalent parity test into the default tier.
# ----------------------------------------------------------------------
SLOW_FILES = {
    # spawn real server/worker subprocesses; inherently many-second
    "test_cluster.py",
    "test_distributed.py",
}
SLOW_TESTS = {
    "test_server.py": {
        "test_resnet_transfer_tune_pipeline_fast",  # 116s
        "test_generate_through_predict_verb",
        "test_train_checkpoint_and_patch_resume",
    },
    "test_transformer.py": {
        "test_sharded_fused_head_matches_flat",  # ~30s per param
        "test_fused_head_matches_full_logits_loss_and_grads",
        "test_fused_proj_trains_and_generates",
        "test_gqa_artifact_round_trip",
        "test_fused_proj_tree_is_mesh_independent",
        "test_fused_proj_matches_unfused_math",
        "test_gqa_trains_under_tp_and_sp",
        "test_beam_search_matches_greedy_and_finds_optimum",
        "test_gqa_flash_sharded_fit_stays_native",
        "test_remat_policies_match_no_remat",
        "test_sliding_window_locality_and_decode_parity",
        "test_moe_expert_parallel_fit",
        "test_sequence_parallel_fit",
        "test_gqa_cached_decode_matches_full_forward",
        "test_sliding_window_sequence_parallel_fit",
        "test_text_classifier_learns_and_round_trips",
        "test_feature_stack_interactions",
        "test_lm_learns_copy_task",
        "test_causality",
        "test_ring_attention_32k_step_lowers",
        "test_rope_base_changes_positions_and_round_trips",
        "test_ring_fit_uses_sharded_fused_head",
        "test_param_shardings_tp",
    },
    "test_parallel.py": {
        "test_ring_attention_grads_flow",
        "test_ulysses_gqa_native_matches_oracle",
        "test_ring_windowed_multi_tile_shards",
        "test_ring_windowed_flash_grads_match_oracle",
    },
    "test_pp_transformer.py": {
        "test_pp_pipelined_flash_both_schedules",
        "test_pp_windowed_matches_banded_oracle",
    },
    "test_durability.py": {
        "test_kill_and_restart_resumes_checkpointed_train",
    },
    "test_weights_io.py": {
        "test_from_savedmodel_rnn_stack_parity",
        "test_resnet50_pretrained_transfer_roundtrip",
        "test_save_keras_roundtrip_through_real_keras",
        "test_save_keras_bidirectional_and_gelu_roundtrip",
    },
    "test_services_core.py": {
        "test_sandbox_blocks_dangerous_builtins",
        "test_hash_resolves_tensorflow_shim",
    },
    "test_sweep.py": {
        "test_grid_search_over_text_classifier",
    },
    "test_models.py": {
        "test_hoisted_lstm_matches_real_keras",
    },
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        fname = os.path.basename(str(item.fspath))
        name = getattr(item, "originalname", None) or item.name
        if fname in SLOW_FILES or name in SLOW_TESTS.get(fname, set()):
            item.add_marker(pytest.mark.slow)
