"""The main path's Pallas kernels, asked of the TPU's own compiler.

No chip is attached here: the installed TPU compiler compiles for a
DESCRIBED ``v5e:2x2`` topology, which refuses what interpret mode on
the CPU cannot see (tile alignment, VMEM budget, Mosaic lowering).
Nothing runs, so these say nothing about results or times — they guard
"the kernel still compiles for the chip" at ~2 s each and no chip time.

The topology is described inside a module-scoped fixture and never at
import: only one process may load the TPU library, and under xdist
every worker imports every test file. Keep these cases in ONE file and
compile in the test's own process.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from learningorchestra_tpu.ops import attention as attn


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache
    # but can never be read back without a chip: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes, dtype=jnp.bfloat16) -> str:
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _loss(fn):
    def loss(q, k, v):
        return fn(q, k, v).astype(jnp.float32).sum()
    return loss


SMOKE = (16, 1024, 16, 64)  # chip_smoke.py's own training shape

# (id, q shape, kv shape, flash kwargs, differentiate)
CASES = [
    ("smoke_fwd", SMOKE, SMOKE, {"causal": True}, False),
    ("smoke_fwd_bwd", SMOKE, SMOKE, {"causal": True}, True),
    ("gqa_16_4_d128", (2, 2048, 16, 128), (2, 2048, 4, 128),
     {"causal": True}, True),
    ("window_256", (4, 1024, 8, 64), (4, 1024, 8, 64),
     {"causal": True, "window": 256}, True),
    ("unaligned_seq_1100", (2, 1100, 8, 64), (2, 1100, 8, 64),
     {"causal": True}, True),
]


@pytest.mark.parametrize("qs,kvs,kwargs,grad",
                         [c[1:] for c in CASES],
                         ids=[c[0] for c in CASES])
def test_flash_attention_compiles_for_v5e(one_chip, qs, kvs, kwargs,
                                          grad):
    fn = functools.partial(attn.flash_attention, interpret=False,
                           **kwargs)
    if grad:
        fn = jax.value_and_grad(_loss(fn), argnums=(0, 1, 2))
    text = _compiled_text(fn, one_chip, qs, kvs, kvs)
    assert "tpu_custom_call" in text


def test_flash_kernels_carry_their_own_names_for_v5e(one_chip):
    """The chip's compiler takes a ``pallas_call``'s ``name=`` into the
    instruction name, so a profiler trace tells the three training
    kernels apart (``benchmark/layer_metrics/flash_*_ms.train.py``)
    and not by the module scope they run under."""
    fn = functools.partial(attn.flash_attention, interpret=False,
                           causal=True)

    def loss(q, k, v):
        with jax.named_scope("attn"):  # as the model's module does
            return fn(q, k, v).astype(jnp.float32).sum()

    q, kv = (2, 2048, 16, 128), (2, 2048, 4, 128)
    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                          one_chip, q, kv, kv)
    calls = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = [^\n]*custom-call\(",
                       text, re.M)
    kernels = {name.split(".")[0] for name in calls}
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} <= kernels, calls
    assert not any(name.startswith("attn") for name in calls), calls


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_ring_hop_kernel_compiles_for_v5e(one_chip, grad):
    """``flash_attention_with_lse`` at a non-zero ``kv_offset`` — the
    per-hop block of ring attention (parallel/ring.py), with the
    gradient flowing through BOTH outputs as the lse merge needs."""
    def hop(q, k, v):
        o, lse = attn.flash_attention_with_lse(
            q, k, v, causal=True, kv_offset=-1024, interpret=False)
        return o.astype(jnp.float32).sum() + lse.sum()

    fn = jax.value_and_grad(hop, argnums=(0, 1, 2)) if grad else hop
    shape = (2, 1024, 16, 64)
    text = _compiled_text(fn, one_chip, shape, shape, shape)
    assert "tpu_custom_call" in text


def _custom_calls(text):
    calls = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = [^\n]*custom-call\(",
                       text, re.M)
    return {name.split(".")[0] for name in calls}


def test_bd_flash_kernels_compile_for_v5e_at_the_cells_shape(one_chip):
    """Attention under the block-diffusion mask at SDAR's widths: 2
    rows of [noisy ; clean] 2 x 4096 positions, 32 query heads on 4 KV
    heads of 128, blocks of 4; forward and both backward kernels, each
    under its own name."""
    fn = functools.partial(attn.flash_bd_attention, block_length=4,
                           interpret=False)

    def loss(q, k, v):
        with jax.named_scope("attn"):  # as the model's module does
            return fn(q, k, v).astype(jnp.float32).sum()

    q, kv = (2, 8192, 32, 128), (2, 8192, 4, 128)
    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                          one_chip, q, kv, kv)
    assert {"flash_bd_fwd", "flash_bd_bwd_dq", "flash_bd_bwd_dkv"} \
        <= _custom_calls(text)


@pytest.mark.parametrize("tile_m", [128, 256])
def test_grouped_product_compiles_for_v5e_at_the_cells_shape(one_chip,
                                                             tile_m):
    """The expert layer's grouped product at SDAR's widths: 16 held
    experts of 2048 x 768 and back, the worst-case buffer of a step's
    16,384 positions (every one of their 8 choices held here); forward,
    and the backward's two kernels."""
    from learningorchestra_tpu.ops import grouped_matmul as gmm

    rows = 16384 * 8 + 16 * tile_m

    def loss(x, w_up, w_down, tile_group, n_active):
        with jax.named_scope("moe/experts"):  # as parallel/moe.py does
            h = gmm.grouped_matmul(x, w_up, tile_group, n_active,
                                   tile_m=tile_m, interpret=False)
            y = gmm.grouped_matmul(h, w_down, tile_group, n_active,
                                   tile_m=tile_m, interpret=False)
        return y.astype(jnp.float32).sum()

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sds((rows, 2048)), sds((16, 2048, 768)), sds((16, 768, 2048)),
        sds((rows // tile_m,), jnp.int32), sds((1,), jnp.int32)
    ).compile().as_text()
    assert {"moe_gmm_fwd", "moe_gmm_dx", "moe_gmm_dw"} \
        <= _custom_calls(text)


@pytest.mark.parametrize("experts,held,width,rows", [
    (128, 16, 768, 135168), (256, 32, 512, 139264)],
    ids=["sdar", "laguna"])
def test_expert_layer_gradient_compiles_for_v5e_with_the_chunked_passes(
        one_chip, monkeypatch, experts, held, width, rows):
    """One expert layer of the sdar cell (8 of 128 experts a token, 16
    held, of width 768) or of the Laguna cell (8 of 256, 32 held, of
    width 512) under per-block recomputation, forward and backward:
    16,384 positions of 2,048. The dispatch and ``silu x up``, and the
    combine's backward, are ``while`` loops over the used chunks of the
    row buffer of ``rows`` rows, around the products' kernels, each of
    which stays one call over the whole buffer (``moe_gmm_dw`` three
    times a layer: the benchmark counts its steps by that). What lands
    on the tokens is gathered by each copy's row, ``k`` rows a token:
    no scatter, and no array of a row for every copy."""
    from learningorchestra_tpu.ops import grouped_matmul as gmm
    from learningorchestra_tpu.ops import row_gather
    from learningorchestra_tpu.parallel import moe

    # the backend here is the CPU: the kernels are asked of Mosaic
    monkeypatch.setattr(gmm, "_auto_interpret", lambda: False)
    monkeypatch.setattr(row_gather, "_auto_interpret", lambda: False)
    t, k, d = 16384, 8, 2048

    def layer(p, x):
        return moe.moe_layer(p, x, k=k)[0]

    def loss(p, x):
        y = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.nothing_saveable)(p, x)
        return y.astype(jnp.float32).sum()

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {"gate": sds((d, experts), jnp.float32),
              "experts": {"w_gate": sds((held, d, width)),
                          "w_up": sds((held, d, width)),
                          "w_down": sds((held, width, d))}}
    assert moe.buffer_shape(t, k, experts, held)[1] == rows
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, sds((t, d))).compile().as_text()
    assert {"moe_gmm_fwd", "moe_gmm_dx", "moe_gmm_dw"} \
        <= _custom_calls(text)
    assert len(re.findall(r"%moe_gmm_dw[.\d]* = [^\n]*custom-call\(",
                          text)) == 3
    # forward, dispatch and ``silu x up``, both recomputed (the
    # combine's recomputation has no reader and is gone); backward,
    # those two and the combine's
    assert len(re.findall(r" while\(", text)) >= 7
    # the combine's sum of rows, and the dispatch's backward
    assert len(re.findall(r"%moe_rows_sum[.\d]* = [^\n]*custom-call\(",
                          text)) == 2
    assert " conditional(" not in text
    assert "scatter(" not in text
    assert not [i for i in _instructions(text) if _elements(i[1]) == t * k * d]
    # the loops update the row buffers in place (``gated``'s gradients
    # start as its inputs): no copy of a whole one
    assert not re.search(rf"= bf16\[{rows},\d+\]\{{[^}}]*\}} copy\(", text)


def test_ssd_kernels_compile_for_v5e_at_the_cells_shape(one_chip):
    """The state-space scan at granite-4.0-h-micro's widths: one row of
    8,192 positions, 64 heads of 64, a state of 128, chunks of 256;
    forward and backward, each kernel under its own name, the forward
    twice (with and without the chunks' starting states)."""
    from learningorchestra_tpu.ops import ssd

    def loss(x, dt, a, b, c, d):
        with jax.named_scope("ssm/scan"):  # as the model's module does
            y, state = ssd.ssd(x, dt, a, b, c, d, 256, impl="pallas",
                               interpret=False)
        return y.astype(jnp.float32).sum() + state.sum()

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((1, 8192, 64, 64)), sds((1, 8192, 64), jnp.float32),
            sds((64,), jnp.float32), sds((1, 8192, 128)),
            sds((1, 8192, 128)), sds((64,), jnp.float32))
    text = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)))).lower(
        *args).compile().as_text()
    calls = _custom_calls(text)
    assert {"ssd_fwd", "ssd_bwd"} <= calls, calls
    assert not any(c.startswith("ssm") for c in calls), calls
    # nothing chunk x chunk a head leaves the kernels: no array of
    # 32 chunks x 64 heads x 256 x 256 in the program
    assert not re.search(r"\[(1,)?(32,64|64,32),256,256\]", text)
    fwd_only = jax.jit(loss).lower(*args).compile().as_text()
    assert "ssd_fwd" in fwd_only and "ssd_bwd" not in fwd_only


def _instructions(text):
    """(name, result type, opcode, op_name) of each instruction line of
    a compiled module, fused computations' lines included."""
    line = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\("
                      r"(?:.*op_name=\"([^\"]*)\")?", re.M)
    return [m.groups("") for m in line.finditer(text)]


def _elements(shape):
    dims = re.match(r"\w+\[([\d,]*)\]", shape)
    n = 1
    for d in (dims.group(1).split(",") if dims and dims.group(1) else []):
        n *= int(d)
    return n


def test_mamba_mixer_step_leaves_no_copy_of_the_scans_arrays_for_v5e(
        one_chip, monkeypatch):
    """One ``_Mamba2`` mixer of the granite cell, forward and backward:
    one row of 8,192 positions, 64 heads of 64, a state of 128, chunks
    of 256, the kernels forced (``impl="auto"`` is ``jnp`` on the CPU
    backend even when compiling for the chip). The kernels read x and
    dy and write y and dx in the mixer's ``(b, s, heads x 64)`` layout,
    so XLA computes no array of x's 8192 x 4096 elements under
    ``ssm/scan``: no transpose to heads before positions, no layout
    copy, no float32 cast of x, no ``dt x``, no skip."""
    from learningorchestra_tpu.models import transformer as tlm
    from learningorchestra_tpu.ops import ssd

    monkeypatch.setattr(ssd, "resolve_impl", lambda impl="auto": "pallas")
    monkeypatch.setattr(ssd, "_auto_interpret", lambda: False)
    mixer = tlm._Mamba2(64, 64, 128, 4, 256)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    u = sds((1, 8192, 2048))
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: sds(a.shape, jnp.float32 if any(
            n in jax.tree_util.keystr(path) for n in tlm.FLOAT32_LEAVES)
            else jnp.bfloat16),
        jax.eval_shape(mixer.init, jax.random.PRNGKey(0), u))

    def loss(p, u_):
        out, stats = mixer.apply(p, u_)
        return out.astype(jnp.float32).sum() + stats.sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, u).compile().as_text()
    assert {"ssd_fwd", "ssd_bwd"} <= _custom_calls(text)
    scan = [i for i in _instructions(text) if "ssm/scan" in i[3]]
    assert scan
    made = [(name, shape, op) for name, shape, op, _ in scan
            if _elements(shape) == 8192 * 4096
            and op not in ("custom-call", "get-tuple-element", "bitcast")]
    assert not made, made
    assert not [i for i in scan if "[1,64,8192,64]" in i[1]
                or i[1].startswith("f32[1,8192,4096]")]


def test_flash_kernels_compile_for_v5e_at_head_64_with_a_given_scale(
        one_chip):
    """The hybrid's attention layer: one row of 8,192 positions, 32
    query heads on 8 KV heads of 64 (half a lane tile), the softmax's
    scale given as a number (1/64, not 1/sqrt(64)); forward and both
    backward kernels, each under its own name."""
    fn = functools.partial(attn.flash_attention, interpret=False,
                           causal=True, scale=0.015625)

    def loss(q, k, v):
        with jax.named_scope("attn"):
            return fn(q, k, v).astype(jnp.float32).sum()

    q, kv = (1, 8192, 32, 64), (1, 8192, 8, 64)
    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                          one_chip, q, kv, kv)
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} \
        <= _custom_calls(text)


def test_windowed_flash_kernels_carry_names_of_their_own_for_v5e(
        one_chip):
    """The Laguna cell's sliding layers: 2 rows of 8,192 positions, 64
    query heads on 8 KV heads of 128 under a window of 512, forward and
    both backward kernels as ``flash_win_*`` (the banded grid), where an
    unwindowed call keeps ``flash_*``; the full layers' group of 6 (48
    on 8) compiles too."""
    def loss_of(**kw):
        fn = functools.partial(attn.flash_attention, interpret=False,
                               causal=True, **kw)

        def loss(q, k, v):
            with jax.named_scope("attn"):  # as the model's module does
                return fn(q, k, v).astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))

    kv = (2, 8192, 8, 128)
    text = _compiled_text(loss_of(window=512), one_chip, (2, 8192, 64, 128),
                          kv, kv)
    names = _custom_calls(text)
    assert {"flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv"} \
        <= names
    assert not {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} & names
    full = _custom_calls(_compiled_text(loss_of(), one_chip,
                                        (2, 8192, 48, 128), kv, kv))
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} <= full
    assert not any(n.startswith("flash_win") for n in full)


def test_laguna_cells_training_step_compiles_for_v5e_and_fits(
        one_chip, monkeypatch):
    """The Laguna cell's whole step at its configuration: 2 rows of
    8,192 tokens, the model's loss and gradients under bf16 compute and
    AdamW's update, float32 state donated. The compiler's own account of
    the program's bytes (arguments, outputs not aliased, temporaries)
    stays under the chip's 16 GB with room for the runtime's own."""
    import json

    import optax

    from learningorchestra_tpu.models import LanguageModel
    from learningorchestra_tpu.models import transformer as tlm
    from learningorchestra_tpu.ops import grouped_matmul as gmm
    from learningorchestra_tpu.runtime import mesh as mesh_lib

    # the backend here is the CPU: the kernels are asked of Mosaic
    monkeypatch.setattr(attn, "_auto_interpret", lambda: False)
    monkeypatch.setattr(gmm, "_auto_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "laguna-xs.2-l5.json")) as f:
        lm_kwargs = json.load(f)["language_model"]
    lm = LanguageModel(**dict(lm_kwargs, attention="flash"))
    mesh = mesh_lib.build_mesh("auto", devices=list(one_chip.device_set))
    lm.set_mesh(mesh)
    module = lm._module_for(8192)
    loss_fn = tlm.next_token_loss(0.0, head_chunk=lm._head_chunk() or 1024,
                                  mesh=mesh, expert_layers=lm.expert_layers)
    params = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"]
    opt = optax.adamw(3e-4, weight_decay=1e-4)

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def step(p, o, x):
        def loss(p):
            low = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16), p)
            out = module.apply({"params": low}, x, train=True)
            return loss_fn(out, {"x": x}, None)[0]

        value, g = jax.value_and_grad(loss)(p)
        upd, o = opt.update(g, o, p)
        return optax.apply_updates(p, upd), o, value

    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(jax.eval_shape(opt.init, params)),
        jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)
    ).compile()
    names = _custom_calls(compiled.as_text())
    assert {"flash_fwd", "flash_win_fwd", "flash_win_bwd_dkv",
            "moe_gmm_fwd", "moe_gmm_dw"} <= names
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    # 691.6 M float32 parameters and two moments: 8.30 GB of arguments
    assert m.argument_size_in_bytes == pytest.approx(8.30e9, rel=0.01)
    assert held < 15.2e9, m
