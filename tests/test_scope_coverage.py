"""Every op of the expert layer, of the Mamba-2 conv and of the head loss
carries its ``jax.named_scope`` in the compiled training step: the
``op_name`` a device trace shows for it, in the forward, the
recomputation and the backward (a ``custom_vjp``'s backward rule
included). An op is tied to its source by the HLO metadata's stack
frame; the scalar computations that a reduce, a sort or a scatter
applies (``to_apply``) are not ops of their own on the device.

The steps are the block-diffusion expert cell's and the hybrid
state-space cell's CPU rehearsal sizes (``benchmark/traffic/``). A
jitted helper of ``jax.numpy`` (``//`` is ``jit(floor_divide)``) that
two layers call on the same shapes is lowered once and carries the
scope of the call lowered first; which one that is depends on what the
process traced before, so the caches are cleared first."""

import inspect
import json
import os
import re
from typing import List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXPERT_FILES = ("parallel/moe.py", "ops/grouped_matmul.py",
                "ops/row_gather.py")
HEAD_LOSS = ("_fused_head_loss", "_head_targets", "_head_chunk_sums",
             "_head_chunk_scan", "_head_chunk_scan_fwd",
             "_head_chunk_scan_bwd")


class Op(NamedTuple):
    file: str
    function: str
    line: int
    op_name: str


def compiled_step(traffic: str) -> str:
    from learningorchestra_tpu.models import LanguageModel

    with open(os.path.join(ROOT, "benchmark", "traffic",
                           traffic + ".json")) as f:
        sizes = json.load(f)["rehearsal"]
    jax.clear_caches()
    lm = LanguageModel(**sizes["language_model"])
    rows = np.ones((sizes["batch_size"], sizes["seq"]), np.int32)
    lm._build_params(rows[:, :8])
    engine = lm._get_engine()
    state = engine.init_state(lm.params)
    return jax.jit(engine._train_step_body).lower(
        state, {"x": jax.ShapeDtypeStruct(rows.shape, jnp.int32)},
        jax.random.PRNGKey(0)).compile().as_text()


def ops_of(hlo: str) -> List[Op]:
    """Each instruction's innermost source frame and ``op_name``, but
    those of the scalar computations other instructions apply."""
    tables = {"FileNames": {}, "FunctionNames": {}, "FileLocations": {},
              "StackFrames": {}}
    section = None
    for line in hlo.splitlines():
        if line in tables:
            section = tables[line]
        elif not line.strip():
            section = None
        elif section is not None:
            key, _, rest = line.partition(" ")
            section[int(key)] = rest
    files = {k: v.strip('"') for k, v in tables["FileNames"].items()}
    funcs = {k: v.strip('"') for k, v in tables["FunctionNames"].items()}

    def field(text, name):
        return int(re.search(rf"\b{name}=(\d+)", text).group(1))

    def source(frame: int):
        loc = tables["FileLocations"][
            field(tables["StackFrames"][frame], "file_location_id")]
        return (files[field(loc, "file_name_id")],
                funcs[field(loc, "function_name_id")], field(loc, "line"))

    applied = {m.group(2) for m in re.finditer(
        r" (\S+?)\(.*to_apply=%?([\w.\-]+)", hlo) if m.group(1) != "call"}
    out, computation = [], None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            computation = head.group(2)
            continue
        m = re.search(r'op_name="([^"]*)"[^}]*stack_frame_id=(\d+)', line)
        if m and computation not in applied:
            out.append(Op(*source(int(m.group(2))), m.group(1)))
    return out


@pytest.fixture(scope="module")
def expert_step():
    return ops_of(compiled_step("train-bd4-seq4k"))


@pytest.fixture(scope="module")
def hybrid_step():
    return ops_of(compiled_step("train-ssm-seq8k"))


def test_every_op_of_the_expert_layer_is_under_moe(expert_step):
    mine = [op for op in expert_step if op.file.endswith(EXPERT_FILES)]
    assert {f for f in EXPERT_FILES
            if any(op.file.endswith(f) for op in mine)} == set(EXPERT_FILES)
    assert any("transpose(" in op.op_name for op in mine)   # the backward
    assert any("rematted_computation" in op.op_name for op in mine)
    assert [op for op in mine if "moe/" not in op.op_name] == []


def _conv_lines():
    """The lines of ``_Mamba2``'s ``ssm/conv`` block."""
    from learningorchestra_tpu.models.transformer import _Mamba2

    lines, first = inspect.getsourcelines(_Mamba2.__call__)
    at = next(i for i, s in enumerate(lines)
              if 'named_scope("ssm/conv")' in s)
    indent = len(lines[at]) - len(lines[at].lstrip())
    end = next(i for i in range(at + 1, len(lines))
               if lines[i].strip()
               and len(lines[i]) - len(lines[i].lstrip()) <= indent)
    return range(first + at + 1, first + end)


def test_the_conv_is_under_ssm_conv(hybrid_step):
    lines = _conv_lines()
    conv = [op for op in hybrid_step if op.function == "_Mamba2.__call__"
            and op.line in lines]
    assert any("transpose(" in op.op_name for op in conv)
    assert [op for op in conv if "ssm/conv" not in op.op_name] == []


@pytest.mark.parametrize("step", ["expert_step", "hybrid_step"])
def test_the_head_loss_is_under_head_loss(step, request):
    head = [op for op in request.getfixturevalue(step)
            if op.function in HEAD_LOSS]
    assert {op.function for op in head} >= {
        "_head_chunk_sums", "_head_chunk_scan_fwd", "_fused_head_loss"}
    assert [op for op in head if "head_loss" not in op.op_name] == []
