"""Seeded weights of the ``granitemoehybrid`` tree (benchmark/weights.py's
rule for a tree it does not describe: Mamba-2 mixers beside attention
layers, one tied table and no head of its own).

A leaf depends only on the seed and on its path (``weights.make_leaf``'s
fold of the path's CRC); ``make_tree`` for the program and the
reference's ``make_leaf`` are the same leaf-at-a-time calls.

What was chosen beyond ``weights.py``'s rules, and why. The catalog's
config gives no range for ``dt_bias`` and ``A_log``, and fresh normal
numbers there would give a scan that forgets within a position or never
decays at all; a trained checkpoint of this family remembers over
hundreds to thousands of positions on some heads and a dozen on others:

* ``dt_bias`` (kind ``dt_bias``): the inverse softplus of a step drawn
  log-uniformly from 0.001 to 0.1, the Mamba-2 convention.
* the ``dt`` columns of ``in_proj`` (kind ``in_proj@<first dt column>``)
  are a quarter of a kernel's size, so that the data moves a head's step
  by a factor of about e^(+-0.25) around its bias and ``dt`` after the
  softplus stays inside 0.001 to 0.1 but for a tail: with whole-size
  columns a step's spread (e^(+-1)) swamped the heads' own.
* ``A_log`` (kind ``a_log``): the logarithm of a number drawn uniformly
  from 1 to 16, the Mamba-2 convention. With both, ``a_t = exp(-exp(A_log)
  dt_t)`` has a mean of 0.83 to 0.89 (counter ``ssmDecayMean_l<i>``: well
  inside 0 and 1) and a head's memory, 1 / (A dt), runs from under a
  position to 1,000: a fifth of the heads remember over more than 64
  positions and about 2 of the 64 over more than a chunk of 256. That
  is what the convention's ranges give. Those few heads hold most of
  the state: a state dropped at the chunk boundaries moves the RMS of
  what a layer holds at a row's END, 255 positions after the last
  boundary, by 0.136 to 0.209 of itself (my chip runs, PR 32, three
  seeds; a reckoning that took every head's input as stationary and of
  one size said under 0.4%, and was wrong), and the positions just
  behind a boundary, where every head has lost its memory, show it in
  the loss too (``loss_epoch0_rel`` 0.017 to 0.019).
* ``D`` is a norm's scale (1 + 0.1 normal), ``conv_kernel`` a kernel of
  fan-in 4, ``conv_bias`` 0.1 normal (not nought, so that a bias that is
  dropped shows).
* the tied table (kind ``table``) is normal(0, 0.1): times the
  ``embedding_multiplier`` of 12 the stream starts at an RMS of 1.2, of
  the size of what the twenty residual writes add to it. **What that
  makes of the loss** (my chip runs, PR 32): the stream still holds 12 e
  of the row's own token at the last layer, so the tied head scores that
  token at about 12 |e|^2 / (8 rms) = 20 where every other token's logit
  deviates by 0.6: at the seed's weights the model predicts its INPUT,
  the loss starts at 21.5 and AdamW's first three steps bring it to 9.5,
  log(12,544) = 9.44. A trained checkpoint has unlearned that; fresh
  weights of any tied model with a large embedding multiplier have it.
  The checked epochs therefore cross both regimes: epoch 0 a loss ruled
  by one large logit, which shows a residual write that is 0.12% short
  (the program's first twelve seeds read ``loss_epoch0_rel`` 6e-4 for a
  multiplier rounded to bf16; 3e-5 to 9e-5 since), epoch 1 the plain one.

Nothing else departs: kernels normal(0, 1/sqrt(fan_in)), norm scales 1 +
0.1 normal. The residual writes are already damped by the model's
``residual_multiplier`` of 0.22, and attention at a scale of 1/64 over
heads of 64 is soft, so the stack does not amplify a rounding the way
SDAR's did (``weights_sdar.py``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

from benchmark import weights

Path = Tuple[str, ...]

DT_RANGE = (1e-3, 1e-1)
A_RANGE = (1.0, 16.0)
DT_COLUMNS_SCALE = 0.25
TABLE_SCALE = 0.1


def sizes(lm: Dict[str, Any]) -> Dict[str, Any]:
    d = int(lm["d_model"])
    heads = int(lm["n_heads"])
    layers = int(lm["n_layers"])
    types = tuple(lm.get("layer_types") or ("attention",) * layers)
    ssm_heads, ssm_hd = int(lm["ssm_heads"]), int(lm["ssm_head_dim"])
    d_inner = ssm_heads * ssm_hd
    state = int(lm["ssm_state"])
    return {"d": d, "heads": heads,
            "kv": int(lm.get("n_kv_heads") or heads),
            "hd": int(lm.get("head_dim") or d // heads),
            "ff": int(lm["d_ff"]), "vocab": int(lm["vocab_size"]),
            "layers": layers, "types": types,
            "mamba_layers": tuple(i for i, t in enumerate(types)
                                  if t == "mamba"),
            "ssm_heads": ssm_heads, "ssm_hd": ssm_hd, "d_inner": d_inner,
            "state": state, "conv": int(lm["ssm_conv"]),
            "chunk": int(lm["ssm_chunk"]),
            "conv_dim": d_inner + 2 * state,
            "in_proj": 2 * d_inner + 2 * state + ssm_heads}


def leaf_table(lm: Dict[str, Any]) -> List[Tuple[Path, Tuple[int, ...], str]]:
    """(path, shape, kind) of every parameter of the hybrid
    ``LanguageModel`` that ``lm`` describes: a tied table, and in each
    layer a Mamba-2 mixer or GQA attention, then the gated MLP."""
    z = sizes(lm)
    d, ff = z["d"], z["ff"]
    if not lm.get("tie_embeddings"):
        raise ValueError("weights_granite describes a tied table")
    table: List[Tuple[Path, Tuple[int, ...], str]] = [
        (("embed", "embedding"), (z["vocab"], d), "table")]
    for i, kind in enumerate(z["types"]):
        layer = f"layer_{i}"
        if kind == "mamba":
            dt_first = z["in_proj"] - z["ssm_heads"]
            table += [
                ((layer, "ssm_norm", "scale"), (d,), "scale"),
                ((layer, "ssm", "in_proj", "kernel"), (d, z["in_proj"]),
                 f"in_proj@{dt_first}"),
                ((layer, "ssm", "conv_kernel"), (z["conv"], z["conv_dim"]),
                 "kernel"),
                ((layer, "ssm", "conv_bias"), (z["conv_dim"],), "bias"),
                ((layer, "ssm", "dt_bias"), (z["ssm_heads"],), "dt_bias"),
                ((layer, "ssm", "A_log"), (z["ssm_heads"],), "a_log"),
                ((layer, "ssm", "D"), (z["ssm_heads"],), "scale"),
                ((layer, "ssm", "norm", "scale"), (z["d_inner"],), "scale"),
                ((layer, "ssm", "out_proj", "kernel"), (z["d_inner"], d),
                 "kernel"),
            ]
        else:
            proj, kv = z["heads"] * z["hd"], z["kv"] * z["hd"]
            table += [
                ((layer, "attn_norm", "scale"), (d,), "scale"),
                ((layer, "attn", "q_proj", "kernel"), (d, proj), "kernel"),
                ((layer, "attn", "k_proj", "kernel"), (d, kv), "kernel"),
                ((layer, "attn", "v_proj", "kernel"), (d, kv), "kernel"),
                ((layer, "attn", "o_proj", "kernel"), (proj, d), "kernel"),
            ]
        table += [
            ((layer, "mlp_norm", "scale"), (d,), "scale"),
            ((layer, "mlp", "gate", "kernel"), (d, ff), "kernel"),
            ((layer, "mlp", "up_proj", "kernel"), (d, ff), "kernel"),
            ((layer, "mlp", "down_proj", "kernel"), (ff, d), "kernel"),
        ]
    table.append((("final_norm", "scale"), (d,), "scale"))
    return table


seed_key = weights.seed_key


def _uniform(key, path: Path, shape):
    """Uniform on (0, 1) from the same fold of the path that
    ``weights.make_leaf`` draws its normals from."""
    import jax
    import jax.numpy as jnp

    n = weights.make_leaf(key, path, shape, "embed")     # normal(0, 1)
    return 0.5 * (1.0 + jax.scipy.special.erf(n / jnp.sqrt(2.0)))


def make_leaf(key, path: Path, shape: Tuple[int, ...], kind: str):
    """One parameter, float32, from the seed's key and its path."""
    import jax.numpy as jnp

    if kind == "table":
        return TABLE_SCALE * weights.make_leaf(key, path, shape, "embed")
    if kind == "bias":
        return 0.1 * weights.make_leaf(key, path, shape, "embed")
    if kind == "dt_bias":
        lo, hi = (math.log(v) for v in DT_RANGE)
        dt = jnp.exp(lo + (hi - lo) * _uniform(key, path, shape))
        return dt + jnp.log(-jnp.expm1(-dt))     # softplus^-1(dt)
    if kind == "a_log":
        lo, hi = A_RANGE
        return jnp.log(lo + (hi - lo) * _uniform(key, path, shape))
    if kind.startswith("in_proj@"):
        w = weights.make_leaf(key, path, shape, "kernel")
        return jnp.where(jnp.arange(shape[1]) >= int(kind[8:]),
                         DT_COLUMNS_SCALE, 1.0) * w
    return weights.make_leaf(key, path, shape, kind)


def make_tree(seed: int, lm: Dict[str, Any]) -> Dict[str, Any]:
    """The whole nested parameter tree, made on the device a leaf at a
    time, as the reference makes it (``weights_sdar.make_tree``'s
    reason: a leaf's few small programs are shared by every leaf of its
    shape)."""
    key = seed_key(seed)
    tree: Dict[str, Any] = {}
    for path, shape, kind in leaf_table(lm):
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = make_leaf(key, path, shape, kind)
    return tree
