"""Seeded weights of the ``sdar_moe`` tree (benchmark/weights.py's rule
for a tree it does not describe: QK-norm scales, a router, stacked
gated experts, a head width of its own).

A leaf depends only on the seed and on its path (``weights.make_leaf``'s
fold of the path's CRC); ``make_tree`` for the program and the reference's
``make_leaf`` are the same leaf-at-a-time calls.

Three rules differ from ``weights.py``'s, each for a property that a
trained checkpoint of this family has and fresh normal weights lack. A
block-diffusion row is masked at a quarter of its positions, all with
ONE embedding (``MASK``), so under ``weights.py``'s rule they all met
the same eight experts: the busiest held expert took 5.8 times the mean,
a step's cost followed the seed, and thousands of identical positions
flipped together on one near-tie of the router (PERF.md, PR 26).

* Attention is selective: the QK-norm scales are 2.5 (1 + 0.1 normal),
  scores deviate by about 6, a query picks few keys, and a masked
  position carries its own context.
* What a block writes to the residual stream is small: ``o_proj`` and
  the experts' ``w_down`` are scaled by 1/sqrt(2 * 48) (the published
  depth; the GPT-2 rule for the projections that write to the stream).
  Selective attention amplifies an error in its queries sixfold; with
  writes of the embedding's size the stack was chaotic (a bf16 rounding
  of 1% in the first layer was 53% in the sixth, so no comparison could
  tell bf16 from fp8); with small writes an error stays what it was.
* The expert branch does not see ``MASK``: its embedding lies in the
  last sixteenth of the channels alone (at the norm of any other row),
  and ``mlp_norm.scale`` is zero there. The router and the experts see
  of a masked position what attention brought it, so the masked
  positions spread evenly (busiest held expert 1.2 to 1.6 times the
  mean) and stay spread: with ``MASK`` in the router's input, AdamW's
  sign-like steps move every column of ``gate`` along that one
  direction, 0.5 of a logit a step where the 8th and 9th expert lie
  0.04 apart, and a quarter of the row changes experts at once.
  ``attn_norm`` sees every channel, so a masked query is a stable one.

A kind beyond ``weights.py``'s: ``experts@<offset>``, a stack ``(held,
fan_in, fan_out)`` of kernels, each normal(0, 1/sqrt(fan_in)). Expert
``j`` of the stack is the model's expert ``offset + j`` and its numbers
depend on that id alone, so a share's stack is a slice of the uncut
model's (the test that the shares add up rests on it).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from benchmark import weights

Path = Tuple[str, ...]


def sizes(lm: Dict[str, Any]) -> Dict[str, int]:
    d = int(lm["d_model"])
    heads = int(lm["n_heads"])
    experts = int(lm["n_experts"])
    return {"d": d, "heads": heads,
            "kv": int(lm.get("n_kv_heads") or heads),
            "hd": int(lm.get("head_dim") or d // heads),
            "ff": int(lm["d_ff"]), "vocab": int(lm["vocab_size"]),
            "layers": int(lm["n_layers"]), "experts": experts,
            "held": int(lm.get("experts_held") or experts),
            "offset": int(lm.get("expert_offset") or 0),
            "k": int(lm["moe_k"])}


def leaf_table(lm: Dict[str, Any]) -> List[Tuple[Path, Tuple[int, ...], str]]:
    """(path, shape, kind) of every parameter of the ``LanguageModel``
    that ``lm`` describes: QK-normed GQA attention and a gated expert
    layer in every block."""
    z = sizes(lm)
    d, hd, ff, held = z["d"], z["hd"], z["ff"], z["held"]
    experts = f"experts@{z['offset']}"
    mask_id = lm.get("mask_token_id")
    mask_id = z["vocab"] - 1 if mask_id is None else int(mask_id)
    table: List[Tuple[Path, Tuple[int, ...], str]] = [
        (("embed", "embedding"), (z["vocab"], d), f"embed@{mask_id}")]
    for i in range(z["layers"]):
        layer = f"layer_{i}"
        table += [
            ((layer, "attn_norm", "scale"), (d,), "scale"),
            ((layer, "attn", "q_proj", "kernel"), (d, z["heads"] * hd),
             "kernel"),
            ((layer, "attn", "k_proj", "kernel"), (d, z["kv"] * hd),
             "kernel"),
            ((layer, "attn", "v_proj", "kernel"), (d, z["kv"] * hd),
             "kernel"),
            ((layer, "attn", "q_norm", "scale"), (hd,), "qk_scale"),
            ((layer, "attn", "k_norm", "scale"), (hd,), "qk_scale"),
            ((layer, "attn", "o_proj", "kernel"), (z["heads"] * hd, d),
             "write"),
            ((layer, "mlp_norm", "scale"), (d,), "scale_blind"),
            ((layer, "moe", "gate"), (d, z["experts"]), "kernel"),
            ((layer, "moe", "experts", "w_gate"), (held, d, ff), experts),
            ((layer, "moe", "experts", "w_up"), (held, d, ff), experts),
            ((layer, "moe", "experts", "w_down"), (held, ff, d),
             experts + "/write"),
        ]
    table += [(("final_norm", "scale"), (d,), "scale"),
              (("lm_head", "kernel"), (d, z["vocab"]), "kernel")]
    return table


seed_key = weights.seed_key


WRITE_SCALE = (2 * 48) ** -0.5   # the published depth: two writes a layer


def mask_channels(d: int):
    """(d,) bool: the channels that hold ``MASK``'s embedding."""
    import jax.numpy as jnp

    return jnp.arange(d) >= d - max(1, d // 16)


def make_leaf(key, path: Path, shape: Tuple[int, ...], kind: str):
    """One parameter, float32, from the seed's key and its path."""
    import jax
    import jax.numpy as jnp

    kind, write, _ = kind.partition("/write")
    scale = WRITE_SCALE if write or kind == "write" else 1.0
    if kind == "qk_scale":   # 2.5 (1 + 0.1 normal)
        return 2.5 * weights.make_leaf(key, path, shape, "scale")
    if kind.startswith("embed@"):   # MASK's row in its own channels
        n = weights.make_leaf(key, path, shape, "embed")
        mine = mask_channels(shape[1])
        row = jnp.where(mine, n[int(kind[6:])], 0.0) \
            * (shape[1] / jnp.sum(mine)) ** 0.5
        return n.at[int(kind[6:])].set(row)
    if kind == "scale_blind":
        return jnp.where(mask_channels(shape[0]), 0.0,
                         weights.make_leaf(key, path, shape, "scale"))
    if kind.startswith("experts@"):
        first = int(kind[8:])
        return scale * jnp.stack([
            weights.make_leaf(jax.random.fold_in(key, first + j), path,
                              shape[1:], "kernel")
            for j in range(shape[0])])
    return scale * weights.make_leaf(
        key, path, shape, "kernel" if kind == "write" else kind)


def make_tree(seed: int, lm: Dict[str, Any]) -> Dict[str, Any]:
    """The whole nested parameter tree, made on the device a leaf at a
    time, as the reference makes it. ``weights.make_tree``'s one jitted
    call took the chip's compiler 110 s for this tree (a random draw
    for each of 18 x 16 experts in one program); a leaf's few small
    programs are shared by every leaf of its shape."""
    key = seed_key(seed)
    tree: Dict[str, Any] = {}
    for path, shape, kind in leaf_table(lm):
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = make_leaf(key, path, shape, kind)
    return tree
