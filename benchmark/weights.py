"""Weights from ``--seed``: the benchmark's own input, not the program's.

The program and the plain reference are given the same numbers, each
through its own call: the program gets the whole tree from ONE jitted
call on the device (``make_tree``), the reference regenerates leaves
one at a time (``make_leaf``) so that it never holds more than a
layer. A leaf depends only on the seed and on its path, so either side
can make any leaf without the other.

Distributions: kernels normal(0, 1/sqrt(fan_in)) (the variance of the
lecun-normal a fresh ``LanguageModel`` draws), the embedding
normal(0, 1), norm scales 1 + 0.1 normal (not all ones, so a scale
that is dropped shows).
"""

from __future__ import annotations

import zlib
from typing import Any, Dict, List, Tuple

Path = Tuple[str, ...]


def leaf_table(lm: Dict[str, Any]) -> List[Tuple[Path, Tuple[int, ...], str]]:
    """(path, shape, kind) of every parameter of the dense decoder the
    ``LanguageModel`` keyword arguments ``lm`` describe. Kinds:
    ``embed``, ``scale``, ``kernel``."""
    d = int(lm["d_model"])
    heads = int(lm["n_heads"])
    kv = int(lm.get("n_kv_heads") or heads)
    hd = d // heads
    ff = int(lm["d_ff"])
    vocab = int(lm["vocab_size"])
    table: List[Tuple[Path, Tuple[int, ...], str]] = [
        (("embed", "embedding"), (vocab, d), "embed")]
    for i in range(int(lm["n_layers"])):
        layer = f"layer_{i}"
        table += [
            ((layer, "attn_norm", "scale"), (d,), "scale"),
            ((layer, "attn", "q_proj", "kernel"), (d, heads * hd), "kernel"),
            ((layer, "attn", "k_proj", "kernel"), (d, kv * hd), "kernel"),
            ((layer, "attn", "v_proj", "kernel"), (d, kv * hd), "kernel"),
            ((layer, "attn", "o_proj", "kernel"), (heads * hd, d), "kernel"),
            ((layer, "mlp_norm", "scale"), (d,), "scale"),
            ((layer, "mlp", "gate", "kernel"), (d, ff), "kernel"),
            ((layer, "mlp", "up_proj", "kernel"), (d, ff), "kernel"),
            ((layer, "mlp", "down_proj", "kernel"), (ff, d), "kernel"),
        ]
    table += [(("final_norm", "scale"), (d,), "scale"),
              (("lm_head", "kernel"), (d, vocab), "kernel")]
    return table


def seed_key(seed: int):
    """A jax key from any whole number the driver may pass (its seeds
    pass 2**31, which a 32-bit key constructor refuses)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make_leaf(key, path: Path, shape: Tuple[int, ...], kind: str):
    """One parameter, float32, from the seed's key and its path."""
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
    n = jax.random.normal(k, shape, jnp.float32)
    if kind == "scale":
        return 1.0 + 0.1 * n
    if kind == "embed":
        return n
    return n * (1.0 / float(shape[0]) ** 0.5)


def make_tree(seed: int, lm: Dict[str, Any]) -> Dict[str, Any]:
    """The whole nested parameter tree, made on the device by one
    jitted call."""
    import jax

    table = leaf_table(lm)

    @jax.jit
    def build(key):
        tree: Dict[str, Any] = {}
        for path, shape, kind in table:
            node = tree
            for part in path[:-1]:
                node = node.setdefault(part, {})
            node[path[-1]] = make_leaf(key, path, shape, kind)
        return tree

    return build(seed_key(seed))
