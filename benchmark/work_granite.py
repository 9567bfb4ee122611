"""Operations and bytes the ALGORITHM of a next-token training step of
the ``granitemoehybrid`` stack needs, from shapes alone (``work.py``'s
rule: recomputation, padding, upcasts and extra passes count for
nothing).

Counted, for ``batch`` rows of ``seq`` tokens:

- every kernel a token is multiplied by: the gated MLP of every layer,
  a Mamba-2 layer's ``in_proj`` and ``out_proj``, the attention layer's
  four projections, the tied table as the head (6 operations a
  parameter and token, forward and backward);
- causal attention over the keys each position sees (``work.py``'s
  term) in the attention layers;
- the state-space scan in its chunked form at the configuration's
  chunk ``Q``, whatever implements it: a chunk and row takes ``C B^T``
  once (the heads share ``B`` and ``C``: ``2 Q^2 N``) and, a head, the
  masked ``Q x Q`` product with ``dt x`` (``2 Q^2 P``), the carried
  state's part of the output (``2 Q N P``) and the state's update (``2
  Q P N``); the backward twice that. The masked product is counted as
  the whole square: a chunk is the unit the matrix unit works on.

Left out: norms, the conv (4 multiply-adds a channel), softplus, gates,
exponentials and cumulative sums of the decays, the softmax, the
embedding lookup, AdamW.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import weights_granite, work

sizes = weights_granite.sizes


def param_counts(lm: Dict[str, Any]) -> Dict[str, int]:
    z = sizes(lm)
    d = z["d"]
    mlp = 3 * d * z["ff"]
    mamba_proj = d * z["in_proj"] + z["d_inner"] * d
    mamba_small = (z["conv"] * z["conv_dim"] + z["conv_dim"]
                   + 3 * z["ssm_heads"] + z["d_inner"])
    attn_proj = 2 * d * z["heads"] * z["hd"] + 2 * d * z["kv"] * z["hd"]
    norms = 2 * d
    mamba_layer = mamba_proj + mamba_small + norms + mlp
    attn_layer = attn_proj + norms + mlp
    n_mamba = len(z["mamba_layers"])
    n_attn = z["layers"] - n_mamba
    table = z["vocab"] * d
    return {"mlp": mlp, "mamba_proj": mamba_proj, "attn_proj": attn_proj,
            "mamba_layer": mamba_layer, "attn_layer": attn_layer,
            "table": table, "n_mamba": n_mamba, "n_attn": n_attn,
            "total": n_mamba * mamba_layer + n_attn * attn_layer
            + table + d}


def ssd_forward(lm: Dict[str, Any], config: Dict[str, Any], batch: int,
                seq: int) -> Tuple[float, float]:
    """(operations, bytes) of ONE Mamba-2 layer's scan forward over
    ``batch`` rows of ``seq``: x, B, C read and y written once at the
    stated precision, dt (a float32 a head and position) read once."""
    z = sizes(lm)
    q, n, p, heads = z["chunk"], z["state"], z["ssm_hd"], z["ssm_heads"]
    chunks = batch * -(-seq // q)
    ops = chunks * (2.0 * q * q * n
                    + heads * (2.0 * q * q * p + 4.0 * q * n * p))
    b = work.dtype_bytes(config)
    byt = batch * seq * (b * (2 * z["d_inner"] + 2 * n) + 4 * heads)
    return ops, float(byt)


def ssd_backward(lm, config, batch: int, seq: int) -> Tuple[float, float]:
    """Twice the forward's products (each towards both of its
    operands); x, dt, B, C and dy read, dx, ddt, dB, dC written once."""
    ops, _ = ssd_forward(lm, config, batch, seq)
    z = sizes(lm)
    b = work.dtype_bytes(config)
    byt = batch * seq * (b * (4 * z["d_inner"] + 4 * z["state"])
                         + 8 * z["ssm_heads"])
    return 2.0 * ops, float(byt)


def attention_lm(lm: Dict[str, Any]) -> Dict[str, Any]:
    """``lm`` as ``work.py``'s flash counts read it: one attention
    layer, no window; ``work.sizes`` takes the head's width as d_model
    over the heads, which is this model's (2048 / 32 = 64)."""
    z = sizes(lm)
    if z["hd"] * z["heads"] != z["d"]:
        raise ValueError("work.py's flash count takes head_dim as "
                         "d_model / n_heads")
    return dict(lm, n_layers=1, sliding_window=0)


def train_flops_per_step(lm: Dict[str, Any], batch: int, seq: int,
                         ) -> Dict[str, float]:
    """Model FLOPs of one step by part, forward plus twice that for the
    backward pass, and their ``total``."""
    z = sizes(lm)
    p = param_counts(lm)
    tokens = float(batch * seq)
    bf16 = {"torch_dtype": "bfloat16"}
    parts = {
        "mlp": 6.0 * z["layers"] * p["mlp"] * tokens,
        "mamba_proj": 6.0 * p["n_mamba"] * p["mamba_proj"] * tokens,
        "scan": 3.0 * p["n_mamba"] * ssd_forward(lm, bf16, batch, seq)[0],
        "attention": 6.0 * p["n_attn"] * p["attn_proj"] * tokens
        + 3.0 * p["n_attn"] * work.flash_forward(
            attention_lm(lm), bf16, batch, seq)[0],
        # position p predicts token p + 1: seq - 1 targets a row
        "head": 6.0 * p["table"] * batch * (seq - 1),
    }
    parts["total"] = sum(parts.values())
    return parts
