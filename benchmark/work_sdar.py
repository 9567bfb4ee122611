"""Operations and bytes the ALGORITHM of a block-diffusion training step
of the ``sdar_moe`` block needs, from shapes alone (``work.py``'s rule:
recomputation, padding, upcasts and extra passes count for nothing).

Counted, for ``batch`` rows of ``seq`` tokens (2 * seq positions a row
go through the layers: the noisy copy and the clean row):

- the layer products (q, k, v, o and the router) over all positions;
- the held experts' three products over the EXPECTED routed copies,
  ``positions * k * held / experts`` (a uniform router);
- attention over exactly the keys the mask shows: a noisy query sees
  its block and the clean blocks before it, a clean query its block
  and those before: ``seq * block + seq**2`` pairs a row and head;
- the head over the EXPECTED masked positions, ``seq * (0.1 + 1) / 2``.

Left out: norms, RoPE, softmaxes, the router's top-k, the sort, gather
and scatter of the expert layer (bytes and latency, no product), the
embedding lookup, the noise, AdamW.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import weights_sdar, work

T_LOW = 0.1   # t ~ U(T_LOW, 1): the expected masked share is its mean


def sizes(lm: Dict[str, Any]) -> Dict[str, int]:
    return dict(weights_sdar.sizes(lm),
                block=int(lm.get("block_length") or 4))


def param_counts(lm: Dict[str, Any]) -> Dict[str, int]:
    z = sizes(lm)
    attn = 2 * z["d"] * z["heads"] * z["hd"] + 2 * z["d"] * z["kv"] * z["hd"]
    router = z["d"] * z["experts"]
    expert = 3 * z["d"] * z["ff"]
    norms = 2 * z["d"] + 2 * z["hd"]
    layer = attn + router + z["held"] * expert + norms
    head = z["d"] * z["vocab"]
    return {"attention": attn, "router": router, "expert": expert,
            "layer": layer, "head": head, "embed": head,
            "total": z["layers"] * layer + 2 * head + z["d"]}


def keys_seen_bd(seq: int, block: int) -> float:
    """Pairs (query, visible key) of one row [noisy ; clean] and head:
    noisy queries ``seq * block`` (own block) plus ``block**2 * nb * (nb
    - 1) / 2``; clean ones ``block**2 * nb * (nb + 1) / 2``."""
    nb = seq // block
    return float(seq * block + block * block * nb * nb)


def expected_copies(lm: Dict[str, Any], batch: int, seq: int) -> float:
    z = sizes(lm)
    return 2.0 * batch * seq * z["k"] * z["held"] / z["experts"]


def expected_masked(batch: int, seq: int) -> float:
    return batch * seq * (T_LOW + 1.0) / 2.0


def train_flops_per_step(lm: Dict[str, Any], batch: int, seq: int,
                         ) -> Dict[str, float]:
    """Model FLOPs of one step by part, forward plus twice that for
    the backward pass, and their ``total``."""
    z = sizes(lm)
    p = param_counts(lm)
    positions = 2.0 * batch * seq
    parts = {
        "layer_products": 6.0 * z["layers"] * (p["attention"] + p["router"])
        * positions,
        "experts": 6.0 * z["layers"] * p["expert"]
        * expected_copies(lm, batch, seq),
        "attention": 3.0 * z["layers"] * flash_bd_forward(
            lm, {"torch_dtype": "bfloat16"}, batch, seq)[0],
        "head": 6.0 * p["head"] * expected_masked(batch, seq),
    }
    parts["total"] = sum(parts.values())
    return parts


def flash_bd_forward(lm: Dict[str, Any], config: Dict[str, Any],
                     batch: int, seq: int) -> Tuple[float, float]:
    """(operations, bytes) of ONE layer's attention forward under the
    block-diffusion mask: QK^T and PV over the visible pairs; q and o of
    both halves, k and v of both halves, each once."""
    z = sizes(lm)
    ops = 4.0 * batch * z["heads"] * z["hd"] * keys_seen_bd(seq, z["block"])
    byt = 2 * batch * seq * z["hd"] * work.dtype_bytes(config) * (
        2 * z["heads"] + 2 * z["kv"])
    return ops, float(byt)


def flash_bd_backward(lm, config, batch: int, seq: int,
                      ) -> Tuple[float, float]:
    """As ``work.flash_backward``: five products are 2.5 forwards; q,
    k, v, o, do read and dq, dk, dv written once."""
    ops, byt = flash_bd_forward(lm, config, batch, seq)
    return 2.5 * ops, 2.0 * byt


def moe_gmm(lm: Dict[str, Any], config: Dict[str, Any], batch: int,
            seq: int) -> Dict[str, Tuple[float, float]]:
    """(operations, bytes) of ONE layer's grouped products over the
    expected copies: ``forward`` (gate, up, down) and ``backward`` (the
    same three, each towards its input and towards its matrix). A
    product reads its rows and the held matrices once and writes its
    result once."""
    z = sizes(lm)
    n = expected_copies(lm, batch, seq)
    b = work.dtype_bytes(config)
    one = 2.0 * n * z["d"] * z["ff"]
    matrix = z["held"] * z["d"] * z["ff"] * b
    rows_d, rows_ff = n * z["d"] * b, n * z["ff"] * b
    fwd_bytes = 2 * (rows_d + matrix + rows_ff) + (rows_ff + matrix + rows_d)
    # towards the input: dy and the matrix read, dx written; towards the
    # matrix: x and dy read, dw written (float32 accumulate, stored as b)
    bwd_bytes = 2 * fwd_bytes
    return {"forward": (3 * one, float(fwd_bytes)),
            "backward": (6 * one, float(bwd_bytes))}
