"""Operations and bytes the ALGORITHM needs, from shapes alone.

Everything here is computed from the configuration's sizes at the
precision the configuration states, whatever the program does to
implement it: recomputation, padding, upcasts and extra passes over a
cache count for nothing. The yardstick stays here, under the
benchmark's own paths, where a PR that claims a gain cannot move it.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Tuple

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def peaks_for(device_kind: str) -> Dict[str, float]:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device_kind {device_kind!r}; "
                       f"known: {[k for k in table if k[0] != '_']}")
    return table[device_kind]


def dtype_bytes(config: Dict[str, Any]) -> int:
    return _DTYPE_BYTES[config["torch_dtype"]]


def sizes(lm: Dict[str, Any]) -> Dict[str, int]:
    d = int(lm["d_model"])
    heads = int(lm["n_heads"])
    kv = int(lm.get("n_kv_heads") or heads)
    hd = d // heads
    return {"d": d, "heads": heads, "kv": kv, "hd": hd,
            "ff": int(lm["d_ff"]), "vocab": int(lm["vocab_size"]),
            "layers": int(lm["n_layers"]),
            "window": int(lm.get("sliding_window") or 0)}


def param_counts(lm: Dict[str, Any]) -> Dict[str, int]:
    """Parameters by role. ``matmul`` is what a token is multiplied
    by (every kernel and the head); the embedding is a lookup."""
    z = sizes(lm)
    attn = z["d"] * z["heads"] * z["hd"] * 2 + z["d"] * z["kv"] * z["hd"] * 2
    mlp = 3 * z["d"] * z["ff"]
    norms = 2 * z["d"]
    head = z["d"] * z["vocab"]
    embed = z["vocab"] * z["d"]
    matmul = z["layers"] * (attn + mlp) + head
    return {"layer_matmul": attn + mlp, "head": head, "embed": embed,
            "matmul": matmul,
            "total": matmul + embed + z["layers"] * norms + z["d"]}


def keys_seen(start: int, stop: int, window: int) -> float:
    """Sum over positions start..stop-1 of the keys each attends to."""
    def tri(n):  # keys seen by positions 0..n-1 with no window
        return n * (n + 1) / 2.0
    if window <= 0:
        return tri(stop) - tri(start)
    def upto(n):  # positions 0..n-1, banded
        return tri(n) if n <= window else tri(window) + (n - window) * window
    return upto(stop) - upto(start)


def forward_flops_span(lm: Dict[str, Any], start: int, stop: int) -> float:
    """Forward operations of the tokens at positions start..stop-1."""
    z = sizes(lm)
    n = max(0, stop - start)
    if n == 0:
        return 0.0
    return (2.0 * param_counts(lm)["matmul"] * n
            + 4.0 * z["layers"] * z["heads"] * z["hd"]
            * keys_seen(start, stop, z["window"]))


def train_flops_per_token(lm: Dict[str, Any], seq: int) -> float:
    """Model FLOPs of one trained token in a row of ``seq`` tokens:
    forward plus twice that for the backward pass (6 per matmul
    parameter and the causal attention term). Recomputation does not
    count."""
    return 3.0 * forward_flops_span(lm, 0, seq) / seq


def flash_forward(lm: Dict[str, Any], config: Dict[str, Any], batch: int,
                  seq: int) -> Tuple[float, float]:
    """(operations, bytes) of ONE layer's causal attention forward over
    ``batch`` rows of ``seq``: QK^T and PV over the visible band; q, k,
    v read and o written once."""
    z = sizes(lm)
    keys = keys_seen(0, seq, z["window"])
    ops = 4.0 * batch * z["heads"] * z["hd"] * keys
    byt = batch * seq * z["hd"] * dtype_bytes(config) * (
        2 * z["heads"] + 2 * z["kv"])
    return ops, float(byt)


def flash_backward(lm: Dict[str, Any], config: Dict[str, Any], batch: int,
                   seq: int) -> Tuple[float, float]:
    """(operations, bytes) of one layer's attention backward: the five
    products the algorithm needs (S again, dP, dV, dQ, dK) are 2.5
    forwards; q, k, v, o, do read and dq, dk, dv written once."""
    z = sizes(lm)
    ops, _ = flash_forward(lm, config, batch, seq)
    byt = batch * seq * z["hd"] * dtype_bytes(config) * (
        4 * z["heads"] + 4 * z["kv"])
    return 2.5 * ops, float(byt)


def roofline_seconds(ops: float, byt: float, peaks: Dict[str, float],
                     ) -> Tuple[float, str]:
    """The least time the chip could take, and which bound holds."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = byt / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
