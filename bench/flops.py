"""Peaks of the chip and the operations a model step needs, kept with
the benchmark so that no change to the program can move them."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; a kind that is not
    in `peaks.json` is an error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name} (known: {sorted(table)})")
    return table[device_kind]


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product for every token of a
    dense decoder with grouped-query attention and a gated MLP: the q,
    k, v and output projections, the three MLP matrices, and the LM head
    (the tied embedding counts once, as the head)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    layer = d * q + 2 * d * kv + q * d + 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward operations per trained token, no recompute:
    6 per matmul weight, plus the attention score and value products,
    2 x 2 x seq x (heads x head_dim) a layer forward and twice that
    backward (the PaLM count, causal mask not discounted)."""
    attn = 12 * cfg["num_hidden_layers"] * seq_len * (
        cfg["num_attention_heads"] * cfg["head_dim"])
    return 6.0 * matmul_params(cfg) + attn
