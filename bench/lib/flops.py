"""Sizes of a decoder configuration that the metrics count with."""
from __future__ import annotations

from bench.reference.weights import dims, shapes


def n_params(cfg: dict) -> int:
    """Every parameter, the tied head counted once."""
    total = 0
    for shape in shapes(cfg).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product per token: the
    projections of every layer and the (tied) head; the embedding lookup,
    norms and biases are not products."""
    m = dims(cfg)
    q, kv = m["h"] * m["hd"], m["hk"] * m["hd"]
    per_layer = 2 * m["d"] * q + 2 * m["d"] * kv + 3 * m["d"] * m["f"]
    return m["L"] * per_layer + m["v"] * m["d"]


def attn_flops_per_key(cfg: dict) -> int:
    """Forward FLOPs of one query against one key, all layers: the score
    and the weighted value, 2 * 2 * heads * head_dim each layer."""
    m = dims(cfg)
    return 4 * m["L"] * m["h"] * m["hd"]


def kv_bytes_per_position(cfg: dict, bytes_per_value: int) -> int:
    """Keys and values of one position in every layer."""
    m = dims(cfg)
    return 2 * m["L"] * m["hk"] * m["hd"] * bytes_per_value
