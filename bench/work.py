"""Operations and bytes the served model needs, from its configuration.

Counted from the algorithm, never from how a kernel is cut: a matmul of
M rows against a (K, N) weight is 2*M*K*N operations and reads the weight
once at its packed size (``base3`` one byte a weight, ``trit2`` a quarter,
K padded to a multiple of four) plus an f32 scale per column.  Attention
over a context of ``c`` positions is 4*heads*head_dim*c operations per
layer and query, and reads ``c`` positions of K and V per layer.
"""
from __future__ import annotations

import json
import os

BF16, F32 = 2, 4


def matmuls(arch: dict) -> dict:
    """name -> (count, K, N) of every packed weight matrix."""
    d, h, kvh, hd, f = (
        arch["hidden_size"], arch["num_attention_heads"],
        arch["num_key_value_heads"], arch["head_dim"],
        arch["intermediate_size"])
    n_layers = arch["num_hidden_layers"]
    vp = padded_vocab(arch)
    return {"wq": (n_layers, d, h * hd), "wk": (n_layers, d, kvh * hd),
            "wv": (n_layers, d, kvh * hd), "wo": (n_layers, h * hd, d),
            "w1": (n_layers, d, f), "w3": (n_layers, d, f),
            "w2": (n_layers, f, d), "unembed": (1, d, vp)}


def padded_vocab(arch: dict) -> int:
    return -(-arch["vocab_size"] // 256) * 256


def packed_bytes(k: int, n: int, packing: str) -> int:
    if packing == "base3":
        return k * n + F32 * n
    if packing == "trit2":
        return -(-k // 4) * n + F32 * n
    raise ValueError(f"unknown packing {packing!r}")


def weight_bytes(arch: dict, packing: str) -> int:
    """Device bytes of the served weights: packed matrices and scales,
    the bf16 embedding and the bf16 norm gains."""
    d, n_layers = arch["hidden_size"], arch["num_hidden_layers"]
    total = sum(c * packed_bytes(k, n, packing)
                for c, k, n in matmuls(arch).values())
    total += padded_vocab(arch) * d * BF16
    gains = n_layers * 2 * d + d
    if arch["qk_norm"]:
        gains += n_layers * 2 * arch["head_dim"]
    return total + gains * BF16


def decode_matmul(arch: dict, packing: str, rows: int, steps: int) -> dict:
    """Matmul work of ``steps`` decode steps that serve ``rows`` live
    rows in all: every step reads every weight once."""
    mats = matmuls(arch).values()
    return {"ops": 2 * rows * sum(c * k * n for c, k, n in mats),
            "bytes": steps * sum(c * packed_bytes(k, n, packing)
                                 for c, k, n in mats)}


def attention(arch: dict, contexts) -> dict:
    """Attention work of one decode query per entry of ``contexts``
    (its number of cached positions), over all layers."""
    n_layers, h, kvh, hd = (
        arch["num_hidden_layers"], arch["num_attention_heads"],
        arch["num_key_value_heads"], arch["head_dim"])
    pos = sum(contexts)
    return {"flops": 4 * h * hd * pos * n_layers,
            "bytes": 2 * kvh * hd * BF16 * pos * n_layers}


def roofline_s(ops: float, nbytes: float, peak_ops: float,
               peak_bw: float) -> float:
    """The least time a chip with these peaks needs for the work."""
    return max(ops / peak_ops, nbytes / peak_bw)


def peaks(device_kind: str, root: str | None = None) -> dict:
    """The chip's peaks from ``peaks.json``; an unknown kind is an
    error, never a default."""
    path = os.path.join(root or os.path.dirname(__file__), "peaks.json")
    with open(path) as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table['devices'])}") from None
