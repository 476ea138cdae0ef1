"""Operations and bytes the served model needs, from its configuration.

What every architecture shares is here: a matrix's packed bytes, the
padded vocabulary, the roofline and the chip's peaks.  Which matrices a
step reads and how far attention looks are the architecture module's
(``bench/archs/``); ``matmuls``, ``weight_bytes``, ``decode_matmul`` and
``attention`` below are the dense module's.

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


def padded_vocab(arch: dict) -> int:
    return -(-arch["vocab_size"] // 256) * 256


def packed_bytes(k: int, n: int, packing: str) -> int:
    if packing == "base3":
        return k * n + F32 * n
    if packing == "trit2":
        return -(-k // 4) * n + F32 * n
    raise ValueError(f"unknown packing {packing!r}")


def _dense():
    from . import archs
    return archs.load({})


def matmuls(arch: dict) -> dict:
    """The dense architecture's packed matrices (``archs/dense.py``)."""
    return _dense().matmuls(arch)


def weight_bytes(arch: dict, packing: str) -> int:
    """The dense architecture's served bytes (``archs/dense.py``)."""
    return _dense().weight_bytes(arch, packing)


def decode_matmul(arch: dict, packing: str, rows: int, steps: int) -> dict:
    """The dense architecture's decode matmul work (``archs/dense.py``)."""
    return _dense().decode_matmul(arch, packing, rows, steps)


def attention(arch: dict, contexts) -> dict:
    """The dense architecture's decode attention work
    (``archs/dense.py``)."""
    return _dense().attention(arch, contexts)


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
