"""Peaks keyed by device kind, and work counted from shapes."""
import pytest

import tiny  # noqa: F401
from bench import harness, work


def test_v5e_peaks():
    pk = work.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["int8_ops_per_s"] == 393e12
    assert pk["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_fails():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")


def test_published_weight_bytes():
    import json
    import os

    def load(name):
        with open(os.path.join(tiny.REPO, "bench", "configs",
                               name + ".json")) as f:
            return harness.arch(json.load(f))
    # trit2: four weights a byte plus f32 scales, bf16 embedding
    q = load("qwen3-14b-trit2")
    mats = sum(c * k * n for name, (c, k, n) in work.matmuls(q).items()
               if name != "unembed")
    assert mats == 13_212_057_600
    assert 5.0e9 < work.weight_bytes(q, "trit2") < 5.1e9
    i = load("internlm2-1.8b-base3")
    # KV read a position: one attention query over one cached position
    assert work.attention(i, [1])["bytes"] == 98_304
    assert work.attention(q, [1])["bytes"] == 163_840
    assert 2.05e9 < work.weight_bytes(i, "base3") < 2.1e9


def test_decode_work_counts_weights_once_a_step():
    a = harness.arch(tiny.CONFIG)
    one = work.decode_matmul(a, "trit2", rows=4, steps=1)
    two = work.decode_matmul(a, "trit2", rows=8, steps=1)
    assert two["ops"] == 2 * one["ops"] and two["bytes"] == one["bytes"]
    assert work.roofline_s(1e12, 819e9, 393e12, 819e9) == pytest.approx(1.0)
