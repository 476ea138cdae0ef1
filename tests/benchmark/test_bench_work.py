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


def test_window_work_is_one_query_per_decode_token():
    """The window's decode work: a request's token ``j`` (``j >= 1``,
    its first from prefill) attended ``prompt_len + j`` positions, and
    only tokens that reached the host inside the window count."""
    from types import SimpleNamespace

    from bench import archs, decode_work
    reqs = [SimpleNamespace(prompt_len=10, tokens_at_open=0,
                            tokens_at_close=4),     # tokens 1, 2, 3
            SimpleNamespace(prompt_len=7, tokens_at_open=5,
                            tokens_at_close=7),     # tokens 5, 6
            SimpleNamespace(prompt_len=3, tokens_at_open=2,
                            tokens_at_close=2)]     # none in the window
    a = harness.arch(tiny.CONFIG)
    run = SimpleNamespace(all_requests=reqs, counters={"decode_steps": 3},
                          module=archs.load(tiny.CONFIG), arch=a,
                          packing="trit2")
    mm, att = decode_work.of(run)
    assert mm == work.decode_matmul(a, "trit2", rows=5, steps=3)
    assert att == work.attention(a, [11, 12, 13, 12, 13])
    assert att["flops"] == 4 * 4 * 32 * 61 * 2
