"""Stacks that mix sliding-window and full attention layers: the paged
kernel's lower bound, YaRN's table, and prefill / decode / paged serving
agreeing with each other."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cim_linear import CIMConfig, ternarize_params
from repro.kernels import paged_attention as pa
from repro.models import layers, registry
from repro.models.config import ModelConfig
from repro.serve import PagedScheduler, Request, Scheduler

MELLUM_YARN = (16.0, 8192, 32.0, 1.0, 1.2772588722239782)


def _published_yarn(dim, base, factor, original, beta_fast, beta_slow):
    """The published ``rope_type: yarn`` table, step by step as its
    reference code computes it (truncated correction range)."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) \
            / (2 * math.log(base))
    low = math.floor(find_correction_dim(beta_fast))
    high = math.ceil(find_correction_dim(beta_slow))
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    linear = (np.arange(dim // 2, dtype=np.float32) - low) / (high - low)
    ramp = np.clip(linear, 0, 1)
    extrapolation_factor = 1 - ramp
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inv_extrapolation = 1.0 / pos_freqs
    inv_interpolation = 1.0 / (factor * pos_freqs)
    return (inv_interpolation * (1 - extrapolation_factor)
            + inv_extrapolation * extrapolation_factor)


def test_yarn_table_matches_the_published_formula():
    factor, orig, fast, slow, _ = MELLUM_YARN
    got = layers.yarn_inv_freq(128, 5e5, factor, orig, fast, slow)
    want = _published_yarn(128, 5e5, factor, orig, fast, slow)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = 1.0 / 5e5 ** (np.arange(0, 128, 2) / 128)
    # the fastest dimensions keep their frequency, the slowest are
    # divided by the factor
    np.testing.assert_allclose(got[:4], plain[:4], rtol=1e-6)
    np.testing.assert_allclose(got[-4:], plain[-4:] / factor, rtol=1e-6)


def test_layer_tables_follow_layer_kinds():
    cfg = ModelConfig(name="w", family="dense", num_layers=4, d_model=64,
                      num_heads=2, num_kv_heads=1, d_ff=64, vocab_size=256,
                      head_dim=32, rope_theta=5e5,
                      layer_windows=(8, 8, 8, 0), rope_yarn=MELLUM_YARN)
    win, inv, scale = layers.layer_attn_tables(cfg)
    assert win.tolist() == [8, 8, 8, 0]
    assert scale.tolist() == pytest.approx([1, 1, 1, MELLUM_YARN[-1]])
    np.testing.assert_array_equal(inv[0], inv[2])
    np.testing.assert_allclose(inv[3], layers.yarn_inv_freq(
        32, 5e5, *MELLUM_YARN[:4]))


def _pool(seed=0, slots=3, kvh=2, rep=2, hd=32, ps=8, w=5):
    rng = np.random.default_rng(seed)
    num_pages = slots * w + 1
    k = jnp.asarray(rng.normal(size=(num_pages, ps, kvh, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(num_pages, ps, kvh, hd)), jnp.float32)
    table = jnp.asarray(1 + np.arange(slots * w).reshape(slots, w),
                        jnp.int32)
    q = jnp.asarray(rng.normal(size=(slots, kvh, rep, hd)), jnp.float32)
    return q, k, v, table


@pytest.mark.parametrize("lo", [[5, 13, 0], [8, 16, 24], [0, 0, 0]],
                         ids=["inside_a_page", "page_boundary", "zero"])
def test_windowed_kernel_matches_gather_oracle(lo):
    """The kernel with a per-slot lower bound against the gather oracle,
    and both against attention over only the keys in [lo, len)."""
    q, k, v, table = _pool()
    lens = jnp.asarray([29, 17, 40], jnp.int32)
    lo = jnp.asarray(lo, jnp.int32)
    kv = pa.PagedAttentionKV(k, v, table, lens, lo)
    acc, m, l = pa.paged_attention(q, kv, interpret=True)
    racc, rm, rl = pa.paged_attention_ref(q, kv)
    np.testing.assert_allclose(np.asarray(acc / l[..., None]),
                               np.asarray(racc / rl[..., None]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(m), np.asarray(rm), rtol=1e-5,
                               atol=1e-5)
    # the oracle masks what the window hides: the same statistics as
    # attention over only the visible keys
    for s in range(table.shape[0]):
        keys = jnp.concatenate([k[int(p)] for p in table[s]])
        vals = jnp.concatenate([v[int(p)] for p in table[s]])
        a, b = int(lo[s]), int(lens[s])
        sc = jnp.einsum("krd,tkd->krt", q[s], keys[a:b],
                        precision="highest")
        p = jax.nn.softmax(sc, axis=-1)
        out = jnp.einsum("krt,tkd->krd", p, vals[a:b], precision="highest")
        np.testing.assert_allclose(np.asarray(acc[s] / l[s][..., None]),
                                   np.asarray(out), rtol=2e-5, atol=2e-5)


def test_zero_window_is_the_unwindowed_kernel_bitwise():
    """``lo = 0`` (full layers, every dense model) and no ``lo`` at all
    give the same bits."""
    q, k, v, table = _pool(seed=3)
    lens = jnp.asarray([29, 0, 40], jnp.int32)
    a = pa.paged_attention(q, pa.PagedAttentionKV(k, v, table, lens),
                           interpret=True)
    b = pa.paged_attention(q, pa.PagedAttentionKV(
        k, v, table, lens, jnp.zeros((3,), jnp.int32)), interpret=True)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _windowed(family="dense", **kw):
    base = dict(name="w", family=family, num_layers=4, d_model=128,
                num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=512,
                head_dim=32, rope_theta=5e5, layer_windows=(8, 8, 8, 0),
                rope_yarn=(4.0, 16, 32.0, 1.0, 1.1386))
    base.update(kw)
    return ModelConfig(**base)


def test_prefill_decode_match_forward_with_windows():
    """decode(t_k | prefill(t_0..k-1)) equals the full forward at k on a
    stack of window and YaRN layers, with prompts past the window."""
    cfg = _windowed(dtype=jnp.float32)
    model = registry.build(cfg)
    params = model.init(jax.random.key(2), jnp.float32)
    toks = jax.random.randint(jax.random.key(4), (2, 24), 0, 512)
    full = model.forward(params, {"tokens": toks}).astype(jnp.float32)
    logits, state = model.prefill(params, {"tokens": toks[:, :20]}, 24)
    np.testing.assert_allclose(np.asarray(logits[:, -1]),
                               np.asarray(full[:, 19]), atol=2e-3,
                               rtol=2e-3)
    for t in range(20, 23):
        logits, state = model.decode(params, toks[:, t:t + 1], state)
        np.testing.assert_allclose(np.asarray(logits[:, -1]),
                                   np.asarray(full[:, t]), atol=2e-3,
                                   rtol=2e-3)
    # the window is felt: without it the logits move
    no_win = registry.build(dataclasses.replace(cfg, layer_windows=()))
    other = no_win.forward(params, {"tokens": toks}).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(other[:, 20:] - full[:, 20:]))) > 1e-2


def _serve(eng, cfg):
    rng = np.random.default_rng(0)
    for i, (n, m) in enumerate([(20, 9), (9, 12), (30, 5)]):
        eng.submit(Request(uid=i, prompt=rng.integers(
            0, cfg.vocab_size, n).astype(np.int32), max_new=m))
    return {r.uid: r.out_tokens for r in eng.run()}


def _packed(cfg):
    model = registry.build(cfg)
    cim = CIMConfig(mode="ternary", packing="trit2", domain="int8")
    return model, ternarize_params(model.init(jax.random.key(0)), cim), cim


def test_windowed_stack_serves_paged_as_dense():
    """Per-layer windows keep every position, so a window/YaRN stack
    pages: the fused read (the kernel's lower bound) and the gather read
    serve the dense pool's tokens, prompts past the window included."""
    cfg = _windowed()
    model, params, cim = _packed(cfg)
    assert model.supports_paged_kv
    want = _serve(Scheduler(model, params, capacity=48, slots=2, chunk=4,
                            cim=cim), cfg)
    for fused in (True, False):
        got = _serve(PagedScheduler(model, params, capacity=48, slots=2,
                                    chunk=4, page_size=8, cim=cim,
                                    fused_attn=fused), cfg)
        assert got == want, fused


def test_windowed_moe_serves_paged():
    """A packed MoE window/YaRN stack: the gather read serves the dense
    pool's tokens bitwise (the fused read is round-off-equal only, which
    a router near-tie can turn into another token); on the fused read the
    MoE counters add up, live rows x k x layers per step."""
    cfg = _windowed(family="moe", num_experts=16, experts_per_token=4)
    model, params, cim = _packed(cfg)
    want = _serve(Scheduler(model, params, capacity=48, slots=2, chunk=4,
                            cim=cim), cfg)
    got = _serve(PagedScheduler(model, params, capacity=48, slots=2,
                                chunk=4, page_size=8, cim=cim,
                                fused_attn=False), cfg)
    assert got == want
    fused = PagedScheduler(model, params, capacity=48, slots=2, chunk=4,
                           page_size=8, cim=cim, fused_attn=True)
    served = _serve(fused, cfg)
    assert {u: len(t) for u, t in served.items()} == {0: 9, 1: 12, 2: 5}
    assert fused.moe_rows_routed == (fused.occupied_slot_steps
                                     * cfg.experts_per_token
                                     * cfg.num_layers)
    assert 0 < fused.moe_experts_read <= (fused.decode_steps
                                          * cfg.num_experts
                                          * cfg.num_layers)
    assert fused.host_transfers == fused.chunks_run
