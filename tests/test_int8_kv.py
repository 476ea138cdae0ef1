"""int8 KV cache (beyond-paper: the paper's narrow-storage + restore
mechanism applied to the decode-time activations)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.models import registry
from repro.models.attention import quantize_kv


def test_quantize_kv_roundtrip_error():
    x = jax.random.normal(jax.random.key(0), (2, 8, 4, 16))
    q, s = quantize_kv(x)
    deq = q.astype(jnp.float32) * s[..., None]
    # max error bounded by half a code step per (b, pos, head)
    assert float(jnp.max(jnp.abs(deq - x) / s[..., None])) <= 0.5 + 1e-4


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "glm4-9b",
                                  "mixtral-8x7b"])
def test_int8_kv_decode_close_to_bf16(arch):
    cfg = dataclasses.replace(configs.smoke(arch), dtype=jnp.float32)
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    model, model8 = registry.build(cfg), registry.build(cfg8)
    params = model.init(jax.random.key(4))
    toks = jax.random.randint(jax.random.key(5), (2, 12), 0, cfg.vocab_size)

    lg, st = model.prefill(params, {"tokens": toks}, capacity=24)
    lg8, st8 = model8.prefill(params, {"tokens": toks}, capacity=24)
    assert st8["k"].dtype == jnp.int8
    assert jnp.allclose(lg, lg8, atol=1e-4)     # prefill logits identical

    tok = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
    for _ in range(3):
        lg, st = model.decode(params, tok, st)
        lg8, st8 = model8.decode(params, tok, st8)
        # int8 cache introduces bounded quantization noise (random-weight
        # models have near-uniform attention, the worst case for it)
        denom = jnp.maximum(jnp.max(jnp.abs(lg)), 1e-6)
        assert float(jnp.max(jnp.abs(lg - lg8)) / denom) < 0.25
        assert float(jnp.mean(jnp.abs(lg - lg8)) / denom) < 0.05
        tok = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)


def test_int8_kv_cache_defs_have_scales():
    cfg = dataclasses.replace(configs.get("qwen3-14b"),
                              kv_cache_dtype="int8")
    model = registry.build(cfg)
    defs = model.cache_defs(4, 128)
    assert defs["k"].dtype == jnp.int8
    assert defs["k_scale"].shape == (cfg.num_layers, 4, 128,
                                     cfg.num_kv_heads)


def test_init_cache_allocates_int8_scale_buffers():
    """ISSUE 5 satellite: attn.init_cache used to allocate no
    k_scale/v_scale while registry gates its int8 read path on them."""
    from repro.models.attention import init_cache
    c = init_cache(2, 16, 4, 8, kv_cache_dtype="int8")
    assert c.k.dtype == jnp.int8 and c.v.dtype == jnp.int8
    assert c.k_scale is not None and c.v_scale is not None
    assert c.k_scale.shape == (2, 16, 4) and c.k_scale.dtype == jnp.float32
    # default float path unchanged
    c = init_cache(2, 16, 4, 8)
    assert c.k_scale is None and c.v_scale is None


def test_int8_kv_scheduler_parity_mixed_lengths():
    """Mixed-length int8-KV pools: the continuous Scheduler (dense slot
    pool, scale buffers allocated up front on the slot axis) and the
    PagedScheduler (scale pages alongside the KV pages) must both be
    bitwise token-identical to the plain batch-1 prefill/decode loop."""
    from repro.serve import PagedScheduler, Request, Scheduler
    from serve_reference import reference_tokens
    cfg = dataclasses.replace(configs.smoke("internlm2-1.8b"),
                              dtype=jnp.float32, kv_cache_dtype="int8")
    model = registry.build(cfg)
    params = model.init(jax.random.key(0))
    key = jax.random.key(1)

    def reqs():
        return [Request(uid=i, prompt=jax.random.randint(
            jax.random.fold_in(key, i), (plen,), 0, cfg.vocab_size),
            max_new=mn)
            for i, (plen, mn) in enumerate([(6, 4), (10, 6), (8, 3),
                                            (6, 5)])]

    def run(eng):
        for r in reqs():
            eng.submit(r)
        return {r.uid: list(r.out_tokens) for r in eng.run()}

    ref = reference_tokens(model, params, reqs(), 32)
    dense = Scheduler(model, params, capacity=32, slots=2, chunk=3)
    # the slot pool carries int8 codes + f32 scale lanes from t=0
    assert dense.pool["k"].dtype == jnp.int8
    assert dense.pool["k_scale"].shape[0] == 2      # slot axis
    assert run(dense) == ref
    paged = PagedScheduler(model, params, capacity=32, slots=2, chunk=3,
                           page_size=4)
    assert paged.pool.k_pages.dtype == jnp.int8
    assert paged.pool.k_scale_pages is not None     # scale pages up front
    assert run(paged) == ref
