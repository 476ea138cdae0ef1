"""Grouped-query attention: training, prefill, decode (full/SWA/cross).

Layout: q (B,S,H,hd), kv (B,T,KV,hd).  GQA is computed with grouped
einsums (no materialized head repetition).  Decode updates a KV cache via
dynamic_update_slice; sliding-window decode uses a rolling buffer of size
`window` so the long_500k cell stays O(window) in memory for SWA archs.

Stacks that mix window and full layers (``cfg.layer_windows``) pass each
layer's ``LayerAttn`` (window, RoPE table, RoPE scale) through the layer
scan: the window is a mask over a cache that holds every position (and,
in the fused paged read, a per-slot lower bound of the pages visited).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import ATTN_OUT, apply_rope, dense, rms_norm


class LayerAttn(NamedTuple):
    """One layer's attention kind inside a layer scan (traced values):
    ``window`` () int32, 0 = full; ``inv_freq`` (hd/2,) f32 RoPE
    frequencies; ``rope_scale`` () f32 on cos and sin (YaRN)."""
    window: jax.Array
    inv_freq: jax.Array
    rope_scale: jax.Array


def _window_of(cfg: ModelConfig, layer: Optional[LayerAttn]):
    return cfg.sliding_window if layer is None else layer.window


def _in_window(kpos, qpos, window):
    """Keys inside a window of ``window`` positions ending at the query
    (a Python int, or a traced scalar where 0 means no window)."""
    if isinstance(window, int):
        return kpos > (qpos - window) if window > 0 else True
    return (window <= 0) | (kpos > (qpos - window))


class KVCache(NamedTuple):
    k: jax.Array          # (B, C, KV, hd);  C = max_seq or window
    v: jax.Array
    index: jax.Array      # scalar int32 — next write position (absolute)
    # int8-KV mode (beyond-paper: the paper's dense-storage/restore idea
    # applied to activations): k/v are int8, scales are per-(B, C, KV)
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(B, S, KV, hd) -> (int8 codes, (B, S, KV) f32 scales)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def init_cache(batch: int, capacity: int, kv_heads: int, hd: int,
               dtype=jnp.bfloat16, kv_cache_dtype: str = "") -> KVCache:
    """Zero-initialized KV cache.  ``kv_cache_dtype='int8'`` allocates
    the int8 code buffers AND their per-(B, C, KV) f32 scale buffers up
    front — ``registry`` gates its int8 read path on the scales being
    present, so a cache built without them would fail mid-decode."""
    shape = (batch, capacity, kv_heads, hd)
    if kv_cache_dtype == "int8":
        # distinct buffers: k/v scale leaves are donated independently
        return KVCache(jnp.zeros(shape, jnp.int8),
                       jnp.zeros(shape, jnp.int8),
                       jnp.zeros((), jnp.int32),
                       jnp.zeros(shape[:-1], jnp.float32),
                       jnp.zeros(shape[:-1], jnp.float32))
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((), jnp.int32))


def _project_qkv(x, w, cfg: ModelConfig, x_kv=None, positions=None,
                 rope: bool = True, cim_cfg=None,
                 layer: Optional[LayerAttn] = None):
    b, s, d = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    src = x if x_kv is None else x_kv
    q = dense(x, w["wq"], cim_cfg).reshape(b, s, h, hd)
    k = dense(src, w["wk"], cim_cfg).reshape(b, src.shape[1], kv, hd)
    v = dense(src, w["wv"], cim_cfg).reshape(b, src.shape[1], kv, hd)
    if cfg.qk_norm and "q_norm" in w:
        q = rms_norm(q, w["q_norm"], cfg.norm_eps)
        k = rms_norm(k, w["k_norm"], cfg.norm_eps)
    if rope and positions is not None:
        table = (() if layer is None
                 else (layer.inv_freq, layer.rope_scale))
        q = apply_rope(q, positions, cfg.rope_theta, *table)
        kpos = positions if x_kv is None else jnp.arange(src.shape[1])
        k = apply_rope(k, kpos, cfg.rope_theta, *table)
    return q, k, v


def _gqa_attend(q, k, v, mask, cfg: ModelConfig):
    """q (B,S,H,hd) x k/v (B,T,KV,hd), additive mask (B,1,1,S,T) or None."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    rep = h // kv
    qg = q.reshape(b, s, kv, rep, hd)
    scores = jnp.einsum("bskrd,btkd->bkrst", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if mask is not None:
        scores = scores + mask
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrst,btkd->bskrd", probs, v)
    return out.reshape(b, s, h * hd)


def flash_attention(q, k, v, cfg: ModelConfig, causal: bool = True,
                    q_offset=0, chunk: int = 512,
                    window=None) -> jax.Array:
    """Memory-bounded attention: lax.scan over KV chunks with running
    (max, denom, acc) — the flash-attention recurrence in pure jnp.  Never
    materializes the (S, T) score matrix; per-step footprint is
    O(B·H·S·chunk).  Required for the 32k cells (32k² scores would be TBs).

    q (B,S,H,hd); k/v (B,T,KV,hd) or ``paged_kv.PagedKV`` gather-views.
    Paged operands are materialized up front — the gather costs one
    dense copy of K/V, so the paged layout's residency saving does NOT
    extend through this function; a per-chunk page gather (a
    layout-specialized ``kv_layout='paged'`` executor) is the seam for
    that.  Masks (causal and/or sliding window) are rebuilt per chunk
    from positions, so no (S,T) mask exists either.
    """
    from . import paged_kv
    k = paged_kv.materialize(k)
    v = paged_kv.materialize(v)
    b, s, h, hd = q.shape
    t = k.shape[1]
    kv = k.shape[2]
    rep = h // kv
    window = cfg.sliding_window if window is None else window
    if t % chunk:
        pad = -t % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = k.shape[1] // chunk
    qg = (q.reshape(b, s, kv, rep, hd).astype(jnp.float32)
          / jnp.sqrt(jnp.asarray(hd, jnp.float32)))
    kc = k.reshape(b, nc, chunk, kv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nc, chunk, kv, hd).transpose(1, 0, 2, 3, 4)
    qpos = (jnp.arange(s) + q_offset)[:, None]           # (S,1)

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, c0 = inp
        sc = jnp.einsum("bskrd,bukd->bskru", qg, kb.astype(jnp.float32))
        kpos = (c0 + jnp.arange(chunk))[None, :]         # (1,chunk)
        ok = kpos <= qpos if causal else (kpos < t)
        ok &= kpos < t                                   # mask padding
        ok &= _in_window(kpos, qpos, window)
        sc = jnp.where(ok[None, :, None, None, :], sc, -1e30)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bskru,bukd->bskrd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, s, kv, rep), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, s, kv, rep), jnp.float32)
    a0 = jnp.zeros((b, s, kv, rep, hd), jnp.float32)
    starts = jnp.arange(nc) * chunk
    # checkpoint the chunk step: without it, grad-of-scan stacks every
    # chunk's (S x chunk) probs in f32 for the backward pass — O(S*T)
    # memory, exactly what flash attention exists to avoid.  With it,
    # backward replays each chunk (the standard flash-bwd recompute).
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step), (m0, l0, a0),
                                  (kc, vc, starts))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, s, h * hd).astype(q.dtype)


FLASH_THRESHOLD = 2048  # direct attention below this (smoke tests, decode)


def _constrain_qkv(q, k, v):
    """Anchor attention operand shardings (batch over DP, q-heads over TP
    where divisible, kv-seq per mode) — attention has no dense() inside,
    so without this XLA replicates the whole score computation."""
    from repro.dist.sharding import constrain_act
    q = constrain_act(q, ("batch", "seq", "head_count", "none"))
    k = constrain_act(k, ("batch", "kv_seq", "none", "none"))
    v = constrain_act(v, ("batch", "kv_seq", "none", "none"))
    return q, k, v


def attend(q, k, v, cfg: ModelConfig, causal: bool = True, q_offset=0,
           window=None):
    """Dispatch: direct masked attention for short sequences, flash
    above.  ``window`` (default ``cfg.sliding_window``) may be a traced
    per-layer scalar.
    ``k``/``v`` may be ``paged_kv.PagedKV`` gather-views — the
    paged layout gathers into the dense (B, T, KV, hd) operand here, so
    both branches (and their outputs) are identical to dense K/V."""
    from . import paged_kv
    k = paged_kv.materialize(k)
    v = paged_kv.materialize(v)
    q, k, v = _constrain_qkv(q, k, v)
    s, t = q.shape[1], k.shape[1]
    window = cfg.sliding_window if window is None else window
    if max(s, t) <= FLASH_THRESHOLD:
        off = q_offset if s != t else 0
        mask = causal_mask(s, t, window, off) if causal else None
        return _gqa_attend(q, k, v, mask, cfg)
    return flash_attention(q, k, v, cfg, causal=causal, q_offset=q_offset,
                           window=window)


def causal_mask(s: int, t: int, window=0, offset: int = 0) -> jax.Array:
    """Additive (1,1,1,s,t) mask; offset = absolute position of query 0;
    ``window`` an int or a traced scalar (0 = none)."""
    qpos = jnp.arange(s)[:, None] + offset
    kpos = jnp.arange(t)[None, :]
    ok = (kpos <= qpos) & _in_window(kpos, qpos, window)
    return jnp.where(ok, 0.0, -1e30)[None, None, None]


def self_attention(x, w, cfg: ModelConfig, positions=None, causal=True,
                   cim_cfg=None, layer: Optional[LayerAttn] = None
                   ) -> jax.Array:
    """Training/prefill self-attention (full or sliding-window)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = _project_qkv(x, w, cfg, positions=positions, cim_cfg=cim_cfg,
                           layer=layer)
    out = attend(q, k, v, cfg, causal=causal,
                 window=_window_of(cfg, layer))
    return dense(out, w["wo"], cim_cfg, x_axes=ATTN_OUT)


def cross_attention(x, x_kv, w, cfg: ModelConfig, cim_cfg=None) -> jax.Array:
    q, k, v = _project_qkv(x, w, cfg, x_kv=x_kv, rope=False, cim_cfg=cim_cfg)
    out = attend(q, k, v, cfg, causal=False)
    return dense(out, w["wo"], cim_cfg, x_axes=ATTN_OUT)


def prefill_attention(x, w, cfg: ModelConfig, cache: KVCache,
                      cim_cfg=None, layer: Optional[LayerAttn] = None
                      ) -> tuple[jax.Array, KVCache]:
    """Prefill: run causal attention AND populate the cache."""
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q, k, v = _project_qkv(x, w, cfg, positions=positions, cim_cfg=cim_cfg,
                           layer=layer)
    out = attend(q, k, v, cfg, causal=True, window=_window_of(cfg, layer))
    cap = cache.k.shape[1]
    if cfg.sliding_window and cap == cfg.sliding_window:
        keep = min(s, cap)
        newk = jax.lax.dynamic_slice_in_dim(k, s - keep, keep, 1)
        newv = jax.lax.dynamic_slice_in_dim(v, s - keep, keep, 1)
        # rolling buffer laid out so that slot = absolute_pos % window
        roll = (s - keep) % cap
        newk = jnp.roll(jnp.pad(newk, ((0, 0), (0, cap - keep), (0, 0), (0, 0))),
                        roll, axis=1)
        newv = jnp.roll(jnp.pad(newv, ((0, 0), (0, cap - keep), (0, 0), (0, 0))),
                        roll, axis=1)
        cache = KVCache(newk.astype(cache.k.dtype), newv.astype(cache.v.dtype),
                        jnp.asarray(s, jnp.int32))
    else:
        newk = jax.lax.dynamic_update_slice_in_dim(
            cache.k, k.astype(cache.k.dtype), 0, 1)
        newv = jax.lax.dynamic_update_slice_in_dim(
            cache.v, v.astype(cache.v.dtype), 0, 1)
        cache = KVCache(newk, newv, jnp.asarray(s, jnp.int32))
    return dense(out, w["wo"], cim_cfg, x_axes=ATTN_OUT), cache


def decode_attention_read(x, w, cfg: ModelConfig, cache: KVCache,
                          cim_cfg=None, layer: Optional[LayerAttn] = None):
    """One-token decode that does NOT write the cache: attends over the
    (stale) cache slice + the freshly projected k/v of the current token,
    and returns them for a single model-level cache update.

    Rationale (measured on the 32k-decode dry-run): per-layer
    dynamic-update-slice of the cache makes XLA stage full-cache copies
    inside the layer scan (~2x the whole KV cache of HBM traffic per
    decoded token); reading the cache once and batching all layers'
    updates into ONE top-level in-place DUS leaves only the unavoidable
    params + cache read.

    Returns (out, k_new (B,1,KV,hd), v_new)."""
    b, s, _ = x.shape
    assert s == 1, "decode_attention is single-token"
    pos = cache.index
    q, k, v = _project_qkv(x, w, cfg, positions=pos[None, None],
                           cim_cfg=cim_cfg, layer=layer)
    cap = cache.k.shape[1]
    rolling = cfg.sliding_window and cap == cfg.sliding_window
    slot = pos % cap if rolling else pos
    slots = jnp.arange(cap)
    if rolling:
        # previously written slots, excluding the one the new token will
        # overwrite (it holds the entry that just left the window)
        valid = (slots < jnp.minimum(pos, cap)) & (slots != slot)
    else:
        valid = slots < pos
        if layer is not None:
            valid &= _in_window(slots, pos, layer.window)
    q, ck, cv = _constrain_qkv(q, cache.k, cache.v)
    # NO concatenation: a (cap+1)-long axis breaks the cache's sequence
    # sharding (measured: it all-gathers the whole cache).  Instead merge
    # the cache block and the new token with the flash two-block rule.
    b, _, h, hd = q.shape
    kv = ck.shape[2]
    rep = h // kv
    int8_kv = cache.k_scale is not None
    # keep the cache operands in their storage dtype: .astype(f32) on the
    # (B, cap, KV, hd) cache would materialize an f32 copy of the whole
    # cache every layer — the dots accumulate in f32 instead.  int8-KV:
    # per-(position, head) scales factor OUT of the dots (s = scale·q·k8,
    # acc = Σ (p·v_scale)·v8), so no dequantized cache copy ever exists.
    qg = (q / jnp.sqrt(jnp.asarray(hd, q.dtype))).reshape(b, 1, kv, rep, hd)
    dot_k = ck.astype(q.dtype) if int8_kv else ck
    sc = jnp.einsum("bskrd,btkd->bkrst", qg, dot_k,
                    preferred_element_type=jnp.float32)
    if int8_kv:
        sc = sc * cache.k_scale.transpose(0, 2, 1)[:, :, None, None, :]
    sc = sc + jnp.where(valid, 0.0, -1e30)[None, None, None, None, :]
    m_c = jnp.max(sc, axis=-1)                            # (b,kv,rep,1)
    p_c = jnp.exp(sc - m_c[..., None])
    l_c = jnp.sum(p_c, axis=-1)
    if int8_kv:
        p_eff = (p_c * cache.v_scale.transpose(0, 2, 1)
                 [:, :, None, None, :]).astype(q.dtype)
        acc_c = jnp.einsum("bkrst,btkd->bkrsd", p_eff, cv.astype(q.dtype),
                           preferred_element_type=jnp.float32)
    else:
        acc_c = jnp.einsum("bkrst,btkd->bkrsd", p_c.astype(ck.dtype), cv,
                           preferred_element_type=jnp.float32)
    # new-token block: score (b, kv, rep, 1), value (b, kv, hd)
    s_n = jnp.einsum("bskrd,bukd->bkrs", qg, k,
                     preferred_element_type=jnp.float32)
    v_n = v.astype(jnp.float32)[:, 0]                     # (b, kv, hd)
    m = jnp.maximum(m_c, s_n)
    w_c = jnp.exp(m_c - m)
    w_n = jnp.exp(s_n - m)
    acc = acc_c * w_c[..., None] + \
        w_n[..., None] * v_n[:, :, None, None, :]
    l = l_c * w_c + w_n
    out = (acc / l[..., None]).astype(q.dtype)            # (b,kv,rep,1,hd)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, 1, h * hd)
    return (dense(out, w["wo"], cim_cfg, x_axes=ATTN_OUT),
            k.astype(cache.k.dtype), v.astype(cache.v.dtype))


def paged_decode_attention_read(x, w, cfg: ModelConfig, k_pages, v_pages,
                                page_table, pos, plan, cim_cfg=None,
                                layer: Optional[LayerAttn] = None):
    """Batched one-token decode read straight off the page pool: the
    planned ``attention`` executor (``kernels.paged_attention``)
    consumes the page table in-kernel, so the gathered dense KV copy the
    ``slot_view`` path materializes never exists here.  ``k_pages`` /
    ``v_pages`` are passed through untouched: the fused decode step
    gives every layer the whole pool flattened to ``(L*P, ...)`` and
    this layer's ``page_table``, offset to its pages.  The executor
    returns partial flash statistics over the pooled context; the fresh
    token's own k/v merge in with the same two-block rule (and the same
    masking constant) as ``decode_attention_read``, so the two paths
    agree to f32 round-off — and bitwise at the sampled argmax.

    ``x`` is (S, 1, d) — all S slots at once, not vmapped: the executor
    runs one grid over every (slot, page) cell.  Returns
    (out (S, 1, d), k_new (S, KV, hd), v_new).

    A window layer (``layer.window > 0``) gives each slot the first
    position it may see, ``lo = max(pos - window + 1, 0)``: the kernel
    skips the pages wholly below it and masks the keys under it."""
    from repro.kernels import execute
    from repro.kernels.paged_attention import PagedAttentionKV
    s_dim, s1, _ = x.shape
    assert s1 == 1, "paged decode read is single-token"
    q, k, v = _project_qkv(x, w, cfg, positions=pos[:, None],
                           cim_cfg=cim_cfg, layer=layer)
    lo = None
    if layer is not None:
        lo = jnp.where(layer.window > 0,
                       jnp.maximum(pos - layer.window + 1, 0),
                       0).astype(jnp.int32)
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    rep = h // kvh
    qg = (q / jnp.sqrt(jnp.asarray(hd, q.dtype))).reshape(
        s_dim, kvh, rep, hd)
    acc_c, m_c, l_c = execute(
        plan, qg, PagedAttentionKV(k_pages, v_pages, page_table, pos, lo))
    # new-token block, then the flash two-block merge (the dense decode
    # read's rule verbatim): slots with no live context come back with
    # m_c = -1e30, l_c = 0 and renormalize onto the fresh token alone
    s_n = jnp.einsum("skrd,skd->skr", qg, k[:, 0].astype(qg.dtype),
                     preferred_element_type=jnp.float32)
    v_n = v.astype(jnp.float32)[:, 0]                     # (S, KV, hd)
    m = jnp.maximum(m_c, s_n)
    w_c = jnp.exp(m_c - m)
    w_n = jnp.exp(s_n - m)
    acc = acc_c * w_c[..., None] + w_n[..., None] * v_n[:, :, None, :]
    l = l_c * w_c + w_n
    out = (acc / l[..., None]).astype(q.dtype)            # (S,KV,rep,hd)
    out = out.reshape(s_dim, 1, h * hd)
    return (dense(out, w["wo"], cim_cfg, x_axes=ATTN_OUT),
            k[:, 0], v[:, 0])


def decode_attention(x, w, cfg: ModelConfig, cache: KVCache,
                     cim_cfg=None) -> tuple[jax.Array, KVCache]:
    """One-token decode against the cache (full or rolling window)."""
    b, s, _ = x.shape
    assert s == 1, "decode_attention is single-token"
    pos = cache.index                                  # absolute position
    q, k, v = _project_qkv(x, w, cfg, positions=pos[None, None],
                           cim_cfg=cim_cfg)
    cap = cache.k.shape[1]
    slot = pos % cap if cfg.sliding_window and cap == cfg.sliding_window else pos
    newk = jax.lax.dynamic_update_slice_in_dim(cache.k, k.astype(cache.k.dtype),
                                               slot, 1)
    newv = jax.lax.dynamic_update_slice_in_dim(cache.v, v.astype(cache.v.dtype),
                                               slot, 1)
    # validity mask over cache slots
    slots = jnp.arange(cap)
    if cfg.sliding_window and cap == cfg.sliding_window:
        valid = slots < jnp.minimum(pos + 1, cap)      # rolling: all written
    else:
        valid = slots <= pos
    mask = jnp.where(valid, 0.0, -1e30)[None, None, None, None, :]
    q, ck, cv = _constrain_qkv(q, newk, newv)
    out = _gqa_attend(q, ck, cv, mask, cfg)
    return (dense(out, w["wo"], cim_cfg, x_axes=ATTN_OUT),
            KVCache(newk, newv, pos + 1))
