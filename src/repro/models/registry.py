"""Model registry: every assigned architecture as a composable model.

A model instance exposes a uniform interface used by train/, serve/ and
launch/dryrun:

  param_defs             — pytree of ParamDef (shapes + logical axes)
  forward(p, batch)      — full-sequence logits (training / eval)
  cache_defs(B, cap)     — pytree of ParamDef for the decode state
  prefill(p, batch, cap) — consume a prompt, return (last_logits, state)
  decode(p, token, st)   — one-token step against the state

All stacks scan over layers (params carry a leading L axis) so HLO size
is O(1) in depth — a hard requirement for 100-layer dry-run compiles.
Every weight matmul routes through layers.dense() and therefore through
the paper's CIM execution modes (float | ternary packed | macro-exact).

Families:
  TransformerLM  — dense / moe / vlm (cross-attn every k-th layer)
  EncDecModel    — whisper (stub frame embeddings -> enc; dec self+cross)
  XLSTMModel     — alternating mLSTM/sLSTM pairs
  ZambaModel     — Mamba2 backbone + ONE shared (tied) attention block
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import attention as attn
from . import moe as moe_mod
from . import ssm
from .config import ModelConfig, ParamDef, init_params, is_def
from . import layers as layers_mod
from .layers import (attn_defs, dense, gelu_mlp, mlp_defs, norm_def, rms_norm,
                     sinusoidal_positions, swiglu)


# =====================================================================
# helpers
# =====================================================================

def _embed_defs(cfg: ModelConfig) -> dict:
    v = cfg.padded_vocab
    # the lookup table uses 'vocab_in' (never sharded over 'model'):
    # gathering from a vocab-sharded table forces SPMD into a full
    # rematerialization (all-gather of the whole table); keeping vocab
    # replicated and sharding the embed dim over 'data' (FSDP) keeps the
    # gather local.  The unembed projection stays TP over 'vocab'.
    return {
        "embed": ParamDef((v, cfg.d_model), ("vocab_in", "embed"), "embed"),
        "unembed": ParamDef((cfg.d_model, v), ("embed", "vocab")),
        "final_norm": norm_def(cfg),
    }


def _take_embed(table: jax.Array, tokens: jax.Array) -> jax.Array:
    from repro.dist.sharding import constrain_act
    return constrain_act(jnp.take(table, tokens, axis=0))


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def _slice_tree(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


class BaseModel:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.param_defs = self._param_defs()

    # --- overridables -------------------------------------------------
    def _param_defs(self) -> Any:
        raise NotImplementedError

    def forward(self, params, batch: dict, cim=None, return_aux: bool = False):
        raise NotImplementedError

    def cache_defs(self, batch: int, capacity: int) -> Any:
        raise NotImplementedError

    def prefill(self, params, batch: dict, capacity: int, cim=None):
        raise NotImplementedError

    def decode(self, params, token: jax.Array, state: Any, cim=None):
        raise NotImplementedError

    # --- paged KV (serve.PagedScheduler) ------------------------------
    @property
    def supports_paged_kv(self) -> bool:
        """Whether decode can run against the paged KV block pool
        (models/paged_kv.py).  Families whose decode state is not a
        plain per-position KV cache (SSM carries, tied cross caches)
        serve from the dense slot pool."""
        return False

    def decode_paged(self, params, token, pool, page_table, pos,
                     cim=None):
        """Read-only one-token decode against a gathered page view:
        returns (logits, k_new, v_new) — the cache write is the
        scheduler's page scatter (paged_kv.append_tokens)."""
        raise NotImplementedError(
            f"paged KV decode is not implemented for "
            f"{type(self).__name__} (family {self.cfg.family!r}); "
            f"serve it from the dense slot pool")

    def decode_paged_fused(self, params, tokens, pool, page_table, pos,
                           cim=None, attn_plan=None, live=None):
        """Batched one-token decode consuming the page pool in place
        through a planned ``op='attention'`` executor — no gathered
        dense copy.  Same read-only contract as :meth:`decode_paged`,
        but over ALL slots at once: ``tokens (S,)``, ``page_table
        (S, W)``, ``pos (S,)``; returns (logits (S, 1, V), kts
        (L, S, KV, hd), vts, moe_stats (2,) int32)."""
        raise NotImplementedError(
            f"fused paged decode is not implemented for "
            f"{type(self).__name__} (family {self.cfg.family!r}); "
            f"serve it through the slot_view gather path")

    # --- common -------------------------------------------------------
    def init(self, key: jax.Array, dtype=None):
        return init_params(key, self.param_defs, dtype or self.cfg.dtype)

    def init_cache(self, batch: int, capacity: int):
        defs = self.cache_defs(batch, capacity)

        def mk(d: ParamDef):
            dt = d.dtype or self.cfg.dtype
            if d.init == "ones":
                return jnp.ones(d.shape, dt)
            return jnp.zeros(d.shape, dt)
        return jax.tree.map(mk, defs, is_leaf=is_def)

    def loss(self, params, batch: dict, cim=None) -> jax.Array:
        """Mean next-token cross-entropy (+ MoE load-balance aux loss)."""
        logits, aux = self.forward(params, batch, cim=cim, return_aux=True)
        labels = batch["labels"]
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None],
                                   axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        ce = jnp.sum((logz - gold) * mask) / jnp.maximum(mask.sum(), 1.0)
        return ce + aux


# =====================================================================
# TransformerLM — dense / moe / vlm
# =====================================================================

class TransformerLM(BaseModel):
    """Decoder-only transformer.  MoE when cfg.num_experts > 0; gated
    cross-attention blocks every cfg.cross_attn_every layers (vlm)."""

    def _block_defs(self, L: int) -> dict:
        cfg = self.cfg
        d = {
            "ln1": norm_def(cfg, L),
            "ln2": norm_def(cfg, L),
            **attn_defs(cfg, L),
        }
        if cfg.num_experts:
            d.update(moe_mod.moe_defs(cfg, L))
        else:
            d.update(mlp_defs(cfg, L))
        return d

    def _param_defs(self):
        cfg = self.cfg
        p = {**_embed_defs(cfg), "blocks": self._block_defs(cfg.num_layers)}
        if cfg.cross_attn_every:
            n_cross = cfg.num_layers // cfg.cross_attn_every
            p["cross_blocks"] = {
                "ln": norm_def(cfg, n_cross),
                "gate": ParamDef((n_cross,), ("layers",), "zeros",
                                 jnp.float32),
                **attn_defs(cfg, n_cross, cross=True),
            }
        return p

    # ----- shared layer bodies ----------------------------------------
    def _mlp(self, x, wl, cim):
        return self._ffn(x, wl, cim)[:2]

    def _ffn(self, x, wl, cim, live=None):
        """The block's MLP: ``(y, aux_loss, moe_stats)``.  Packed experts
        under an exact int8 plan serve dropless through the grouped
        kernel (``moe.moe_serve``; ``live`` (B,) masks rows out of the
        routing, ``moe_stats`` = [routed (row, expert) pairs, experts
        with a row]); float experts keep GShard's capacity dispatch."""
        cfg = self.cfg
        none = jnp.zeros((2,), jnp.int32)
        if cfg.num_experts:
            if moe_mod.serves_dropless(wl, cim):
                y, stats = moe_mod.moe_serve(x, wl, cfg, cim, live)
                return y, 0.0, stats
            return moe_mod.moe_block(x, wl, cfg, cim) + (none,)
        return swiglu(x, wl["w1"], wl["w3"], wl["w2"], cim), 0.0, none

    def _layer_attn(self):
        """Each layer's ``attn.LayerAttn`` stacked over layers, scanned
        beside the blocks; None for a stack of one attention kind."""
        if not self.cfg.per_layer_attn:
            return None
        win, inv, scale = layers_mod.layer_attn_tables(self.cfg)
        return attn.LayerAttn(jnp.asarray(win), jnp.asarray(inv),
                              jnp.asarray(scale))

    def _self_block(self, x, wl, cim, positions=None, layer=None):
        cfg = self.cfg
        h = attn.self_attention(rms_norm(x, wl["ln1"], cfg.norm_eps), wl, cfg,
                                positions=positions, cim_cfg=cim,
                                layer=layer)
        x = x + h
        m, aux = self._mlp(rms_norm(x, wl["ln2"], cfg.norm_eps), wl, cim)
        return x + m, aux

    def _cross_block(self, x, kv_src, wc, cim):
        cfg = self.cfg
        h = attn.cross_attention(rms_norm(x, wc["ln"], cfg.norm_eps), kv_src,
                                 wc, cfg, cim_cfg=cim)
        return x + jnp.tanh(wc["gate"]).astype(x.dtype) * h

    # ----- forward (train) --------------------------------------------
    def forward(self, params, batch, cim=None, return_aux: bool = False):
        cfg = self.cfg
        x = _take_embed(params["embed"], batch["tokens"]).astype(cfg.dtype)
        aux = jnp.zeros((), jnp.float32)
        if cfg.cross_attn_every:
            x = self._forward_vlm(x, params, batch, cim)
        else:
            def body(carry, inp):
                x, aux = carry
                wl, lat = inp
                x, a = self._self_block(x, wl, cim, layer=lat)
                return (x, aux + a), None
            (x, aux), _ = jax.lax.scan(_maybe_remat(body, cfg), (x, aux),
                                       (params["blocks"],
                                        self._layer_attn()))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = dense(x, params["unembed"], cim)
        return (logits, aux) if return_aux else logits

    def _forward_vlm(self, x, params, batch, cim):
        cfg = self.cfg
        k = cfg.cross_attn_every
        ng = cfg.num_layers // k
        grouped = jax.tree.map(
            lambda a: a.reshape((ng, k) + a.shape[1:]), params["blocks"])
        patches = batch["patches"].astype(cfg.dtype)

        def group(x, wg):
            w_self, w_cross = wg
            inner = _maybe_remat(
                lambda x, wl: (self._self_block(x, wl, cim)[0], None), cfg)
            x, _ = jax.lax.scan(inner, x, w_self)
            x = self._cross_block(x, patches, w_cross, cim)
            return x, None

        x, _ = jax.lax.scan(group, x, (grouped, params["cross_blocks"]))
        return x

    # ----- serve --------------------------------------------------------
    def cache_defs(self, batch: int, capacity: int):
        cfg = self.cfg
        L, kv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.hd
        cap = min(capacity, cfg.sliding_window) if cfg.sliding_window else capacity
        kvshape = (L, batch, cap, kv, hd)
        kvaxes = ("layers", "batch", "cache_seq", "kv", "none")
        kvdt = jnp.int8 if cfg.kv_cache_dtype == "int8" else None
        defs = {"k": ParamDef(kvshape, kvaxes, dtype=kvdt),
                "v": ParamDef(kvshape, kvaxes, dtype=kvdt),
                "pos": ParamDef((), (), "zeros", jnp.int32)}
        if cfg.kv_cache_dtype == "int8":
            saxes = ("layers", "batch", "cache_seq", "kv")
            defs["k_scale"] = ParamDef((L, batch, cap, kv), saxes, "zeros",
                                       jnp.float32)
            defs["v_scale"] = ParamDef((L, batch, cap, kv), saxes, "zeros",
                                       jnp.float32)
        if cfg.cross_attn_every:
            ng = cfg.num_layers // cfg.cross_attn_every
            # cross k/v computed once from patch embeddings at prefill
            p = (ng, batch, self.cfg.encoder_seq or 1024, kv, hd)
            pax = ("layers", "batch", "seq", "kv", "none")
            defs["xk"] = ParamDef(p, pax)
            defs["xv"] = ParamDef(p, pax)
        return defs

    def _scan_cached(self, x, params, state, step_fn, cim):
        """Scan over layers threading per-layer KV cache slices
        (prefill: the whole cache is legitimately materialized once)."""
        cfg = self.cfg
        if not cfg.cross_attn_every:
            def body(x, inp):
                wl, k_l, v_l, lat = inp
                cache = attn.KVCache(k_l, v_l, state["pos"])
                x, newc = step_fn(x, wl, cache, lat, cim)
                return x, (newc.k, newc.v)
            x, (ks, vs) = jax.lax.scan(
                body, x, (params["blocks"], state["k"], state["v"],
                          self._layer_attn()))
            return x, ks, vs
        k = cfg.cross_attn_every
        ng = cfg.num_layers // k
        grouped = jax.tree.map(
            lambda a: a.reshape((ng, k) + a.shape[1:]), params["blocks"])
        kg = state["k"].reshape((ng, k) + state["k"].shape[1:])
        vg = state["v"].reshape((ng, k) + state["v"].shape[1:])

        def group(x, inp):
            wg, wc, k_g, v_g, xk_g, xv_g = inp

            def body(x, inner):
                wl, k_l, v_l = inner
                cache = attn.KVCache(k_l, v_l, state["pos"])
                x, newc = step_fn(x, wl, cache, None, cim)
                return x, (newc.k, newc.v)
            x, (ks, vs) = jax.lax.scan(body, x, (wg, k_g, v_g))
            h = attn._gqa_attend(
                attn.dense(rms_norm(x, wc["ln"], cfg.norm_eps), wc["wq"], cim)
                .reshape(x.shape[0], x.shape[1], cfg.num_heads, cfg.hd),
                xk_g, xv_g, None, cfg)
            h = dense(h, wc["wo"], cim, x_axes=layers_mod.ATTN_OUT)
            x = x + jnp.tanh(wc["gate"]).astype(x.dtype) * h
            return x, (ks, vs)

        x, (ks, vs) = jax.lax.scan(
            group, x, (grouped, params["cross_blocks"], kg, vg,
                       state["xk"], state["xv"]))
        ks = ks.reshape((ng * k,) + ks.shape[2:])
        vs = vs.reshape((ng * k,) + vs.shape[2:])
        return x, ks, vs

    def _precompute_cross(self, params, patches, cim):
        """Project patch embeddings to per-cross-layer K/V once."""
        cfg = self.cfg
        kv, hd = cfg.num_kv_heads, cfg.hd
        b, p, _ = patches.shape

        def one(wc):
            k = dense(patches.astype(cfg.dtype), wc["wk"], cim).reshape(
                b, p, kv, hd)
            v = dense(patches.astype(cfg.dtype), wc["wv"], cim).reshape(
                b, p, kv, hd)
            return k, v
        xk, xv = jax.lax.map(one, params["cross_blocks"])
        return xk, xv

    def prefill(self, params, batch, capacity: int, cim=None):
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        state = self.init_cache(b, capacity)
        if cfg.cross_attn_every:
            state["xk"], state["xv"] = self._precompute_cross(
                params, batch["patches"], cim)
        x = _take_embed(params["embed"], tokens).astype(cfg.dtype)
        state["pos"] = jnp.zeros((), jnp.int32)

        def step(x, wl, cache, lat, cim):
            xa = rms_norm(x, wl["ln1"], cfg.norm_eps)
            out, newc = attn.prefill_attention(xa, wl, cfg, cache, cim,
                                               layer=lat)
            x = x + out
            m, _ = self._mlp(rms_norm(x, wl["ln2"], cfg.norm_eps), wl, cim)
            return x + m, newc

        scratch = state
        if cfg.kv_cache_dtype == "int8":
            # prefill builds the cache in compute dtype, then quantizes
            z = jnp.zeros(state["k"].shape, cfg.dtype)
            scratch = dict(state, k=z, v=z)
        x, ks, vs = self._scan_cached(x, params, scratch, step, cim)
        if cfg.kv_cache_dtype == "int8":
            state["k"], state["k_scale"] = attn.quantize_kv(ks)
            state["v"], state["v_scale"] = attn.quantize_kv(vs)
        else:
            state["k"], state["v"] = ks, vs
        state["pos"] = jnp.asarray(s, jnp.int32)
        x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
        return dense(x, params["unembed"], cim), state

    def _decode_read_scan(self, params, x, state, cim):
        """Read-only decode layer scan: attend over the (stale) cached
        KV + the fresh token, collect every layer's new k/v for ONE
        batched write.  ``state`` is either the dense cache dict or a
        paged gather-view (paged_kv.slot_view) — same layout, so the
        dense and paged decode paths share this graph bit-for-bit."""
        cfg = self.cfg
        int8_kv = cfg.kv_cache_dtype == "int8"

        def body(x, inp):
            if int8_kv:
                wl, lat, k_l, v_l, ks_l, vs_l = inp
                cache = attn.KVCache(k_l, v_l, state["pos"], ks_l, vs_l)
            else:
                wl, lat, k_l, v_l = inp
                cache = attn.KVCache(k_l, v_l, state["pos"])
            xa = rms_norm(x, wl["ln1"], cfg.norm_eps)
            out, kt, vt = attn.decode_attention_read(xa, wl, cfg, cache,
                                                     cim, layer=lat)
            x = x + out
            m, _ = self._mlp(rms_norm(x, wl["ln2"], cfg.norm_eps), wl, cim)
            return x + m, (kt, vt)

        xs = (params["blocks"], self._layer_attn(), state["k"], state["v"])
        if int8_kv:
            xs = xs + (state["k_scale"], state["v_scale"])
        x, (kts, vts) = jax.lax.scan(body, x, xs)
        return x, kts, vts

    @property
    def supports_paged_kv(self) -> bool:
        # the vlm grouped path fuses its cache write into the layer
        # scan, and a model-wide ``sliding_window`` decodes against a
        # ROLLING cache (slot = pos % window, engaged only when cap ==
        # window) that a page-gathered view's capacity would silently
        # disarm.  Per-layer windows (``layer_windows``) keep every
        # position and mask (or, fused, skip) per layer, so they page
        # like the plain full-cache read-then-write decode; a page
        # still holds every layer's KV, so window layers free no pages
        return not self.cfg.cross_attn_every and \
            not self.cfg.sliding_window

    def decode_paged(self, params, token, pool, page_table, pos,
                     cim=None):
        """One-token decode against the paged page pool: gather the
        slot's page-table row into the dense cache layout
        (paged_kv.slot_view) and run the shared read-only scan.
        Returns (logits, kts (L, 1, 1, KV, hd), vts) in COMPUTE dtype —
        the scheduler scatters them into pages (and quantizes for
        int8-KV pools), mirroring ``decode``'s dense write."""
        if not self.supports_paged_kv:
            return super().decode_paged(params, token, pool, page_table,
                                        pos, cim)
        from . import paged_kv
        cfg = self.cfg
        view = paged_kv.slot_view(pool, page_table, pos)
        x = _take_embed(params["embed"], token).astype(cfg.dtype)
        x, kts, vts = self._decode_read_scan(params, x, view, cim)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return dense(x, params["unembed"], cim), kts, vts

    def decode_paged_fused(self, params, tokens, pool, page_table, pos,
                           cim=None, attn_plan=None, live=None):
        """Batched one-token decode straight off the page pool: the
        layer scan carries each layer's raw page arrays and the planned
        attention executor (``attn_plan``, an ``op='attention'``
        ExecutionPlan) reads them through the page table in-kernel —
        the ``slot_view`` gather copy is never materialized.  Returns
        (logits (S, 1, V), kts (L, S, KV, hd), vts, moe_stats (2,)) in
        compute dtype; the scheduler's page scatter is unchanged.
        ``live`` (S,) keeps dead slots out of a dropless MoE's routing;
        ``moe_stats`` are its counts summed over layers (``_ffn``),
        zeros for a dense model."""
        if not self.supports_paged_kv:
            return super().decode_paged_fused(params, tokens, pool,
                                              page_table, pos, cim,
                                              attn_plan)
        if attn_plan is None:
            raise ValueError("decode_paged_fused needs a resolved "
                             "attention plan (PagedScheduler resolves "
                             "one per pool geometry)")
        from . import paged_kv
        cfg = self.cfg
        k_pages, v_pages = paged_kv.raw_pool_view(pool)
        x = _take_embed(params["embed"], tokens[:, None]).astype(cfg.dtype)

        def body(x, inp):
            wl, lat, k_l, v_l = inp
            xa = rms_norm(x, wl["ln1"], cfg.norm_eps)
            out, kt, vt = attn.paged_decode_attention_read(
                xa, wl, cfg, k_l, v_l, page_table, pos, attn_plan, cim,
                layer=lat)
            x = x + out
            m, _, st = self._ffn(rms_norm(x, wl["ln2"], cfg.norm_eps), wl,
                                 cim, live)
            return x + m, (kt, vt, st)

        x, (kts, vts, sts) = jax.lax.scan(
            body, x, (params["blocks"], self._layer_attn(), k_pages,
                      v_pages))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return dense(x, params["unembed"], cim), kts, vts, sts.sum(0)

    def decode(self, params, token, state, cim=None):
        cfg = self.cfg
        x = _take_embed(params["embed"], token).astype(cfg.dtype)

        if cfg.cross_attn_every:                 # vlm: grouped path
            def step(x, wl, cache, _, cim):
                xa = rms_norm(x, wl["ln1"], cfg.norm_eps)
                out, newc = attn.decode_attention(xa, wl, cfg, cache, cim)
                x = x + out
                m, _ = self._mlp(rms_norm(x, wl["ln2"], cfg.norm_eps), wl,
                                 cim)
                return x + m, newc

            x, ks, vs = self._scan_cached(x, params, state, step, cim)
            new_state = dict(state, k=ks, v=vs, pos=state["pos"] + 1)
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
            return dense(x, params["unembed"], cim), new_state

        # read-only layer scan + ONE batched in-place cache write
        int8_kv = cfg.kv_cache_dtype == "int8"
        x, kts, vts = self._decode_read_scan(params, x, state, cim)
        cap = state["k"].shape[2]
        rolling = cfg.sliding_window and cap == cfg.sliding_window
        pos = state["pos"]
        slot = (pos % cap if rolling else pos).astype(jnp.int32)
        zero = jnp.zeros((), jnp.int32)
        idx = (zero, zero, slot, zero, zero)
        new_state = dict(state, pos=pos + 1)
        if int8_kv:
            kq, ksc = attn.quantize_kv(kts)          # (L,B,1,kv,*) codes
            vq, vsc = attn.quantize_kv(vts)
            new_state["k"] = jax.lax.dynamic_update_slice(state["k"], kq,
                                                          idx)
            new_state["v"] = jax.lax.dynamic_update_slice(state["v"], vq,
                                                          idx)
            new_state["k_scale"] = jax.lax.dynamic_update_slice(
                state["k_scale"], ksc, idx[:-1])
            new_state["v_scale"] = jax.lax.dynamic_update_slice(
                state["v_scale"], vsc, idx[:-1])
        else:
            new_state["k"] = jax.lax.dynamic_update_slice(
                state["k"], kts.astype(state["k"].dtype), idx)
            new_state["v"] = jax.lax.dynamic_update_slice(
                state["v"], vts.astype(state["v"].dtype), idx)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return dense(x, params["unembed"], cim), new_state


# =====================================================================
# EncDec — whisper backbone (conv frontend stubbed: frames are embeddings)
# =====================================================================

class EncDecModel(BaseModel):
    def _param_defs(self):
        cfg = self.cfg
        Le, Ld = cfg.encoder_layers, cfg.num_layers
        return {
            **_embed_defs(cfg),
            "enc_blocks": {"ln1": norm_def(cfg, Le), "ln2": norm_def(cfg, Le),
                           **attn_defs(cfg, Le), **mlp_defs(cfg, Le, gated=False)},
            "enc_norm": norm_def(cfg),
            "dec_blocks": {"ln1": norm_def(cfg, Ld), "ln2": norm_def(cfg, Ld),
                           "ln3": norm_def(cfg, Ld),
                           **attn_defs(cfg, Ld),
                           **{f"x_{k}": v for k, v in
                              attn_defs(cfg, Ld, cross=True).items()},
                           **mlp_defs(cfg, Ld, gated=False)},
        }

    def encode(self, params, frames, cim=None):
        cfg = self.cfg
        x = frames.astype(cfg.dtype)
        x = x + sinusoidal_positions(x.shape[1], cfg.d_model).astype(cfg.dtype)

        def body(x, wl):
            h = attn.self_attention(rms_norm(x, wl["ln1"], cfg.norm_eps), wl,
                                    cfg, causal=False, cim_cfg=cim)
            x = x + h
            m = gelu_mlp(rms_norm(x, wl["ln2"], cfg.norm_eps),
                         wl["w1"], wl["w2"], cim)
            return x + m, None
        x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, params["enc_blocks"])
        return rms_norm(x, params["enc_norm"], cfg.norm_eps)

    def _dec_block(self, x, enc, wl, cim, cache=None, mode="train"):
        cfg = self.cfg
        xa = rms_norm(x, wl["ln1"], cfg.norm_eps)
        if mode == "train":
            h = attn.self_attention(xa, wl, cfg, cim_cfg=cim)
            newc = None
        elif mode == "prefill":
            h, newc = attn.prefill_attention(xa, wl, cfg, cache, cim)
        else:
            h, newc = attn.decode_attention(xa, wl, cfg, cache, cim)
        x = x + h
        wx = {k[2:]: v for k, v in wl.items() if k.startswith("x_")}
        h = attn.cross_attention(rms_norm(x, wl["ln2"], cfg.norm_eps), enc,
                                 wx, cfg, cim_cfg=cim)
        x = x + h
        m = gelu_mlp(rms_norm(x, wl["ln3"], cfg.norm_eps), wl["w1"], wl["w2"],
                     cim)
        return x + m, newc

    def forward(self, params, batch, cim=None, return_aux: bool = False):
        cfg = self.cfg
        enc = self.encode(params, batch["frames"], cim)
        x = _take_embed(params["embed"], batch["tokens"]).astype(cfg.dtype)
        body = _maybe_remat(
            lambda x, wl: (self._dec_block(x, enc, wl, cim)[0], None), cfg)
        x, _ = jax.lax.scan(body, x, params["dec_blocks"])
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = dense(x, params["unembed"], cim)
        return (logits, jnp.zeros((), jnp.float32)) if return_aux else logits

    def cache_defs(self, batch: int, capacity: int):
        cfg = self.cfg
        L, kv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.hd
        enc_s = cfg.encoder_seq
        kvshape = (L, batch, capacity, kv, hd)
        kvaxes = ("layers", "batch", "cache_seq", "kv", "none")
        xshape = (L, batch, enc_s, kv, hd)
        xaxes = ("layers", "batch", "seq", "kv", "none")
        return {"k": ParamDef(kvshape, kvaxes), "v": ParamDef(kvshape, kvaxes),
                "xk": ParamDef(xshape, xaxes), "xv": ParamDef(xshape, xaxes),
                "pos": ParamDef((), (), "zeros", jnp.int32)}

    def _cross_kv(self, params, enc, cim):
        cfg = self.cfg
        b, t, _ = enc.shape
        kv, hd = cfg.num_kv_heads, cfg.hd

        def one(wl):
            k = dense(enc, wl["x_wk"], cim).reshape(b, t, kv, hd)
            v = dense(enc, wl["x_wv"], cim).reshape(b, t, kv, hd)
            return k, v
        return jax.lax.map(one, params["dec_blocks"])

    def _run_dec(self, params, x, state, mode, cim):
        cfg = self.cfg

        def body(x, inp):
            wl, k_l, v_l, xk_l, xv_l = inp
            cache = attn.KVCache(k_l, v_l, state["pos"])
            wx = {k[2:]: v for k, v in wl.items() if k.startswith("x_")}
            xa = rms_norm(x, wl["ln1"], cfg.norm_eps)
            if mode == "prefill":
                h, newc = attn.prefill_attention(xa, wl, cfg, cache, cim)
            else:
                h, newc = attn.decode_attention(xa, wl, cfg, cache, cim)
            x = x + h
            # cross-attn against precomputed enc K/V
            q = dense(rms_norm(x, wl["ln2"], cfg.norm_eps), wx["wq"], cim)
            q = q.reshape(x.shape[0], x.shape[1], cfg.num_heads, cfg.hd)
            h = attn._gqa_attend(q, xk_l, xv_l, None, cfg)
            x = x + dense(h, wx["wo"], cim, x_axes=layers_mod.ATTN_OUT)
            m = gelu_mlp(rms_norm(x, wl["ln3"], cfg.norm_eps), wl["w1"],
                         wl["w2"], cim)
            return x + m, (newc.k, newc.v)

        x, (ks, vs) = jax.lax.scan(
            body, x, (params["dec_blocks"], state["k"], state["v"],
                      state["xk"], state["xv"]))
        return x, ks, vs

    def prefill(self, params, batch, capacity: int, cim=None):
        cfg = self.cfg
        b, s = batch["tokens"].shape
        enc = self.encode(params, batch["frames"], cim)
        state = self.init_cache(b, capacity)
        state["xk"], state["xv"] = self._cross_kv(params, enc, cim)
        x = _take_embed(params["embed"], batch["tokens"]).astype(cfg.dtype)
        x, ks, vs = self._run_dec(params, x, state, "prefill", cim)
        state["k"], state["v"] = ks, vs
        state["pos"] = jnp.asarray(s, jnp.int32)
        x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
        return dense(x, params["unembed"], cim), state

    def decode(self, params, token, state, cim=None):
        cfg = self.cfg
        x = _take_embed(params["embed"], token).astype(cfg.dtype)
        x, ks, vs = self._run_dec(params, x, state, "decode", cim)
        new_state = dict(state, k=ks, v=vs, pos=state["pos"] + 1)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return dense(x, params["unembed"], cim), new_state


# =====================================================================
# xLSTM — alternating (mLSTM, sLSTM) pairs
# =====================================================================

class XLSTMModel(BaseModel):
    @property
    def n_pairs(self) -> int:
        return self.cfg.num_layers // 2

    def _param_defs(self):
        cfg = self.cfg
        n = self.n_pairs
        return {
            **_embed_defs(cfg),
            "m_ln": norm_def(cfg, n),
            "s_ln": norm_def(cfg, n),
            "mlstm": ssm.mlstm_defs(cfg, n),
            "slstm": ssm.slstm_defs(cfg, n),
        }

    def _pair(self, x, wl, cim, m_state=None, s_state=None):
        cfg = self.cfg
        wm, ws, lm, ls = wl["mlstm"], wl["slstm"], wl["m_ln"], wl["s_ln"]
        h, new_m = ssm.mlstm_block(rms_norm(x, lm, cfg.norm_eps), wm, cfg,
                                   m_state, cim)
        x = x + h
        h, new_s = ssm.slstm_block(rms_norm(x, ls, cfg.norm_eps), ws, cfg,
                                   s_state, cim)
        return x + h, new_m, new_s

    def forward(self, params, batch, cim=None, return_aux: bool = False):
        cfg = self.cfg
        x = _take_embed(params["embed"], batch["tokens"]).astype(cfg.dtype)
        stack = {"mlstm": params["mlstm"], "slstm": params["slstm"],
                 "m_ln": params["m_ln"], "s_ln": params["s_ln"]}
        body = _maybe_remat(
            lambda x, wl: (self._pair(x, wl, cim)[0], None), cfg)
        x, _ = jax.lax.scan(body, x, stack)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = dense(x, params["unembed"], cim)
        return (logits, jnp.zeros((), jnp.float32)) if return_aux else logits

    def cache_defs(self, batch: int, capacity: int):
        cfg = self.cfg
        n = self.n_pairs
        d_up, heads, hd = ssm.xlstm_dims(cfg)
        sh, shd = cfg.num_heads, cfg.d_model // cfg.num_heads
        f32 = jnp.float32
        ax4 = ("layers", "batch", "heads", "none", "none")
        ax3 = ("layers", "batch", "heads", "none")
        ax2 = ("layers", "batch", "heads")
        return {
            "m_C": ParamDef((n, batch, heads, hd, hd), ax4, "zeros", f32),
            "m_n": ParamDef((n, batch, heads, hd), ax3, "zeros", f32),
            "m_m": ParamDef((n, batch, heads), ax2, "zeros", f32),
            "s_c": ParamDef((n, batch, sh, shd), ax3, "zeros", f32),
            "s_n": ParamDef((n, batch, sh, shd), ax3, "ones", f32),
            "s_m": ParamDef((n, batch, sh), ax2, "zeros", f32),
            "s_h": ParamDef((n, batch, sh, shd), ax3, "zeros", f32),
            "pos": ParamDef((), (), "zeros", jnp.int32),
        }

    def _scan_pairs(self, params, x, state, cim, use_state: bool):
        stack = {"mlstm": params["mlstm"], "slstm": params["slstm"],
                 "m_ln": params["m_ln"], "s_ln": params["s_ln"]}

        def body(x, inp):
            wl, st = inp
            if use_state:
                m_st = ssm.XLSTMState(st["m_C"], st["m_n"], st["m_m"],
                                      jnp.zeros_like(st["s_h"][..., :0]),
                                      state["pos"])
                s_st = ssm.XLSTMState(st["s_c"][..., None], st["s_n"],
                                      st["s_m"], st["s_h"], state["pos"])
            else:
                m_st = s_st = None
            x, new_m, new_s = self._pair(x, wl, cim, m_st, s_st)
            out = {"m_C": new_m.C, "m_n": new_m.n, "m_m": new_m.m,
                   "s_c": new_s.C[..., 0], "s_n": new_s.n, "s_m": new_s.m,
                   "s_h": new_s.h}
            return x, out

        st_in = {k: state[k] for k in
                 ("m_C", "m_n", "m_m", "s_c", "s_n", "s_m", "s_h")}
        x, st_out = jax.lax.scan(body, x, (stack, st_in))
        return x, st_out

    def prefill(self, params, batch, capacity: int, cim=None):
        cfg = self.cfg
        b, s = batch["tokens"].shape
        state = self.init_cache(b, capacity)
        x = _take_embed(params["embed"], batch["tokens"]).astype(cfg.dtype)
        x, st = self._scan_pairs(params, x, state, cim, use_state=False)
        state.update(st)
        state["pos"] = jnp.asarray(s, jnp.int32)
        x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
        return dense(x, params["unembed"], cim), state

    def decode(self, params, token, state, cim=None):
        cfg = self.cfg
        x = _take_embed(params["embed"], token).astype(cfg.dtype)
        x, st = self._scan_pairs(params, x, state, cim, use_state=True)
        new_state = dict(state, **st, pos=state["pos"] + 1)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return dense(x, params["unembed"], cim), new_state


# =====================================================================
# Zamba2 — Mamba2 backbone + one SHARED attention block every k layers
# =====================================================================

class ZambaModel(BaseModel):
    """cfg.num_layers Mamba2 layers; after every cfg.attn_every of them the
    single shared (weight-tied) attention block runs — tied weights, but a
    separate KV cache per invocation."""

    @property
    def n_groups(self) -> int:
        return self.cfg.num_layers // self.cfg.attn_every

    @property
    def n_tail(self) -> int:
        return self.cfg.num_layers - self.n_groups * self.cfg.attn_every

    def _param_defs(self):
        cfg = self.cfg
        L = cfg.num_layers
        return {
            **_embed_defs(cfg),
            "mamba_ln": norm_def(cfg, L),
            "mamba": ssm.mamba2_defs(cfg, L),
            "shared_ln": norm_def(cfg),
            "shared_attn": {k: ParamDef(v.shape[1:], v.axes[1:], v.init,
                                        v.dtype)
                            for k, v in attn_defs(cfg, 1).items()},
            # Zamba2's shared block is attention + MLP (both weight-tied);
            # d_ff comes from the assigned config (14336 for zamba2-7b).
            "shared_mlp_ln": norm_def(cfg),
            "shared_mlp": {k: ParamDef(v.shape[1:], v.axes[1:], v.init,
                                       v.dtype)
                           for k, v in mlp_defs(cfg, 1).items()},
        }

    def _shared_mlp(self, x, params, cim):
        cfg = self.cfg
        wm = params["shared_mlp"]
        return swiglu(rms_norm(x, params["shared_mlp_ln"], cfg.norm_eps),
                      wm["w1"], wm["w3"], wm["w2"], cim)

    def _mamba_scan(self, x, stack, cim, states=None):
        cfg = self.cfg

        def body(x, inp):
            if states is None:
                wl, ln = inp
                st = None
            else:
                wl, ln, st = inp
            h, new_st = ssm.mamba2_block(rms_norm(x, ln, cfg.norm_eps), wl,
                                         cfg, st, cim)
            out = None if states is None else new_st
            return x + h, out
        xs = (stack["mamba"], stack["mamba_ln"]) if states is None else (
            stack["mamba"], stack["mamba_ln"], states)
        return jax.lax.scan(_maybe_remat(body, cfg) if states is None
                            else body, x, xs)

    def _grouped(self, params):
        cfg = self.cfg
        k, ng = cfg.attn_every, self.n_groups
        head = jax.tree.map(lambda a: a[: ng * k].reshape((ng, k) + a.shape[1:]),
                            {"mamba": params["mamba"],
                             "mamba_ln": params["mamba_ln"]})
        tail = jax.tree.map(lambda a: a[ng * k:],
                            {"mamba": params["mamba"],
                             "mamba_ln": params["mamba_ln"]})
        return head, tail

    def forward(self, params, batch, cim=None, return_aux: bool = False):
        cfg = self.cfg
        x = _take_embed(params["embed"], batch["tokens"]).astype(cfg.dtype)
        head, tail = self._grouped(params)
        shared = params["shared_attn"]

        def group(x, wg):
            x, _ = self._mamba_scan(x, wg, cim)
            h = attn.self_attention(
                rms_norm(x, params["shared_ln"], cfg.norm_eps), shared, cfg,
                cim_cfg=cim)
            x = x + h
            return x + self._shared_mlp(x, params, cim), None

        x, _ = jax.lax.scan(group, x, head)
        if self.n_tail:
            x, _ = self._mamba_scan(x, tail, cim)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = dense(x, params["unembed"], cim)
        return (logits, jnp.zeros((), jnp.float32)) if return_aux else logits

    def cache_defs(self, batch: int, capacity: int):
        cfg = self.cfg
        L, ng = cfg.num_layers, self.n_groups
        d_inner, heads, hd, st, groups, conv_dim = ssm.mamba2_dims(cfg)
        kv, ahd = cfg.num_kv_heads, cfg.hd
        f32 = jnp.float32
        return {
            "h": ParamDef((L, batch, heads, hd, st),
                          ("layers", "batch", "heads", "none", "none"),
                          "zeros", f32),
            "conv": ParamDef((L, batch, 3, conv_dim),
                             ("layers", "batch", "none", "inner"), "zeros"),
            "k": ParamDef((ng, batch, capacity, kv, ahd),
                          ("layers", "batch", "cache_seq", "kv", "none")),
            "v": ParamDef((ng, batch, capacity, kv, ahd),
                          ("layers", "batch", "cache_seq", "kv", "none")),
            "pos": ParamDef((), (), "zeros", jnp.int32),
        }

    def _run(self, params, x, state, mode, cim):
        cfg = self.cfg
        k, ng = cfg.attn_every, self.n_groups
        head, tail = self._grouped(params)
        shared = params["shared_attn"]
        # broadcast the scalar position over the layer axis so the state
        # pytree slices uniformly through the grouped scans
        L = cfg.num_layers
        mamba_states = ssm.Mamba2State(
            state["h"], state["conv"],
            jnp.broadcast_to(state["pos"], (L,)))
        head_states = jax.tree.map(
            lambda a: a[: ng * k].reshape((ng, k) + a.shape[1:]),
            mamba_states)
        tail_states = jax.tree.map(lambda a: a[ng * k:], mamba_states)

        def group(x, inp):
            wg, sg, k_l, v_l = inp
            x, new_sg = self._mamba_scan(x, wg, cim, states=sg)
            cache = attn.KVCache(k_l, v_l, state["pos"])
            xa = rms_norm(x, params["shared_ln"], cfg.norm_eps)
            if mode == "prefill":
                h, newc = attn.prefill_attention(xa, shared, cfg, cache, cim)
            else:
                h, newc = attn.decode_attention(xa, shared, cfg, cache, cim)
            x = x + h
            x = x + self._shared_mlp(x, params, cim)
            return x, (new_sg, newc.k, newc.v)

        x, (new_head, ks, vs) = jax.lax.scan(
            group, x, (head, head_states, state["k"], state["v"]))
        if self.n_tail:
            x, new_tail = self._mamba_scan(x, tail, cim, states=tail_states)
        else:
            new_tail = tail_states
        flat_head = jax.tree.map(
            lambda a: a.reshape((ng * k,) + a.shape[2:]), new_head)
        merged = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0),
                              flat_head, new_tail)
        return x, merged, ks, vs

    def prefill(self, params, batch, capacity: int, cim=None):
        cfg = self.cfg
        b, s = batch["tokens"].shape
        state = self.init_cache(b, capacity)
        x = _take_embed(params["embed"], batch["tokens"]).astype(cfg.dtype)
        x, mstates, ks, vs = self._run(params, x, state, "prefill", cim)
        state.update(h=mstates.h, conv=mstates.conv, k=ks, v=vs,
                     pos=jnp.asarray(s, jnp.int32))
        x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
        return dense(x, params["unembed"], cim), state

    def decode(self, params, token, state, cim=None):
        cfg = self.cfg
        x = _take_embed(params["embed"], token).astype(cfg.dtype)
        x, mstates, ks, vs = self._run(params, x, state, "decode", cim)
        new_state = dict(state, h=mstates.h, conv=mstates.conv, k=ks, v=vs,
                         pos=state["pos"] + 1)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return dense(x, params["unembed"], cim), new_state


# =====================================================================

@functools.lru_cache(maxsize=None)
def build(cfg: ModelConfig) -> BaseModel:
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        return TransformerLM(cfg)
    if fam == "audio":
        return EncDecModel(cfg)
    if fam == "ssm" and cfg.ssm_kind == "xlstm":
        return XLSTMModel(cfg)
    if fam == "hybrid":
        return ZambaModel(cfg)
    raise ValueError(f"unknown family {fam!r} / ssm_kind {cfg.ssm_kind!r}")
