"""ShapeDtypeStruct stand-ins for every model input, per (arch x shape).

Everything here is weak-type-correct, carries a NamedSharding resolved
through the logical-axis rules, and never allocates device memory — the
dry-run lowers and compiles against these.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.shapes import ShapeCell
from repro.dist import sharding as shd
from repro.models.config import ParamDef, abstract_params


def _sds(shape, dtype, axes, rules, mesh):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=shd.named_sharding(axes, shape, rules, mesh))


def train_batch_specs(cfg, cell: ShapeCell, rules, mesh) -> dict:
    b, s = cell.global_batch, cell.seq_len
    batch = {
        "tokens": _sds((b, s), jnp.int32, ("batch", "seq"), rules, mesh),
        "labels": _sds((b, s), jnp.int32, ("batch", "seq"), rules, mesh),
    }
    if cfg.family == "audio":
        batch["frames"] = _sds((b, cfg.encoder_seq, cfg.d_model), cfg.dtype,
                               ("batch", "seq", "act_embed"), rules, mesh)
    if cfg.family == "vlm":
        batch["patches"] = _sds((b, cfg.encoder_seq, cfg.d_model), cfg.dtype,
                                ("batch", "seq", "act_embed"), rules, mesh)
    return batch


def prefill_batch_specs(cfg, cell: ShapeCell, rules, mesh) -> dict:
    b, s = cell.global_batch, cell.seq_len
    batch = {"tokens": _sds((b, s), jnp.int32, ("batch", "seq"), rules, mesh)}
    if cfg.family == "audio":
        batch["frames"] = _sds((b, cfg.encoder_seq, cfg.d_model), cfg.dtype,
                               ("batch", "seq", "act_embed"), rules, mesh)
    if cfg.family == "vlm":
        batch["patches"] = _sds((b, cfg.encoder_seq, cfg.d_model), cfg.dtype,
                                ("batch", "seq", "act_embed"), rules, mesh)
    return batch


def decode_token_spec(cell: ShapeCell, rules, mesh):
    return _sds((cell.global_batch, 1), jnp.int32, ("batch", "none"),
                rules, mesh)


def abstract_model_params(model, rules, mesh, packed: str | None = None):
    """Params as ShapeDtypeStructs with shardings.

    packed='base3'|'trit2' replaces every eligible weight with an abstract
    PackedTernary (uint8 data + per-column scales) — the ternary-served
    dry-run (paper density mechanism in the memory-roofline term).
    """
    mk = lambda d: shd.named_sharding(d.axes, d.shape, rules, mesh)
    if packed is None:
        return abstract_params(model.param_defs, model.cfg.dtype, mk)

    from repro.kernels.ops import PackedTernary, TRIT2_PER_BYTE

    def convert(d: ParamDef):
        dt = d.dtype or model.cfg.dtype
        # routers stay float (routing-logit precision; f32 ParamDefs are
        # excluded because their dtype is pinned)
        eligible = (d.init == "normal" and len(d.shape) >= 2
                    and min(d.shape[-2:]) >= 256 and "vocab" != d.axes[0]
                    and d.dtype is None)
        if not eligible:
            sh = mk(d)
            return jax.ShapeDtypeStruct(d.shape, dt, sharding=sh)
        k, n = d.shape[-2], d.shape[-1]
        lead = d.shape[:-2]
        if packed == "trit2":
            data_shape = lead + (k // TRIT2_PER_BYTE, n)   # 4 trits / byte
        else:
            data_shape = lead + (k, n)                     # 1 byte / 5-trit
        data = jax.ShapeDtypeStruct(
            data_shape, jnp.uint8,
            sharding=shd.named_sharding(d.axes, data_shape, rules, mesh))
        scale_shape = lead + (n,)
        scale_axes = d.axes[:-2] + (d.axes[-1],)
        scale = jax.ShapeDtypeStruct(
            scale_shape, jnp.float32,
            sharding=shd.named_sharding(scale_axes, scale_shape, rules, mesh))
        return PackedTernary(data, scale, packed)

    return jax.tree.map(convert, model.param_defs,
                        is_leaf=lambda x: isinstance(x, ParamDef))


def abstract_cache(model, cell: ShapeCell, rules, mesh):
    """Decode-state ShapeDtypeStructs (KV caches / SSM states / pos)."""
    defs = model.cache_defs(cell.global_batch, cell.seq_len)
    mk = lambda d: shd.named_sharding(d.axes, d.shape, rules, mesh)
    return abstract_params(defs, model.cfg.dtype, mk)


def paged_pool_specs(model, cell: ShapeCell, rules, mesh, slots: int,
                     page_size: int, num_pages: int):
    """Inputs of serve.make_paged_decode_loop beyond params: the paged
    KV block pool (pages on a leading 'page' logical axis, folded over
    the DP mesh axes — the page-pool mirror of the slot specs), the
    per-slot page tables / write positions, and the control lanes."""
    from repro.models.paged_kv import PagedKVCache

    cfg = model.cfg
    L, kv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.hd
    int8 = cfg.kv_cache_dtype == "int8"
    kvdt = jnp.int8 if int8 else cfg.dtype
    pshape = (L, num_pages, page_size, kv, hd)
    paxes = ("layers", "page", "none", "kv", "none")
    pool = {"k_pages": _sds(pshape, kvdt, paxes, rules, mesh),
            "v_pages": _sds(pshape, kvdt, paxes, rules, mesh)}
    if int8:
        sshape, saxes = pshape[:-1], paxes[:-1]
        pool["k_scale_pages"] = _sds(sshape, jnp.float32, saxes, rules,
                                     mesh)
        pool["v_scale_pages"] = _sds(sshape, jnp.float32, saxes, rules,
                                     mesh)
    pool_abs = PagedKVCache(pool["k_pages"], pool["v_pages"],
                            pool.get("k_scale_pages"),
                            pool.get("v_scale_pages"))
    pages_per_slot = -(-cell.seq_len // page_size)
    table = _sds((slots, pages_per_slot), jnp.int32, ("slot", "none"),
                 rules, mesh)
    lane = lambda dt: _sds((slots,), dt, ("slot",), rules, mesh)
    return (pool_abs, table, lane(jnp.int32), lane(jnp.int32),
            lane(jnp.bool_), lane(jnp.int32), lane(jnp.bool_),
            lane(jnp.int32), lane(jnp.int32))


def slot_pool_specs(model, cell: ShapeCell, rules, mesh, slots: int):
    """Inputs of serve.make_chunked_decode_loop beyond params: the
    pooled decode state (per-slot batch-1 caches stacked on a leading
    'slot' axis, folded over the DP mesh axes) and the per-slot control
    lanes (tok, live, made, fresh, max_new, eos — all (slots,),
    slot-sharded like the pool)."""
    defs = model.cache_defs(1, cell.seq_len)
    pooled = jax.tree.map(
        lambda d: ParamDef((slots,) + d.shape, ("slot",) + d.axes,
                           d.init, d.dtype),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    mk = lambda d: shd.named_sharding(d.axes, d.shape, rules, mesh)
    pool_abs = abstract_params(pooled, model.cfg.dtype, mk)
    lane = lambda dt: _sds((slots,), dt, ("slot",), rules, mesh)
    return (pool_abs, lane(jnp.int32), lane(jnp.bool_), lane(jnp.int32),
            lane(jnp.bool_), lane(jnp.int32), lane(jnp.int32))
