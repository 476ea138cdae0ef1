"""Mixture-of-Experts layer: top-k routing, two ways.

Training (``moe_block``): capacity-bounded dispatch/combine einsums
(GShard style).  Tokens are routed in fixed-size GROUPS (default 1024):
capacity is per-group (C = g*k*cf/E), so dispatch tensors stay
O(T * g * k * cf) globally instead of O(T^2) — the standard GShard trick
that keeps MoE memory linear in tokens.  The group axis shards over
'data' (+'pod') and the expert axis over 'model' (EP); XLA inserts the
dispatch/combine all-to-alls.  Tokens past an expert's capacity are
dropped.

Serving (``moe_serve``): dropless.  Packed experts under an exact int8
plan (``serves_dropless``) route every live row to exactly its k
experts: f32 router logits, softmax, top-k, renormalized gates; the
(row, expert) pairs are laid out sorted by expert in whole tiles and
run through the grouped int8 kernel (``kernels/grouped_matmul.py``,
``op='ternary_grouped'``) for w1 and w3, SiLU, then w2, and combined with the
gates.  No expert is dequantized outside the kernel's VMEM.  Prefill
(batch-1 prompts) and the batched decode step share it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig, ParamDef

MOE_GROUP = 1024


def moe_defs(cfg: ModelConfig, layers: int) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    L = (layers,)
    return {
        "router": ParamDef(L + (d, e), ("layers", "embed", "none"),
                           dtype=jnp.float32),
        "w1": ParamDef(L + (e, d, f), ("layers", "expert", "embed", "mlp")),
        "w3": ParamDef(L + (e, d, f), ("layers", "expert", "embed", "mlp")),
        "w2": ParamDef(L + (e, f, d), ("layers", "expert", "mlp", "embed")),
    }


def moe_block(x: jax.Array, w, cfg: ModelConfig, cim_cfg=None,
              group_size: int = MOE_GROUP):
    """x (B,S,D) -> (y, aux_loss). Per-group capacity; overflow dropped."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    t = b * s
    g = min(group_size, t)
    pad = -t % g
    xt = x.reshape(t, d)
    if pad:
        xt = jnp.pad(xt, ((0, pad), (0, 0)))
    ng = xt.shape[0] // g
    xg = xt.reshape(ng, g, d)                                    # (G,g,D)

    logits = jnp.einsum("ngd,de->nge", xg.astype(jnp.float32),
                        w["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # (G,g,E)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                # (G,g,k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    cap = max(1, int(g * k * cfg.moe_capacity_factor / e))
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)        # (G,g,k,E)
    # capacity slot of each (token, choice) within its expert, per group:
    flat = onehot.reshape(ng, g * k, e)
    pos = (jnp.cumsum(flat, axis=1) * flat - 1).reshape(ng, g, k, e)
    within = (pos >= 0) & (pos < cap)

    dispatch = jnp.zeros((ng, g, e, cap), x.dtype)
    combine = jnp.zeros((ng, g, e, cap), x.dtype)
    for i in range(k):                                           # k <= 8
        sel = (onehot[:, :, i] * within[:, :, i]).astype(x.dtype)  # (G,g,E)
        oh_cap = jax.nn.one_hot(jnp.clip(pos[:, :, i], 0, cap - 1), cap,
                                dtype=x.dtype)                   # (G,g,E,C)
        d_i = oh_cap * sel[..., None]
        dispatch = dispatch + d_i
        combine = combine + d_i * gate_vals[:, :, i, None, None].astype(x.dtype)

    def expert_w(name):
        """Expert weights may be PackedTernary (paper 5-trit storage);
        dequant is elementwise and fuses into the einsum operand, so the
        HBM read stays at the packed width."""
        from repro.kernels.ops import PackedTernary, _dequant_xla
        ww = w[name]
        if isinstance(ww, PackedTernary):
            return _dequant_xla(ww, x.dtype)
        return ww

    xe = jnp.einsum("ngec,ngd->necd", dispatch, xg)              # (G,E,C,D)
    h = jax.nn.silu(jnp.einsum("necd,edf->necf", xe, expert_w("w1"))) * \
        jnp.einsum("necd,edf->necf", xe, expert_w("w3"))
    ye = jnp.einsum("necf,efd->necd", h, expert_w("w2"))         # (G,E,C,D)
    y = jnp.einsum("ngec,necd->ngd", combine, ye)
    y = y.reshape(t + pad, d)[:t].reshape(b, s, d)

    # load-balancing auxiliary loss (Switch/GShard)
    me = probs.mean(axis=(0, 1))                                 # (E,)
    ce = onehot.sum(axis=2).astype(jnp.float32).mean(axis=(0, 1))
    aux = e * jnp.sum(me * ce) * 1e-2
    return y, aux


# ------------------------------------------------------------- serving

def serves_dropless(w, cim) -> bool:
    """Whether ``moe_serve`` serves these expert weights: packed experts
    under an exact-fidelity int8 ternary plan request."""
    from repro.kernels.ops import PackedTernary
    return (isinstance(w["w1"], PackedTernary) and cim is not None
            and cim.mode == "ternary" and cim.domain == "int8"
            and cim.fidelity == "exact")


def route_layout(idx, valid, experts: int, bm: int, tiles: int):
    """Expert-sorted tile layout of the routed (row, expert) pairs.

    ``idx`` (T, k) expert ids, ``valid`` (T, k) which pairs are routed.
    Each expert's pairs take consecutive rows from its group's start,
    groups padded to whole ``bm``-row tiles.  Returns ``dest`` (T, k),
    each pair's row (``tiles * bm``, past the end, for a pair not
    routed), ``tile_expert`` (tiles,) int32 (idle tiles repeat the last
    live tile's expert), ``num_tiles`` (1,) and ``stats`` (2,) int32:
    [routed pairs, experts with a pair]."""
    t, k = idx.shape
    flat, ok = idx.reshape(-1), valid.reshape(-1)
    onehot = ((flat[:, None] == jnp.arange(experts)[None, :])
              & ok[:, None]).astype(jnp.int32)               # (T*k, E)
    counts = onehot.sum(0)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, 0), flat[:, None],
                               axis=1)[:, 0] - 1
    padded = -(-counts // bm) * bm
    ends = jnp.cumsum(padded)
    dest = jnp.where(ok, (ends - padded)[flat] + rank, tiles * bm)
    num_tiles = ends[-1] // bm
    first_row = jnp.arange(tiles, dtype=jnp.int32) * bm
    te = jnp.minimum(jnp.searchsorted(ends, first_row, side="right"),
                     experts - 1).astype(jnp.int32)
    te = jnp.where(jnp.arange(tiles) < num_tiles, te,
                   te[jnp.maximum(num_tiles - 1, 0)])
    stats = jnp.stack([ok.sum(dtype=jnp.int32),
                       (counts > 0).sum(dtype=jnp.int32)])
    return (dest.reshape(t, k), te, num_tiles.reshape(1).astype(jnp.int32),
            stats)


def moe_serve(x: jax.Array, w, cfg: ModelConfig, cim, live=None):
    """Dropless MoE over packed experts: x (B, S, D) -> (y, stats).

    ``live`` (B,) bool, when given, routes no pair of a dead row (its
    output is 0).  ``stats`` (2,) int32: routed (row, expert) pairs and
    experts with at least one pair."""
    from repro.kernels import execute, plan_matmul, shape_of
    from repro.kernels.grouped_matmul import GroupedTernary, tile_rows
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    t = b * s
    xt = x.reshape(t, d)
    rows = t * k
    bm = tile_rows(rows, e)
    tiles = rows // bm + min(e, rows)      # bound on sum_e ceil(c_e / bm)
    with jax.named_scope("moe.route"):
        logits = jnp.dot(xt.astype(jnp.float32),
                         w["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, k)
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        valid = jnp.ones((t, k), jnp.bool_)
        if live is not None:
            valid = valid & jnp.repeat(live, s).reshape(t, 1)
        dest, tile_expert, num_tiles, stats = route_layout(
            idx, valid, e, bm, tiles)
        xs = jnp.zeros((tiles * bm, d), x.dtype).at[dest.reshape(-1)].set(
            jnp.repeat(xt, k, axis=0), mode="drop")

    def grouped(v, name):
        gw = GroupedTernary(w[name], tile_expert, num_tiles)
        plan = plan_matmul(shape_of(v, gw), op="ternary_grouped",
                           backend="auto", domain="int8", packing=gw.mode,
                           interpret=cim.interpret,
                           kv_layout=cim.kv_layout)
        return execute(plan, v, gw).astype(x.dtype)

    h = jax.nn.silu(grouped(xs, "w1")) * grouped(xs, "w3")
    ys = grouped(h, "w2")
    yk = ys[jnp.minimum(dest, tiles * bm - 1)].astype(jnp.float32)
    y = jnp.sum(jnp.where(valid[..., None], gate[..., None] * yk, 0.0),
                axis=1)
    return y.astype(x.dtype).reshape(b, s, d), stats
