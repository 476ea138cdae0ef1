"""Shared neural building blocks (pure functions, no framework)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import cim_linear
from .config import ModelConfig, ParamDef


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale


def dense(x: jax.Array, w, cim_cfg: Optional[cim_linear.CIMConfig] = None,
          x_axes: Optional[tuple] = None) -> jax.Array:
    """Linear layer; routes through the CIM execution modes when configured
    or when `w` is already a PackedTernary (ternary-served models).

    Every dense input re-anchors the activation sharding (no-op off-mesh):
    XLA drops the DP sharding through scan loop state + remat regions,
    silently replicating the batch on every device otherwise.  For
    row-parallel matmuls (wo, w2) pass `x_axes` naming the sharded
    contraction dim ('heads' / 'mlp') — the default (replicated last dim)
    would force an all-gather of the sharded intermediate."""
    from repro.dist.sharding import constrain_act
    from repro.kernels.ops import PackedTernary
    if x.ndim == 3:
        x = constrain_act(x, x_axes)
    if isinstance(w, PackedTernary):
        cfg = cim_cfg or cim_linear.CIMConfig(mode="ternary")
        return cim_linear.linear(x, w, cfg).astype(x.dtype)
    if cim_cfg is not None and cim_cfg.mode != "float":
        return cim_linear.linear(x, w, cim_cfg).astype(x.dtype)
    return x @ w


MLP_MID = ("batch", "seq", "mlp")      # row-parallel w2 contraction
ATTN_OUT = ("batch", "seq", "heads")   # row-parallel wo contraction


def swiglu(x: jax.Array, w1, w3, w2, cim_cfg=None) -> jax.Array:
    """SwiGLU MLP: (silu(x@w1) * (x@w3)) @ w2."""
    return dense(jax.nn.silu(dense(x, w1, cim_cfg)) * dense(x, w3, cim_cfg),
                 w2, cim_cfg, x_axes=MLP_MID)


def gelu_mlp(x: jax.Array, w1, w2, cim_cfg=None) -> jax.Array:
    return dense(jax.nn.gelu(dense(x, w1, cim_cfg)), w2, cim_cfg,
                 x_axes=MLP_MID)


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """YaRN's blended inverse frequencies (arXiv:2309.00071), as the
    published ``rope_type: yarn`` computes them: dimensions that turn
    more than ``beta_fast`` times over ``original_max`` positions keep
    their frequency, those under ``beta_slow`` turns are divided by
    ``factor``, with a linear ramp between (bounds floored / ceiled).
    Returns a float32 numpy array of ``head_dim / 2``."""
    import numpy as np
    dim = head_dim

    def corr_dim(rot):
        return (dim * math.log(original_max / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extra, inter = 1.0 / pos, 1.0 / (factor * pos)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp               # weight of the unscaled frequency
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def layer_attn_tables(cfg: ModelConfig):
    """Per-layer (window (L,) int32, inv_freq (L, hd/2) f32, rope scale
    (L,) f32) of a ``per_layer_attn`` config: window layers plain RoPE
    at ``rope_theta``, full layers YaRN when ``rope_yarn`` is set."""
    import numpy as np
    n = cfg.num_layers
    windows = tuple(cfg.layer_windows) or (cfg.sliding_window,) * n
    plain = 1.0 / (cfg.rope_theta ** (np.arange(0, cfg.hd, 2,
                                               dtype=np.float32) / cfg.hd))
    full, full_scale = plain, 1.0
    if cfg.rope_yarn:
        factor, orig, fast, slow, att = cfg.rope_yarn
        full = yarn_inv_freq(cfg.hd, cfg.rope_theta, factor, orig, fast,
                             slow)
        full_scale = att
    inv = np.stack([plain if w else full for w in windows])
    scale = np.asarray([1.0 if w else full_scale for w in windows],
                       np.float32)
    return np.asarray(windows, np.int32), inv.astype(np.float32), scale


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               inv_freq: Optional[jax.Array] = None,
               scale=None) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) or (S,).  ``inv_freq``
    (hd/2,) replaces the plain frequencies at ``theta`` and ``scale``
    multiplies cos and sin (YaRN's attention factor)."""
    hd = x.shape[-1]
    freqs = (rope_frequencies(hd, theta) if inv_freq is None
             else inv_freq)                             # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    if angles.ndim == 2:                                # (S, hd/2) -> (1,S,..)
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, dim: int) -> jax.Array:
    pos = jnp.arange(seq)[:, None].astype(jnp.float32)
    div = jnp.exp(jnp.arange(0, dim, 2).astype(jnp.float32)
                  * (-math.log(10000.0) / dim))
    pe = jnp.zeros((seq, dim))
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# ------------------------------ param defs for common blocks -------------

def attn_defs(cfg: ModelConfig, layers: int, cross: bool = False) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    L = (layers,)
    # kv projections are ROW-parallel ('embed_rp' shards the contraction
    # over 'model'): GQA kv-head counts (2/8) rarely divide a 16-way TP
    # axis, so column-parallel kv would be computed fully replicated.
    defs = {
        "wq": ParamDef(L + (d, h * hd), ("layers", "embed", "heads")),
        "wk": ParamDef(L + (d, kv * hd), ("layers", "embed_rp", "kv")),
        "wv": ParamDef(L + (d, kv * hd), ("layers", "embed_rp", "kv")),
        "wo": ParamDef(L + (h * hd, d), ("layers", "heads", "embed")),
    }
    if cfg.qk_norm and not cross:
        defs["q_norm"] = ParamDef(L + (hd,), ("layers", "none"), "ones")
        defs["k_norm"] = ParamDef(L + (hd,), ("layers", "none"), "ones")
    return defs


def mlp_defs(cfg: ModelConfig, layers: int, d_ff: int | None = None,
             gated: bool = True) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    L = (layers,)
    defs = {
        "w1": ParamDef(L + (d, f), ("layers", "embed", "mlp")),
        "w2": ParamDef(L + (f, d), ("layers", "mlp", "embed")),
    }
    if gated:
        defs["w3"] = ParamDef(L + (d, f), ("layers", "embed", "mlp"))
    return defs


def norm_def(cfg: ModelConfig, layers: int | None = None) -> ParamDef:
    if layers is None:
        return ParamDef((cfg.d_model,), ("embed",), "ones")
    return ParamDef((layers, cfg.d_model), ("layers", "embed"), "ones")
