"""Model configuration + parameter-definition system.

Every architecture is described by a ModelConfig; every parameter is
declared once as a ParamDef (shape + logical axes + initializer), from
which we derive (a) real initialized params for smoke tests/examples,
(b) ShapeDtypeStructs with NamedShardings for the multi-pod dry-run
(never allocating), and (c) PartitionSpecs for jit in_shardings.
Logical->physical axis rules live in repro.dist.sharding.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // num_heads
    # attention details
    rope_theta: float = 10_000.0
    qk_norm: bool = False
    sliding_window: int = 0        # 0 = full attention
    # per-layer attention kinds (mixed window/full stacks): layer l's
    # window, 0 = full attention; empty -> every layer ``sliding_window``.
    # Paged and dense caches hold every position for these layers (no
    # rolling buffer); the window is a mask and a skip in the kernel.
    layer_windows: tuple = ()
    # YaRN RoPE of the full-attention layers of a ``layer_windows`` stack:
    # (factor, original_max_position_embeddings, beta_fast, beta_slow,
    # attention_factor); window layers keep plain RoPE at rope_theta
    rope_yarn: tuple = ()
    # MoE
    num_experts: int = 0
    experts_per_token: int = 0
    moe_capacity_factor: float = 1.25
    # SSM / hybrid
    ssm_state: int = 0
    ssm_kind: str = ""             # mamba2 | xlstm
    ssm_heads: int = 0             # mamba2 value heads (0 -> d_model // 64)
    attn_every: int = 0            # hybrid: shared attn after every k ssm layers
    slstm_every: int = 0           # xlstm: sLSTM block interval (rest mLSTM)
    # encoder-decoder / multimodal
    encoder_layers: int = 0        # whisper
    encoder_seq: int = 0           # stub frontend tokens (frames/patches)
    cross_attn_every: int = 0      # vlm: every k-th layer cross-attends
    # numerics
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: str = "none"            # none | full  (activation checkpointing)
    kv_cache_dtype: str = ""       # "" (= dtype) | "int8" (scaled KV cache)
    # long-context capability (sub-quadratic attention): SSM state and/or
    # rolling-window attention -> long_500k cell is runnable
    @property
    def sub_quadratic(self) -> bool:
        return self.family in ("ssm", "hybrid") or self.sliding_window > 0

    @property
    def per_layer_attn(self) -> bool:
        """Whether the layer scans carry each layer's window and RoPE
        table (``layers.layer_attn_tables``)."""
        return bool(self.layer_windows or self.rope_yarn)

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to a multiple of 256 — MXU-aligned and 16-way
        TP-shardable.  Embedding/unembed tables use this; data pipelines
        sample < vocab_size so pad rows are never valid targets."""
        return -(-self.vocab_size // 256) * 256

    @property
    def n_ssm_heads(self) -> int:
        return self.ssm_heads or max(1, self.d_model // 64)

    def param_count(self) -> int:
        """Total parameters (for 6ND roofline bookkeeping)."""
        from . import registry
        shapes = registry.build(self).param_defs
        return sum(math.prod(d.shape) for d in jax.tree.leaves(
            shapes, is_leaf=lambda x: isinstance(x, ParamDef)))

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed experts count)."""
        total = self.param_count()
        if self.num_experts:
            from . import registry
            shapes = registry.build(self).param_defs
            expert = sum(
                math.prod(d.shape) for d in jax.tree.leaves(
                    shapes, is_leaf=lambda x: isinstance(x, ParamDef))
                if isinstance(d, ParamDef) and "expert" in d.axes)
            active_frac = self.experts_per_token / self.num_experts
            return int(total - expert + expert * active_frac)
        return total


class ParamDef(NamedTuple):
    """Declarative parameter: shape + logical axes + init style."""
    shape: tuple
    axes: tuple                    # logical names, len == ndim
    init: str = "normal"           # normal | zeros | ones | embed
    dtype: Any = None              # None -> config dtype

    def initializer(self, key: jax.Array, cfg_dtype) -> jax.Array:
        dtype = self.dtype or cfg_dtype
        if self.init == "zeros":
            return jnp.zeros(self.shape, dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, dtype)
        fan_in = self.shape[-2] if len(self.shape) >= 2 else self.shape[-1]
        if self.init == "embed":
            fan_in = 1.0
        std = 1.0 / math.sqrt(fan_in)
        return (std * jax.random.normal(key, self.shape)).astype(dtype)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def init_params(key: jax.Array, defs: Any, dtype=jnp.float32) -> Any:
    """Materialize real parameters (smoke tests / examples only)."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_def)
    keys = jax.random.split(key, len(leaves))
    vals = [d.initializer(k, dtype) for d, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(defs: Any, dtype=jnp.bfloat16, sharding_fn=None) -> Any:
    """ShapeDtypeStructs (optionally with shardings) — the dry-run path."""
    def mk(d: ParamDef):
        dt = d.dtype or dtype
        sh = sharding_fn(d) if sharding_fn else None
        if sh is not None:
            return jax.ShapeDtypeStruct(d.shape, dt, sharding=sh)
        return jax.ShapeDtypeStruct(d.shape, dt)
    return jax.tree.map(mk, defs, is_leaf=is_def)


def param_bytes(defs: Any, bytes_per_param: float = 2.0) -> float:
    return sum(math.prod(d.shape) for d in
               jax.tree.leaves(defs, is_leaf=is_def)) * bytes_per_param
