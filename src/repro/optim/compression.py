"""Gradient compression: int8 quantization with error feedback, and an
explicit int8 all-gather gradient sync for the DP axis.

The paper's core trick is *narrow on-the-wire representations backed by a
full-precision compute medium* (trits in ReRAM, restored into SRAM).  The
distributed-training analogue is compressing the gradient before it
crosses the interconnect: each DP shard quantizes its local gradient to
int8 (+ f32 scale), all-gathers the compressed bytes over the 'data'
axis, and sums the dequantized shards — 2x fewer collective bytes than
bf16, 4x fewer than f32.  Error feedback (Karimireddy et al., 2019)
accumulates the per-shard quantization residual locally so the bias
vanishes over steps.

``int8_allgather_sync`` is written with shard_map + lax collectives so
the int8 all-gather is visible in the dry-run HLO (the collective-bytes
reduction is measurable in §Roofline, not just claimed).
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def compress_int8(g: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8: g ~= q * scale."""
    amax = jnp.max(jnp.abs(g.astype(jnp.float32)))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(g.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def decompress_int8(q: jax.Array, scale: jax.Array,
                    dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def ef_init(params: Any) -> Any:
    """Error-feedback residual buffers (same shapes as grads, f32)."""
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def ef_compress_grads(grads: Any, residual: Any) -> tuple[Any, Any]:
    """(compressed-then-decompressed grads, new residuals).

    The returned grads are exactly what the other DP shards would
    reconstruct; the residual carries this shard's quantization error
    into the next step.
    """
    def leaf(g, e):
        corrected = g.astype(jnp.float32) + e
        q, s = compress_int8(corrected)
        deq = decompress_int8(q, s)
        return deq.astype(g.dtype), corrected - deq

    out = jax.tree.map(leaf, grads, residual)
    new_g = jax.tree.map(lambda o: o[0], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    new_e = jax.tree.map(lambda o: o[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    return new_g, new_e


def int8_allgather_sync(grads: Any, mesh, axes: tuple = ("data",),
                        residual: Any | None = None):
    """Sync DP-sharded gradients with int8 on the wire.

    Inside shard_map over `axes`: quantize the local (microbatch) grad to
    int8, all_gather the bytes, dequantize and mean.  Equivalent to
    psum(grad)/N up to int8 rounding; with `residual` the rounding error
    is fed back.  Returns (synced grads, new residual).
    """
    axes = tuple(a for a in axes if a in mesh.axis_names
                 and mesh.shape[a] > 1)
    if residual is not None:
        grads, residual = ef_compress_grads(grads, residual)
    if not axes:
        return grads, residual

    n = 1
    for a in axes:
        n *= mesh.shape[a]

    def sync(g):
        def one(x):
            q, s = compress_int8(x)
            qs = jax.lax.all_gather(q, axes, tiled=False)   # (N, ...) int8
            ss = jax.lax.all_gather(s, axes, tiled=False)   # (N,) f32
            qs = qs.reshape((n,) + x.shape)
            ss = ss.reshape((n,) + (1,) * x.ndim)
            return (jnp.sum(qs.astype(jnp.float32) * ss, axis=0) / n
                    ).astype(x.dtype)
        return jax.tree.map(one, g)

    specs = jax.tree.map(lambda _: P(), grads)
    synced = jax.shard_map(sync, mesh=mesh, in_specs=(specs,),
                           out_specs=specs, check_vma=False)(grads)
    return synced, residual
