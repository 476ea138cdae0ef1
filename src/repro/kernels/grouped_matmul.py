"""Grouped ("ragged") int8 ternary matmul over a stack of packed experts.

A mixture-of-experts layer multiplies each routed row by the weights of
its own expert.  The rows arrive sorted by expert, each expert's group
padded to a whole number of ``bm``-row tiles, so that every tile belongs
to one expert:

  x            (T*bm, K)       float rows, expert-sorted; padding rows 0
  tile_expert  (T,) int32      the expert of each tile
  num_tiles    (1,) int32      tiles that hold a row; the rest are idle
  w            PackedTernary   (E, K | K/4, N) uint8 codes, (E, N) scales

``y[r] = (sum_k xq[r, k] * decode(w[e(r)])[k, :]) * xs[r] * scale[e(r)]``
with ``xq, xs = quantize_acts_int8(x)`` per row: the int8 domain of
``ternary_matmul_int8``, whose epilogue order it keeps, so a tile equals
``ternary_matmul_int8`` over that expert's rows bitwise (int32 sums are
exact).

The Pallas kernel (``ternary_gmm_int8``) takes ``tile_expert`` and
``num_tiles`` by scalar prefetch.  Its grid is ``(N / bn, T)``, tiles
innermost: one step multiplies one tile by the whole K of its expert,
unpacking the codes in VMEM a K-chunk at a time, so no unpacked copy of
an expert exists outside VMEM.  The weight block of step ``(j, t)`` is
``(tile_expert[t], :, j)``; consecutive tiles of one expert repeat it
and the pipeline fetches it once, so each expert's codes are read at
most once per call.  A tile at or past ``num_tiles`` is skipped: its
block indices repeat the last live tile's (no DMA) and its body does not
run.  The rows of skipped tiles are unspecified; callers gather only the
rows they routed.

Two backends register for ``op='ternary_grouped'`` (int8 domain, both
packings): ``pallas_gmm`` (this kernel; interpret mode off-TPU) and
``ref_gmm`` (the XLA oracle below).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ternary_matmul import (INT8_SUBLANE, MXU_LANE, TRIT2_PER_BYTE,
                             _decode_w)

W_BLOCK_BYTES = 2 * 2**20      # packed weight bytes per block (x2 buffers)
CHUNK_ELEMS = 256 * 1024       # unpacked (bk, bn) elements per K-chunk


class GroupedTernary(NamedTuple):
    """The weight operand of a grouped plan: an expert stack and the
    tile map of the expert-sorted rows.  ``shape`` is ``(K, N)``, so
    ``shape_of(x, gw)`` is the plan's ``(T*bm, K, N)``."""
    w: object                  # ops.PackedTernary, (E, K|K/4, N)
    tile_expert: jax.Array     # (T,) int32
    num_tiles: jax.Array       # (1,) int32

    @property
    def shape(self) -> tuple:
        return tuple(self.w.shape[-2:])

    @property
    def mode(self) -> str:
        return self.w.mode


def block_n(kw: int, n: int) -> int:
    """Columns per weight block: all of N when the packed block fits
    ``W_BLOCK_BYTES``, else the widest lane multiple dividing N that
    does."""
    if kw * n <= W_BLOCK_BYTES or n % MXU_LANE:
        return n
    bn = n
    while bn > MXU_LANE and (kw * bn > W_BLOCK_BYTES or n % bn):
        bn -= MXU_LANE
    return bn


def chunk_k(k: int, bn: int) -> int:
    """K per in-kernel chunk: the largest of 512/256/128 that divides K
    and keeps the unpacked chunk under ``CHUNK_ELEMS``; all of K when no
    lane multiple divides it."""
    for bk in (512, 256, 128):
        if k % bk == 0 and (bk * bn <= CHUNK_ELEMS or bk == MXU_LANE):
            return bk
    return k


def tile_rows(rows: int, experts: int) -> int:
    """Rows per tile for ``rows`` routed rows over ``experts``: the int8
    sublane tile (32) up to 128, near the mean rows an expert gets."""
    mean = -(-rows // max(experts, 1))
    return min(128, -(-mean // INT8_SUBLANE) * INT8_SUBLANE)


def _gmm_kernel(te_ref, nt_ref, x_ref, xs_ref, w_ref, s_ref, o_ref, *,
                mode: str, bk: int, nk: int):
    @pl.when(pl.program_id(1) < nt_ref[0])
    def _tile():
        bkw = bk if mode == "base3" else bk // TRIT2_PER_BYTE
        acc = None
        for c in range(nk):                       # K-chunks, unrolled
            x = x_ref[:, c * bk:(c + 1) * bk]               # (bm, bk) int8
            w = _decode_w(w_ref[0, c * bkw:(c + 1) * bkw, :], mode,
                          jnp.int8)                         # (bk, bn)
            part = jax.lax.dot(x, w, preferred_element_type=jnp.int32)
            acc = part if acc is None else acc + part
        o_ref[...] = (acc.astype(jnp.float32)
                      * xs_ref[...].astype(jnp.float32)
                      * s_ref[0].astype(jnp.float32)).astype(o_ref.dtype)


def _live_tile(t, nt):
    """Tile ``t`` clamped to the last live tile (a skipped tile repeats
    its block indices, so the pipeline fetches nothing for it)."""
    return jnp.maximum(jnp.minimum(t, nt[0] - 1), 0)


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def ternary_gmm_int8(x_int, x_scale, w_data, w_scale, tile_expert,
                     num_tiles, *, mode: str, interpret: bool = False):
    """The grouped int8 matmul: ``x_int (T*bm, K)`` int8 expert-sorted
    rows with per-row ``x_scale (T*bm,)``, ``w_data (E, K|K/4, N)``
    uint8 codes with ``w_scale (E, N)``; returns ``(T*bm, N)`` f32."""
    assert x_int.dtype == jnp.int8, x_int.dtype
    m, k = x_int.shape
    e, kw, n = w_data.shape
    assert kw * (TRIT2_PER_BYTE if mode == "trit2" else 1) == k, (kw, k)
    tiles = int(tile_expert.shape[0])
    bm = m // tiles
    assert bm * tiles == m, (m, tiles)
    assert tiles == 1 or bm % INT8_SUBLANE == 0, (bm, tiles)
    bn = block_n(kw, n)
    bk = chunk_k(k, bn)
    xs = x_scale.astype(jnp.float32).reshape(m, 1)
    ws = w_scale.astype(jnp.float32).reshape(e, 1, n)

    def row(j, t, te, nt):
        return (_live_tile(t, nt), 0)

    def expert(j, t, te, nt):
        return (te[_live_tile(t, nt)], 0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                # (tile_expert, num_tiles)
        grid=(n // bn, tiles),
        in_specs=[
            pl.BlockSpec((bm, k), row),
            pl.BlockSpec((bm, 1), row),
            pl.BlockSpec((1, kw, bn), expert),
            pl.BlockSpec((1, 1, bn), expert),
        ],
        out_specs=pl.BlockSpec(
            (bm, bn), lambda j, t, te, nt: (_live_tile(t, nt), j)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, mode=mode, bk=bk, nk=k // bk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="ternary_gmm_int8",
    )(tile_expert.astype(jnp.int32), num_tiles.astype(jnp.int32),
      x_int, xs, w_data, ws)


def ternary_gmm_int8_ref(x_int, x_scale, w, tile_expert):
    """XLA oracle: every tile against its expert's unpacked int8 codes
    (materialized per tile), the kernel's epilogue order.  Rows of idle
    tiles are computed too (they are unspecified in the kernel)."""
    from .ops import _dequant_xla_int8
    m, k = x_int.shape
    tiles = int(tile_expert.shape[0])
    bm = m // tiles
    wd = _dequant_xla_int8(w)[:, :k, :][tile_expert]          # (T, K, N)
    acc = jnp.einsum("tbk,tkn->tbn", x_int.reshape(tiles, bm, k), wd,
                     preferred_element_type=jnp.int32)
    scale = w.scale.astype(jnp.float32)[tile_expert][:, None, :]
    y = (acc.astype(jnp.float32)
         * x_scale.astype(jnp.float32).reshape(tiles, bm, 1) * scale)
    return y.reshape(m, -1)


# ------------------------------------------------------------ backends

def _operand(plan, x, gw):
    if not isinstance(gw, GroupedTernary):
        raise ValueError(f"grouped plans take a GroupedTernary weight "
                         f"operand; got {type(gw).__name__}")
    from .ops import quantize_acts_int8
    xi, xs = quantize_acts_int8(x.reshape(-1, x.shape[-1]))
    k = gw.w.kdim
    if xi.shape[-1] != k:          # trit2 pads K to a byte multiple
        xi = jnp.pad(xi, ((0, 0), (0, k - xi.shape[-1])))
    return xi, xs


def run_pallas(plan, x, gw):
    xi, xs = _operand(plan, x, gw)
    y = ternary_gmm_int8(xi, xs, gw.w.data, gw.w.scale, gw.tile_expert,
                         gw.num_tiles, mode=gw.w.mode,
                         interpret=plan.interpret)
    return y.reshape(*x.shape[:-1], y.shape[-1])


def run_ref(plan, x, gw):
    xi, xs = _operand(plan, x, gw)
    y = ternary_gmm_int8_ref(xi, xs, gw.w, gw.tile_expert)
    return y.reshape(*x.shape[:-1], y.shape[-1])


EVAL_EXPERTS = 2


def eval_operands(shape, packing: str) -> tuple:
    """Abstract ``(x, GroupedTernary)`` operands whose ``shape_of`` is
    plan shape ``(m, k, n)``: ``m`` rows as one tile of ``EVAL_EXPERTS``
    experts (the capability pass pushes these through
    ``jax.eval_shape``)."""
    from .ops import PackedTernary
    m, k, n = (int(v) for v in shape)
    kw = k // TRIT2_PER_BYTE if packing == "trit2" else k
    w = PackedTernary(
        jax.ShapeDtypeStruct((EVAL_EXPERTS, kw, n), jnp.uint8),
        jax.ShapeDtypeStruct((EVAL_EXPERTS, n), jnp.float32), packing)
    return (jax.ShapeDtypeStruct((m, k), jnp.float32),
            GroupedTernary(w, jax.ShapeDtypeStruct((1,), jnp.int32),
                           jax.ShapeDtypeStruct((1,), jnp.int32)))
