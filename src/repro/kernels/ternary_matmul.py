"""Pallas TPU kernel: packed-ternary weight matmul with VMEM dequant-on-load.

The TPU image of the paper's density + DC-free-restore mechanism
(DESIGN.md §2): weights live in HBM in a packed ternary format, are
unpacked *inside* the kernel's VMEM tiles (the "restore"), and feed the
MXU in bf16/f32.  No dequantized copy of the weights ever exists in HBM.

Packing modes
  base3  — one uint8 per 5-trit weight (value+121; decode = subtract).
           Paper-faithful precision (Table 3), 2x denser than bf16.
  trit2  — four 1-trit weights per uint8 (2-bit fields).  Pure-ternary
           mode, 8x denser than bf16; the memory-roofline option for
           weight-bound decode shapes.

Grid: (M/bm, N/bn, K/bk), K innermost for in-place accumulation.
BlockSpecs keep x:(bm,bk), w:(bk|bk/4, bn), out:(bm,bn) in VMEM.  Block
shapes default to a shape-adaptive choice (:func:`select_block_shapes`):
128/128/512 for prefill-sized M, and a skinny-M variant for decode
(bm = next sublane multiple >= M, deeper bk) so a batch-8 decode step
does not pad M 16x up to the MXU tile.  Per-output-column scales are
applied once on the final K step.

Two arithmetic domains:
  float — dequant to the activation dtype in VMEM (the trits are exact
          in bf16), MXU dot accumulating in f32: one pass for bf16
          activations, HIGHEST for f32 ones (the default; matches the
          unpack-then-matmul oracle to f32 round-off).
  int8  — ``ternary_matmul_int8``: activations arrive pre-quantized to
          int8 (per-row scales), weights decode to int8 in VMEM, the MXU
          runs an int8 x int8 -> int32 dot and ALL float scaling is
          deferred to the epilogue.  Integer accumulation is exact, so
          pallas == xla == oracle bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TRIT2_PER_BYTE = 4
BASE3_OFFSET = 121  # trit_range(5)

MXU_LANE = 128            # last-dim tile (all dtypes)
SUBLANE = 8               # f32 second-to-last-dim tile
INT8_SUBLANE = 32         # int8 second-to-last-dim tile
DEFAULT_BLOCKS = (128, 128, 512)
SKINNY_BK = 1024          # deeper K tile for decode shapes
VMEM_BUDGET_BYTES = 8 * 2**20   # half of 16MB: leave room for double-buffer


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _vmem_working_set(bm: int, bn: int, bk: int, mode: str,
                      domain: str = "float") -> int:
    """Per-step VMEM bytes of the BlockSpecs (x/w double-buffered)."""
    x_tile = bm * bk * (1 if domain == "int8" else 4)
    w_tile = (bk // TRIT2_PER_BYTE if mode == "trit2" else bk) * bn
    return 2 * (x_tile + w_tile) + 2 * bm * bn * 4 + bm * bn * 4 + bn * 4


def select_block_shapes(m: int, kdim: int, n: int, mode: str = "base3", *,
                        domain: str = "float",
                        vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
                        ) -> tuple[int, int, int]:
    """Pick (bm, bn, bk) from the actual problem shape.

    Prefill-sized M keeps the MXU-square 128/128/512 tiles.  Decode /
    skinny M (< 128) shrinks bm to the next sublane multiple >= M — a
    batch-8 decode step then pads M 1x instead of 16x — and spends the
    freed VMEM on a deeper K tile so each weight DMA streams more of the
    reduction.  The sublane quantum and the x-tile byte width follow the
    arithmetic domain (f32: 8-row tiles, 4 B/elt; int8: 32-row tiles,
    1 B/elt).  bn/bk stay lane-aligned (128 multiples, so the trit2
    packed tile bk/4 stays whole); bk is clamped to the padded K extent
    and halved until the double-buffered working set fits the budget.
    """
    sublane = INT8_SUBLANE if domain == "int8" else SUBLANE
    bm_full, bn_full, bk_full = DEFAULT_BLOCKS
    if m >= bm_full:
        bm, bk = bm_full, bk_full
    else:
        bm = _round_up(max(m, 1), sublane)
        bk = SKINNY_BK
    bn = bn_full
    bk = min(bk, _round_up(kdim, MXU_LANE))
    while bk > MXU_LANE and _vmem_working_set(bm, bn, bk, mode,
                                              domain) > vmem_budget_bytes:
        bk = _round_up(bk // 2, MXU_LANE)   # keep the lane alignment
    return bm, bn, bk


def _decode_w(w_packed: jax.Array, mode: str, dtype) -> jax.Array:
    """uint8 packed tile -> (bk, bn) weight values in `dtype`.

    base3: [-121, 121] via a single subtract; trit2: {-1, 0, +1} from the
    2-bit fields (4 trits/byte).  All decoded values are small integers,
    so the float and int8 domains decode through the same exact path.
    """
    if mode == "base3":
        return (w_packed.astype(jnp.int32) - BASE3_OFFSET).astype(dtype)
    # Mosaic shifts and subtracts only 32-bit integers: widen the byte
    # first, decode in int32, and narrow to `dtype` last
    kp, bn = w_packed.shape
    w32 = w_packed.astype(jnp.int32)
    fields = [(w32 >> (2 * i)) & 0x3 for i in range(TRIT2_PER_BYTE)]
    codes = jnp.stack(fields, axis=1)                    # (bk/4, 4, bn)
    vals = (codes == 1).astype(jnp.int32) - (codes == 2).astype(jnp.int32)
    return vals.reshape(kp * TRIT2_PER_BYTE, bn).astype(dtype)


def _dot_precision(dtype):
    """MXU precision for a dot whose float operands are ``dtype``.  The
    MXU multiplies bf16: one pass is exact for bf16 (and narrower)
    operands, but rounds f32 operands to 8 mantissa bits, so f32 asks
    for HIGHEST (several passes, f32-accurate)."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _checked_dims(x: jax.Array, w_packed: jax.Array,
                  mode: str) -> tuple[int, int, int]:
    """Validate x/w packing agreement; returns (M, K, N)."""
    m, kdim = x.shape
    kw, n = w_packed.shape
    if mode == "base3":
        assert kw == kdim, (kw, kdim)
    elif mode == "trit2":
        assert kw * TRIT2_PER_BYTE == kdim, (kw, kdim)
    else:
        raise ValueError(f"unknown packing mode {mode!r}; expected one of "
                         f"['base3', 'trit2']")
    return m, kdim, n


def _pad_to_blocks(x, w_packed, scale, mode: str, bm: int, bn: int, bk: int):
    """Pad operands to block multiples.  x pads with zeros; w pads with
    the byte that decodes to 0 so padded K rows contribute nothing."""
    m, kdim = x.shape
    n = w_packed.shape[1]
    mp, np_, kp = (-m % bm), (-n % bn), (-kdim % bk)
    if mp or kp:
        x = jnp.pad(x, ((0, mp), (0, kp)))
    if np_ or kp:
        kw_pad = kp if mode == "base3" else kp // TRIT2_PER_BYTE
        pad_val = BASE3_OFFSET if mode == "base3" else 0  # decode -> 0
        w_packed = jnp.pad(w_packed, ((0, kw_pad), (0, np_)),
                           constant_values=pad_val)
    if np_:
        scale = jnp.pad(scale, (0, np_))
    return x, w_packed, scale, mp


def _kernel(x_ref, w_ref, scale_ref, o_ref, acc_ref, *, mode: str, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                       # (bm, bk)
    w = _decode_w(w_ref[...], mode, x.dtype)             # (bk, bn)
    acc_ref[...] += jax.lax.dot(x, w, precision=_dot_precision(x.dtype),
                                preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] * scale_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mode", "bm", "bn", "bk",
                                             "interpret", "out_dtype"))
def ternary_matmul(x: jax.Array, w_packed: jax.Array, scale: jax.Array,
                   *, mode: str = "base3", bm: int | None = None,
                   bn: int | None = None, bk: int | None = None,
                   interpret: bool = False,
                   out_dtype=jnp.float32) -> jax.Array:
    """y[m,n] = sum_k x[m,k] * decode(w_packed)[k,n] * scale[n].

    x: (M, K) float; w_packed: (K, N) uint8 [base3] or (K/4, N) uint8
    [trit2]; scale: (N,) float (per-column) or scalar broadcastable.
    Block shapes default to the shape-adaptive choice; pass bm/bn/bk to
    pin them (tests, sweeps).
    """
    m, kdim, n = _checked_dims(x, w_packed, mode)
    abm, abn, abk = select_block_shapes(m, kdim, n, mode)
    bm, bn, bk = bm or abm, bn or abn, bk or abk
    # the scales stay f32 whatever x is, as in the oracle
    scale = jnp.broadcast_to(jnp.asarray(scale, jnp.float32).reshape(-1),
                             (n,))
    x, w_packed, scale, _ = _pad_to_blocks(x, w_packed, scale, mode,
                                           bm, bn, bk)
    # scales ride as 2-D rows/columns: Mosaic refuses a 1-D f32 block
    # (its T(128) tiling disagrees with XLA's T(1024) layout)
    scale = scale.reshape(1, -1)
    mt, nt, kt = x.shape[0] // bm, w_packed.shape[1] // bn, x.shape[1] // bk
    bkw = bk if mode == "base3" else bk // TRIT2_PER_BYTE

    out = pl.pallas_call(
        functools.partial(_kernel, mode=mode, nk=kt),
        grid=(mt, nt, kt),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bkw, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], w_packed.shape[1]), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w_packed, scale)
    return out[:m, :n]


# ------------------------------------------------------------ int8 domain

def _kernel_int8(x_ref, xs_ref, w_ref, scale_ref, o_ref, acc_ref, *,
                 mode: str, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _decode_w(w_ref[...], mode, jnp.int8)            # (bk, bn) int8
    x = x_ref[...]                                       # (bm, bk) int8
    acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _finish():
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * xs_ref[...].astype(jnp.float32)
                      * scale_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mode", "bm", "bn", "bk",
                                             "interpret", "out_dtype"))
def ternary_matmul_int8(x_int: jax.Array, x_scale: jax.Array,
                        w_packed: jax.Array, scale: jax.Array, *,
                        mode: str = "trit2", bm: int | None = None,
                        bn: int | None = None, bk: int | None = None,
                        interpret: bool = False,
                        out_dtype=jnp.float32) -> jax.Array:
    """Int-domain variant: y[m,n] = (sum_k x_int[m,k] * decode(w)[k,n])
    * x_scale[m] * scale[n], accumulated in int32 on the MXU.

    x_int: (M, K) int8 (pre-quantized activations); x_scale: (M,) f32
    per-row dequant scales; w_packed/scale as in :func:`ternary_matmul`.
    The integer accumulation is exact, so results bit-match the
    int-domain oracle regardless of blocking.
    """
    assert x_int.dtype == jnp.int8, x_int.dtype
    m, kdim, n = _checked_dims(x_int, w_packed, mode)
    abm, abn, abk = select_block_shapes(m, kdim, n, mode, domain="int8")
    bm, bn, bk = bm or abm, bn or abn, bk or abk
    scale = jnp.broadcast_to(jnp.asarray(scale, jnp.float32).reshape(-1), (n,))
    x_scale = jnp.broadcast_to(jnp.asarray(x_scale, jnp.float32).reshape(-1),
                               (m,))
    x_int, w_packed, scale, mp = _pad_to_blocks(x_int, w_packed, scale,
                                                mode, bm, bn, bk)
    if mp:
        x_scale = jnp.pad(x_scale, (0, mp))
    x_scale, scale = x_scale.reshape(-1, 1), scale.reshape(1, -1)
    mt, nt, kt = (x_int.shape[0] // bm, w_packed.shape[1] // bn,
                  x_int.shape[1] // bk)
    bkw = bk if mode == "base3" else bk // TRIT2_PER_BYTE

    out = pl.pallas_call(
        functools.partial(_kernel_int8, mode=mode, nk=kt),
        grid=(mt, nt, kt),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bkw, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x_int.shape[0], w_packed.shape[1]),
                                       out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_int, x_scale, w_packed, scale)
    return out[:m, :n]
