"""Packed-weight containers, quantizers, and the legacy jit'd wrappers.

The kernel layer's public API is the plan/registry pair in
``kernels.plan`` (see src/repro/kernels/README.md):

    plan = plan_matmul(shape_of(x, pw), cfg=cim_cfg)   # resolve once
    y = execute(plan, x, pw)                           # run anywhere

``ternary_matmul`` / ``ternary_matmul_int8`` / ``cim_matmul`` below are
thin deprecation shims over that API: the old routing kwargs
(``backend=``, ``domain=``, ``interpret=``, ``bm/bn/bk``) still work
but emit a ``DeprecationWarning`` — backend selection now lives in the
capability registry, not in per-call if/elif chains, and the platform
probe for ``interpret`` is evaluated once per resolved plan instead of
on every wrapper invocation.

PackedTernary is a registered pytree (data/scale are children, the
packing mode is static aux), so packed weights flow through jit, scan
slicing (models scan over a leading layer axis) and the dry-run's
ShapeDtypeStruct lowering.

The xla implementation functions (``ternary_matmul_xla``,
``ternary_matmul_int8_xla``) remain importable: they are the 'xla'
backend's runners and the dry-run's lowering path (Pallas TPU kernels
cannot lower on the CPU host platform, and the packed uint8 weight
reads must show up faithfully in the memory-roofline term).
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from repro.core.packing import pack_base3, pack_trits2
from repro.core.ternary import quantize_8b_truncate_5t, trit_range
from .plan import (PACKINGS, check_choice, execute, plan_matmul,
                   shape_of)

TRIT2_PER_BYTE = 4
BASE3_OFFSET = trit_range(5)        # 121


@jax.tree_util.register_pytree_node_class
class PackedTernary:
    """A weight matrix packed for the ternary_matmul kernel.

    data : uint8 (..., K, N) [base3] or (..., K/4, N) [trit2]
    scale: f32  (..., N) — per-output-column
    mode : 'base3' | 'trit2' (static)
    """

    def __init__(self, data, scale, mode: str = "base3"):
        self.data = data
        self.scale = scale
        self.mode = mode

    @property
    def kdim(self) -> int:
        k = self.data.shape[-2]
        return k * TRIT2_PER_BYTE if self.mode == "trit2" else k

    @property
    def ndim(self) -> int:
        return self.data.ndim

    @property
    def shape(self) -> tuple:
        return self.data.shape[:-2] + (self.kdim, self.data.shape[-1])

    def tree_flatten(self):
        return (self.data, self.scale), (self.mode,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0])

    def __repr__(self):
        return (f"PackedTernary(mode={self.mode!r}, "
                f"data={getattr(self.data, 'shape', None)}, "
                f"scale={getattr(self.scale, 'shape', None)})")


def pack_weights(w: jax.Array, mode: str = "base3",
                 num_trits: int = 5) -> PackedTernary:
    """Quantize a float (..., K, N) weight with the paper's truncating flow
    and pack for HBM-dense storage (per-output-column scales).  A leading
    stack axis (scan-over-layers weights) is supported."""
    check_choice("packing mode", mode, PACKINGS)
    if mode == "base3":
        # the truncated codes are the 5-trit values themselves: pack
        # them directly (a balanced-ternary round trip is the identity
        # on [-121, 121] and would hold five int8 planes per weight)
        q = quantize_8b_truncate_5t(w, num_trits, axis=-2)
        data = pack_base3(q.values, num_trits)           # (..., K, N) uint8
        scale = jnp.squeeze(q.scale, axis=-2)            # (..., N)
    else:
        # single-trit weights: w ~ scale * t, t in {-1,0,1}; threshold at
        # 0.75 * mean|w| per column (standard TWN choice).
        absw = jnp.abs(w)
        thr = 0.75 * jnp.mean(absw, axis=-2, keepdims=True)
        t = jnp.sign(w) * (absw > thr)
        nonzero = jnp.maximum(jnp.sum(jnp.abs(t), axis=-2), 1.0)
        scale = jnp.sum(absw * jnp.abs(t), axis=-2) / nonzero   # (..., N)
        k = w.shape[-2]
        kpad = -k % TRIT2_PER_BYTE
        if kpad:
            pad = [(0, 0)] * w.ndim
            pad[-2] = (0, kpad)
            t = jnp.pad(t, pad)
        tk = jnp.moveaxis(t.astype(jnp.int8), -2, 0)     # (K, ..., N)
        data = jnp.moveaxis(pack_trits2(tk), 0, -2)      # (..., K/4, N)
    return PackedTernary(data, scale.astype(jnp.float32), mode)


# ------------------------------------------------------------------ xla path

def _unpack_trit2_xla(p: jax.Array, dtype) -> jax.Array:
    """uint8 (..., K/4, N) -> (..., K, N) trit values in `dtype`."""
    fields = [(p >> (2 * i)) & 0x3 for i in range(TRIT2_PER_BYTE)]
    codes = jnp.stack(fields, axis=-2)                   # (..., K/4, 4, N)
    dec = (codes == 1).astype(dtype) - (codes == 2).astype(dtype)
    return dec.reshape(p.shape[:-2] +
                       (p.shape[-2] * TRIT2_PER_BYTE, p.shape[-1]))


def _dequant_xla(w: PackedTernary, dtype=jnp.float32) -> jax.Array:
    """Fused-by-XLA dequantization of a packed weight (any leading dims)."""
    if w.mode == "base3":
        dec = w.data.astype(jnp.float32) - float(BASE3_OFFSET)
    else:
        dec = _unpack_trit2_xla(w.data, jnp.float32)
    return (dec * w.scale.astype(jnp.float32)[..., None, :]).astype(dtype)


def ternary_matmul_xla(x: jax.Array, w: PackedTernary) -> jax.Array:
    """x (..., K) @ packed w -> (..., N) f32 via fused jnp dequant."""
    # trit2 packing pads K to a byte multiple; drop the padded rows on the
    # CONTRACTION axis (the K-penultimate one — leading-axis slicing would
    # truncate the layer stack of 3-D scan-over-layers weights).
    wd = _dequant_xla(w)[..., : x.shape[-1], :]
    return jnp.matmul(x.astype(jnp.float32), wd,
                      preferred_element_type=jnp.float32)


# ----------------------------------------------------------- int8 domain

def quantize_acts_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-row int8 quantization of activations (..., K).

    Returns (x_int8, x_scale) with x ~ x_int8 * x_scale[..., None].  The
    shared entry point for every int-domain backend, so pallas/xla/oracle
    all consume bit-identical integers.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    x_scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    xi = jnp.clip(jnp.round(x.astype(jnp.float32) / x_scale[..., None]),
                  -127, 127).astype(jnp.int8)
    return xi, x_scale


def _dequant_xla_int8(w: PackedTernary) -> jax.Array:
    """Packed weight -> int8 trit/value matrix (no float scale applied)."""
    if w.mode == "base3":
        return (w.data.astype(jnp.int32) - BASE3_OFFSET).astype(jnp.int8)
    return _unpack_trit2_xla(w.data, jnp.int8)


def ternary_matmul_int8_xla(x_int: jax.Array, x_scale: jax.Array,
                            w: PackedTernary) -> jax.Array:
    """Int-domain xla backend: int8 x int8 -> int32 dot, float epilogue.

    Mirrors the kernel's epilogue order (acc * x_scale * w_scale) so the
    two backends stay bitwise identical.
    """
    wd = _dequant_xla_int8(w)[..., : x_int.shape[-1], :]
    acc = jnp.matmul(x_int, wd, preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32)
            * x_scale.astype(jnp.float32)[..., None]
            * w.scale.astype(jnp.float32)[..., None, :])


# ------------------------------------------------------ deprecation shims

def _warn_legacy(fn: str, used: dict, stacklevel: int = 1) -> None:
    """Emit the routing-kwarg DeprecationWarning at the SHIM CALLER's
    frame.  ``stacklevel`` counts frames between the shim and the user
    (1 = the shim was called directly); each shim passes its depth
    explicitly so a future shim sitting one level deeper cannot
    silently misattribute the warning.  The reported filename must be
    the user's call site — pinned by
    tests/test_kernels.py::test_shim_warning_points_at_caller."""
    used = {k: v for k, v in used.items() if v is not None}
    if used:
        warnings.warn(
            f"ops.{fn}({', '.join(sorted(used))}=...) routing kwargs are "
            f"deprecated: resolve an ExecutionPlan once with "
            f"repro.kernels.plan_matmul and run repro.kernels.execute "
            f"(src/repro/kernels/README.md has the migration table)",
            DeprecationWarning, stacklevel=2 + stacklevel)


def ternary_matmul(x: jax.Array, w: PackedTernary, *, interpret=None,
                   backend: str = "auto", domain: str = "float",
                   bm: int | None = None, bn: int | None = None,
                   bk: int | None = None) -> jax.Array:
    """x (..., K) @ packed w (K, N) -> (..., N) fp32.

    Deprecation shim: equivalent to ``execute(plan_matmul(...), x, w)``;
    the routing kwargs survive behind a DeprecationWarning.
    """
    _warn_legacy("ternary_matmul", {
        "interpret": interpret, "bm": bm, "bn": bn, "bk": bk,
        "backend": None if backend == "auto" else backend,
        "domain": None if domain == "float" else domain}, stacklevel=1)
    plan = plan_matmul(shape_of(x, w), backend=backend, domain=domain,
                       packing=w.mode, interpret=interpret,
                       bm=bm, bn=bn, bk=bk)
    return execute(plan, x, w)


def ternary_matmul_int8(x: jax.Array, w: PackedTernary, *, interpret=None,
                        backend: str = "auto", bm: int | None = None,
                        bn: int | None = None,
                        bk: int | None = None) -> jax.Array:
    """Decode fast lane: quantize x per-row to int8 once, then run the
    whole matmul in the integer domain (MXU int8 dot, int32 accumulate)
    with every float scale deferred to the epilogue.

    Deprecation shim for an int8-domain plan (see ``ternary_matmul``).
    """
    _warn_legacy("ternary_matmul_int8", {
        "interpret": interpret, "bm": bm, "bn": bn, "bk": bk,
        "backend": None if backend == "auto" else backend}, stacklevel=1)
    plan = plan_matmul(shape_of(x, w), backend=backend, domain="int8",
                       packing=w.mode, interpret=interpret,
                       bm=bm, bn=bn, bk=bk)
    return execute(plan, x, w)


def cim_matmul(x: jax.Array, w: "PackedTernary | jax.Array", *,
               adc_bits: int = 5, num_trits: int = 5, interpret=None,
               bm: int | None = None, bn: int | None = None,
               bk: int | None = None) -> jax.Array:
    """Macro-exact CIM matmul: float x (..., K) x weight (K, N) -> (..., N).

    Accepts a float weight (ternarized on the fly) or a base3 PackedTernary.
    Deprecation shim for an ``op='cim'`` plan.
    """
    _warn_legacy("cim_matmul", {"interpret": interpret, "bm": bm,
                                "bn": bn, "bk": bk}, stacklevel=1)
    plan = plan_matmul(shape_of(x, w), op="cim", interpret=interpret,
                       bm=bm, bn=bn, bk=bk, adc_bits=adc_bits,
                       num_trits=num_trits)
    return execute(plan, x, w)
