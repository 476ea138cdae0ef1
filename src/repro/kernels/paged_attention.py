"""Fused paged-attention executor: decode reads straight off the page pool.

The paged serving path (PR 5) stores KV in a block pool of
``(page, page_size, KV, hd)`` pages plus a per-slot page table; until
now every decode read first gathered the live pages back into a dense
``(slots, capacity, KV, hd)`` copy and ran dense attention over it —
re-materializing exactly the traffic the paged layout exists to avoid.

This module registers attention as a planned op: the Pallas kernel
consumes the page table *in-kernel* through scalar-prefetch BlockSpec
index maps — grid step ``(s, w)`` DMAs page ``page_table[s, w]`` of the
pool directly into VMEM, so the gathered dense copy is never built.
Page 0 is the pool's reserved null page: table rows are padded with 0,
and the positional mask (``kpos >= len`` -> -1e30, the same identity
the dense read uses) provably zeroes whatever the last live page holds
past ``len``: ``exp(-1e30 - m)`` underflows to exactly 0.0 in f32 once
any real key has been seen.

Only live cells are visited.  ``len[s]`` (``PagedAttentionKV.pos``) is
slot ``s``'s live context; a cell is live when ``w * page_size <
len[s]``:

  * compute: the cell body (scores, mask, online-softmax update) runs
    under ``pl.when(w * page_size < len[s])``; the ``w == 0`` init and
    the ``w == last`` flush always run, so every slot writes its
    statistics;
  * DMA: the K and V index maps clamp the window to the slot's last
    live page, ``page_table[s, min(w, max(len[s] - 1, 0) // page_size)]``,
    so every skipped cell repeats the previous block index and the
    pipeline issues no fetch for it.

Window layers skip from below too.  ``lo[s]``
(``PagedAttentionKV.lo``, 0 when absent) is the first position slot
``s`` may see, ``max(pos - window + 1, 0)`` on a sliding-window layer:
a cell is live only when it also ends past ``lo[s]``, the index maps
clamp the window up to the first page holding ``lo[s]`` (the cells
below it repeat that page's index and fetch it once), and keys below
``lo[s]`` are masked like those past ``len``.  With ``lo = 0`` every
condition reads as before.  A view without ``lo`` (every layer of a model
with no windows) runs the kernel without the lower bound at all, the
same kernel as before windows existed; one with ``lo`` (every layer of a
model with windows, full ones at ``lo = 0``) gives the same bits.

A skipped cell contributed exactly nothing before (its keys are all
masked: ``p = 0`` and ``corr = 1``), so skipping it changes no bit of
a live slot's ``(acc, m, l)``; pages past a slot's last live page are
never read, whatever they hold.  A dead slot is given ``len = 0``: it
reads page ``page_table[s, 0]`` once (the null page for a retired
slot's zeroed row) and returns ``acc = 0, m = -1e30, l = 0``, which the
caller's new-token merge renormalizes away.  When every cell is live
the kernel does exactly the work of one that visits them all.  The
paged scheduler counts the cells it computes against the grid
(``PagedScheduler.attn_cells_computed`` / ``attn_cells_grid``, per
layer, from the positions the host tracks).

The kernel runs the pool in per-page streaming (online-softmax) order
and returns the *partial* flash statistics ``(acc, m, l)`` rather than
a normalized output: the caller merges the current step's own (not yet
appended) KV with the standard two-block rule, exactly as the dense
``decode_attention_read`` does, so token parity against the gather
path is bitwise at the argmax.

Two backends register under the ExecutionPlan registry (never kwargs):

  * ``paged_attn``    — this Pallas kernel (interpret-mode on CPU CI,
    real lowering on TPU), priority 100;
  * ``paged_attn_ref`` — a gather-based XLA oracle computing the same
    statistics with global (single-pass) softmax, priority 10.

Both declare ``ops={'attention'}``, ``kv_layouts={'paged'}``,
``domains={'float'}`` (int8-KV pools carry scale pages the fused path
does not read yet — the scheduler falls back to the gather path there).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30          # the dense read's masking constant (attention.py)


class PagedAttentionKV(NamedTuple):
    """The raw page-pool view one attention layer reads: no gathered
    copy, just the pool pages plus the routing state.  A registered
    pytree (NamedTuple), so it flows through jit/scan/vmap; its
    ``shape`` property makes it a valid ``execute()`` weight operand —
    the plan shape is ``(S*KV*rep, hd, W*page_size)``: queries times
    head dim against the per-slot context capacity.

    Fields::

      k_pages, v_pages : (num_pages, page_size, KV, hd)  one layer's pool
      page_table       : (S, W) int32   pool page id per slot x window
      pos              : (S,) int32     live context length per slot
                                        (0 for a dead slot)
      lo               : (S,) int32     first visible position per slot
                                        (window layers; None = 0)
    """
    k_pages: jax.Array
    v_pages: jax.Array
    page_table: jax.Array
    pos: jax.Array
    lo: Optional[jax.Array] = None

    @property
    def shape(self) -> tuple:
        # (K, N) of the weight operand: shape_of(q, kv) must equal the
        # plan's (M, K, N) = (S*KV*rep, hd, W*ps)
        return (int(self.k_pages.shape[-1]),
                int(self.page_table.shape[-1])
                * int(self.k_pages.shape[-3]))


def _dims(q, kv) -> tuple:
    if q.ndim != 4:
        raise ValueError(f"paged attention takes q (slots, KV, rep, hd); "
                         f"got ndim={q.ndim}")
    s, kvh, rep, hd = (int(d) for d in q.shape)
    num_pages, ps, kvh_p, hd_p = (int(d) for d in kv.k_pages.shape)
    w = int(kv.page_table.shape[-1])
    if (kvh_p, hd_p) != (kvh, hd) or kv.v_pages.shape != kv.k_pages.shape:
        raise ValueError(f"page pool {kv.k_pages.shape}/"
                         f"{kv.v_pages.shape} does not match q "
                         f"{q.shape}")
    if int(kv.page_table.shape[0]) != s or int(kv.pos.shape[0]) != s:
        raise ValueError(f"page table {kv.page_table.shape} / pos "
                         f"{kv.pos.shape} do not cover {s} slots")
    return s, kvh, rep, hd, num_pages, ps, w


def _fused_kernel(*refs, page_size: int, last_w: int, windowed: bool):
    """One grid step = one (slot, page-window) cell.  ``k_ref``/``v_ref``
    hold page ``page_table[s, w]`` (the index map did the routing); the
    VMEM scratch carries the online-softmax state across the w axis.
    Cells at or past the slot's live length ``len_ref[s]``, and cells
    ending at or before its first visible position ``lo_ref[s]``, are
    skipped."""
    if windowed:
        (pt_ref, len_ref, lo_ref, q_ref, k_ref, v_ref, acc_ref, m_ref,
         l_ref, m_s, l_s, acc_s) = refs
    else:
        (pt_ref, len_ref, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, m_s,
         l_s, acc_s) = refs
    s = pl.program_id(0)
    w = pl.program_id(1)
    live = w * page_size < len_ref[s]
    if windowed:
        live &= (w + 1) * page_size > lo_ref[s]

    @pl.when(w == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, NEG_INF, m_s.dtype)
        l_s[...] = jnp.zeros(l_s.shape, l_s.dtype)
        acc_s[...] = jnp.zeros(acc_s.shape, acc_s.dtype)

    @pl.when(live)
    def _cell():
        q = q_ref[0]                                    # (KV, rep, hd)
        k = k_ref[0]                                    # (ps, KV, hd)
        v = v_ref[0].astype(jnp.float32)
        # the MXU multiplies bf16: one pass is exact for a bf16 pool and
        # query, f32 operands (and the f32 softmax weights p below) need
        # HIGHEST, or they are rounded to 8 mantissa bits
        qk_dtype = jnp.promote_types(q.dtype, k.dtype)
        sc = jnp.einsum("krd,tkd->krt", q.astype(qk_dtype),
                        k.astype(qk_dtype),
                        precision=(jax.lax.Precision.HIGHEST
                                   if qk_dtype == jnp.float32 else None),
                        preferred_element_type=jnp.float32)
        kpos = w * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page_size), 2)
        visible = kpos < len_ref[s]
        if windowed:
            visible &= kpos >= lo_ref[s]
        sc = jnp.where(visible, sc, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + p.sum(axis=-1)
        acc_s[...] = acc_s[...] * corr[..., None] + jnp.einsum(
            "krt,tkd->krd", p, v, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(w == last_w)
    def _flush():
        acc_ref[0] = acc_s[...]
        m_ref[0] = m_s[...]
        l_ref[0] = l_s[...]


def _live_page(i, j, pt, lens, *los, page_size: int):
    """The pool page cell ``(i, j)`` reads: window ``j`` clamped to slot
    ``i``'s live pages (from the one holding ``lo``, when given, to the
    last live one), so a skipped cell repeats a neighbour's block index
    and is not fetched."""
    last = jnp.maximum(lens[i] - 1, 0) // page_size
    if not los:
        return pt[i, jnp.minimum(j, last)]
    first = jnp.minimum(los[0][i] // page_size, last)
    return pt[i, jnp.clip(j, first, last)]


def paged_attention(q, kv: PagedAttentionKV, *,
                    interpret: bool = False) -> tuple:
    """Flash statistics of ``q`` against the paged context: returns
    ``(acc, m, l)`` with shapes ``(S, KV, rep, hd)`` / ``(S, KV, rep)``
    x2, all f32; ``out = acc / l[..., None]`` after the caller's
    new-token merge.  A slot with ``kv.pos == 0`` returns ``acc = 0,
    m = -1e30, l = 0``."""
    s, kvh, rep, hd, num_pages, ps, w = _dims(q, kv)
    page = functools.partial(_live_page, page_size=ps)
    # (page_table, len) and, on a window layer, lo
    scalars = (kv.page_table, kv.pos) + (
        () if kv.lo is None else (kv.lo.astype(kv.pos.dtype),))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(s, w),
        in_specs=[
            pl.BlockSpec((1, kvh, rep, hd), lambda i, j, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, ps, kvh, hd),
                         lambda i, j, *sc: (page(i, j, *sc), 0, 0, 0)),
            pl.BlockSpec((1, ps, kvh, hd),
                         lambda i, j, *sc: (page(i, j, *sc), 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, kvh, rep, hd), lambda i, j, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, kvh, rep), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec((1, kvh, rep), lambda i, j, *_: (i, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((kvh, rep), jnp.float32),
            pltpu.VMEM((kvh, rep), jnp.float32),
            pltpu.VMEM((kvh, rep, hd), jnp.float32),
        ],
    )
    fn = pl.pallas_call(
        functools.partial(_fused_kernel, page_size=ps, last_w=w - 1,
                          windowed=kv.lo is not None),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((s, kvh, rep, hd), jnp.float32),
            jax.ShapeDtypeStruct((s, kvh, rep), jnp.float32),
            jax.ShapeDtypeStruct((s, kvh, rep), jnp.float32),
        ],
        interpret=interpret,
        name="paged_attention",
    )
    acc, m, l = fn(*scalars, q, kv.k_pages, kv.v_pages)
    return acc, m, l


def _lo(kv: PagedAttentionKV) -> jax.Array:
    return (jnp.zeros_like(kv.pos) if kv.lo is None
            else kv.lo.astype(kv.pos.dtype))


def paged_attention_ref(q, kv: PagedAttentionKV) -> tuple:
    """Gather-based XLA oracle: materializes the dense copy the fused
    kernel avoids, computes the same ``(acc, m, l)`` statistics with a
    global (single-pass) softmax.  All three match the kernel to f32
    round-off: the score dot products contract in a different order
    (so ``m`` differs by about hd * eps relative), and ``acc``/``l``
    also sum in single-pass rather than per-page order."""
    s, kvh, rep, hd, num_pages, ps, w = _dims(q, kv)
    kg = kv.k_pages[kv.page_table].reshape(s, w * ps, kvh, hd)
    vg = kv.v_pages[kv.page_table].reshape(s, w * ps, kvh, hd)
    q32 = q.astype(jnp.float32)
    sc = jnp.einsum("skrd,stkd->skrt", q32, kg.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    kpos = jnp.arange(w * ps, dtype=jnp.int32)[None, :]
    valid = (kpos < kv.pos[:, None]) & (kpos >= _lo(kv)[:, None])
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    m = sc.max(axis=-1)
    # masked keys weigh exactly 0 (as exp(-1e30 - m) does once a live
    # key sets m), so a slot with no live key reads l = 0, acc = 0
    p = jnp.where(valid[:, None, None, :], jnp.exp(sc - m[..., None]), 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("skrt,stkd->skrd", p, vg.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return acc, m, l


def _check_operand(plan, x, w) -> None:
    if not isinstance(w, PagedAttentionKV):
        raise ValueError(f"attention plans take a PagedAttentionKV "
                         f"weight operand; got {type(w).__name__}")
    if plan.kv_layout != "paged":
        raise ValueError(f"backend {plan.backend!r} only reads the "
                         f"paged layout; plan has {plan.kv_layout!r}")


def run_pallas(plan, x, w):
    _check_operand(plan, x, w)
    return paged_attention(x, w, interpret=plan.interpret)


def run_gather(plan, x, w):
    _check_operand(plan, x, w)
    return paged_attention_ref(x, w)


EVAL_PAGE_SIZE = 8


def eval_operands(shape) -> tuple:
    """Abstract ``(q, PagedAttentionKV)`` operands whose ``shape_of``
    matches plan shape ``(m, k, n)`` — factored as S=m single-KV-head
    queries of head dim k over n context slots (the capability pass
    pushes these through ``jax.eval_shape``)."""
    m, k, n = (int(v) for v in shape)
    ps = EVAL_PAGE_SIZE if n % EVAL_PAGE_SIZE == 0 else 1
    w = n // ps
    q = jax.ShapeDtypeStruct((m, 1, 1, k), jnp.float32)
    pages = jax.ShapeDtypeStruct((w + 1, ps, 1, k), jnp.float32)
    kv = PagedAttentionKV(
        pages, pages,
        jax.ShapeDtypeStruct((m, w), jnp.int32),
        jax.ShapeDtypeStruct((m,), jnp.int32))
    return q, kv


def eval_output(shape) -> tuple:
    """Expected ``(acc, m, l)`` shapes for :func:`eval_operands`."""
    m, k, n = (int(v) for v in shape)
    return ((m, 1, 1, k), (m, 1, 1), (m, 1, 1))
