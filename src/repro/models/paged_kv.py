"""Paged, prefix-shared KV storage for the serving slot pool.

The paper's pitch is density: fit more model state in the same storage
by packing it tighter (7.8x at the cell level).  The serving analogue is
KV-cache density — the dense slot pool (serve.init_slot_pool) gives
every slot a ``(1, capacity, KV, hd)`` cache padded to full capacity, so
resident KV scales as ``slots x max_seq`` even when most slots hold
short requests, and identical prompt prefixes are duplicated per slot.

This module replaces the per-slot dense cache with a **block pool**:

  * :class:`PagedKVCache` — fixed-size pages on a leading ``page`` axis
    (``k_pages (L, P, page_size, KV, hd)``; int8-KV scale pages ride
    alongside with the same paging).  Page 0 is a reserved null page —
    never allocated, the target of masked/dead writes and of unused
    page-table entries.
  * :func:`slot_view` — the gather: a slot's page-table row gathered
    back into the dense ``(L, 1, cap, KV, hd)`` cache layout the
    existing attention read path consumes.  Positions at or beyond the
    slot's ``pos`` are masked by the same validity rule as the dense
    cache, and masked float contributions are EXACTLY zero
    (``exp(-1e30 - m) == 0.0``), so paged attention is **bitwise
    identical** to the dense pool (pinned in tests/test_paged.py).
  * :func:`append_tokens` — the per-decode-step scatter of every slot's
    new K/V token into its current page (dead slots are routed to the
    null page so a freed-and-reused page is never clobbered).
  * :func:`write_prompt_pages` — admission-time scatter of a prefill's
    KV slab into freshly allocated pages (pages whose hashed prefix
    already resides in the pool are mapped shared instead — see
    :class:`PageAllocator`).
  * :class:`PagedKV` + :func:`materialize` — a gather-view wrapper so
    ``attend``/``flash_attention`` accept paged K/V operands directly.
  * :class:`PageAllocator` — host-side free list with refcounted
    prefix sharing: full prompt pages are registered under a hash of
    the token prefix that determines their contents (causal attention:
    page j's KV depends exactly on tokens ``[0, (j+1)*page_size)``), so
    a later prompt with the same prefix maps the existing pages
    read-only instead of writing duplicates.  Shared pages are freed
    when their refcount drops to zero.

Which executors may run under the paged layout is a kernel-registry
capability (``kv_layout`` on ``ExecutionPlan``/``BackendSpec`` —
src/repro/kernels/README.md), not a kwarg threaded through ops/serve.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig


class PagedKVCache(NamedTuple):
    """The device-side page pool (shared by every slot of a scheduler).

    k_pages/v_pages: (L, P, page_size, KV, hd) in the cache storage
    dtype; k_scale_pages/v_scale_pages: (L, P, page_size, KV) f32,
    present only for int8-KV models (allocated up front, like the dense
    pool's scale buffers).
    """
    k_pages: jax.Array
    v_pages: jax.Array
    k_scale_pages: Optional[jax.Array] = None
    v_scale_pages: Optional[jax.Array] = None

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[2]

    @property
    def num_pages(self) -> int:
        return self.k_pages.shape[1]

    @property
    def page_bytes(self) -> int:
        """Device bytes one page occupies across k/v (+ scales)."""
        per = 0
        for leaf in self:
            if leaf is not None:
                per += leaf.nbytes // leaf.shape[1]
        return per


def init_page_pool(cfg: ModelConfig, num_pages: int,
                   page_size: int) -> PagedKVCache:
    """Allocate the page pool for a TransformerLM-family config.  Page 0
    is the reserved null page: never allocated, the landing zone for
    dead-slot scratch writes — its contents are garbage-by-design and
    every read of it is position-masked (do NOT assume it stays
    zero)."""
    if num_pages < 2:
        raise ValueError(f"num_pages must be >= 2 (page 0 is the "
                         f"reserved null page), got {num_pages}")
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    L, kv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.hd
    int8 = cfg.kv_cache_dtype == "int8"
    dt = jnp.int8 if int8 else cfg.dtype
    shape = (L, num_pages, page_size, kv, hd)
    k = jnp.zeros(shape, dt)
    v = jnp.zeros(shape, dt)
    if not int8:
        return PagedKVCache(k, v)
    # distinct buffers: k/v scale pages are donated independently
    return PagedKVCache(k, v, jnp.zeros(shape[:-1], jnp.float32),
                        jnp.zeros(shape[:-1], jnp.float32))


def slot_view(pool: PagedKVCache, page_table: jax.Array,
              pos: jax.Array) -> dict:
    """Gather one slot's pages into the dense decode-state layout.

    ``page_table`` (W,) int32 page ids (unused entries may point
    anywhere valid — the contents are masked by ``pos``); ``pos`` the
    slot's scalar next-write position.  Returns the ``{"k", "v", "pos"
    [, "k_scale", "v_scale"]}`` state-view ``registry`` decode reads —
    batch 1, capacity ``W * page_size``.
    """
    def gather(pages):
        g = pages[:, page_table]                 # (L, W, ps, ...)
        return g.reshape((g.shape[0], 1, g.shape[1] * g.shape[2])
                         + g.shape[3:])
    view = {"k": gather(pool.k_pages), "v": gather(pool.v_pages),
            "pos": pos}
    if pool.k_scale_pages is not None:
        view["k_scale"] = gather(pool.k_scale_pages)
        view["v_scale"] = gather(pool.v_scale_pages)
    return view


def raw_pool_view(pool: PagedKVCache, page_table: jax.Array) -> tuple:
    """The raw page arrays with the layer folded into the page axis,
    ``(L*P, page_size, KV, hd)``, and each layer's page table into
    them, ``(L, S, W)`` from the ``(S, W)`` table, for layout-specialized
    executors that consume the page table in-kernel
    (``kernels.paged_attention``) instead of gathering a dense copy.
    Page ``p`` of layer ``l`` is row ``l*P + p``, so layer ``l`` reads
    the whole pool through ``page_table + l*P`` (page 0 of each layer,
    its null page, stays what a padded row reads) and no layer's pages
    are sliced out.  Merging the two leading axes of the row-major pool
    is a bitcast.

    Float-KV pools only: int8 pools carry per-position scale pages the
    fused read path does not consume — callers (PagedScheduler) fall
    back to the :func:`slot_view` gather there."""
    if pool.k_scale_pages is not None:
        raise ValueError(
            "raw pool view is float-KV only: int8 page pools carry "
            "scale pages the fused attention read does not consume; "
            "use the slot_view gather path")
    n_layers, n_pages = pool.k_pages.shape[:2]
    k, v = (a.reshape((n_layers * n_pages,) + a.shape[2:])
            for a in (pool.k_pages, pool.v_pages))
    offsets = jnp.arange(n_layers, dtype=page_table.dtype) * n_pages
    return k, v, page_table + offsets[:, None, None]


def append_tokens(pool: PagedKVCache, kts: jax.Array, vts: jax.Array,
                  page_table: jax.Array, pos: jax.Array,
                  live: jax.Array) -> PagedKVCache:
    """Scatter every slot's freshly projected K/V token into its current
    page — the paged counterpart of the dense pool's one batched
    dynamic-update-slice per decode step.

    kts/vts: (slots, L, KV, hd) compute-dtype token projections (the
    ``decode_paged`` read returns them); page_table (slots, W) int32;
    pos (slots,) the per-slot write positions; ``live`` masks the
    scatter — dead slots (retired, or scratch-decoding past their
    budget) are routed to the null page so they can never corrupt a
    page that was freed and reallocated to another slot.
    """
    ps = pool.page_size
    slots = kts.shape[0]
    rows = jnp.arange(slots)
    # clamp the page index for scratch decodes past the table width
    pidx = jnp.minimum(pos // ps, page_table.shape[1] - 1)
    pid = jnp.where(live, page_table[rows, pidx], 0)
    off = jnp.where(live, pos % ps, 0)
    k_t = jnp.moveaxis(kts, 0, 1)                # (L, slots, KV, hd)
    v_t = jnp.moveaxis(vts, 0, 1)
    if pool.k_scale_pages is not None:
        from .attention import quantize_kv
        kq, ksc = quantize_kv(k_t)
        vq, vsc = quantize_kv(v_t)
        return pool._replace(
            k_pages=pool.k_pages.at[:, pid, off].set(kq),
            v_pages=pool.v_pages.at[:, pid, off].set(vq),
            k_scale_pages=pool.k_scale_pages.at[:, pid, off].set(ksc),
            v_scale_pages=pool.v_scale_pages.at[:, pid, off].set(vsc))
    return pool._replace(
        k_pages=pool.k_pages.at[:, pid, off].set(
            k_t.astype(pool.k_pages.dtype)),
        v_pages=pool.v_pages.at[:, pid, off].set(
            v_t.astype(pool.v_pages.dtype)))


def write_prompt_pages(pool: PagedKVCache, state: dict,
                       pool_ids: jax.Array,
                       src_pages: jax.Array) -> PagedKVCache:
    """Admission: copy a batch-1 prefill state's KV into the pool.

    ``state`` is the dense prefill state (``k (L, 1, cap, KV, hd)`` in
    storage dtype, scales included for int8-KV models; ``cap`` must be
    a page multiple).  ``src_pages[i]`` names the page-aligned chunk of
    the slab that lands in pool page ``pool_ids[i]`` — prefix-shared
    pages are simply absent from both arrays (their contents already
    reside in the pool, bit-for-bit).
    """
    ps = pool.page_size

    def put(pages, slab):
        cap = slab.shape[1]
        view = slab.reshape((slab.shape[0], cap // ps, ps)
                            + slab.shape[2:])
        return pages.at[:, pool_ids].set(
            view[:, src_pages].astype(pages.dtype))

    new = pool._replace(k_pages=put(pool.k_pages, state["k"][:, 0]),
                        v_pages=put(pool.v_pages, state["v"][:, 0]))
    if pool.k_scale_pages is not None:
        new = new._replace(
            k_scale_pages=put(pool.k_scale_pages, state["k_scale"][:, 0]),
            v_scale_pages=put(pool.v_scale_pages, state["v_scale"][:, 0]))
    return new


# ---------------------------------------------------------------------
# attend()/flash_attention() wiring: paged K/V operands
# ---------------------------------------------------------------------

class PagedKV(NamedTuple):
    """A paged K or V operand for ``models.attention.attend`` /
    ``flash_attention``: per-batch-row page tables over a shared page
    pool.  ``pages (P, page_size, KV, hd)``; ``page_table (B, n)``.
    The attention entry points gather (:func:`materialize`) before
    computing, so the paged layout needs no second attention
    implementation — and stays bitwise identical to dense operands.
    """
    pages: jax.Array
    page_table: jax.Array


def materialize(x):
    """Gather a :class:`PagedKV` view into a dense (B, T, KV, hd) array
    (identity on anything else)."""
    if not isinstance(x, PagedKV):
        return x
    b, n = x.page_table.shape
    g = x.pages[x.page_table]                    # (B, n, ps, KV, hd)
    return g.reshape((b, n * g.shape[2]) + g.shape[3:])


# ---------------------------------------------------------------------
# host-side page accounting
# ---------------------------------------------------------------------

class PageAllocator:
    """Free-list + refcounted prefix registry for one page pool.

    Pure host bookkeeping: page *contents* never leave the device; this
    tracks which pool indices are free, how many slots reference each
    shared page, and which hashed token prefixes already reside in the
    pool.  ``alloc`` is all-or-nothing so admission can be deferred
    atomically when the pool is exhausted (the scheduler retries after
    the next retire).
    """

    def __init__(self, num_pages: int, page_size: int):
        self.num_pages = num_pages
        self.page_size = page_size
        # page 0 reserved as the null page; hand out ascending ids
        self._free = list(range(num_pages - 1, 0, -1))
        self._refcount: dict = {}          # page id -> live references
        self._prefix: dict = {}            # prefix key -> page id
        self._key_of: dict = {}            # page id -> prefix key
        self.prefix_hits = 0
        self.prefix_lookups = 0
        self.peak_in_use = 0

    @property
    def pages_in_use(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    def _note_peak(self):
        self.peak_in_use = max(self.peak_in_use, self.pages_in_use)

    def alloc(self, n: int):
        """n fresh private pages (refcount 1), or None if the pool
        cannot satisfy all of them."""
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        for pid in ids:
            self._refcount[pid] = 1
        self._note_peak()
        return ids

    def lookup_prefix(self, key):
        """Map a shared page if its prefix key resides in the pool
        (refcount++); returns the page id or None."""
        self.prefix_lookups += 1
        pid = self._prefix.get(key)
        if pid is None:
            return None
        self._refcount[pid] += 1
        self.prefix_hits += 1
        return pid

    def register_prefix(self, key, pid: int) -> None:
        """Publish a freshly written prompt page for future sharing."""
        self._prefix[key] = pid
        self._key_of[pid] = key

    def release(self, pids) -> None:
        """Drop one reference per page; pages reaching refcount 0 return
        to the free list (and leave the prefix registry)."""
        for pid in pids:
            self._refcount[pid] -= 1
            if self._refcount[pid] == 0:
                del self._refcount[pid]
                key = self._key_of.pop(pid, None)
                if key is not None:
                    del self._prefix[key]
                self._free.append(pid)

    def reset_stats(self) -> None:
        """Zero the measurement counters (peak watermark re-anchored to
        the current occupancy) without touching allocation state — so a
        bench can warm up, reset, and then measure only its replays."""
        self.peak_in_use = self.pages_in_use
        self.prefix_hits = 0
        self.prefix_lookups = 0

    @property
    def prefix_hit_rate(self) -> float:
        if not self.prefix_lookups:
            return 0.0
        return self.prefix_hits / self.prefix_lookups


def prefix_key(prompt_np, page: int, page_size: int):
    """Hashable identity of prompt page ``page``: the token prefix that
    (causally) determines the page's KV contents."""
    return (page, prompt_np[: (page + 1) * page_size].tobytes())
