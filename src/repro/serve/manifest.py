"""Audited manifest of the serving engine's jitted entry points.

Every jitted function the serving path can dispatch is named here,
together with the donation and output-arity facts its factory
declares.  The ``jaxpr`` analysis pass (``repro.analysis``,
JX001–JX004) traces each entry against abstract inputs and proves the
declarations hold in the lowered artifact — a donated buffer that XLA
silently copies instead of aliasing (the 2x-KV-pool failure mode), a
widened dtype, or a callback smuggled into the hot path fails `make
analyze`, not a production serve.

An entry's ``build(model)`` returns ``(jitted_fn, args)`` where every
arg leaf is a ShapeDtypeStruct — nothing allocates.  The geometry
constants are deliberately tiny (the contracts are shape-independent);
``donated_argnums`` restates what the factory declares so drift
between this manifest and ``engine.py`` is itself caught (the trace
warns/loses aliasing when the real jit donates differently).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.models.config import ParamDef, abstract_params, is_def

# tiny trace geometry: batch rows, KV capacity, pool slots, chunk
# steps, positions per KV page
B, CAP, SLOTS, CHUNK, PAGE = 2, 32, 4, 3, 8


class AuditedEntry(NamedTuple):
    """One jitted entry point under dataflow audit."""
    name: str
    build: Callable[[Any], tuple]     # model -> (jitted_fn, args)
    donated_argnums: tuple            # what the factory declares
    out_arity: int                    # declared output tuple length
    note: str = ""


def _params(model):
    return abstract_params(model.param_defs, model.cfg.dtype)


def _cache(model, b: int, cap: int):
    return abstract_params(model.cache_defs(b, cap), model.cfg.dtype)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _lane(dtype=jnp.int32):
    return _sds((SLOTS,), dtype)


def _slot_pool(model):
    """Per-slot batch-1 caches stacked on the leading slot axis — the
    abstract mirror of ``engine.init_slot_pool``."""
    pooled = jax.tree.map(
        lambda d: ParamDef((SLOTS,) + d.shape, ("slot",) + d.axes,
                           d.init, d.dtype),
        model.cache_defs(1, CAP), is_leaf=is_def)
    return abstract_params(pooled, model.cfg.dtype)


def _page_geometry():
    per_slot = -(-CAP // PAGE)
    return per_slot, 1 + SLOTS * per_slot


def _page_pool(model):
    from repro.models.paged_kv import PagedKVCache
    cfg = model.cfg
    _per_slot, num_pages = _page_geometry()
    pshape = (cfg.num_layers, num_pages, PAGE, cfg.num_kv_heads, cfg.hd)
    return PagedKVCache(_sds(pshape, cfg.dtype), _sds(pshape, cfg.dtype))


def _prefill(model):
    from .engine import make_prefill_step
    fn = make_prefill_step(model, CAP)
    return fn, (_params(model), {"tokens": _sds((B, CAP), jnp.int32)})


def _decode_step(model):
    from .engine import make_decode_step
    fn = make_decode_step(model)
    return fn, (_params(model), _sds((B,), jnp.int32),
                _cache(model, B, CAP))


def _chunked_loop(model):
    from .engine import make_chunked_decode_loop
    fn = make_chunked_decode_loop(model, CHUNK)
    return fn, (_params(model), _lane(), _slot_pool(model),
                _lane(jnp.bool_), _lane(), _lane(jnp.bool_), _lane(),
                _lane())


def _admit(model):
    from .engine import make_admit_fn
    fn = make_admit_fn()
    scalar = _sds((), jnp.int32)
    return fn, (_slot_pool(model), _lane(), _lane(jnp.bool_), _lane(),
                _lane(jnp.bool_), _lane(), _lane(), scalar,
                _cache(model, 1, CAP), _sds((1,), jnp.int32), scalar,
                scalar)


def _paged_loop(model):
    from .engine import make_paged_decode_loop
    fn = make_paged_decode_loop(model, CHUNK)
    per_slot, _num_pages = _page_geometry()
    table = _sds((SLOTS, per_slot), jnp.int32)
    return fn, (_params(model), _lane(), _page_pool(model), table,
                _lane(), _lane(jnp.bool_), _lane(), _lane(jnp.bool_),
                _lane(), _lane())


def _paged_admit(model):
    from .engine import make_paged_admit_fn
    fn = make_paged_admit_fn()
    scalar = _sds((), jnp.int32)
    return fn, (_lane(), _lane(jnp.bool_), _lane(), _lane(jnp.bool_),
                _lane(), _lane(), _lane(), scalar,
                _sds((1,), jnp.int32), scalar, scalar, scalar)


def entries() -> tuple[AuditedEntry, ...]:
    """The serving engine's audited jitted surface."""
    return (
        AuditedEntry("serve.prefill_step", _prefill, (), 2,
                     "batched prefill; nothing donated (params are "
                     "reused across admissions)"),
        AuditedEntry("serve.decode_step", _decode_step, (2,), 2,
                     "plain per-token step; the cache is donated and "
                     "must alias (no 2x cache memory)"),
        AuditedEntry("serve.chunked_decode_loop", _chunked_loop, (), 8,
                     "continuous-batching chunk; deliberately NO "
                     "donation — the while_loop carries the pool "
                     "internally and XLA cannot alias into loop state"),
        AuditedEntry("serve.admit", _admit, (0, 1, 2, 3, 4, 5, 6), 7,
                     "admission scatter: pool + every control lane "
                     "donated and aliased in place"),
        AuditedEntry("serve.paged_decode_loop", _paged_loop, (), 9,
                     "paged-KV chunk; no donation (while_loop carries "
                     "the page pool)"),
        AuditedEntry("serve.paged_admit", _paged_admit,
                     (0, 1, 2, 3, 4, 5, 6), 7,
                     "lane-only admission scatter for the paged pool"),
    )
