"""Capability-based execution planning for the kernel layer.

Every matmul in the framework resolves ONCE into a frozen, hashable
:class:`ExecutionPlan` — *what* to compute (op, domain, packing mode,
problem shape) plus *how* a backend realizes it (backend name, block
shapes, interpret flag) — and then runs through :func:`execute`.  The
old routing kwargs (``backend=``, ``domain=``, ``interpret=``,
``bm/bn/bk``) threaded through ``ops.ternary_matmul`` ->
``CIMConfig`` -> models -> serve survive only as deprecation shims.

Backends self-describe through :class:`BackendSpec`: the ops they
implement, the arithmetic domains, packing modes and platforms they
support, and a priority.  ``backend='auto'`` selects the
highest-priority capable backend for the current platform instead of
an if/elif chain; an explicit backend that lacks a capability fails
loudly with the list of what it *does* support.  The built-in
backends (pallas, xla, ref) register from ``kernels.backends``.

Resolution is cached per (shape, phase, request) via ``lru_cache``, so
plan construction inside a jit trace is a dict hit, and the per-call
platform probe of the old wrappers (``_default_interpret`` on every
invocation) is evaluated once per plan.

Contract: for any fixed plan, every backend capable of that plan's
(domain, packing) cell computes the same function — pallas == xla ==
ref bitwise in the int8 domain, and to f32 round-off in float (see
tests/test_kernels.py / tests/test_fastlane.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

OPS = ("ternary", "cim", "attention", "ternary_grouped")
DOMAINS = ("float", "int8")
PACKINGS = ("base3", "trit2")
PHASES = ("auto", "decode", "prefill")
KV_LAYOUTS = ("dense", "paged")
FIDELITIES = ("exact", "device")

CIM_DEFAULT_BLOCKS = (128, 128, 128)    # kernels.cim_mac defaults

# Bounded plan-cache size: varied-shape traffic (paged serving widens the
# set of (M, K, N) keys a long-lived process resolves) must not grow the
# resolution cache without bound.  2^12 plans cover every (shape x request)
# cell a production sweep touches; eviction only ever costs a re-resolve.
PLAN_CACHE_SIZE = 4096


def check_choice(kind: str, value: Any, choices) -> None:
    """Uniform unknown-name error: every rejected backend/domain/mode
    string names the valid choices (ISSUE 4 satellite: some entrypoints
    used to raise bare ``ValueError(mode)``, others fell through)."""
    if value not in choices:
        raise ValueError(f"unknown {kind} {value!r}; expected one of "
                         f"{sorted(choices)}")


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """A fully resolved kernel execution: frozen and hashable, so it is
    a dict/jit-static key.  Produced by :func:`plan_matmul`; consumed by
    :func:`execute`.

    ``blocks`` is the (bm, bn, bk) tile choice for block-tiled backends
    (pallas) and None for backends that tile internally (xla, ref).
    ``interpret`` is resolved once at plan time (True off-TPU).
    ``phase`` is advisory metadata today (blocks are shape-resolved).
    ``kv_layout`` names the KV-cache layout the surrounding serving loop
    feeds this matmul from (``dense`` slot caches or the ``paged`` block
    pool): backends declare which layouts they can be planned under, so
    paged serving is a registered executor capability, not a kwarg
    threaded through ops/serve.  ``fidelity`` names the execution
    fidelity the plan was resolved for: ``exact`` (the bitwise kernel
    contract) or ``device`` (fault-injected analog path — sampled
    conductances + ADC transfer, ``repro.faults``).  The requested
    fidelity is routed through :func:`route_fidelity` first, so
    accuracy-critical phases (prefill) resolve to exact backends even
    under a ``device`` request.  ``adc_bits`` / ``num_trits`` are set
    for the macro-exact ``cim`` op and for device-fidelity plans.
    """
    op: str                                  # ternary | cim | attention | ternary_grouped
    backend: str                             # resolved name (never 'auto')
    domain: str                              # float | int8
    packing: str                             # base3 | trit2
    m: int
    k: int
    n: int
    phase: str = "auto"                      # auto | decode | prefill
    blocks: Optional[tuple] = None           # (bm, bn, bk) | None
    interpret: bool = False
    kv_layout: str = "dense"                 # dense | paged
    adc_bits: Optional[int] = None           # cim op / device fidelity
    num_trits: Optional[int] = None          # cim op / device fidelity
    fidelity: str = "exact"                  # exact | device (post-routing)
    block_source: str = "heuristic"          # heuristic | autotune | pinned

    @property
    def shape(self) -> tuple:
        return (self.m, self.k, self.n)

    def describe(self) -> dict:
        """JSON-friendly record of the resolved plan (bench artifacts)."""
        return {"backend": self.backend, "domain": self.domain,
                "packing": self.packing, "phase": self.phase,
                "blocks": list(self.blocks) if self.blocks else None,
                "interpret": self.interpret,
                "kv_layout": self.kv_layout,
                "fidelity": self.fidelity,
                "block_source": self.block_source}


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """Capability declaration + runner for one execution backend.

    ``runner(plan, x, w) -> y`` receives the resolved plan; selection
    never inspects the runner.  ``needs_blocks`` backends get (bm, bn,
    bk) resolved into the plan (shape-adaptive unless pinned).
    ``kv_layouts`` is the set of KV-cache layouts the backend can be
    planned under (``dense`` and/or ``paged``): a paged serving loop
    requests ``kv_layout='paged'`` and a dense-only backend is rejected
    at plan time instead of silently reading a layout it cannot.
    ``fidelities`` is the set of execution fidelities the backend
    implements: the built-ins are ``exact`` (bitwise kernel contract);
    the fault-injected analog path (``repro.faults``) registers a
    ``device``-only backend, so a fidelity request is a capability
    match, not a kwarg threaded through ops/serve.
    """
    name: str
    ops: frozenset
    domains: frozenset
    packings: frozenset
    platforms: frozenset
    priority: int
    runner: Callable
    needs_blocks: bool = False
    kv_layouts: frozenset = frozenset({"dense"})
    fidelities: frozenset = frozenset({"exact"})

    def supports(self, op: str, domain: str, packing: str,
                 platform: str, kv_layout: str = "dense",
                 fidelity: str = "exact") -> bool:
        return (op in self.ops and domain in self.domains
                and packing in self.packings and platform in self.platforms
                and kv_layout in self.kv_layouts
                and fidelity in self.fidelities)


_REGISTRY: dict[str, BackendSpec] = {}


def _ensure_builtin_backends() -> None:
    # populate lazily so `import repro.kernels.plan` alone works and the
    # registry survives partial package initialization
    if not _REGISTRY:
        from . import backends  # noqa: F401  (registers on import)


def register_backend(spec: BackendSpec, *, override: bool = False) -> None:
    """Register an execution backend.  Re-registering an existing name
    requires ``override=True`` (tests swap in capability-limited
    doubles)."""
    if spec.name in _REGISTRY and not override:
        raise ValueError(f"backend {spec.name!r} already registered; "
                         f"pass override=True to replace it")
    _REGISTRY[spec.name] = spec
    plan_cache_clear()        # capabilities changed: cached plans stale


def unregister_backend(name: str) -> None:
    """Remove a backend (test cleanup for registered doubles)."""
    _REGISTRY.pop(name, None)
    plan_cache_clear()


def backend_names() -> list:
    _ensure_builtin_backends()
    return sorted(_REGISTRY)


def get_backend(name: str) -> BackendSpec:
    _ensure_builtin_backends()
    if name not in _REGISTRY:
        raise ValueError(f"unknown backend {name!r}; registered: "
                         f"{backend_names()}")
    return _REGISTRY[name]


def route_fidelity(fidelity: str, phase: str) -> str:
    """Noise-aware routing policy: which fidelity a phase actually runs.

    ``exact`` requests always stay exact.  A ``device`` request runs the
    fault-injected path only for error-tolerant phases (``decode``
    sampling, ``auto``); the accuracy-critical ``prefill`` phase is
    routed back to an exact backend — prefill mistakes corrupt the
    whole KV prefix, while a decode-step upset perturbs one sampled
    token (the graceful-degradation contract of the serve engines)."""
    check_choice("fidelity", fidelity, FIDELITIES)
    check_choice("phase", phase, PHASES)
    if fidelity == "device" and phase == "prefill":
        return "exact"
    return fidelity


def resolve_backend(op: str = "ternary", backend: str = "auto",
                    domain: str = "float", packing: str = "base3",
                    platform: Optional[str] = None,
                    kv_layout: str = "dense",
                    fidelity: str = "exact") -> BackendSpec:
    """Capability match: 'auto' picks the highest-priority backend that
    supports (op, domain, packing, kv_layout, fidelity) on `platform`;
    an explicit name is validated against its declared capabilities and
    fails loudly."""
    _ensure_builtin_backends()
    if platform is None:
        platform = _platform()
    if backend in (None, "auto"):
        cands = [s for s in _REGISTRY.values()
                 if s.supports(op, domain, packing, platform, kv_layout,
                               fidelity)]
        if not cands:
            raise ValueError(
                f"no registered backend supports op={op!r} domain={domain!r} "
                f"packing={packing!r} kv_layout={kv_layout!r} "
                f"fidelity={fidelity!r} on platform "
                f"{platform!r}; registered: {backend_names()}")
        return max(cands, key=lambda s: s.priority)
    spec = get_backend(backend)
    for kind, value, have in (("op", op, spec.ops),
                              ("domain", domain, spec.domains),
                              ("packing mode", packing, spec.packings),
                              ("kv layout", kv_layout, spec.kv_layouts),
                              ("fidelity", fidelity, spec.fidelities),
                              ("platform", platform, spec.platforms)):
        if value not in have:
            raise ValueError(
                f"backend {backend!r} does not support {kind} {value!r} "
                f"(supports {sorted(have)}); registered backends: "
                f"{backend_names()}")
    return spec


def _platform() -> str:
    import jax
    return jax.default_backend()


def default_interpret(platform: Optional[str] = None) -> bool:
    """Pallas kernels run in interpret mode off-TPU.  Evaluated once per
    resolved plan (the old wrappers probed the backend on every call)."""
    return (platform or _platform()) != "tpu"


def shape_of(x, w) -> tuple:
    """(M, K, N) problem shape of ``x (..., K) @ w (..., K, N)``: M is
    the flattened leading extent (the kernels run on 2-D views)."""
    m = 1
    for d in x.shape[:-1]:
        m = m * int(d)
    return (m, int(x.shape[-1]), int(w.shape[-1]))


@functools.lru_cache(maxsize=PLAN_CACHE_SIZE)
def _resolve(op, m, k, n, phase, backend, domain, packing, interpret,
             bm, bn, bk, kv_layout, fidelity, adc_bits, num_trits,
             platform) -> ExecutionPlan:
    check_choice("op", op, OPS)
    check_choice("phase", phase, PHASES)
    check_choice("domain", domain, DOMAINS)
    check_choice("packing mode", packing, PACKINGS)
    check_choice("kv layout", kv_layout, KV_LAYOUTS)
    # noise-aware routing BEFORE capability match: a device request on
    # an accuracy-critical phase resolves against exact backends
    fidelity = route_fidelity(fidelity, phase)
    spec = resolve_backend(op, backend, domain, packing, platform,
                           kv_layout, fidelity)
    if interpret is None:
        interpret = default_interpret(platform)
    blocks = None
    block_source = "heuristic"
    if spec.needs_blocks:
        if op == "cim":
            dm, dn, dk = CIM_DEFAULT_BLOCKS
        else:
            from . import autotune
            tuned = autotune.lookup_blocks(m, k, n, phase, platform,
                                           packing, domain)
            if tuned is not None:
                dm, dn, dk = tuned
                block_source = "autotune"
            else:
                from .ternary_matmul import (TRIT2_PER_BYTE,
                                             select_block_shapes)
                # the kernel pads trit2 K to a byte multiple before
                # tiling; select against the extent it will actually see
                kdim = (k + (-k % TRIT2_PER_BYTE) if packing == "trit2"
                        else k)
                dm, dn, dk = select_block_shapes(m, kdim, n, packing,
                                                 domain=domain)
        if bm or bn or bk:
            block_source = "pinned"
        blocks = (bm or dm, bn or dn, bk or dk)
    return ExecutionPlan(op=op, backend=spec.name, domain=domain,
                         packing=packing, m=m, k=k, n=n, phase=phase,
                         blocks=blocks, interpret=bool(interpret),
                         kv_layout=kv_layout, adc_bits=adc_bits,
                         num_trits=num_trits, fidelity=fidelity,
                         block_source=block_source)


def plan_matmul(shape, phase: str = "auto", cfg: Any = None, *,
                op: str = "ternary", backend: Optional[str] = None,
                domain: Optional[str] = None, packing: Optional[str] = None,
                interpret: Optional[bool] = None, bm: Optional[int] = None,
                bn: Optional[int] = None, bk: Optional[int] = None,
                kv_layout: Optional[str] = None,
                fidelity: Optional[str] = None,
                adc_bits: Optional[int] = None,
                num_trits: Optional[int] = None) -> ExecutionPlan:
    """Resolve an :class:`ExecutionPlan` for a (M, K, N) matmul.

    ``cfg`` is any object carrying plan-request attributes (``backend``,
    ``domain``, ``packing``, ``interpret``, ``kv_layout``, ``fidelity``
    — e.g. a ``core.cim_linear.CIMConfig``); explicit keyword arguments
    override it.  Resolution is cached on the full request (bounded at
    ``PLAN_CACHE_SIZE`` entries — see ``plan_cache_info``), so calling
    this per layer inside a jit trace costs a dict lookup; pass
    ``bm/bn/bk`` to pin block shapes (tests, sweeps), otherwise
    block-tiled backends get the shape-adaptive choice.
    ``kv_layout='paged'`` requests a backend capable of running under
    the paged KV block pool.  ``fidelity='device'`` requests the
    fault-injected analog path (routed per phase — see
    :func:`route_fidelity`).  ``op='cim'`` plans the macro-exact CIM
    MAC (``adc_bits`` / ``num_trits`` default 5, as do device-fidelity
    ternary plans, whose ADC model needs them).
    """
    m, k, n = (int(s) for s in shape)
    if cfg is not None:
        # a config collapses to a plan request through plan_request()
        # (e.g. CIMConfig); bare attribute carriers work too
        req = (cfg.plan_request() if hasattr(cfg, "plan_request") else
               {f: getattr(cfg, f, None)
                for f in ("backend", "domain", "packing", "interpret",
                          "kv_layout", "fidelity")})
        backend = backend if backend is not None else req.get("backend")
        domain = domain if domain is not None else req.get("domain")
        packing = packing if packing is not None else req.get("packing")
        interpret = (interpret if interpret is not None
                     else req.get("interpret"))
        kv_layout = (kv_layout if kv_layout is not None
                     else req.get("kv_layout"))
        fidelity = (fidelity if fidelity is not None
                    else req.get("fidelity"))
    fidelity = "exact" if fidelity is None else fidelity
    if op == "cim" or fidelity == "device":
        adc_bits = 5 if adc_bits is None else adc_bits
        num_trits = 5 if num_trits is None else num_trits
    _ensure_builtin_backends()
    return _resolve(op, m, k, n, phase,
                    "auto" if backend is None else backend,
                    "float" if domain is None else domain,
                    "base3" if packing is None else packing,
                    interpret, bm, bn, bk,
                    "dense" if kv_layout is None else kv_layout,
                    fidelity, adc_bits, num_trits, _platform())


def plan_cache_info():
    """CacheInfo of the bounded plan-resolution cache (hits, misses,
    ``maxsize == PLAN_CACHE_SIZE``, currsize)."""
    return _resolve.cache_info()


def plan_cache_clear() -> None:
    _resolve.cache_clear()


def execute(plan: ExecutionPlan, x, w):
    """Run a resolved plan: ``x (..., K) @ w -> (..., N)``.

    ``w`` is an ``ops.PackedTernary`` for ternary plans, or a float
    (K, N) array / base3 PackedTernary for cim plans.  The plan's shape
    and packing are validated against the operands — a plan resolved for
    one shape must not silently run another (plans are per-shape)."""
    spec = get_backend(plan.backend)
    got = shape_of(x, w)
    if got != plan.shape:
        raise ValueError(f"operand shape {got} does not match plan "
                         f"{plan.shape} (plans are resolved per shape; "
                         f"call plan_matmul for this shape)")
    mode = getattr(w, "mode", None)
    if plan.op == "ternary" and mode is not None and mode != plan.packing:
        raise ValueError(f"weight packing {mode!r} does not match plan "
                         f"packing {plan.packing!r}")
    return spec.runner(plan, x, w)
