"""`jaxpr` — static dataflow audit of the jitted entry points
(JX001–JX004).

The serve/train/frontend packages name their jitted surfaces in
audited manifests (``repro.serve.manifest`` et al.: factory, abstract
inputs, declared donation + output arity).  This pass traces each
entry to a closed jaxpr — no device code runs — and proves the
contracts the dynamic ``sanitize``/``frontend`` passes can only
observe:

| rule  | contract |
|-------|----------|
| JX001 | declared buffer donations actually alias in the lowered artifact: the ``tf.aliasing_output`` count equals the donated leaf count and lowering emits no donation warning (a silently-copied donated KV pool is 2x cache memory) |
| JX002 | dtype discipline on the hot path: no float64/complex128 aval anywhere in the jaxpr (including sub-jaxprs) and no weak-typed top-level output (a python scalar escaping the graph re-promotes downstream) |
| JX003 | no host round-trip primitives inside jitted regions: ``pure_callback``/``io_callback``/``debug_callback``/infeed/outfeed never appear |
| JX004 | transfer contract: the closed jaxpr carries zero effects (the return value is the ONE per-chunk transfer — an effect is an extra channel) and the traced output arity matches the manifest's hand-audited declaration |

Violation injection (tests / ``--inject-jaxpr``): ``donation``,
``widen``, ``callback``, ``transfer``.
"""
from __future__ import annotations

import math
import warnings
from typing import Any, Iterator, Optional

import jax
import jax.numpy as jnp

from .base import Finding
from . import abscache

PASS = "jaxpr"

_BANNED_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback", "infeed", "outfeed",
})

_BANNED_DTYPES = ("float64", "complex128")


def _subjaxprs(value) -> Iterator:
    from jax.extend import core as jex_core
    if isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jex_core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _subjaxprs(v)


def _iter_eqns(jaxpr) -> Iterator:
    """Every equation in a jaxpr, recursing through sub-jaxprs
    (while_loop bodies, scans, custom_jvp remat regions...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in _subjaxprs(param):
                yield from _iter_eqns(sub)


def _check_entry(entry, model, inject: Optional[str]) -> list[Finding]:
    findings = []
    fn, args = entry.build(model)
    where = entry.name

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        traced = fn.trace(*args)
        lowered = traced.lower()
    closed = traced.jaxpr

    # ---- JX001: donation aliasing --------------------------------
    donated_leaves = sum(len(jax.tree.leaves(args[i]))
                         for i in entry.donated_argnums)
    aliased = lowered.as_text().count("tf.aliasing_output")
    if aliased != donated_leaves:
        findings.append(Finding(
            PASS, "JX001", where,
            f"{donated_leaves} donated buffer leaf(s) declared but "
            f"{aliased} alias in the lowered module — XLA will copy "
            f"the non-aliased donations"))
    for w in caught:
        if "donated" in str(w.message).lower():
            findings.append(Finding(
                PASS, "JX001", where,
                f"lowering warned about donation: {w.message}"))

    # ---- JX002: dtype discipline ---------------------------------
    bad_dtypes = set()
    for eqn in _iter_eqns(closed.jaxpr):
        for var in (*eqn.invars, *eqn.outvars):
            aval = getattr(var, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt in _BANNED_DTYPES:
                bad_dtypes.add((dt, eqn.primitive.name))
    for dt, prim in sorted(bad_dtypes):
        findings.append(Finding(
            PASS, "JX002", where,
            f"{dt} aval on primitive {prim!r} — an unintended "
            f"promotion doubles hot-path bandwidth"))
    for i, aval in enumerate(closed.out_avals):
        if getattr(aval, "weak_type", False) \
                and jnp.issubdtype(aval.dtype, jnp.floating):
            findings.append(Finding(
                PASS, "JX002", where,
                f"output {i} is weak-typed {aval.dtype} — a python "
                f"scalar escaped the graph and will re-promote "
                f"downstream"))

    # ---- JX003: no host round-trips ------------------------------
    banned_seen = set()
    for eqn in _iter_eqns(closed.jaxpr):
        if eqn.primitive.name in _BANNED_PRIMITIVES:
            banned_seen.add(eqn.primitive.name)
    for prim in sorted(banned_seen):
        findings.append(Finding(
            PASS, "JX003", where,
            f"host-callback primitive {prim!r} inside the jitted "
            f"region — a hidden device->host round trip per dispatch"))

    # ---- JX004: transfer contract --------------------------------
    if closed.effects:
        findings.append(Finding(
            PASS, "JX004", where,
            f"jaxpr carries effects {sorted(map(str, closed.effects))} "
            f"— the per-chunk transfer must be the only channel out"))
    outs = jax.eval_shape(fn, *args)
    arity = len(outs) if isinstance(outs, (tuple, list)) else 1
    if arity != entry.out_arity:
        findings.append(Finding(
            PASS, "JX004", where,
            f"traced output arity {arity} != manifest's audited "
            f"arity {entry.out_arity} — the host-side unpack of the "
            f"per-chunk transfer has drifted"))
    return findings


# ---------------------------------------------------------------------
# injected entries (the gate-gates-itself tests)
# ---------------------------------------------------------------------

def _injected_entry(inject: str):
    from repro.serve.manifest import AuditedEntry

    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    if inject == "donation":
        def build(_model):
            # donated input used but returned in a different dtype:
            # XLA cannot alias it and warns at lower time
            fn = jax.jit(lambda a: (a.astype(jnp.bfloat16) * 2,),
                         donate_argnums=(0,))
            return fn, (x,)
        return AuditedEntry("injected.donation", build, (0,), 1)
    if inject == "widen":
        def build(_model):
            def widen(a):
                with jax.enable_x64(True):
                    return (a.astype(jnp.float64).sum(),)
            return jax.jit(widen), (x,)
        return AuditedEntry("injected.widen", build, (), 1)
    if inject == "callback":
        def build(_model):
            def chatty(a):
                jax.debug.print("mean={m}", m=a.mean())
                return (a * 2,)
            return jax.jit(chatty), (x,)
        return AuditedEntry("injected.callback", build, (), 1)
    if inject == "transfer":
        def build(_model):
            return jax.jit(lambda a: (a, a * 2, a.sum())), (x,)
        # declared arity 2, traced arity 3: the host unpack drifted
        return AuditedEntry("injected.transfer", build, (), 2)
    raise ValueError(f"unknown jaxpr injection {inject!r}")


def manifest_entries() -> tuple:
    """The audited jitted surface across serve, train and frontend."""
    from repro.frontend import manifest as frontend_manifest
    from repro.serve import manifest as serve_manifest
    from repro.train import manifest as train_manifest
    return (serve_manifest.entries() + train_manifest.entries()
            + frontend_manifest.entries())


# ------------------------------------------------------------- runner

def run(inject: Optional[str] = None) -> list[Finding]:
    """Trace every audited entry point and prove JX001–JX004."""
    model = abscache.smoke_model()
    entries = list(manifest_entries())
    if inject is not None:
        entries.append(_injected_entry(inject))
    findings = []
    for entry in entries:
        try:
            findings.extend(_check_entry(entry, model, inject))
        except Exception as e:                # a broken build IS a finding
            findings.append(Finding(
                PASS, "JX004", entry.name,
                f"entry fails to trace: {type(e).__name__}: {e}"))
    return findings
