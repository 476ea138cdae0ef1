"""Pass 1 — capability-lattice checker.

Enumerates the full (op x backend x domain x packing x kv_layout x
fidelity x platform) lattice from the LIVE backend registry in
``repro.kernels`` and proves, cell by cell:

  * every declared-capable cell resolves through the public
    ``plan_matmul`` path (current platform) or the internal cached
    resolver with an explicit platform (cross-platform cells — the
    public entry probes ``jax.default_backend()``), and
  * abstract-evaluates through ``execute`` under ``jax.eval_shape`` —
    no kernel is ever executed or compiled — producing the contracted
    ``(M, N)`` float32 output;
  * every UNdeclared cell raises the loud capability error (the
    "fails loudly with what it does support" contract of
    ``resolve_backend``), and every empty ``auto`` cell raises the
    no-capable-backend error;
  * ``auto`` resolution picks the highest-priority capable backend of
    each capable cell;
  * the hand-written capability matrix in
    ``src/repro/kernels/README.md`` matches the registry exactly
    (parse the markdown table; any drift is a finding).

One semantic footnote the lattice cannot express: ``op='cim'`` plans
accept float weights under any packing (ternarized on the fly), but a
*packed* weight must be base3 — the checker proves the trit2-packed
rejection is loud (CAP005) instead of modeling packing as a cim
capability axis.
"""
from __future__ import annotations

import os
import re
from typing import Optional

from .base import Finding, REPO_ROOT

PASS = "capability"
README_PATH = os.path.join(REPO_ROOT, "src", "repro", "kernels",
                           "README.md")

# one small shape per abstract eval; value is irrelevant (eval_shape
# never executes), it only has to satisfy packing divisibility
EVAL_SHAPE = (8, 64, 128)

# the six machine-checked matrix columns, in table order
MATRIX_COLUMNS = ("ops", "domains", "packings", "platforms", "kv layouts",
                  "fidelities")


def _registry():
    from repro.kernels import plan as plan_mod
    plan_mod._ensure_builtin_backends()
    return dict(plan_mod._REGISTRY)


def _lattice_axes(registry):
    from repro.kernels.plan import (DOMAINS, FIDELITIES, KV_LAYOUTS, OPS,
                                    PACKINGS)
    platforms = sorted(set().union(*(s.platforms
                                     for s in registry.values())))
    return OPS, DOMAINS, PACKINGS, KV_LAYOUTS, FIDELITIES, platforms


def _eval_operands(op: str, packing: str, shape):
    """ShapeDtypeStruct operands for one abstract eval of `execute`."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import PackedTernary, TRIT2_PER_BYTE
    m, k, n = shape
    if op == "attention":
        # no packed weight: the operand is the raw page-pool view
        from repro.kernels import paged_attention
        return paged_attention.eval_operands(shape)
    if op == "ternary_grouped":
        # expert-sorted rows against a stack of packed experts
        from repro.kernels import grouped_matmul
        return grouped_matmul.eval_operands(shape, packing)
    x = jax.ShapeDtypeStruct((m, k), jnp.float32)
    if op == "cim":
        # float weights: ternarized on the fly by the runner, valid
        # under every packing request (see module docstring)
        return x, jax.ShapeDtypeStruct((k, n), jnp.float32)
    kw = k // TRIT2_PER_BYTE if packing == "trit2" else k
    w = PackedTernary(jax.ShapeDtypeStruct((kw, n), jnp.uint8),
                      jax.ShapeDtypeStruct((n,), jnp.float32), packing)
    return x, w


def _check_declared_cell(name, op, domain, packing, kv_layout, fidelity,
                         platform, current_platform) -> Optional[Finding]:
    """A declared-capable cell must resolve and abstract-eval."""
    import jax
    from repro.kernels import execute, plan_matmul
    from repro.kernels.plan import _resolve
    cell = (f"op={op} backend={name} domain={domain} packing={packing} "
            f"kv_layout={kv_layout} fidelity={fidelity} "
            f"platform={platform}")
    m, k, n = EVAL_SHAPE
    adc = 5 if (op == "cim" or fidelity == "device") else None
    try:
        if platform == current_platform:
            plan = plan_matmul(EVAL_SHAPE, op=op, backend=name,
                               domain=domain, packing=packing,
                               kv_layout=kv_layout, fidelity=fidelity)
        else:
            # the public entry probes the live platform; cross-platform
            # cells go through the same cached resolver explicitly
            plan = _resolve(op, m, k, n, "auto", name, domain, packing,
                            None, None, None, None, kv_layout, fidelity,
                            adc, adc, platform)
    except Exception as e:
        return Finding(PASS, "CAP001", cell,
                       f"declared-capable cell failed to resolve: {e!r}")
    if plan.backend != name:
        return Finding(PASS, "CAP001", cell,
                       f"resolved to backend {plan.backend!r}")
    if platform != current_platform:
        return None          # cannot abstract-eval a foreign platform's
                             # interpret/runner configuration faithfully
    try:
        x, w = _eval_operands(op, packing, EVAL_SHAPE)
        out = jax.eval_shape(lambda xx, ww: execute(plan, xx, ww), x, w)
    except Exception as e:
        return Finding(PASS, "CAP002", cell,
                       f"declared-capable cell failed abstract eval "
                       f"through execute: {e!r}")
    import jax.numpy as jnp
    if op == "attention":
        # contract: partial flash statistics (acc, m, l), all f32
        from repro.kernels import paged_attention
        want = paged_attention.eval_output(EVAL_SHAPE)
        got = tuple(tuple(o.shape) for o in out)
        if (got != want
                or any(o.dtype != jnp.float32 for o in out)):
            return Finding(PASS, "CAP002", cell,
                           f"abstract eval produced {got} "
                           f"{[str(o.dtype) for o in out]}, expected "
                           f"{want} float32 (acc, m, l)")
        return None
    if tuple(out.shape) != (m, n) or out.dtype != jnp.float32:
        return Finding(PASS, "CAP002", cell,
                       f"abstract eval produced {out.shape} {out.dtype}, "
                       f"expected ({m}, {n}) float32")
    return None


def _check_undeclared_cell(name, op, domain, packing, kv_layout, fidelity,
                           platform) -> Optional[Finding]:
    """An undeclared cell must raise the loud capability error."""
    from repro.kernels.plan import resolve_backend
    cell = (f"op={op} backend={name} domain={domain} packing={packing} "
            f"kv_layout={kv_layout} fidelity={fidelity} "
            f"platform={platform}")
    try:
        resolve_backend(op, name, domain, packing, platform, kv_layout,
                        fidelity)
    except ValueError as e:
        if "does not support" not in str(e):
            return Finding(PASS, "CAP003", cell,
                           f"capability rejection lost the loud "
                           f"'does not support' message: {e}")
        return None
    return Finding(PASS, "CAP003", cell,
                   "undeclared cell resolved without a capability error")


def _check_auto_cell(registry, op, domain, packing, kv_layout, fidelity,
                     platform) -> Optional[Finding]:
    """'auto' must pick the highest-priority capable backend, or raise
    the no-capable-backend error when the cell is empty."""
    from repro.kernels.plan import resolve_backend
    cell = (f"op={op} backend=auto domain={domain} packing={packing} "
            f"kv_layout={kv_layout} fidelity={fidelity} "
            f"platform={platform}")
    capable = [s for s in registry.values()
               if s.supports(op, domain, packing, platform, kv_layout,
                             fidelity)]
    try:
        spec = resolve_backend(op, "auto", domain, packing, platform,
                               kv_layout, fidelity)
    except ValueError as e:
        if capable:
            return Finding(PASS, "CAP004", cell,
                           f"auto failed on a capable cell: {e}")
        if "no registered backend" not in str(e):
            return Finding(PASS, "CAP004", cell,
                           f"empty cell lost the loud no-capable-backend "
                           f"message: {e}")
        return None
    if not capable:
        return Finding(PASS, "CAP004", cell,
                       f"auto resolved {spec.name!r} on an empty cell")
    best = max(capable, key=lambda s: s.priority)
    if spec.name != best.name:
        return Finding(PASS, "CAP004", cell,
                       f"auto picked {spec.name!r} (priority "
                       f"{spec.priority}) over {best.name!r} (priority "
                       f"{best.priority})")
    return None


def _check_cim_packed_trit2_rejection() -> list:
    """The loud-rejection footnote: a trit2 PackedTernary under a cim
    plan must raise (base3 carries the multi-trit planes cim needs)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import execute, plan_matmul
    from repro.kernels.ops import PackedTernary, TRIT2_PER_BYTE
    m, k, n = EVAL_SHAPE
    plan = plan_matmul(EVAL_SHAPE, op="cim")
    x = jax.ShapeDtypeStruct((m, k), jnp.float32)
    w = PackedTernary(
        jax.ShapeDtypeStruct((k // TRIT2_PER_BYTE, n), jnp.uint8),
        jax.ShapeDtypeStruct((n,), jnp.float32), "trit2")
    try:
        jax.eval_shape(lambda xx, ww: execute(plan, xx, ww), x, w)
    except ValueError as e:
        if "base3" in str(e):
            return []
        return [Finding(PASS, "CAP005", "op=cim packed=trit2",
                        f"rejection does not name base3: {e}")]
    return [Finding(PASS, "CAP005", "op=cim packed=trit2",
                    "trit2-packed weights were accepted by a cim plan")]


# ----------------------------------------------------- README matrix

def render_capability_matrix(notes: Optional[dict] = None) -> str:
    """The markdown capability table, generated from the live registry
    (highest priority first — the order 'auto' prefers).  ``notes``
    maps backend name -> prose cell; unknown backends get ''."""
    notes = notes or {}
    registry = _registry()
    head = ("| backend | ops | domains | packings | platforms "
            "| kv layouts | fidelities | notes |")
    sep = ("|---------|-----|---------|----------|-----------"
           "|------------|------------|-------|")
    rows = [head, sep]
    for spec in sorted(registry.values(), key=lambda s: -s.priority):
        cells = [f"`{spec.name}`"]
        for vals in (spec.ops, spec.domains, spec.packings,
                     spec.platforms, spec.kv_layouts, spec.fidelities):
            cells.append(", ".join(sorted(vals)))
        cells.append(notes.get(spec.name, ""))
        rows.append("| " + " | ".join(cells) + " |")
    return "\n".join(rows)


def parse_capability_matrix(text: str) -> dict:
    """Parse the backend table out of README markdown: backend name ->
    {column -> frozenset of entries} for the machine-checked columns.
    Raises ValueError if no recognizable table is present."""
    lines = text.splitlines()
    header = None
    for i, line in enumerate(lines):
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if cells and cells[0].lower() == "backend":
            header = [c.lower() for c in cells]
            start = i
            break
    if header is None:
        raise ValueError("no capability matrix table (header row "
                         "starting with 'backend') found")
    missing = [c for c in MATRIX_COLUMNS if c not in header]
    if missing:
        raise ValueError(f"capability matrix is missing columns "
                         f"{missing}; has {header}")
    out = {}
    for line in lines[start + 2:]:
        if not line.strip().startswith("|"):
            break
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if len(cells) < len(header):
            break
        row = dict(zip(header, cells))
        name = row["backend"].strip("`")
        out[name] = {
            col: frozenset(v.strip() for v in row[col].split(",")
                           if v.strip())
            for col in MATRIX_COLUMNS}
    if not out:
        raise ValueError("capability matrix table has no backend rows")
    return out


def parse_matrix_notes(text: str) -> dict:
    """backend -> notes cell of an existing matrix (for re-rendering)."""
    lines = text.splitlines()
    notes = {}
    for line in lines:
        m = re.match(r"\|\s*`([a-z0-9_]+)`\s*\|", line)
        if m:
            cells = [c.strip() for c in line.strip().strip("|").split("|")]
            notes[m.group(1)] = cells[-1]
    return notes


def _check_readme_matrix(registry, readme_path: str) -> list:
    findings = []
    where = os.path.relpath(readme_path, REPO_ROOT) \
        if os.path.isabs(readme_path) else readme_path
    try:
        with open(readme_path) as f:
            table = parse_capability_matrix(f.read())
    except (OSError, ValueError) as e:
        return [Finding(PASS, "CAP006", where,
                        f"cannot check capability matrix: {e}")]
    documented = set(table)
    live = set(registry)
    for name in sorted(live - documented):
        findings.append(Finding(PASS, "CAP006", where,
                                f"registered backend {name!r} missing "
                                f"from the capability matrix"))
    for name in sorted(documented - live):
        findings.append(Finding(PASS, "CAP006", where,
                                f"matrix documents unregistered backend "
                                f"{name!r}"))
    attr = {"ops": "ops", "domains": "domains", "packings": "packings",
            "platforms": "platforms", "kv layouts": "kv_layouts",
            "fidelities": "fidelities"}
    for name in sorted(documented & live):
        spec = registry[name]
        for col, field in attr.items():
            want = frozenset(getattr(spec, field))
            got = table[name][col]
            if want != got:
                findings.append(Finding(
                    PASS, "CAP006", where,
                    f"backend {name!r} column {col!r} drifted: matrix "
                    f"says {sorted(got)}, registry says {sorted(want)}"))
    return findings


# ------------------------------------------------------------- runner

def run(readme_path: Optional[str] = None,
        registry: Optional[dict] = None) -> list:
    """Run the full capability pass; returns findings (empty = clean).

    ``readme_path`` / ``registry`` exist for violation injection in
    tests; the defaults are the live registry and the tracked README.
    """
    registry = registry if registry is not None else _registry()
    (ops, domains, packings, kv_layouts, fidelities,
     platforms) = _lattice_axes(registry)
    import jax
    current = jax.default_backend()
    findings = []
    cells = 0
    for op in ops:
        for domain in domains:
            for packing in packings:
                for kv_layout in kv_layouts:
                    for fidelity in fidelities:
                        for platform in platforms:
                            for name, spec in sorted(registry.items()):
                                cells += 1
                                if spec.supports(op, domain, packing,
                                                 platform, kv_layout,
                                                 fidelity):
                                    f = _check_declared_cell(
                                        name, op, domain, packing,
                                        kv_layout, fidelity, platform,
                                        current)
                                else:
                                    f = _check_undeclared_cell(
                                        name, op, domain, packing,
                                        kv_layout, fidelity, platform)
                                if f:
                                    findings.append(f)
                            f = _check_auto_cell(registry, op, domain,
                                                 packing, kv_layout,
                                                 fidelity, platform)
                            if f:
                                findings.append(f)
    findings.extend(_check_cim_packed_trit2_rejection())
    findings.extend(_check_readme_matrix(
        registry, readme_path or README_PATH))
    return findings
