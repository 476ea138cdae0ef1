"""Measured block-shape autotuner for the block-tiled (pallas) backend.

``select_block_shapes`` is a static heuristic: it reasons about sublane
quanta and a VMEM budget but never runs anything.  This module closes
the loop: ``tune()`` benchmarks a small candidate set of aligned
``(bm, bn, bk)`` tiles per ``(shape, phase, platform, packing, domain)``
cell — once, on the platform that will serve them — and persists the
winners as a schema-validated JSON artifact (``BENCH_autotune.json`` at
the repo root, tracked).

Plan resolution (``plan.\\_resolve``) consults the table through
:func:`lookup_blocks`: a warm hit resolves the measured blocks into the
plan (``block_source='autotune'`` in ``ExecutionPlan.describe()``); a
miss falls back to ``select_block_shapes`` and is logged (once per
cell) — never silent, never fatal.  A doctored or stale table is the
analysis gate's job: ``repro.analysis`` runs :func:`validate_table`
and fails ``make analyze`` loudly (AT001 structure, AT002 invariant,
AT003 duplicate-cell rules), while the serving path degrades to the
heuristic.

On CPU hosts the pallas backend runs in interpret mode, so the table
measures what CPU CI actually executes; re-run ``python -m
repro.kernels.autotune`` on a TPU host to add real-lowering cells (the
table is keyed by platform, entries merge).
"""
from __future__ import annotations

import json
import logging
import os
import time
from typing import Optional

TABLE_VERSION = 1
ENV_VAR = "REPRO_AUTOTUNE_TABLE"

# src/repro/kernels/ -> repo root (the PYTHONPATH=src layout)
_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))
DEFAULT_TABLE_BASENAME = "BENCH_autotune.json"

# tuning sweep (M, K, N): decode token batches through a 1024x1024
# projection, and prefill rows (batch x seq) through 1024- and
# 512-deep ones
DECODE_SHAPES = ((1, 1024, 1024), (4, 1024, 1024), (8, 1024, 1024),
                 (16, 1024, 1024))
PREFILL_SHAPES = ((128, 1024, 1024), (256, 512, 1024))

ENTRY_KEYS = ("m", "k", "n", "phase", "platform", "packing", "domain",
              "blocks", "time_s", "heuristic_blocks", "heuristic_time_s")

_LOG = logging.getLogger("repro.kernels.autotune")

# path -> (key -> blocks) mapping; misses logged once per cell
_TABLE_CACHE: dict = {}
_MISSES_LOGGED: set = set()


def table_path() -> str:
    """The table consulted at plan-resolution time: ``$REPRO_AUTOTUNE_TABLE``
    if set (empty string disables the table entirely), else the tracked
    repo-root artifact."""
    env = os.environ.get(ENV_VAR)
    if env is not None:
        return env
    return os.path.join(_REPO_ROOT, DEFAULT_TABLE_BASENAME)


def cell_key(m: int, k: int, n: int, phase: str, platform: str,
             packing: str, domain: str) -> tuple:
    return (int(m), int(k), int(n), str(phase), str(platform),
            str(packing), str(domain))


def validate_table(payload) -> list:
    """Contract check for a (parsed) autotune table.  Returns a list of
    ``(rule, where, message)`` violations:

      * AT001 — structure: top-level/entry shape, key types, enum
        membership (phase/platform/packing/domain);
      * AT002 — invariants: blocks must be the alignments the pallas
        kernels' correctness rests on (bm a sublane multiple for the
        domain, bn/bk lane multiples, trit2 bk byte-whole) and fit the
        double-buffered VMEM budget the selector promises;
      * AT003 — duplicate cell keys (a table with two winners for one
        cell is ambiguous).

    Shared by the runtime loader (violations degrade to the heuristic),
    the analysis pass (violations fail ``make analyze``) and the bench
    schema gate."""
    from .plan import DOMAINS, PACKINGS, PHASES
    from .ternary_matmul import (INT8_SUBLANE, MXU_LANE, SUBLANE,
                                 TRIT2_PER_BYTE, VMEM_BUDGET_BYTES,
                                 _vmem_working_set)
    out = []
    if not isinstance(payload, dict):
        return [("AT001", "table", "payload is not a JSON object")]
    if payload.get("version") != TABLE_VERSION:
        out.append(("AT001", "table",
                    f"version {payload.get('version')!r} != "
                    f"{TABLE_VERSION}"))
    entries = payload.get("entries")
    if not isinstance(entries, list):
        out.append(("AT001", "table", "'entries' is not a list"))
        return out
    seen = {}
    for i, e in enumerate(entries):
        where = f"entries[{i}]"
        if not isinstance(e, dict):
            out.append(("AT001", where, "entry is not an object"))
            continue
        missing = [key for key in ENTRY_KEYS if key not in e]
        if missing:
            out.append(("AT001", where, f"missing keys {missing}"))
            continue
        ok = True
        for key in ("m", "k", "n"):
            if not isinstance(e[key], int) or e[key] < 1:
                out.append(("AT001", where,
                            f"{key}={e[key]!r} is not a positive int"))
                ok = False
        for key, choices in (("phase", PHASES), ("packing", PACKINGS),
                             ("domain", DOMAINS),
                             ("platform", ("cpu", "gpu", "tpu"))):
            if e[key] not in choices:
                out.append(("AT001", where,
                            f"{key}={e[key]!r} not in {sorted(choices)}"))
                ok = False
        for key in ("time_s", "heuristic_time_s"):
            if not isinstance(e[key], (int, float)) or e[key] <= 0:
                out.append(("AT001", where,
                            f"{key}={e[key]!r} is not a positive number"))
                ok = False
        for key in ("blocks", "heuristic_blocks"):
            b = e[key]
            if (not isinstance(b, list) or len(b) != 3
                    or not all(isinstance(v, int) and v > 0 for v in b)):
                out.append(("AT001", where,
                            f"{key}={b!r} is not a [bm, bn, bk] triple "
                            f"of positive ints"))
                ok = False
        if not ok:
            continue
        bm, bn, bk = e["blocks"]
        cell = (f"{where} ({e['m']},{e['k']},{e['n']}) {e['phase']} "
                f"{e['platform']} {e['packing']}/{e['domain']}")
        sublane = INT8_SUBLANE if e["domain"] == "int8" else SUBLANE
        if bm % sublane:
            out.append(("AT002", cell,
                        f"bm={bm} is not a multiple of the "
                        f"{e['domain']} sublane quantum {sublane}"))
        if bn % MXU_LANE:
            out.append(("AT002", cell,
                        f"bn={bn} is not lane-aligned ({MXU_LANE})"))
        if bk % MXU_LANE:
            out.append(("AT002", cell,
                        f"bk={bk} is not lane-aligned ({MXU_LANE})"))
        if e["packing"] == "trit2" and bk % TRIT2_PER_BYTE:
            out.append(("AT002", cell,
                        f"bk={bk} splits the trit2 packed byte"))
        used = _vmem_working_set(bm, bn, bk, e["packing"], e["domain"])
        if used > VMEM_BUDGET_BYTES and bk > MXU_LANE:
            out.append(("AT002", cell,
                        f"working set {used} B exceeds the "
                        f"{VMEM_BUDGET_BYTES} B VMEM budget with "
                        f"bk={bk} above the {MXU_LANE} floor"))
        key = cell_key(e["m"], e["k"], e["n"], e["phase"], e["platform"],
                       e["packing"], e["domain"])
        if key in seen:
            out.append(("AT003", cell,
                        f"duplicate cell (first at "
                        f"entries[{seen[key]}])"))
        else:
            seen[key] = i
    return out


def load_table(path: Optional[str] = None) -> dict:
    """Parse + validate the table at ``path`` into a ``cell_key ->
    (bm, bn, bk)`` mapping.  Missing file -> empty table (every lookup
    is a logged miss).  Invalid table -> empty table with a warning;
    failing loudly on a doctored artifact is ``make analyze``'s job,
    the serving path keeps working on the heuristic."""
    if path is None:
        path = table_path()
    if path in _TABLE_CACHE:
        return _TABLE_CACHE[path]
    table: dict = {}
    if path and os.path.exists(path):
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, ValueError) as e:
            _LOG.warning("autotune table %s unreadable (%s); using the "
                         "select_block_shapes heuristic", path, e)
            payload = None
        if payload is not None:
            violations = validate_table(payload)
            if violations:
                _LOG.warning(
                    "autotune table %s fails validation (%d violations, "
                    "first: %s); using the select_block_shapes heuristic",
                    path, len(violations), violations[0])
            else:
                for e in payload["entries"]:
                    key = cell_key(e["m"], e["k"], e["n"], e["phase"],
                                   e["platform"], e["packing"],
                                   e["domain"])
                    table[key] = tuple(e["blocks"])
    _TABLE_CACHE[path] = table
    return table


def lookup_blocks(m: int, k: int, n: int, phase: str, platform: str,
                  packing: str, domain: str) -> Optional[tuple]:
    """Measured ``(bm, bn, bk)`` for one cell, or None on a miss (the
    caller falls back to ``select_block_shapes``).  Misses are logged
    once per cell — the table's coverage gaps must be visible, not
    silent."""
    key = cell_key(m, k, n, phase, platform, packing, domain)
    blocks = load_table().get(key)
    if blocks is None and key not in _MISSES_LOGGED:
        _MISSES_LOGGED.add(key)
        _LOG.info("autotune table miss for shape=(%d,%d,%d) phase=%s "
                  "platform=%s packing=%s domain=%s; falling back to "
                  "select_block_shapes", m, k, n, phase, platform,
                  packing, domain)
    return blocks


def reload_table() -> None:
    """Drop the cached table (and the resolved plans built from it) so
    the next lookup re-reads ``table_path()`` — tests point
    ``$REPRO_AUTOTUNE_TABLE`` at fixtures and call this."""
    from .plan import plan_cache_clear
    _TABLE_CACHE.clear()
    _MISSES_LOGGED.clear()
    plan_cache_clear()


def canonical_bytes(entries: list) -> str:
    """Canonical JSON text for a set of entries: sorted by cell key,
    sorted keys, fixed indentation — so save -> load -> save is a
    byte-identical round trip (the determinism the persistence tests
    pin)."""
    entries = sorted(entries, key=lambda e: cell_key(
        e["m"], e["k"], e["n"], e["phase"], e["platform"], e["packing"],
        e["domain"]))
    payload = {"version": TABLE_VERSION, "entries": entries}
    return json.dumps(payload, indent=2, sort_keys=True) + "\n"


def save_table(entries: list, path: Optional[str] = None) -> str:
    """Write the canonical table; refuses to persist an invalid one."""
    if path is None:
        path = table_path()
    text = canonical_bytes(list(entries))
    violations = validate_table(json.loads(text))
    if violations:
        raise ValueError(f"refusing to save an invalid autotune table: "
                         f"{violations[0]}")
    with open(path, "w") as f:
        f.write(text)
    return path


def load_entries(path: Optional[str] = None) -> list:
    """The raw entry list at ``path`` (empty for a missing file)."""
    if path is None:
        path = table_path()
    if not (path and os.path.exists(path)):
        return []
    with open(path) as f:
        return json.load(f).get("entries", [])


def candidate_blocks(m: int, k: int, n: int, packing: str,
                     domain: str, limit: int = 8) -> list:
    """Aligned, VMEM-feasible candidate tiles for one cell: the
    heuristic choice first (the fallback must always be in the race),
    then lane/sublane-aligned variations over each axis."""
    from .ternary_matmul import (INT8_SUBLANE, MXU_LANE, SUBLANE,
                                 TRIT2_PER_BYTE, VMEM_BUDGET_BYTES,
                                 _round_up, _vmem_working_set,
                                 select_block_shapes)
    kdim = k + (-k % TRIT2_PER_BYTE) if packing == "trit2" else k
    heur = tuple(select_block_shapes(m, kdim, n, packing, domain=domain))
    sublane = INT8_SUBLANE if domain == "int8" else SUBLANE
    bm_opts = {heur[0], min(_round_up(m, sublane), 128)}
    bn_opts, bk_opts = {heur[1]}, {heur[2]}
    for c in (128, 256, 512):
        if c <= _round_up(n, MXU_LANE):
            bn_opts.add(c)
        if c <= _round_up(kdim, MXU_LANE):
            bk_opts.add(c)
    cands = []
    for bm in sorted(bm_opts):
        for bn in sorted(bn_opts):
            for bk in sorted(bk_opts):
                if (bm, bn, bk) == heur:
                    continue
                used = _vmem_working_set(bm, bn, bk, packing, domain)
                if used > VMEM_BUDGET_BYTES and bk > MXU_LANE:
                    continue
                cands.append((bm, bn, bk))
    return [heur] + cands[:max(0, limit - 1)]


def _time_best(fn, *args, iters: int = 3) -> float:
    import jax
    jax.block_until_ready(fn(*args))        # compile + warm
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def measure_cell(m: int, k: int, n: int, phase: str, packing: str,
                 domain: str, iters: int = 3,
                 candidate_limit: int = 8) -> dict:
    """Race the candidate tiles through the real pallas execute path
    (jitted, on seeded normal operands) and return
    the winning entry for this cell on the current platform."""
    import functools

    import jax
    import jax.numpy as jnp

    from . import ops
    from .plan import _platform, execute, plan_matmul

    platform = _platform()
    key = jax.random.key((m * 1_000_003 + k * 9176 + n) & 0x7FFFFFFF)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = 0.02 * jax.random.normal(kw, (k, n), jnp.float32)
    pw = ops.pack_weights(w, packing)

    timings = []
    cands = candidate_blocks(m, k, n, packing, domain,
                             limit=candidate_limit)
    for bm, bn, bk in cands:
        plan = plan_matmul((m, k, n), phase, backend="pallas",
                           packing=packing, domain=domain,
                           bm=bm, bn=bn, bk=bk)
        step = jax.jit(functools.partial(execute, plan))
        timings.append(((bm, bn, bk), _time_best(step, x, pw,
                                                 iters=iters)))
    (hblocks, htime) = timings[0]           # heuristic ran first
    blocks, best = min(timings, key=lambda t: t[1])
    return {"m": m, "k": k, "n": n, "phase": phase,
            "platform": platform, "packing": packing, "domain": domain,
            "blocks": list(blocks), "time_s": best,
            "heuristic_blocks": list(hblocks),
            "heuristic_time_s": htime}


def tune(fast: bool = False, iters: int = 3, verbose: bool = False,
         merge_with: Optional[list] = None) -> list:
    """Measure every ``(shape, phase, packing, domain)`` cell of the
    tuning sweep on the current platform; returns the merged entry
    list (existing entries for OTHER platforms/cells are kept, this
    platform's sweep cells are replaced by fresh measurements)."""
    from .plan import DOMAINS, PACKINGS
    decode = DECODE_SHAPES[:2] if fast else DECODE_SHAPES
    prefill = PREFILL_SHAPES[:1] if fast else PREFILL_SHAPES
    limit = 4 if fast else 8
    cells = ([(s, "decode") for s in decode]
             + [(s, "prefill") for s in prefill])
    fresh = []
    for (m, k, n), phase in cells:
        for packing in PACKINGS:
            for domain in DOMAINS:
                entry = measure_cell(m, k, n, phase, packing, domain,
                                     iters=iters, candidate_limit=limit)
                fresh.append(entry)
                if verbose:
                    speedup = (entry["heuristic_time_s"]
                               / entry["time_s"])
                    print(f"  ({m},{k},{n}) {phase} {packing}/{domain}: "
                          f"{tuple(entry['blocks'])} "
                          f"{entry['time_s'] * 1e3:.3f} ms "
                          f"(heuristic {tuple(entry['heuristic_blocks'])}"
                          f" x{speedup:.2f})")
    fresh_keys = {cell_key(e["m"], e["k"], e["n"], e["phase"],
                           e["platform"], e["packing"], e["domain"])
                  for e in fresh}
    kept = [e for e in (merge_with or [])
            if cell_key(e["m"], e["k"], e["n"], e["phase"],
                        e["platform"], e["packing"],
                        e["domain"]) not in fresh_keys]
    return kept + fresh


def main(argv: Optional[list] = None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m repro.kernels.autotune",
        description="Measure (bm, bn, bk) tiles per tuning-sweep "
                    "cell and persist the table plan resolution "
                    "consults.")
    p.add_argument("--out", default=None,
                   help="table path (default: the tracked repo-root "
                        "artifact, or $REPRO_AUTOTUNE_TABLE)")
    p.add_argument("--fast", action="store_true",
                   help="reduced sweep/candidates (CI smoke)")
    p.add_argument("--iters", type=int, default=3)
    args = p.parse_args(argv)

    out = args.out or table_path()
    existing = load_entries(out)
    entries = tune(fast=args.fast, iters=args.iters, verbose=True,
                   merge_with=existing)
    save_table(entries, out)
    print(f"wrote {len(entries)} entries -> {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
