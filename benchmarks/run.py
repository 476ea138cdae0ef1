"""Run every paper-table/figure benchmark:  python -m benchmarks.run

Each module reproduces one table/figure of TL-nvSRAM-CIM (DAC'23) and
returns a dict with the measured values + per-claim pass booleans; the
aggregate summary is printed at the end and written to
experiments/benchmarks/summary.json.

``--fast`` runs only kernel_bench and then asserts the JSON artifacts
(its own output and the tracked ``BENCH_autotune.json``) exist, are
schema-valid, AND carry no ``claim_*`` key holding false anywhere in
the tree — the `make bench` CI contract.  Speed is measured on the
chip by ``bench/run.py``, not here.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

from . import (accuracy_yield, adc_noise, capacity_density, cell_metrics,
               energy_efficiency, kernel_bench, llm_capacity, quantization,
               restore_yield, roofline_table, schema, throughput)
from .common import OUT_DIR, REPO_ROOT, save_json

SUITES = [
    ("quantization (Table 3)", quantization.run),
    ("restore_yield (Fig. 6)", restore_yield.run),
    ("cell_metrics (Table 4)", cell_metrics.run),
    ("throughput (Fig. 9a)", throughput.run),
    ("energy_efficiency (Fig. 9b)", energy_efficiency.run),
    ("capacity_density (Fig. 11)", capacity_density.run),
    ("accuracy_yield (Fig. 10)", accuracy_yield.run),
    ("adc_noise (beyond-paper ablation)", adc_noise.run),
    ("llm_capacity (paper model @ assigned archs)", llm_capacity.run),
    ("kernel_bench (TPU adaptation)", kernel_bench.run),
    ("roofline_table (dry-run)", roofline_table.run),
]

FAST_SUITES = [
    ("kernel_bench (TPU adaptation)", kernel_bench.run),
]

# artifacts `--fast` asserts after the run (schema name derives from the
# BENCH_/.json filename inside schema.validate_file)
FAST_ARTIFACTS = [
    os.path.join(REPO_ROOT, "BENCH_autotune.json"),
    os.path.join(OUT_DIR, "kernel_bench.json"),
]


def _false_claims(node, prefix: str = "") -> list[str]:
    """Recursively collect ``claim_*`` keys holding False anywhere in a
    (parsed) artifact — a committed artifact asserting a failed claim
    must fail the gate, not just the suite run that produced it."""
    bad = []
    if isinstance(node, dict):
        for k, v in node.items():
            where = f"{prefix}.{k}" if prefix else k
            if k.startswith("claim_") and v is False:
                bad.append(where)
            else:
                bad.extend(_false_claims(v, where))
    elif isinstance(node, list):
        for i, v in enumerate(node):
            bad.extend(_false_claims(v, f"{prefix}[{i}]"))
    return bad


def check_artifacts() -> list[str]:
    import json
    errors = []
    for path in FAST_ARTIFACTS:
        errors.extend(schema.validate_file(path))
        if os.path.exists(path):
            try:
                with open(path) as f:
                    payload = json.load(f)
            except ValueError:
                continue           # unparseable: already reported above
            errors.extend(f"{path}: {where} is false"
                          for where in _false_claims(payload))
    return errors


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--fast", action="store_true",
                   help="kernel_bench only + artifact/schema check "
                        "(the `make bench` contract)")
    args = p.parse_args(argv)
    suites = FAST_SUITES if args.fast else SUITES

    summary = {}
    failed = []
    for name, fn in suites:
        print(f"== {name}")
        t0 = time.monotonic()
        try:
            res = fn(verbose=True)
            claims = {k: v for k, v in res.items()
                      if k.startswith("claim_") or k.endswith("_reproduced")
                      or k in ("all_match_oracle", "all_claims_in_band")}
            bad = [k for k, v in claims.items() if v is False]
            summary[name] = {"seconds": round(time.monotonic() - t0, 1),
                             "claims": claims, "failed_claims": bad}
            if bad:
                failed.append((name, bad))
        except Exception as e:  # keep the suite running
            summary[name] = {"error": repr(e)}
            failed.append((name, [repr(e)]))
            import traceback
            traceback.print_exc()
        print()
    print("=" * 64)
    total_claims = sum(len(s.get("claims", {})) for s in summary.values())
    bad_claims = sum(len(s.get("failed_claims", [])) for s in summary.values())
    print(f"benchmarks: {len(suites)} suites, {total_claims} paper-claim "
          f"checks, {bad_claims} outside band")
    for name, bad in failed:
        print(f"  !! {name}: {bad}")
    rc = 1 if failed else 0
    if args.fast:
        errors = check_artifacts()
        if errors:
            for e in errors:
                print(f"  !! schema: {e}")
            rc = 1
        elif not failed:
            print(f"artifacts OK: {', '.join(FAST_ARTIFACTS)}")
        save_json("summary_fast", summary)
    else:
        save_json("summary", summary)
    return rc


if __name__ == "__main__":
    sys.exit(main())
