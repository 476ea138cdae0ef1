"""Schema contracts for the benchmark JSON artifacts.

`make bench` (and tests) validate the artifacts against these minimal
required-key sets so a refactor cannot silently drop a field a reader
depends on.  Keys here are a floor, not a ceiling — suites may add
fields freely.
"""
from __future__ import annotations

import json
import os

# artifact name -> required top-level keys
TOP_LEVEL = {
    "kernel_bench": {
        "sweep", "max_rel_err", "all_match_oracle",
        "vmem_working_set_bytes", "hbm_density",
    },
}


def validate(name: str, payload: dict) -> list[str]:
    """Return a list of schema violations (empty = valid)."""
    if name == "autotune":
        # the measured block-shape table: one contract, shared with the
        # runtime loader and the `make analyze` autotune pass
        from repro.kernels.autotune import validate_table
        return [f"autotune {where}: {message} [{rule}]"
                for rule, where, message in validate_table(payload)]
    required = TOP_LEVEL.get(name)
    if required is None:
        return []                           # no contract for this artifact
    if not isinstance(payload, dict):
        return [f"{name}: top level is {type(payload).__name__}, not object"]
    missing = required - payload.keys()
    if missing:
        return [f"{name}: missing top-level keys {sorted(missing)}"]
    return []


def validate_file(path: str) -> list[str]:
    if not os.path.exists(path):
        return [f"missing artifact: {path}"]
    try:
        with open(path) as f:
            payload = json.load(f)
    except ValueError as e:                 # half-written/corrupt artifact
        return [f"unparseable artifact {path}: {e}"]
    name = os.path.basename(path)
    name = name.removeprefix("BENCH_").removesuffix(".json")
    return validate(name, payload)
