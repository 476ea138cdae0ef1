"""Run one cell of the benchmark once on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout: ``BENCHMARK.json`` names the cell's
configuration, traffic and metrics, and ``src/`` holds the system under
test.  The last line of standard output is the result, one JSON object;
the compared numbers and their limits are the last lines of standard
error.  Without a TPU (or with fewer chips than the cell asks for) the
command exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse       # noqa: E402
import json           # noqa: E402
import os             # noqa: E402
import sys            # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    cell = harness.load_cell(ROOT, args.workload)
    try:
        jax = harness.start_jax()
    except ImportError as e:
        print(f"the system under test is missing: {e}", file=sys.stderr)
        return 3
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"needs {cell.chips} TPU chip(s); JAX finds {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return 3
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
