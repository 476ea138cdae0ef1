"""Readings that set a cell's limit: the program's logit gap and the
int4 control's, on many seeds, in one process.  Not part of a run.

    python bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 10

For each seed, one run of the cell (``harness.run_cell``) with the int4
control in the program's place: the cell is set up and served for
``--seconds`` as a run does it, the check scores the sample a run
scores, and it judges the tokens the control puts first there, so every
line must read ``correct: false``.  One line of JSON per seed, with the
program's own gap beside the control's.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    args = p.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    jax = harness.start_jax()
    cell = harness.load_cell(ROOT, args.workload)
    on_tpu = jax.devices()[0].platform == "tpu"
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        out = harness.run_cell(cell, seed, args.seconds, trace=False,
                               t_start=t0, on_tpu=on_tpu, control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "program_gap": out["control"].get("served_gap"),
                          "control_gap": out["control"].get("control_gap"),
                          "tokens": out["control"].get("tokens"),
                          "seconds": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
