"""Find a Poisson cell's knee: serve its traffic at several fixed rates in
one process and report what each rate sustained.  Not part of a run;
the cell's traffic file then fixes its rate at about 0.8 of the knee.

    python bench/sweep.py --workload <name> --rates 4,8,12 --seconds 20

One JSON line per rate: offered and served tokens/s, the time-to-first-
token and per-token tails, and the queue at the close (requests due but
not yet admitted): a queue that grows through the window marks a rate
above the knee.  The traffic file's pre-roll runs before each window.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, traffic
    jax = harness.start_jax()
    import numpy as np
    cell = harness.load_cell(ROOT, args.workload)
    eng = harness.build(cell, args.seed,
                        jax.devices()[0].platform == "tpu")
    harness.warm_up(eng, cell, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        spec = dict(cell.traffic, rate_per_s=rate)
        reqs = traffic.generate(spec, args.seed, args.seconds,
                                cell.config["vocab_size"])
        pump = harness.Pump(eng, reqs, args.seconds, drain=True,
                            spans=False,
                            preroll=float(spec.get("preroll_s", 0.0)))
        pump.run()
        c = {k: pump.at_close[k] - pump.at_open[k]
             for k in harness.COUNTERS}
        logs = pump.window_requests()
        queued = sum(1 for r in logs if math.isnan(r.admit_s)
                     or r.admit_s > args.seconds)
        ttft = [r.first_s - r.due_s for r in logs
                if not math.isnan(r.first_s)]
        tpot = [(r.last_s - r.first_s) / (r.tokens - 1) for r in logs
                if r.tokens >= 2]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(logs),
            "offered_tok_per_s": sum(r.max_new for r in logs)
            / args.seconds,
            "served_tok_per_s": sum(r.tokens_in_window
                                    for r in pump.logs) / args.seconds,
            "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
            "tpot_p95_ms": float(np.percentile(tpot, 95)) * 1e3,
            "queued_at_close": queued,
            "drain_s": time.monotonic() - pump.t0 - args.seconds,
            "slot_occupancy": c["occupied_slot_steps"]
            / max(1, eng.slots * c["decode_steps"])}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
