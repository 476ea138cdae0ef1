"""Serve one cell with the profiler on and read the program's spans.

    python3 bench/span_run.py --workload <name> --seed <n> --seconds <s> \
        [--save <file.xplane.pb.gz>]

Set-up, traffic and pump are the benchmark's (``harness.py``), and the
window is traced as a ``--trace 1`` run traces it, but nothing is drained
or checked: this reads where a round's and an admission's time goes, it
is not a run of the benchmark.  The last line of standard output is one
JSON object: ``sched.host_gap_ms`` and ``sched.admit_gap_ms`` as the
benchmark's readers compute them (``span_reduce``), the idle gaps named
by the innermost span, the operations that took most device time, and
the device's busy share.
``--save`` keeps the trace, gzipped.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(root: str, workload: str, seed: int, seconds: float,
        save: str | None = None, on_tpu: bool = True) -> dict:
    import jax

    from bench import harness, span_reduce, trace_reduce, traffic
    cell = harness.load_cell(root, workload)
    eng = harness.build(cell, seed, on_tpu)
    harness.warm_up(eng, cell, seed)
    reqs = traffic.generate(cell.traffic, seed, seconds,
                            cell.config["vocab_size"])
    pump = harness.Pump(eng, reqs, seconds, drain=False, spans=True,
                        preroll=float(cell.traffic.get("preroll_s", 0.0)))
    trace_dir = os.path.join(root, "bench_out", "span_run")
    shutil.rmtree(trace_dir, ignore_errors=True)
    win = []

    def open_():
        jax.profiler.start_trace(trace_dir)
        win.append(jax.profiler.TraceAnnotation("bench.window"))
        win[0].__enter__()

    def close():
        jax.block_until_ready(eng.tok)
        win.pop().__exit__(None, None, None)
        jax.profiler.stop_trace()

    try:
        pump.run(on_open=open_, on_close=close)
    finally:
        if win:                 # the pump raised inside the window
            win.pop().__exit__(None, None, None)
            jax.profiler.stop_trace()
    path = trace_reduce.find(trace_dir)
    if save:
        with open(path, "rb") as src, gzip.open(save, "wb") as dst:
            shutil.copyfileobj(src, dst)
    tr = span_reduce.load(path)
    shutil.rmtree(trace_dir)
    lo, hi = trace_reduce.window(tr)
    return {
        "workload": workload, "seed": seed, "window_s": (hi - lo) / 1e9,
        "busy_share": trace_reduce.busy_ns(tr, lo, hi) / (hi - lo),
        "sched.host_gap_ms": span_reduce.host_gap_ms(tr, lo, hi),
        "sched.admit_gap_ms": span_reduce.admit_gap_ms(tr, lo, hi),
        "rounds": sum(1 for n, s, e in trace_reduce.clip(tr.spans, lo, hi)
                      if n == "serve.round"),
        "idle_gaps": span_reduce.idle_gaps(tr, lo, hi),
        "device_ops": trace_reduce.top_ops(tr, lo, hi),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--save", default=None)
    args = p.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    t0 = time.monotonic()
    harness.start_jax()
    out = run(ROOT, args.workload, args.seed, args.seconds, args.save)
    out["wall_s"] = time.monotonic() - t0
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
