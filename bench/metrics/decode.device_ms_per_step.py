"""Device time of the ``chunk_step`` programs per decode step."""


def read(run):
    if run.trace is None:
        return None
    from bench import trace_reduce as tr
    lo, hi = run.trace_window
    ns = tr.module_ns(run.trace, "chunk_step", lo, hi)
    steps = run.counters["decode_steps"]
    return ns / 1e6 / steps if steps and ns else None
