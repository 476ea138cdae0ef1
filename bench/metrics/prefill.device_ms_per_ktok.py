"""Device time of the ``prefill_step`` programs per 1000 prompt tokens
admitted in the traced window."""

def read(run):
    if run.trace is None:
        return None
    from bench import trace_reduce as tr
    lo, hi = run.trace_window
    ns = tr.module_ns(run.trace, "prefill_step", lo, hi)
    tokens = sum(r.prompt_len for r in run.all_requests
                 if 0.0 <= r.admit_s < run.seconds)
    return ns / 1e6 / (tokens / 1e3) if tokens and ns else None
