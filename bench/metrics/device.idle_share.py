"""1 - device busy time over the part of the traced window in which work
was pending (the pump's sleeps, with no request due or in a slot, are
left out)."""


def read(run):
    if run.trace is None:
        return None
    from bench import trace_reduce as tr
    lo, hi = run.trace_window
    pending = (hi - lo) - tr.sleep_ns(run.trace, lo, hi)
    if pending <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_ns(run.trace, lo, hi) / pending)
