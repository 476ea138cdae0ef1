"""Median over the traced window's scheduling rounds of the first
device's idle time inside each (``span_reduce.host_gap_ms``): the chip
time a round loses to the host."""


def read(run):
    if run.trace is None:
        return None
    from bench import span_reduce
    return span_reduce.host_gap_ms(run.trace, *run.trace_window)
