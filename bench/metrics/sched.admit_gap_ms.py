"""The first device's idle time inside the traced window's offers over
the admissions taken in it (``span_reduce.admit_gap_ms``): the chip time
an admission's host work costs."""


def read(run):
    if run.trace is None:
        return None
    from bench import span_reduce
    return span_reduce.admit_gap_ms(run.trace, *run.trace_window)
