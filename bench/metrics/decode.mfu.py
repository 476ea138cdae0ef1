"""Share of the chip's peak that the decode steps reach: the least time
their model operations need (the ternary matmuls at the int8 peak,
attention at the bf16 peak) over the device time of ``chunk_step``."""


def read(run):
    if run.trace is None:
        return None
    from bench import decode_work
    from bench import trace_reduce as tr
    lo, hi = run.trace_window
    ns = tr.module_ns(run.trace, "chunk_step", lo, hi)
    if not ns:
        return None
    pk = run.peaks()
    mm, att = decode_work.of(run)
    need = mm["ops"] / pk["int8_ops_per_s"] \
        + att["flops"] / pk["bf16_flops_per_s"]
    return 100.0 * need / (ns / 1e9)
