"""Roofline time of the decode steps' attention over their live KV
positions over the device time of the fused paged-attention kernel's
runs inside ``chunk_step`` (``jit_chunk_step:%paged_attention.<n>
[pallas]`` in the trace)."""


def is_kernel(name: str) -> bool:
    return ":%paged_attention" in name and name.endswith("[pallas]")


def read(run):
    if run.trace is None:
        return None
    from bench import decode_work
    from bench import trace_reduce as tr
    from bench import work
    lo, hi = run.trace_window
    ns = tr.op_ns(tr.ops_within(run.trace, "chunk_step", lo, hi),
                  is_kernel)
    if not ns:
        return None
    pk = run.peaks()
    _, att = decode_work.of(run)
    need = work.roofline_s(att["flops"], att["bytes"],
                           pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"])
    return 100.0 * need / (ns / 1e9)
