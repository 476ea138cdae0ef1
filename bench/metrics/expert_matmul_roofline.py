"""Roofline time of the decode steps' grouped expert matmuls (the
routed rows' int8 operations; every expert's packed weights read once a
step, an upper bound, see ``bench/archs/moe.py``) over the device time
of the grouped kernel's runs inside ``chunk_step``
(``jit_chunk_step:%ternary_gmm_int8.<n> [pallas]`` in the trace)."""


def is_kernel(name: str) -> bool:
    return ":%ternary_gmm_int8" in name and name.endswith("[pallas]")


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
    mm, _ = decode_work.of(run)
    if "experts" not in mm:
        return None
    pk = run.peaks()
    x = mm["experts"]
    need = work.roofline_s(x["ops"], x["bytes"], pk["int8_ops_per_s"],
                           pk["hbm_bytes_per_s"])
    return 100.0 * need / (ns / 1e9)
