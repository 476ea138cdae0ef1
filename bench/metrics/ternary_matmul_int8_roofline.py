"""Roofline time of the decode steps' matmul work (packed weights read
once a step, int8 operations) over the device time of the int8 ternary
matmul kernel's runs inside ``chunk_step``."""



def is_kernel(name: str) -> bool:
    return ":%ternary_matmul_int8" in name and name.endswith("[pallas]")


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
    mm, _ = decode_work.of(run)
    need = work.roofline_s(mm["ops"], mm["bytes"], pk["int8_ops_per_s"],
                           pk["hbm_bytes_per_s"])
    return 100.0 * need / (ns / 1e9)
