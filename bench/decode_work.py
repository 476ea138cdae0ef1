"""The work of the decode steps in a window, from the requests' tokens.

A request's tokens after its first come from decode steps (the first
from its prefill); the step that produced token ``j`` (``j >= 1``)
attended ``prompt_len + j`` positions.  The window's steps are those
whose tokens arrived between its opening and its close.  What a step's
work is follows the configuration's architecture module
(``bench/archs/``)."""
from __future__ import annotations


def of(run) -> tuple:
    """(matmul work, attention work) of the window's decode steps."""
    rows, contexts = 0, []
    for r in run.all_requests:
        a, b = max(r.tokens_at_open, 1), r.tokens_at_close
        if b > a:
            rows += b - a
            contexts.extend(range(r.prompt_len + a, r.prompt_len + b))
    mod, arch = run.module, run.arch
    steps = run.counters["decode_steps"]
    return (mod.decode_matmul(arch, run.packing, rows, steps, run.counters),
            mod.attention(arch, contexts))
