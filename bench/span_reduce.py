"""The program's own host spans in a profiler trace: what each scheduling
round and each admission costs the chip, and the idle gaps named by the
innermost span over most of each.

``PagedScheduler`` opens ``serve.*`` spans (``jax.profiler``
``TraceAnnotation``) inside the calls the harness wraps in ``bench.*``
spans: ``serve.round`` holds ``serve.dispatch`` (enqueue of the chunk),
``serve.sync`` (the round's one transfer) and ``serve.absorb`` (token
bookkeeping and retirement); ``serve.admit`` holds ``serve.reserve``
(host page reservation) and, when the request is taken,
``serve.prefill`` (enqueue of its device work).  They lie on the host
plane, on the device ops' clock.

``trace_reduce.load`` keeps the harness's spans only; ``load`` here keeps
both, in the same ``Trace``.  Every other function of ``trace_reduce``
reads such a trace as it reads its own.  The readers ``host_gap_ms`` and
``admit_gap_ms`` need only the harness's ``bench.round`` and
``bench.admit``, which wrap exactly the scheduler's ``step_round`` and
``try_admit``, so they read a trace of ``trace_reduce.load`` and of a
program without spans of its own alike.
"""
from __future__ import annotations

import bisect
import statistics
from collections import defaultdict

from . import trace_reduce as tr

PROGRAM_PREFIX = "serve."
PREFILL = "jit_prefill_step"


def load(path: str) -> tr.Trace:
    """``trace_reduce.load`` with the program's ``serve.*`` spans kept
    beside the harness's, from one parse of the file."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    keep = (tr.SPAN_PREFIX, PROGRAM_PREFIX)
    ops, modules, spans, devices = [], [], [], 0
    for plane in data.planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            dev = devices
            devices += 1
            mods, dev_ops = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev_ops = tr._events(line, dev)
                elif line.name == "XLA Modules":
                    mods = sorted(tr._events(line, dev), key=lambda e: e[1])
            ops += tr._label(sorted(dev_ops, key=lambda e: e[1]), mods)
            modules += mods
        elif plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                spans += [ev for ev in tr._events(line)
                          if ev[0].startswith(keep)]
    return tr.Trace(sorted(ops, key=lambda e: e[1]),
                    sorted(modules, key=lambda e: e[1]),
                    sorted(spans, key=lambda e: e[1]), devices)


def _busy(trace: tr.Trace, lo, hi) -> list:
    """Merged intervals in which some operation ran on the first device."""
    return tr.union([(ev[1], ev[2]) for ev in tr.clip(trace.ops, lo, hi)
                     if ev[3] == 0])


def _idle(busy: list, s, e) -> float:
    """Time in ``[s, e)`` outside the merged intervals ``busy``."""
    i = max(bisect.bisect_right(busy, [s, float("inf")]) - 1, 0)
    covered = 0.0
    while i < len(busy) and busy[i][0] < e:
        covered += max(0.0, min(e, busy[i][1]) - max(s, busy[i][0]))
        i += 1
    return float(e - s) - covered


def idle_in_spans(trace: tr.Trace, name: str, lo, hi) -> list:
    """Idle time of the first device (ns) inside each span ``name``,
    spans clipped to the window ``[lo, hi)``."""
    busy = _busy(trace, lo, hi)
    return [_idle(busy, s, e) for n, s, e in tr.clip(trace.spans, lo, hi)
            if n == name]


def host_gap_ms(trace: tr.Trace, lo, hi):
    """Median over the window's scheduling rounds (``bench.round``) of
    the first device's idle time inside each: the chip time a round
    loses to the host.  The median, since a rare stall of the round's
    transfer (0.1-2.7 s on a v5e under the profiler) moves a mean of
    ~70 rounds by whole milliseconds.  None where the window holds no
    round."""
    idle = idle_in_spans(trace, "bench.round", lo, hi)
    return statistics.median(idle) / 1e6 if idle else None


def admit_gap_ms(trace: tr.Trace, lo, hi):
    """The first device's idle time inside the window's offers
    (``bench.admit``) over the admissions taken in it: host reservation
    and dispatch, paid with the chip idle, per request admitted.  A
    taken admission runs one ``prefill_step`` program, and every one
    enqueued in the window runs in it (the window closes on the
    scheduler's last token); a deferred offer enqueues nothing and adds
    its microseconds to the sum.  None where no admission was taken."""
    taken = sum(1 for name, s, _, dev in trace.modules
                if dev == 0 and name.startswith(PREFILL) and lo <= s < hi)
    if not taken:
        return None
    return sum(idle_in_spans(trace, "bench.admit", lo, hi)) / taken / 1e6


def _holder(spans, gs, ge) -> str:
    """The span that is innermost over most of ``[gs, ge)``: each instant
    of the gap goes to the shortest span covering it (spans nest), and
    the span given most of the gap names it; "none" where no span but
    ``bench.window`` covers any of it."""
    over = [(s, e, name) for name, s, e in spans
            if name != "bench.window" and s < ge and e > gs]
    cuts = sorted({gs, ge} | {min(max(x, gs), ge)
                              for s, e, _ in over for x in (s, e)})
    held = defaultdict(float)
    for a, b in zip(cuts, cuts[1:]):
        inside = [sp for sp in over if sp[0] <= a and b <= sp[1]]
        if inside:
            held[min(inside, key=lambda sp: sp[1] - sp[0])] += b - a
    return max(held.items(), key=lambda kv: kv[1])[0][2] if held else "none"


def idle_gaps(trace: tr.Trace, lo, hi, n: int = 10) -> list:
    """[name, seconds] of the longest gaps in which no operation ran on
    the first device, as ``trace_reduce.idle_gaps`` finds them, each
    named by the innermost span over most of it: a gap inside
    ``serve.sync`` is named ``serve.sync``, not ``bench.round``, and one
    that runs from a round's sync into the next round's dispatch is
    named by whichever of the two holds more of it."""
    gaps, prev = [], lo
    for s, e in _busy(trace, lo, hi):
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_holder(trace.spans, gs, ge), (ge - gs) / 1e9]
            for gs, ge in gaps[:n]]
