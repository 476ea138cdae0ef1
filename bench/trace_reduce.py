"""From a ``jax.profiler`` trace to device busy time, program and kernel
times, and the breakdown of the result line.

The trace is the ``*.xplane.pb`` that ``jax.profiler.start_trace`` writes.
Device planes are named ``/device:TPU:<n>``; on each, the line
``XLA Modules`` holds one event per run of a compiled program, named
``jit_<function>(<fingerprint>)``, and ``XLA Ops`` one event per
operation, named by its HLO text (``%fusion.3 = bf16[...] fusion(...)``).
Control flow (``%while``, ``%conditional``) appears as an operation
that encloses its body's operations.  A Pallas kernel is a
``tpu_custom_call``; one called through a jitted function takes that
function's name (``%ternary_matmul_int8.56``), others a generic one
(``%closed_call.10``).  The harness's own host spans (``bench.*``) lie
on the host plane.  All times are nanoseconds on one clock.

Each operation is kept as ``(name, start, end, device)`` with the name
shortened to ``<program>:<instruction>``, e.g.
``jit_chunk_step:%closed_call.10``, and `` [pallas]`` appended for a
Pallas kernel.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    ops: list            # (name, start_ns, end_ns, device), all devices
    modules: list        # (name, start_ns, end_ns, device)
    spans: list          # (name, start_ns, end_ns), the harness's spans
    devices: int


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


PALLAS = "tpu_custom_call"
CONTROL = ("%while", "%conditional")


def _events(line, *tag):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns) + tag
            for e in line.events]


def _op_name(text: str) -> str:
    name = text.split(" = ", 1)[0]
    return name + " [pallas]" if PALLAS in text else name


def _label(ops, modules) -> list:
    """Prefix each op of one device with the program it ran in."""
    out, i = [], 0
    for name, s, e, d in ops:
        while i < len(modules) and modules[i][2] <= s:
            i += 1
        prog = (modules[i][0].split("(", 1)[0]
                if i < len(modules) and modules[i][1] <= s else "?")
        out.append((f"{prog}:{_op_name(name)}", s, e, d))
    return out


def load(path: str) -> Trace:
    """Read a trace file (``.xplane.pb``, or gzipped ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    ops, modules, spans, devices = [], [], [], 0
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = devices
            devices += 1
            mods, dev_ops = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev_ops = _events(line, dev)
                elif line.name == "XLA Modules":
                    mods = sorted(_events(line, dev), key=lambda e: e[1])
            ops += _label(sorted(dev_ops, key=lambda e: e[1]), mods)
            modules += mods
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[0].startswith(SPAN_PREFIX)]
    return Trace(sorted(ops, key=lambda e: e[1]),
                 sorted(modules, key=lambda e: e[1]),
                 sorted(spans, key=lambda e: e[1]), devices)


def union(intervals) -> list:
    """Merged, sorted (start, end) pairs covering ``intervals``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered_ns(intervals) -> float:
    return float(sum(e - s for s, e in union(intervals)))


def window(tr: Trace, name: str = "bench.window") -> tuple:
    """(start, end) of the harness's window span."""
    spans = [(s, e) for n, s, e in tr.spans if n == name]
    if not spans:
        raise ValueError(f"trace holds no {name!r} span")
    return spans[0]


def clip(events, lo, hi):
    return [(ev[0], max(ev[1], lo), min(ev[2], hi)) + tuple(ev[3:])
            for ev in events if ev[2] > lo and ev[1] < hi]


def busy_ns(tr: Trace, lo, hi) -> float:
    """Device time in which some operation ran, averaged over devices."""
    ops = clip(tr.ops, lo, hi)
    return sum(covered_ns([(ev[1], ev[2]) for ev in ops if ev[3] == d])
               for d in range(tr.devices)) / max(tr.devices, 1)


def module_ns(tr: Trace, part: str, lo, hi) -> float:
    """Device time of the runs of programs whose name holds ``part``,
    summed over devices."""
    return float(sum(ev[2] - ev[1] for ev in clip(tr.modules, lo, hi)
                     if part in ev[0]))


def ops_within(tr: Trace, program: str, lo, hi) -> list:
    """Operations that ran inside runs of a program whose name holds
    ``program`` (control-flow ops that enclose others left out)."""
    return [ev for ev in clip(tr.ops, lo, hi)
            if program in ev[0].split(":", 1)[0] and not _control(ev[0])]


def _control(name: str) -> bool:
    return name.split(":", 1)[-1].startswith(CONTROL)


def op_ns(ops, match) -> float:
    """Device time of the ops whose name ``match(name)`` accepts."""
    return float(sum(ev[2] - ev[1] for ev in ops if match(ev[0])))


def top_ops(tr: Trace, lo, hi, n: int = 10) -> list:
    """[name, seconds] of the operations that took most device time."""
    tot = defaultdict(float)
    for ev in clip(tr.ops, lo, hi):
        if not _control(ev[0]):
            tot[ev[0]] += ev[2] - ev[1]
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def idle_gaps(tr: Trace, lo, hi, n: int = 10) -> list:
    """[what the host was doing, seconds] of the longest gaps in which
    no operation ran on the first device, named by the harness span
    that covers most of the gap (the ``bench.window`` span, which covers
    everything, is left out)."""
    busy = union([(ev[1], ev[2]) for ev in clip(tr.ops, lo, hi)
                  if ev[3] == 0])
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for gs, ge in gaps[:n]:
        best, cover = "none", 0.0
        for name, s, e in tr.spans:
            if name == "bench.window":
                continue
            c = min(e, ge) - max(s, gs)
            if c > cover:
                best, cover = name, c
        out.append([best, (ge - gs) / 1e9])
    return out


def sleep_ns(tr: Trace, lo, hi) -> float:
    """Host time in the pump's sleeps (no request due or in a slot)."""
    return covered_ns([(s, e) for n, s, e in clip(tr.spans, lo, hi)
                       if n == "bench.sleep"])
