"""Run one cell of the benchmark once: set-up, the measured window, the
metrics, and the comparison with the plain reference that decides
``correct``.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name in ``BENCHMARK.json``:

- ``bench/configs/<config>.json``: the model's published ``config.json``
  numbers under their own names, the architecture module that reads
  them (``"architecture"``, ``dense`` when absent), the packing and
  domain, the scheduler's sizes and the limits of the check;
- ``bench/archs/<module>.py``: the architecture's model, reference block
  and work counts (``bench/archs/__init__.py`` has the interface);
- ``bench/traffic/<traffic>.json``: the parameters ``traffic.py`` reads,
  and optionally the cell's own ``capacity`` and ``slots`` in place of
  the configuration's;
- ``bench/metrics/<metric>.py``: a reader ``read(run) -> float | None``
  of one metric from the ``Run`` record below.

The system under test is ``repro.serve.PagedScheduler``, driven through
its public pump (``try_admit`` / ``step_round`` / ``is_busy``) by an
open-loop arrival pump whose semantics are those of the scheduler's own
``_arrival_pump``: offer every due request FIFO until one is deferred,
sleep to the next arrival while nothing is in a slot, else run a round.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time

from . import archs

BENCH_DIR = "bench"
DRAIN_S = 150.0          # how long after the close a due request may take
CELL_SIZES = ("capacity", "slots")   # a traffic file may set these


class CellError(RuntimeError):
    """The cell cannot run as its files describe it."""


@dataclasses.dataclass
class Cell:
    name: str
    root: str
    config: dict
    traffic: dict
    chips: int
    metrics: list          # [(name, unit, reader)] for --trace 0
    layer_metrics: list    # [(name, unit, reader)] for --trace 1

    @property
    def module(self):
        """The configuration's architecture module."""
        return archs.load(self.config, self.root)

    @property
    def sched(self) -> dict:
        """The scheduler's sizes: the configuration's, with the traffic
        file's ``capacity`` and ``slots`` where it gives them; the pool's
        pages are always the configuration's."""
        return dict(self.config["scheduler"],
                    **{k: self.traffic[k] for k in CELL_SIZES
                       if k in self.traffic})


@dataclasses.dataclass
class ReqLog:
    uid: int
    due_s: float
    prompt_len: int
    max_new: int
    offered_s: float = math.nan     # first offered to try_admit
    admit_s: float = math.nan
    first_s: float = math.nan       # first token on the host
    last_s: float = math.nan        # latest token on the host
    tokens: int = 0
    tokens_at_open: int = 0         # received before the window opened
    tokens_in_window: int = 0       # received inside the window
    tokens_at_close: int = 0        # by the round that crossed the close
    done_s: float = math.nan
    slot: int = -1                  # the scheduler's slot it was served in


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""
    cell: Cell
    seconds: float
    requests: list                  # [ReqLog] of the requests due in it
    all_requests: list              # [ReqLog], pre-roll's included
    counters: dict                  # scheduler counters over the window
    setup_s: float
    peak_bytes: int
    device_kind: str
    trace: object = None            # trace_reduce.Trace of the window
    trace_window: tuple = None      # (start_ns, end_ns)

    @property
    def module(self):
        return self.cell.module

    @property
    def arch(self) -> dict:
        return self.module.arch(self.cell.config)

    @property
    def packing(self) -> str:
        return self.cell.config["packing"]

    def peaks(self) -> dict:
        from . import work
        return work.peaks(self.device_kind,
                          os.path.join(self.cell.root, BENCH_DIR))


def _load_reader(root: str, name: str):
    path = os.path.join(root, BENCH_DIR, "metrics", name + ".py")
    if not os.path.isfile(path):
        raise CellError(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(root: str, workload: str) -> Cell:
    """The cell ``workload`` as ``root``'s ``BENCHMARK.json`` and files
    describe it."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r}; known: {sorted(cells)}")
    wl = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[wl["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, BENCH_DIR, "traffic",
                           wl["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def mine(metrics):
        return [(m["name"], m["unit"], _load_reader(root, m["name"]))
                for m in metrics if workload in m.get("workloads",
                                                      [workload])]
    cell = Cell(workload, root, config, traffic, int(wl["chips"]),
                mine(bench["end_to_end"]), mine(bench["per_layer"]))
    archs.find(config, root)        # a missing module fails before JAX starts
    if any(k in traffic for k in CELL_SIZES):
        # admission reserves a request's pages in full and nothing is
        # preempted: every slot must be able to hold a whole request
        s = cell.sched
        need = s["slots"] * -(-s["capacity"] // s["page_size"])
        if need > s["num_pages"] - 1:
            raise CellError(
                f"{s['slots']} slots of {s['capacity']} positions need "
                f"{need} pages of {s['page_size']}; the pool has "
                f"{s['num_pages'] - 1} (page 0 is the null page)")
    return cell


# ----------------------------------------------------------------- set-up

def arch(config: dict) -> dict:
    """The model's sizes, as its architecture module gives them."""
    return archs.load(config).arch(config)


def model_config(config: dict):
    """The program's ModelConfig, as the architecture module builds it."""
    return archs.load(config).model_config(config)


def check_plan(eng, on_tpu: bool) -> None:
    """The served path must be the Pallas int8 matmul and the fused
    paged-attention read; no fallback."""
    attn = eng.attn_plan
    bad = []
    if eng.cim.backend != "pallas":
        bad.append(f"matmul backend {eng.cim.backend!r}")
    if attn is None or attn.backend != "paged_attn":
        bad.append(f"attention read {attn and attn.backend!r}")
    if on_tpu and (eng.cim.interpret or attn is None or attn.interpret):
        bad.append("interpret mode on the chip")
    if bad:
        raise CellError("served path is not pallas + fused paged_attn: "
                        + ", ".join(bad))


def build(cell: Cell, seed: int, on_tpu: bool):
    """Model, served weights and scheduler, as the configuration says."""
    from repro.core.cim_linear import CIMConfig
    from repro.models import registry
    from repro.serve import PagedScheduler

    from . import weights
    c, mod = cell.config, cell.module
    model = registry.build(mod.model_config(c))
    params = weights.served_params(model, c["packing"], seed,
                                   getattr(mod, "draw", None))
    sch = cell.sched
    eng = PagedScheduler(
        model, params, capacity=sch["capacity"], slots=sch["slots"],
        chunk=sch["chunk"], page_size=sch["page_size"],
        num_pages=sch["num_pages"],
        cim=CIMConfig(mode="ternary", packing=c["packing"],
                      domain=c["domain"]),
        fused_attn=True)
    check_plan(eng, on_tpu)
    return eng


def warm_up(eng, cell: Cell, seed: int) -> None:
    """Compile every program this cell's traffic drives: one prefill and
    page write per prompt length of the menu, admission, the chunk loop
    and retirement.  The requests are served to the end."""
    import numpy as np
    from repro.serve import Request

    from . import traffic
    prompts, _ = traffic.shapes(cell.traffic)
    rng = np.random.default_rng([seed, 1])
    vocab = cell.config["vocab_size"]
    pending = [Request(uid=-1 - i, prompt=rng.integers(0, vocab, p,
                                                       dtype=np.int32),
                       max_new=eng.chunk + 2)
               for i, p in enumerate(prompts)]
    t0 = time.monotonic()
    while pending or eng.is_busy():
        while pending and eng.try_admit(pending[0]):
            pending.pop(0)
        eng.step_round(lambda: time.monotonic() - t0)
    eng.completed.clear()
    eng.allocator.reset_stats()


COUNTERS = ("chunks_run", "decode_steps", "occupied_slot_steps",
            "host_transfers")


def _counters(eng) -> dict:
    return {k: getattr(eng, k) for k in COUNTERS}


# ------------------------------------------------------------------ window

class Pump:
    """The open-loop arrival pump over the scheduler's public entry.

    Times are seconds from the window's opening.  Arrivals start
    ``preroll`` seconds before it (the traffic file's ``preroll_s``), so
    that the window sees the system in its steady state and not filling
    up; requests due then are served but are not the window's.  Requests
    due at or before the pump's start (a backlog) are admitted before it
    runs, as far as slots and pages allow, with their prefills finished.
    Each request is timed from when it was due.  After the close, a
    Poisson mix keeps being served for up to ``DRAIN_S`` so that the
    tails are over every request due in the window; a backlog stops at
    the close."""

    def __init__(self, eng, reqs, seconds, drain: bool, spans: bool,
                 preroll: float = 0.0):
        from repro.serve import Request
        self.eng, self.seconds, self.drain = eng, seconds, drain
        self.preroll = preroll
        self.logs = [ReqLog(r.uid, r.due_s, len(r.prompt), r.max_new)
                     for r in reqs]
        self.reqs = [Request(uid=r.uid, prompt=r.prompt, max_new=r.max_new,
                             arrival_s=r.due_s) for r in reqs]
        self.spans = spans
        self.at_open = self.at_close = None   # counters at open and close
        self.sleep_s = 0.0                    # slept inside the window
        self.t0 = None                        # clock at the window's opening
        self.pages = []       # (reserved KV pages, live positions) a round

    def span(self, name):
        if not self.spans:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _admit(self, i, t) -> bool:
        """Offer request ``i``; the scheduler takes the first free slot."""
        free = self.eng.free_slots()
        if not (free and self.eng.try_admit(self.reqs[i], t)):
            return False
        self.logs[i].slot = free[0]
        return True

    def run(self, on_open=None, on_close=None) -> None:
        import jax
        eng, logs = self.eng, self.logs
        pending = list(range(len(self.reqs)))
        active: dict = {}
        while pending and logs[pending[0]].due_s <= -self.preroll:
            i = pending[0]
            if not self._admit(i, -self.preroll):
                break
            logs[i].offered_s = logs[i].admit_s = -self.preroll
            active[i] = 0
            pending.pop(0)
        jax.block_until_ready(eng.tok)
        t0 = self.t0 = time.monotonic() + self.preroll
        now = lambda: time.monotonic() - t0           # noqa: E731
        while pending or active:
            t = now()
            if self.at_open is None and t >= 0.0:
                self._open(on_open)
            if self.at_close is None and t >= self.seconds:
                self._close(on_close)
                if not self.drain:
                    break
            if t >= self.seconds + DRAIN_S:
                break
            while pending and logs[pending[0]].due_s <= t:
                i = pending[0]
                if math.isnan(logs[i].offered_s):
                    logs[i].offered_s = t
                with self.span("bench.admit"):
                    ok = self._admit(i, t)
                if not ok:
                    break
                logs[i].admit_s = now()
                active[i] = 0
                pending.pop(0)
            if not eng.is_busy():
                if not pending:
                    break
                t = now()
                delay = logs[pending[0]].due_s - t
                if self.at_open is None:
                    delay = min(delay, -t)
                elif self.at_close is None:
                    delay = min(delay, self.seconds - t)
                if delay > 0:
                    with self.span("bench.sleep"):
                        time.sleep(delay)
                    if self.at_open is not None and self.at_close is None:
                        self.sleep_s += delay
                continue
            with self.span("bench.round"):
                eng.step_round(now)
            t = now()
            if self.at_open is None and t >= 0.0:
                # the round crossed the opening: its tokens reached the
                # host inside the window, those before it did not
                self._open(on_open)
            for i in list(active):
                n = len(self.reqs[i].out_tokens)
                if n > active[i]:
                    log = logs[i]
                    if math.isnan(log.first_s):
                        log.first_s = t
                    log.last_s, log.tokens = t, n
                    if 0.0 <= t <= self.seconds:
                        log.tokens_in_window = n - log.tokens_at_open
                    active[i] = n
                if self.reqs[i].done:
                    logs[i].done_s = t
                    del active[i]
            if self.at_open is not None and self.at_close is None:
                self.pages.append((eng.allocator.pages_in_use, sum(
                    logs[i].prompt_len + logs[i].tokens for i in active)))
        if self.at_open is None:      # all served before the window opened
            self._open(on_open)
        if self.at_close is None:
            self._close(on_close)

    def _open(self, on_open) -> None:
        self.at_open = _counters(self.eng)
        for log in self.logs:
            log.tokens_at_open = log.tokens
        if on_open:
            on_open()

    def _close(self, on_close) -> None:
        self.at_close = _counters(self.eng)
        for log in self.logs:
            log.tokens_at_close = log.tokens
        if on_close:
            on_close()

    def window_requests(self) -> list:
        """Logs of the requests the window owns: due in it, or (a
        backlog) admitted before it and so due when it opened."""
        return [log for log in self.logs
                if 0.0 <= log.due_s < self.seconds
                or (log.due_s <= -self.preroll
                    and not math.isnan(log.admit_s))]


# -------------------------------------------------------------- the check

def sample_requests(pump: Pump, seed: int, most: int) -> list:
    """Requests for the check, drawn from the seed: one for each slot
    that served tokens, so that a fault confined to one slot is seen,
    the longest request (prompt plus served tokens) for its slot and a
    random one for every other; at most ``most``, the longest always
    among them.  Every served token of each counts: a backlog's requests
    still in a slot at the close are scored on the tokens they served."""
    import numpy as np
    served = [i for i, r in enumerate(pump.reqs) if r.out_tokens]
    if not served:
        return []
    longest = max(served, key=lambda i: (len(pump.reqs[i].prompt)
                                         + len(pump.reqs[i].out_tokens),
                                         -i))
    rng = np.random.default_rng([seed, 2])
    by_slot: dict = {}
    for i in served:
        by_slot.setdefault(pump.logs[i].slot, []).append(i)
    slots = sorted(set(by_slot) - {pump.logs[longest].slot})
    rng.shuffle(slots)
    pick = [longest] + [by_slot[s][rng.integers(len(by_slot[s]))]
                        for s in slots[: most - 1]]
    return [(pump.reqs[i].prompt.tolist(), list(pump.reqs[i].out_tokens))
            for i in pick]


# ------------------------------------------------------------------- main

def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(chips: int) -> int:
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def start_jax():
    """Import JAX set up as the serving launcher sets it up (bf16 rounded
    where the model says), with the persistent compile cache in the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says) holding every
    program, however quick to compile.  Call before JAX starts."""
    from repro.launch.serve import pin_bf16_rounding
    pin_bf16_rounding()
    import jax
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


class CompileCount:
    """Programs compiled or loaded from the cache, counted by JAX's
    monitoring event; any in the window means warm-up missed a shape."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, on_tpu: bool = True,
             trace_dir: str | None = None, control: bool = False) -> dict:
    """One run of ``cell``; returns the result line's object.

    With ``control`` the check judges, in the program's place, the tokens
    that the int4 control (``reference.py``) puts first on the same
    prompts and served tokens: a run that must come out not correct."""
    import jax

    from . import reference, trace_reduce, traffic
    compiles = CompileCount()
    t_build = time.monotonic()
    eng = build(cell, seed, on_tpu)
    t_warm = time.monotonic()
    warm_up(eng, cell, seed)
    print(f"setup: imports {t_build - t_start:.2f} s, weights and "
          f"scheduler {t_warm - t_build:.2f} s, warm-up "
          f"{time.monotonic() - t_warm:.2f} s, {compiles.n} programs "
          f"compiled or loaded", file=sys.stderr)
    reqs = traffic.generate(cell.traffic, seed, seconds,
                            cell.config["vocab_size"])
    _, longest = traffic.shapes(cell.traffic)
    if longest > eng.capacity:
        raise CellError(f"traffic needs {longest} positions, capacity "
                        f"{eng.capacity}")
    drain = cell.traffic["arrival"] != "backlog"
    pump = Pump(eng, reqs, seconds, drain, spans=trace,
                preroll=float(cell.traffic.get("preroll_s", 0.0)))
    trace_dir = trace_dir or os.path.join(cell.root, "bench_out", "trace")
    base, win = {}, []

    def open_():
        base.update(_counters(eng), compiles=compiles.n)
        if trace:
            jax.profiler.start_trace(trace_dir)
            win.append(jax.profiler.TraceAnnotation("bench.window"))
            win[0].__enter__()

    def close():
        if trace:
            jax.block_until_ready(eng.tok)
            win[0].__exit__(None, None, None)
            jax.profiler.stop_trace()

    pump.run(on_open=open_, on_close=close)
    # set-up ends where the pump's clock starts: a pre-roll is traffic
    setup_s = pump.t0 - pump.preroll - t_start
    peak = peak_bytes(cell.chips)
    counters = {k: pump.at_close[k] - base[k] for k in COUNTERS}
    counters.update(slots=eng.slots, sleep_s=pump.sleep_s,
                    compiles=compiles.n - base["compiles"])
    dev = device_info(cell.chips)

    run = Run(cell, seconds, pump.window_requests(), pump.logs, counters,
              setup_s, peak, dev["kind"])
    breakdown = None
    if trace:
        tr = trace_reduce.load(trace_reduce.find(trace_dir))
        shutil.rmtree(trace_dir)            # the numbers are read; free disk
        lo, hi = trace_reduce.window(tr)
        run.trace, run.trace_window = tr, (lo, hi)
        dev["busy_s"] = trace_reduce.busy_ns(tr, lo, hi) / 1e9
        dev["window_s"] = (hi - lo) / 1e9
        breakdown = {"device_ops": trace_reduce.top_ops(tr, lo, hi),
                     "idle_gaps": trace_reduce.idle_gaps(tr, lo, hi)}
    dev["memory_peak_bytes"] = peak

    metrics = {}
    for name, unit, read in (cell.layer_metrics if trace else cell.metrics):
        value = read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}

    # the check, with the program's state freed so the reference sets no
    # peak and has the chip's memory
    due = run.requests
    failed = sum(1 for log in due if math.isnan(log.done_s)) if drain else 0
    limits = cell.config["limits"]
    seqs = sample_requests(pump, seed, limits["sample_requests"])
    late = sorted(log.offered_s - log.due_s for log in due
                  if not math.isnan(log.offered_s))
    pages = pump.pages or [(0, 0)]
    page_size = cell.sched["page_size"]
    print(f"kv: pages reserved in the window peak "
          f"{max(p for p, _ in pages)}, mean "
          f"{sum(p for p, _ in pages) / len(pages):.1f} of "
          f"{eng.num_pages - 1}; live positions mean "
          f"{sum(n for _, n in pages) / len(pages) / page_size:.1f} pages; "
          f"pre-roll {pump.preroll:.1f} s", file=sys.stderr)
    del eng, pump
    gc.collect()
    t_ref = time.monotonic()
    gaps = reference.logit_gaps(
        cell.config, seed, seqs, limits["sample_requests"],
        cell.sched["capacity"], control=control,
        root=cell.root) if seqs else {}
    print(f"reference: {time.monotonic() - t_ref:.2f} s over "
          f"{len(seqs)} requests", file=sys.stderr)
    gap = gaps.get("control_gap" if control else "served_gap", math.inf)
    checks = {
        "served_gap": {"value": gap, "limit": limits["served_gap"]},
        "checked_tokens": {"value": gaps.get("tokens", 0),
                           "limit": limits["sample_tokens"]},
        "unfinished": {"value": failed, "limit": 0},
        "compiles_in_window": {"value": counters["compiles"], "limit": 0},
    }
    correct = (gap <= limits["served_gap"]
               and gaps.get("tokens", 0) >= limits["sample_tokens"]
               and failed == 0 and counters["compiles"] == 0)
    out = {"correct": bool(correct), "attempted": len(due),
           "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        out["control"] = gaps
        print(f"control: the int4 reference in the program's place; the "
              f"program's own gap {gaps.get('served_gap')}", file=sys.stderr)
    print(f"pump: {len(late)} requests offered, lateness p95 "
          f"{_pct(late, 95) * 1e3:.3f} ms, max "
          f"{max(late, default=0.0) * 1e3:.3f} ms; slept "
          f"{counters['sleep_s']:.3f} s; counters {counters}",
          file=sys.stderr)
    print("tails: " + json.dumps(tails(run)), file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    out["checks"] = checks
    return out


def tails(run: Run) -> dict:
    """Quantiles of time to first token and time per output token, and
    how many requests each is over, for reading a cell's spread."""
    import numpy as np
    ttft = [r.first_s - r.due_s for r in run.requests
            if not math.isnan(r.first_s)]
    tpot = [(r.last_s - r.first_s) / (r.tokens - 1) for r in run.requests
            if r.tokens >= 2]
    out = {}
    for name, vals in (("ttft_ms", ttft), ("tpot_ms", tpot)):
        if vals:
            out[name] = {f"p{q}": float(np.percentile(vals, q)) * 1e3
                         for q in (50, 75, 90, 95)}
            out[name]["n"] = len(vals)
    return out


def _pct(vals, q) -> float:
    import numpy as np
    return float(np.percentile(vals, q)) if vals else 0.0
