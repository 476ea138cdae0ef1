"""The scheduler's host spans (``serve.*``), recorded by the profiler on
the CPU and read back from the trace file: one ``serve.round`` a chunk,
one ``serve.sync`` a transfer, one ``serve.admit`` an offer, and the
nesting the spans promise (serve/README.md, "Spans")."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.models import registry
from repro.serve import PagedScheduler, Request, Scheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import span_reduce, trace_reduce  # noqa: E402

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def smoke():
    cfg = dataclasses.replace(configs.smoke("internlm2-1.8b"),
                              dtype=jnp.float32)
    model = registry.build(cfg)
    return cfg, model, model.init(jax.random.key(0))


def _requests(cfg, specs):
    key = jax.random.key(1)
    return [Request(uid=uid, max_new=max_new,
                    prompt=jax.random.randint(jax.random.fold_in(key, uid),
                                              (plen,), 0, cfg.vocab_size))
            for uid, plen, max_new in specs]


def _serve_traced(sch, reqs, trace_dir):
    """Drive the public pump under the profiler; returns the spans, the
    offers made and the counters' growth."""
    pending, offers = list(reqs), 0
    before = (sch.chunks_run, sch.host_transfers)
    jax.profiler.start_trace(str(trace_dir))
    try:
        while pending or sch.is_busy():
            while pending:
                offers += 1
                if not sch.try_admit(pending[0]):
                    break
                pending.pop(0)
            if sch.is_busy():
                sch.step_round(lambda: 0.0)
    finally:
        jax.profiler.stop_trace()
    tr = span_reduce.load(trace_reduce.find(str(trace_dir)))
    spans = [s for s in tr.spans if s[0].startswith("serve.")]
    return spans, offers, (sch.chunks_run - before[0],
                           sch.host_transfers - before[1])


def _inside(spans, outer, name):
    _, lo, hi = outer
    return [s for s in spans if s[0] == name and lo <= s[1]
            and s[2] <= hi]


def _named(spans, name):
    return [s for s in spans if s[0] == name]


@pytest.mark.parametrize("paged", [True, False],
                         ids=["paged", "dense"])
def test_spans_count_rounds_transfers_and_offers(smoke, tmp_path, paged):
    cfg, model, params = smoke
    if paged:
        # 4 usable pages: request 1 (4 pages) waits for request 0 (3)
        sch = PagedScheduler(model, params, capacity=16, slots=2, chunk=3,
                             page_size=4, num_pages=5)
    else:
        sch = Scheduler(model, params, capacity=16, slots=1, chunk=3)
    reqs = _requests(cfg, [(0, 4, 6), (1, 8, 6), (2, 4, 3)])
    spans, offers, (chunks, transfers) = _serve_traced(sch, reqs, tmp_path)

    rounds = _named(spans, "serve.round")
    assert chunks > 0 and len(rounds) == chunks
    assert len(_named(spans, "serve.sync")) == transfers == chunks
    for r in rounds:
        for child in ("serve.dispatch", "serve.sync", "serve.absorb"):
            assert len(_inside(spans, r, child)) == 1, (child, r)

    admits = _named(spans, "serve.admit")
    assert len(admits) == offers > len(reqs)       # some offers deferred
    taken = [a for a in admits if _inside(spans, a, "serve.prefill")]
    assert len(taken) == len(reqs)
    for a in taken:
        assert len(_inside(spans, a, "serve.prefill")) == 1
        assert len(_inside(spans, a, "serve.reserve")) == int(paged)
    assert len(_named(spans, "serve.prefill")) == len(reqs)
    if paged:                   # a deferral for pages runs the reservation
        assert len(_named(spans, "serve.reserve")) > len(reqs)
    assert all(len(r.out_tokens) == r.max_new for r in reqs)


def test_spans_lie_inside_the_harness_spans(smoke, tmp_path):
    """The harness wraps ``try_admit`` and ``step_round`` in ``bench.*``
    spans; each of the program's spans nests in one of them."""
    cfg, model, params = smoke
    sch = PagedScheduler(model, params, capacity=16, slots=2, chunk=3,
                         page_size=4)
    reqs = _requests(cfg, [(0, 4, 4), (1, 8, 4)])
    jax.profiler.start_trace(str(tmp_path))
    try:
        for r in reqs:
            with jax.profiler.TraceAnnotation("bench.admit"):
                assert sch.try_admit(r)
        while sch.is_busy():
            with jax.profiler.TraceAnnotation("bench.round"):
                sch.step_round(lambda: 0.0)
    finally:
        jax.profiler.stop_trace()
    tr = span_reduce.load(trace_reduce.find(str(tmp_path)))
    outer = [s for s in tr.spans if s[0] in ("bench.admit", "bench.round")]
    mine = [s for s in tr.spans if s[0].startswith("serve.")]
    assert len(mine) >= 4 * len(outer) - len(reqs)
    for s in mine:
        assert any(o[1] <= s[1] and s[2] <= o[2] for o in outer), s
