"""Serving front-end (ISSUE 9 acceptance):

  * token parity: the front-end drives schedulers through the public
    pump API only, so per-request tokens are bitwise identical to
    driving the scheduler directly with the same records;
  * bounded queue + explicit backpressure: pending never exceeds
    queue_limit, every submit is accounted (completed or rejected with
    a reason), nothing is silently dropped;
  * SLO admission: priority preempts FIFO order at admission, doomed
    deadlines are shed (passed / unmeetable), FIFO never sheds;
  * two-model isolation: interleaved traffic through one server keeps
    per-model tokens bitwise equal to each model's solo direct run;
  * streaming transfer accounting: host_transfers == chunks survives
    the front-end (the stream drains the chunk payload, no extra sync);
  * determinism: one trace replayed twice under a virtual clock gives
    identical admission logs and tokens;
  * trace contract: validate/save/load round-trip, TraceError on
    malformed records; latency_stats p999 + queue-wait/service split.
"""
import os

import jax
import pytest

from repro.frontend import (FIFOAdmission, FrontendServer, ModelRegistry,
                            ModelSpec, SLOAdmission, VirtualClock,
                            deadline_at, replay, replay_direct,
                            trace_requests)
from repro.serve import (Request, TraceError, latency_stats, load_trace,
                         make_trace, save_trace, validate_trace)

jax.config.update("jax_platform_name", "cpu")

ARCH_A, ARCH_B = "internlm2-1.8b", "qwen3-14b"


@pytest.fixture(scope="module")
def registry():
    """Two smoke-model pools, built lazily on first targeted request;
    shared across tests (all front-end/replay counters are per-epoch
    deltas, so a warm registry is safe to reuse)."""
    reg = ModelRegistry()
    for arch in (ARCH_A, ARCH_B):
        reg.register(ModelSpec(name=arch, arch=arch, smoke=True,
                               kind="paged", capacity=64, slots=2,
                               chunk=4, page_size=16))
    return reg


def _virtual_server(reg, admission=None, queue_limit=64):
    clock = VirtualClock()
    server = FrontendServer(reg, admission, queue_limit=queue_limit,
                            clock=clock)
    return server, clock


def _replay(server, clock, records, **kw):
    return replay(server, records, sleep=clock.advance,
                  tick=lambda: clock.advance(0.01), **kw)


# ------------------------------------------------- registry

def test_registry_lazy_instantiation_and_capacity_report():
    reg = ModelRegistry()
    reg.register(ModelSpec(name="m", arch=ARCH_A))
    assert "m" in reg and ARCH_A not in reg
    assert not reg.is_instantiated("m")
    report = reg.capacity_report()
    assert report["m"]["instantiated"] is False
    assert "kv_bytes_pool" not in report["m"]     # no pool was built
    with pytest.raises(ValueError, match="already registered"):
        reg.register(ModelSpec(name="m", arch=ARCH_A))
    with pytest.raises(ValueError, match="kind"):
        reg.register(ModelSpec(name="x", arch=ARCH_A, kind="bucket"))
    with pytest.raises(KeyError, match="unknown model"):
        reg.spec("ghost")


# ------------------------------------------------- token parity

def test_tokens_bitwise_identical_to_direct_scheduler(registry):
    trace = make_trace([0.0] * 6, [6, 8], [5, 7])
    records = trace_requests(trace, registry, [ARCH_A], seed=0)
    server, clock = _virtual_server(registry)
    rep = _replay(server, clock, records, collect_tokens=True)
    assert rep["completed"] == 6 and rep["rejected"] == 0
    fe_tokens = [rep["out_tokens"][u] for u in sorted(rep["out_tokens"])]
    _, by_uid = replay_direct(registry, records)
    dt_tokens = [by_uid[r["uid"]] for r in records]
    assert fe_tokens == dt_tokens
    for toks, rec in zip(fe_tokens, records):
        assert len(toks) == rec["max_new"]


def test_two_model_isolation_interleaved(registry):
    """Interleaved traffic over both pools through ONE server: each
    model's tokens must equal its solo direct run — no cross-model
    state bleed through the shared front-end."""
    trace = make_trace([0.0] * 8, [6, 8], [4, 6])
    records = trace_requests(trace, registry, [ARCH_A, ARCH_B], seed=3)
    assert {r["model"] for r in records} == {ARCH_A, ARCH_B}
    server, clock = _virtual_server(registry)
    rep = _replay(server, clock, records, collect_tokens=True)
    assert rep["completed"] == 8
    _, by_uid = replay_direct(registry, records)
    fe_tokens = [rep["out_tokens"][u] for u in sorted(rep["out_tokens"])]
    for toks, rec in zip(fe_tokens, records):
        assert toks == by_uid[rec["uid"]], rec["model"]


def test_streaming_transfer_accounting_and_hook(registry):
    """host_transfers == chunks across the replay, and the on_tokens
    delivery hook sees every token exactly once, in order."""
    trace = make_trace([0.0] * 3, [6], [6])
    records = trace_requests(trace, registry, [ARCH_A], seed=1)
    server, clock = _virtual_server(registry)
    got = {}
    server.begin()
    streams = [server.submit(r["model"], r["prompt"],
                             max_new=r["max_new"], eos_id=r["eos_id"],
                             on_tokens=lambda s, new:
                             got.setdefault(s.uid, []).extend(new))
               for r in records]
    t0, c0 = server.host_transfers, server.chunks
    server.drain()
    assert server.host_transfers - t0 == server.chunks - c0 > 0
    for s in streams:
        assert s.status == "done" and s.finished
        assert got[s.uid] == s.tokens == list(s.req.out_tokens)
        assert s.ttft_s is not None and s.ttft_s >= 0.0


# ------------------------------------------------- backpressure

def test_bounded_queue_rejects_with_reason(registry):
    trace = make_trace([0.0] * 6, [6], [4])
    records = trace_requests(trace, registry, [ARCH_A], seed=2)
    server, clock = _virtual_server(registry, queue_limit=2)
    rep = _replay(server, clock, records)
    assert rep["max_pending_seen"] <= 2
    assert rep["submitted"] == 6
    assert rep["submitted"] == rep["completed"] + rep["rejected"]
    assert server.in_flight == 0
    assert rep["rejects_by_reason"].get("queue-full", 0) == rep["rejected"]
    assert rep["rejected"] > 0


def test_submit_rejects_unknown_model_and_over_capacity(registry):
    server, _ = _virtual_server(registry)
    s = server.submit("ghost", [1, 2, 3])
    assert s.status == "rejected" and s.reason == "unknown-model"
    s = server.submit(ARCH_A, list(range(60)), max_new=10)  # 70 > 64
    assert s.status == "rejected" and s.reason == "over-capacity"
    assert not s.accepted and s.finished
    assert server.rejects_by_reason == {"unknown-model": 1,
                                        "over-capacity": 1}
    assert server.submitted == len(server.rejected) == 2
    with pytest.raises(ValueError, match="queue_limit"):
        FrontendServer(registry, queue_limit=0)


# ------------------------------------------------- SLO admission

def test_priority_preempts_fifo_admission_order(registry):
    """Four same-arrival requests, priorities [1, 1, 0, 0], two slots:
    the SLO policy admits the urgent class first (uids 2, 3); FIFO
    admits submission order (uids 0, 1)."""
    def first_admits(policy):
        server, clock = _virtual_server(registry, admission=policy)
        server.begin()
        for i, pri in enumerate([1, 1, 0, 0]):
            server.submit(ARCH_A, [1 + i] * 6, max_new=3, priority=pri)
        server.poll()
        admits = [e[1] for e in server.admission_log
                  if e[0] == "admit"]
        server.drain()
        return admits[:2]

    assert first_admits(SLOAdmission()) == [2, 3]
    assert first_admits(FIFOAdmission()) == [0, 1]


def test_slo_sheds_passed_and_unmeetable_deadlines(registry):
    server, clock = _virtual_server(
        registry, admission=SLOAdmission(service_floor_s=1.0))
    server.begin()
    doomed = server.submit(ARCH_A, [1] * 6, max_new=3, deadline_s=0.05)
    tight = server.submit(ARCH_A, [2] * 6, max_new=3, deadline_s=0.5)
    free = server.submit(ARCH_A, [3] * 6, max_new=3)
    clock.advance(0.1)   # past doomed's deadline; tight needs 1.0s floor
    server.drain()
    assert doomed.status == "shed" and doomed.reason == "deadline-passed"
    assert tight.status == "shed" and tight.reason == "deadline-unmeetable"
    assert free.status == "done" and len(free.tokens) == 3
    assert server.rejects_by_reason == {"deadline-passed": 1,
                                        "deadline-unmeetable": 1}
    assert server.submitted == len(server.completed) + len(server.rejected)


def test_fifo_never_sheds_and_deadline_at():
    fifo = FIFOAdmission()
    late = Request(uid=0, prompt=[1], max_new=1, arrival_s=0.0,
                   deadline_s=0.01)
    assert fifo.shed_reason(late, now=99.0) is None
    assert deadline_at(late) == 0.01
    assert deadline_at(Request(uid=1, prompt=[1], max_new=1,
                               arrival_s=2.0)) == float("inf")
    slo = SLOAdmission()
    assert slo.shed_reason(late, now=0.005) is None
    assert slo.shed_reason(late, now=0.01) == "deadline-passed"


def test_admission_log_deterministic_across_replays(registry):
    """Same records, two fresh servers on one virtual timeline recipe:
    identical decision logs, tokens, and shed accounting."""
    trace = make_trace([round(0.01 * i, 6) for i in range(6)],
                       [6, 8], [4, 6], priorities=[0, 1],
                       deadlines=[0.08, None])
    records = trace_requests(trace, registry, [ARCH_A], seed=5)

    def run():
        server, clock = _virtual_server(
            registry, admission=SLOAdmission(service_floor_s=0.02))
        rep = _replay(server, clock, records, collect_tokens=True)
        return server.admission_log, rep

    log1, rep1 = run()
    log2, rep2 = run()
    assert log1 == log2
    assert rep1["out_tokens"] == rep2["out_tokens"]
    assert rep1["shed"] == rep2["shed"]
    assert rep1["deadline_met"] == rep2["deadline_met"]


# ------------------------------------------------- trace contract

def test_trace_roundtrip_and_canonicalization(tmp_path):
    trace = make_trace([0.0, 0.5], [8], [4], priorities=[0, 1],
                       deadlines=[None, 0.25])
    path = os.path.join(tmp_path, "t.json")
    save_trace(path, trace)
    assert load_trace(path) == validate_trace(trace) == trace
    # defaults are filled on the way in
    got = validate_trace([{"arrival_s": 0, "prompt_len": 4, "max_new": 2}])
    assert got == [{"arrival_s": 0.0, "prompt_len": 4, "max_new": 2,
                    "eos_id": -1, "priority": 0, "deadline_s": None}]


@pytest.mark.parametrize("bad,msg", [
    ({"not": "a list"}, "expected a JSON list"),
    ([[1, 2]], "expected an object"),
    ([{"arrival_s": 0.0}], "missing required keys"),
    ([{"arrival_s": -1, "prompt_len": 4, "max_new": 2}],
     "negative arrival_s"),
    ([{"arrival_s": 1.0, "prompt_len": 4, "max_new": 2},
      {"arrival_s": 0.5, "prompt_len": 4, "max_new": 2}],
     "sorted by arrival"),
    ([{"arrival_s": 0, "prompt_len": 0, "max_new": 2}], "prompt_len"),
    ([{"arrival_s": 0, "prompt_len": 4, "max_new": 0}], "max_new"),
    ([{"arrival_s": 0, "prompt_len": 4, "max_new": 2,
       "deadline_s": -0.5}], "deadline_s must be positive"),
    ([{"arrival_s": "soon", "prompt_len": 4, "max_new": 2}],
     "non-numeric"),
])
def test_trace_validation_errors(bad, msg):
    with pytest.raises(TraceError, match=msg):
        validate_trace(bad)


def test_load_trace_malformed_json(tmp_path):
    path = os.path.join(tmp_path, "bad.json")
    with open(path, "w") as f:
        f.write("{nope")
    with pytest.raises(TraceError, match="unparseable JSON"):
        load_trace(path)


# ------------------------------------------------- latency breakdown

def test_latency_stats_p999_and_queue_service_split():
    reqs = [Request(uid=0, prompt=[1], max_new=1, arrival_s=0.0,
                    latency_s=1.0, admit_s=0.3),
            Request(uid=1, prompt=[1], max_new=1, arrival_s=1.0,
                    latency_s=0.5, admit_s=0.8)]   # admit before arrival
    st = latency_stats(reqs)
    assert st["mean_s"] == 0.75
    # uid 0 waited 0.3 then decoded 0.7; uid 1's wait clamps to 0
    assert st["queue_wait_mean_s"] == 0.15
    assert st["service_mean_s"] == 0.6
    assert st["p50_s"] <= st["p99_s"] <= st["p999_s"] <= 1.0
    zero = latency_stats([])
    assert zero["p999_s"] == 0.0 and zero["queue_wait_mean_s"] == 0.0
    many = [Request(uid=i, prompt=[1], max_new=1,
                    latency_s=float(i) / 1000.0, admit_s=0.0)
            for i in range(1001)]
    st = latency_stats(many)
    assert st["p99_s"] < st["p999_s"] < 1.0   # interpolated, not max


def test_virtual_clock():
    clock = VirtualClock()
    assert clock() == 0.0
    clock.advance(0.5)
    clock.sleep(0.25)
    clock.advance(-1.0)        # clamps: time never goes backwards
    assert clock() == 0.75
