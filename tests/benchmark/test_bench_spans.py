"""The reduction of the program's own spans (``serve.*``): idle time
inside rounds and admissions, gaps named by the innermost span, and the
span run of a tiny cell on the CPU."""
import gzip
import os

import pytest

import tiny
from bench import span_reduce as sr
from bench import span_run
from bench import trace_reduce as tr


def _synthetic():
    # device 0: a prefill [10, 30), a chunk [40, 100), a chunk [130, 190);
    # host: an admission taken (its prefill enqueued) and one deferred,
    # two rounds, each ending in its sync and absorb
    ops = [("jit_prefill_step:%fusion.1", 10, 30, 0),
           ("jit_chunk_step:%paged_attention.3 [pallas]", 40, 100, 0),
           ("jit_chunk_step:%paged_attention.3 [pallas]", 130, 190, 0)]
    spans = [("bench.window", 0, 200),
             ("bench.admit", 0, 12), ("serve.admit", 1, 12),
             ("serve.reserve", 2, 5), ("serve.prefill", 6, 11),
             ("bench.round", 32, 120), ("serve.round", 33, 119),
             ("serve.dispatch", 33, 40), ("serve.sync", 40, 104),
             ("serve.absorb", 104, 119),
             ("bench.admit", 121, 124), ("serve.admit", 121, 124),
             ("serve.reserve", 122, 124),
             ("bench.round", 124, 195), ("serve.round", 125, 194),
             ("serve.dispatch", 125, 130), ("serve.sync", 130, 191),
             ("serve.absorb", 191, 194)]
    modules = [("jit_prefill_step(7)", 10, 30, 0),
               ("jit_chunk_step(9)", 40, 100, 0),
               ("jit_chunk_step(9)", 130, 190, 0)]
    return tr.Trace(ops, modules, sorted(spans, key=lambda s: s[1]), 1)


def test_idle_in_spans():
    t = _synthetic()
    # round 1 [33, 119): busy [40, 100); round 2 [125, 194): [130, 190)
    assert sr.idle_in_spans(t, "serve.round", 0, 200) == [26.0, 9.0]
    assert sr.idle_in_spans(t, "serve.sync", 0, 200) == [4.0, 1.0]
    # spans are clipped to the window
    assert sr.idle_in_spans(t, "serve.round", 0, 110) == [17.0]
    assert sr.idle_in_spans(t, "serve.missing", 0, 200) == []


def test_host_gap_is_the_median_idle_time_of_a_round():
    t = _synthetic()
    # bench.round [32, 120) is idle 8 + 20, [124, 195) 6 + 5
    assert sr.host_gap_ms(t, 0, 200) == pytest.approx((28 + 11) / 2 / 1e6)
    assert sr.host_gap_ms(t, 0, 20) is None
    # a third round whose transfer stalls moves the mean, not the median
    stalled = tr.Trace(t.ops, t.modules,
                       t.spans + [("bench.round", 200, 900)], 1)
    assert sr.host_gap_ms(stalled, 0, 900) == pytest.approx(28 / 1e6)


def test_admit_gap_is_idle_in_offers_over_admissions_taken():
    t = _synthetic()
    # the taken offer [0, 12) is idle but for the prefill's [10, 12), the
    # deferred one [121, 124) all through; one prefill_step ran
    assert sr.admit_gap_ms(t, 0, 200) == pytest.approx((10 + 3) / 1e6)
    # no prefill_step started in the window: nothing was taken
    assert sr.admit_gap_ms(t, 100, 200) is None


def test_idle_gaps_named_by_the_innermost_span():
    t = _synthetic()
    gaps = sr.idle_gaps(t, 0, 200)
    # [100, 130), between two rounds: the first round's absorb holds 15,
    # the second's dispatch 5, its sync 4, the deferred reservation 2
    assert gaps[0] == ["serve.absorb", 30e-9]
    # [0, 10): the prefill holds 4, the reservation 3, the admission 2
    assert gaps[1] == ["serve.prefill", 10e-9]
    # [30, 40): the dispatch holds 7, bench.round alone 1
    assert gaps[2] == ["serve.dispatch", 10e-9]
    # [190, 200): the absorb holds 3; [195, 200) lies in no span
    assert gaps[3] == ["serve.absorb", 10e-9]
    # the harness's reduction finds the same gaps, named by bench.*
    assert [g[1] for g in tr.idle_gaps(t, 0, 200)] == [g[1] for g in gaps]
    assert {g[0] for g in tr.idle_gaps(t, 0, 200)} == {"bench.round",
                                                       "bench.admit"}


@pytest.mark.parametrize("sync, name", [((5, 25), "serve.sync"),
                                        ((5, 19), "serve.sync"),
                                        ((5, 13), "serve.absorb")])
def test_a_gap_takes_the_name_of_the_span_holding_most_of_it(sync, name):
    """A gap inside the sync, or spilling a little out of it into the
    absorb, is the sync's; the round around both never names it."""
    ops = [("p:%a", 0, 10, 0), ("p:%b", 20, 30, 0)]
    spans = sorted([("bench.window", 0, 30), ("bench.round", 0, 30),
                    ("serve.round", 1, 29), ("serve.sync",) + sync,
                    ("serve.absorb", sync[1], 29)], key=lambda s: s[1])
    t = tr.Trace(ops, [], spans, 1)
    assert sr.idle_gaps(t, 0, 30) == [[name, 10e-9]]
    assert tr.idle_gaps(t, 0, 30) == [["bench.round", 10e-9]]


def test_load_of_a_trace_without_program_spans_is_the_harness_load():
    """The recorded window of an earlier program (no ``serve.*`` spans)
    loads exactly as ``trace_reduce.load`` loads it, and the readers read
    it from the harness's spans: three rounds, each after a burst of
    admissions, and thirteen admissions taken, each queued behind the
    last one's prefill but for the first of a burst."""
    path = tiny.REPO + "/bench/testdata/chat_window.xplane.pb.gz"
    mine, theirs = sr.load(path), tr.load(path)
    assert mine == theirs
    lo, hi = tr.window(mine)
    assert sr.host_gap_ms(mine, lo, hi) == pytest.approx(30.1608, abs=1e-3)
    assert sr.admit_gap_ms(mine, lo, hi) == pytest.approx(0.29857,
                                                          abs=1e-4)


def test_span_run_of_a_tiny_cell(tmp_path):
    """The span run drives the harness's pump over the real scheduler
    (Pallas in interpret mode) and reads the program's spans back."""
    root = str(tmp_path)
    name = tiny.write_cell(root)
    save = str(tmp_path / "window.xplane.pb.gz")
    out = span_run.run(root, name, seed=2 ** 33 + 5, seconds=1.0,
                       save=save, on_tpu=False)
    assert out["rounds"] > 0
    assert 0 <= out["sched.host_gap_ms"] <= out["window_s"] * 1e3
    assert all(g[0] != "none" for g in out["idle_gaps"])
    with gzip.open(save, "rb") as f:
        assert f.read(1)
    t = sr.load(save)
    lo, hi = tr.window(t)
    names = {s[0] for s in tr.clip(t.spans, lo, hi)}
    assert {"serve.round", "serve.sync", "bench.round"} <= names


RECORDED = os.path.join(tiny.REPO, "bench", "testdata",
                        "chat_spans.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    """A 2.2 s traced window of internlm2-1.8b-base3.chat (32 slots) with
    the program's spans, recorded on one TPU v5e by ``bench/span_run.py``
    (seed 3130000005: five arrivals in the window)."""
    return sr.load(RECORDED)


def _within(span, outer):
    return outer[1] <= span[1] and span[2] <= outer[2]


def test_recorded_spans_nest_in_the_harness_spans(recorded):
    lo, hi = tr.window(recorded)
    spans = tr.clip(recorded.spans, lo, hi)
    mine = [s for s in spans if s[0].startswith("serve.")]
    outer = [s for s in recorded.spans
             if s[0] in ("bench.round", "bench.admit")]
    assert {s[0] for s in mine} == {
        "serve.admit", "serve.reserve", "serve.prefill", "serve.round",
        "serve.dispatch", "serve.sync", "serve.absorb"}
    assert all(any(_within(s, o) for o in outer)
               for s in recorded.spans if s[0].startswith("serve."))
    syncs = [s for s in recorded.spans if s[0] == "serve.sync"]
    for r in (s for s in recorded.spans if s[0] == "serve.round"):
        assert sum(_within(s, r) for s in syncs) == 1


def test_recorded_kernel_has_its_name(recorded):
    lo, hi = tr.window(recorded)
    names = [name for name, _ in tr.top_ops(recorded, lo, hi)]
    assert names[0].startswith("jit_chunk_step:%paged_attention.")
    assert names[0].endswith(" [pallas]")
    assert not any("%closed_call" in n for n in names)


def test_recorded_gaps_named_by_the_program(recorded):
    """The longest gap, 27 ms in the round after two admissions, lies
    inside the chunk's dispatch; the others mostly in the sync, the chip
    idle while the round's transfer lands, or in a prefill's enqueue.
    The harness's reduction calls the longest ``bench.round``."""
    lo, hi = tr.window(recorded)
    gaps = sr.idle_gaps(recorded, lo, hi)
    assert gaps[0][0] == "serve.dispatch"
    assert gaps[0][1] == pytest.approx(0.02725, abs=1e-5)
    assert all(g[0].startswith("serve.") for g in gaps)
    assert {"serve.sync", "serve.prefill"} <= {g[0] for g in gaps}
    assert tr.idle_gaps(recorded, lo, hi)[0] == ["bench.round", gaps[0][1]]


def test_recorded_readers(recorded):
    lo, hi = tr.window(recorded)
    for name in ("serve.round", "serve.admit", "bench.round", "bench.admit"):
        spans = [s for s in tr.clip(recorded.spans, lo, hi) if s[0] == name]
        idle = sr.idle_in_spans(recorded, name, lo, hi)
        assert len(idle) == len(spans) > 0
        assert all(0 <= i <= e - s for i, (_, s, e) in zip(idle, spans))
    # rounds idle 30.08 ms (after two admissions), 2.81 and 3.14 ms, each
    # within 0.03 ms of the serve.round inside it
    assert sr.host_gap_ms(recorded, lo, hi) == pytest.approx(3.1418,
                                                              abs=1e-3)
    rounds = sr.idle_in_spans(recorded, "serve.round", lo, hi)
    outer = sr.idle_in_spans(recorded, "bench.round", lo, hi)
    assert all(0 <= b - a < 4e4 for a, b in zip(rounds, outer))
    # four admissions taken, none deferred: idle 3.09, 0.03, 3.29, 0 ms
    assert sr.admit_gap_ms(recorded, lo, hi) == pytest.approx(1.6008,
                                                               abs=1e-3)
    # the harness's own load keeps enough for both
    plain = tr.load(RECORDED)
    assert sr.host_gap_ms(plain, lo, hi) == sr.host_gap_ms(recorded, lo, hi)
    assert (sr.admit_gap_ms(plain, lo, hi)
            == sr.admit_gap_ms(recorded, lo, hi))


@pytest.mark.parametrize("name, cells", [
    ("sched.host_gap_ms", ["internlm2-1.8b-base3.chat",
                           "qwen3-14b-trit2.batch"]),
    ("sched.admit_gap_ms", ["internlm2-1.8b-base3.chat"])])
def test_benchmark_reads_the_metric_in_its_cells(recorded, name, cells):
    """Each new metric is a ``per_layer`` entry of the repo's benchmark
    with a reader file; the reader gives nothing untraced and the
    reduction's value on the recorded window."""
    from types import SimpleNamespace

    from bench import harness
    for cell in ("internlm2-1.8b-base3.chat", "qwen3-14b-trit2.batch"):
        layer = [m[0] for m in harness.load_cell(tiny.REPO,
                                                 cell).layer_metrics]
        assert (name in layer) == (cell in cells)
    read = harness._load_reader(tiny.REPO, name)
    assert read(SimpleNamespace(trace=None, trace_window=None)) is None
    lo, hi = tr.window(recorded)
    want = getattr(sr, name.split(".")[1])(recorded, lo, hi)
    run = SimpleNamespace(trace=tr.load(RECORDED), trace_window=(lo, hi))
    assert read(run) == want


def test_paged_attention_kernel_is_matched_by_name(recorded):
    """The roofline's kernel, matched by its name, is the set of ops the
    earlier predicate (a Pallas op of ``chunk_step`` that is not the
    ternary matmul) selected on the recorded window, so the reading does
    not move; another architecture's kernel is not charged to it."""
    import importlib.util
    path = os.path.join(tiny.REPO, "bench", "metrics",
                        "paged_attention_roofline.py")
    spec = importlib.util.spec_from_file_location("roofline_reader", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    def before(name):
        return name.endswith("[pallas]") and ":%ternary_matmul" not in name
    lo, hi = tr.window(recorded)
    ops = tr.ops_within(recorded, "chunk_step", lo, hi)
    now = [o for o in ops if reader.is_kernel(o[0])]
    assert now and now == [o for o in ops if before(o[0])]
    assert not reader.is_kernel("jit_chunk_step:%grouped_matmul.3 [pallas]")
