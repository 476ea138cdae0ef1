"""The reduction from a profiler trace to busy time, program and kernel
times and the breakdown."""
import os

import pytest

import tiny  # noqa: F401
from bench import trace_reduce as tr


def _synthetic():
    # device 0: a chunk_step program [0, 100) whose while loop holds a
    # matmul [10, 40) and an attention read [50, 90); a prefill program
    # [200, 260) with one op; host spans name the gap between them
    ops = [("jit_chunk_step:%while.1", 0, 100, 0),
           ("jit_chunk_step:%fusion.1", 0, 10, 0),
           ("jit_chunk_step:%ternary_matmul_int8.3 [pallas]", 10, 40, 0),
           ("jit_chunk_step:%closed_call.4 [pallas]", 50, 90, 0),
           ("jit_prefill_step:%fusion.2", 200, 260, 0)]
    modules = [("jit_chunk_step", 0, 100, 0),
               ("jit_prefill_step", 200, 260, 0)]
    spans = [("bench.window", 0, 300), ("bench.round", 0, 120),
             ("bench.admit", 120, 270), ("bench.sleep", 270, 300)]
    return tr.Trace(ops, modules, spans, 1)


def test_union_and_busy():
    assert tr.union([(5, 7), (0, 3), (2, 4)]) == [[0, 4], [5, 7]]
    t = _synthetic()
    assert tr.busy_ns(t, 0, 300) == 100 + 60
    assert tr.busy_ns(t, 20, 60) == 40


def test_program_and_kernel_times():
    t = _synthetic()
    assert tr.module_ns(t, "chunk_step", 0, 300) == 100
    inside = tr.ops_within(t, "chunk_step", 0, 300)
    assert [o[0].split(":")[1] for o in inside] == [
        "%fusion.1", "%ternary_matmul_int8.3 [pallas]",
        "%closed_call.4 [pallas]"]
    assert tr.op_ns(inside, lambda n: "closed_call" in n) == 40
    assert tr.window(t) == (0, 300)
    assert tr.sleep_ns(t, 0, 300) == 30


def test_breakdown():
    t = _synthetic()
    assert tr.top_ops(t, 0, 300, 2) == [
        ["jit_prefill_step:%fusion.2", 60e-9],
        ["jit_chunk_step:%closed_call.4 [pallas]", 40e-9]]
    gaps = tr.idle_gaps(t, 0, 300)
    assert gaps[0] == ["bench.admit", 100e-9]     # [100, 200)
    assert gaps[1] == ["bench.sleep", 40e-9]      # [260, 300)


RECORDED = os.path.join(tiny.REPO, "bench", "testdata",
                        "chat_window.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    """A 4 s traced window of internlm2-1.8b-base3.chat with 64 slots,
    recorded on one TPU v5e."""
    return tr.load(RECORDED)


def test_recorded_window_and_busy(recorded):
    lo, hi = tr.window(recorded)
    assert recorded.devices == 1
    assert (hi - lo) / 1e9 == pytest.approx(4.0223, abs=1e-3)
    assert tr.busy_ns(recorded, lo, hi) / 1e9 == pytest.approx(3.7024,
                                                                abs=1e-3)
    assert tr.sleep_ns(recorded, lo, hi) / 1e9 == pytest.approx(0.2455,
                                                                 abs=1e-3)


def test_recorded_programs_and_kernels(recorded):
    lo, hi = tr.window(recorded)
    assert tr.module_ns(recorded, "chunk_step", lo, hi) / 1e9 \
        == pytest.approx(3.4209, abs=1e-3)
    assert tr.module_ns(recorded, "prefill_step", lo, hi) > 0
    ops = tr.ops_within(recorded, "chunk_step", lo, hi)
    assert not any(":%while" in op[0] for op in ops)
    attn = tr.op_ns(ops, lambda n: n.endswith("[pallas]")
                    and ":%ternary_matmul" not in n)
    mm = tr.op_ns(ops, lambda n: ":%ternary_matmul_int8" in n
                  and n.endswith("[pallas]"))
    assert attn / 1e9 == pytest.approx(2.6993, abs=1e-3)
    assert 0 < mm < attn


def test_recorded_breakdown(recorded):
    lo, hi = tr.window(recorded)
    top = tr.top_ops(recorded, lo, hi)
    assert len(top) == 10
    assert top[0][0] == "jit_chunk_step:%closed_call.10 [pallas]"
    gaps = tr.idle_gaps(recorded, lo, hi)
    assert gaps[0][0] == "bench.sleep"
    assert all(g[1] > 0 for g in gaps)
