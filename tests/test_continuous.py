"""Continuous-batching scheduler:

  * chunked-loop tokens are bitwise identical per request to the plain
    batch-1 prefill/decode loop (tests/serve_reference.py);
  * slot-state isolation: a slot reclaimed by compaction (admit-scatter
    over a freed slot) reproduces the solo run of the new request
    exactly, with no bleed-through from the previous occupant;
  * no starvation: every request of a bursty arrival trace completes,
    with its full token budget;
  * transfer accounting: the scheduler performs exactly one device->host
    transfer per chunk, and a saturated uniform workload runs exactly
    ceil(decode_steps / chunk) chunks;
  * the sharded slot pool (slot axis folded over 'data') emits the same
    tokens as the unsharded scheduler (slow subprocess test).
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.models import registry
from repro.serve import (Request, Scheduler, bursty_arrivals, make_trace,
                         poisson_arrivals, load_trace)
from serve_reference import reference_tokens

jax.config.update("jax_platform_name", "cpu")


def _setup(arch="internlm2-1.8b", dtype=jnp.float32):
    cfg = dataclasses.replace(configs.smoke(arch), dtype=dtype)
    model = registry.build(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _requests(cfg, specs):
    """specs: list of (uid, prompt_len, max_new[, eos_id, arrival_s])."""
    key = jax.random.key(1)
    out = []
    for spec in specs:
        uid, plen, max_new = spec[:3]
        eos = spec[3] if len(spec) > 3 else -1
        arr = spec[4] if len(spec) > 4 else 0.0
        prompt = jax.random.randint(jax.random.fold_in(key, uid),
                                    (plen,), 0, cfg.vocab_size)
        out.append(Request(uid=uid, prompt=prompt, max_new=max_new,
                           eos_id=eos, arrival_s=arr))
    return out


# ------------------------------------------------- token parity

def test_chunked_tokens_match_both_pr2_drivers():
    """Same requests through the Scheduler and the plain batch-1
    prefill/decode loop: per-request token VALUES must agree bitwise."""
    cfg, model, params = _setup()
    specs = [(i, 8, 3 + 2 * i) for i in range(4)]

    sch = Scheduler(model, params, capacity=64, slots=4, chunk=3)
    for r in _requests(cfg, specs):
        sch.submit(r)
    got = {r.uid: list(r.out_tokens) for r in sch.run()}
    assert got == reference_tokens(model, params, _requests(cfg, specs), 64)
    assert all(len(got[uid]) == mn for uid, _, mn in specs)


def test_mixed_prompt_lengths_one_pool():
    """Slots at different sequence positions coexist: mixed prompt
    lengths decode concurrently in one pool."""
    cfg, model, params = _setup()
    specs = [(0, 4, 5), (1, 8, 5), (2, 16, 5), (3, 6, 5)]
    sch = Scheduler(model, params, capacity=64, slots=4, chunk=4)
    for r in _requests(cfg, specs):
        sch.submit(r)
    got = {r.uid: list(r.out_tokens) for r in sch.run()}
    assert got == reference_tokens(model, params, _requests(cfg, specs), 64)


# ------------------------------------------------- compaction / isolation

def test_slot_reuse_isolation_after_compaction():
    """More requests than slots: freed slots are reclaimed by the admit
    scatter. Every request must match its solo (batch-1) reference run —
    state bleed-through from a previous occupant would diverge here."""
    cfg, model, params = _setup()
    specs = [(i, 8 if i % 2 else 6, 3 + (i % 4)) for i in range(8)]
    sch = Scheduler(model, params, capacity=64, slots=2, chunk=3)
    for r in _requests(cfg, specs):
        sch.submit(r)
    got = {r.uid: list(r.out_tokens) for r in sch.run()}
    assert sorted(got) == [s[0] for s in specs]

    solo = reference_tokens(model, params, _requests(cfg, specs), 64)
    for uid, toks in solo.items():
        assert got[uid] == toks, f"slot reuse corrupted request {uid}"


# ------------------------------------------------- starvation / bursts

def test_no_starvation_under_bursty_trace():
    """Two bursts against a 2-slot pool: every submitted request
    completes with its full budget (FIFO admission; EOS disabled)."""
    cfg, model, params = _setup()
    arrivals = bursty_arrivals(10, bursts=2, gap_s=0.05, spread_s=0.01,
                               seed=3)
    trace = make_trace(arrivals, prompt_lens=[6, 8], max_news=[2, 5, 3])
    specs = [(i, rec["prompt_len"], rec["max_new"], -1, rec["arrival_s"])
             for i, rec in enumerate(trace)]
    sch = Scheduler(model, params, capacity=64, slots=2, chunk=3)
    for r in _requests(cfg, specs):
        sch.submit(r)
    done = sch.run()
    assert sorted(r.uid for r in done) == list(range(10))
    for r in done:
        assert len(r.out_tokens) == r.max_new
        assert r.done and r.latency_s >= 0.0


def test_trace_generators():
    arr = poisson_arrivals(5, rate_per_s=100.0, seed=1)
    assert len(arr) == 5 and arr == sorted(arr) and arr[0] > 0
    assert poisson_arrivals(3, 0.0) == [0.0, 0.0, 0.0]
    arr = bursty_arrivals(6, bursts=2, gap_s=1.0, spread_s=0.0)
    assert arr[:3] == [0.0] * 3 and arr[3:] == [1.0] * 3
    trace = make_trace(arr, [8, 16], [4])
    assert trace[0]["prompt_len"] == 8 and trace[1]["prompt_len"] == 16
    assert all(t["max_new"] == 4 for t in trace)


def test_load_trace_roundtrip(tmp_path):
    path = os.path.join(tmp_path, "trace.json")
    with open(path, "w") as f:
        json.dump([{"arrival_s": 0.5, "prompt_len": 8, "max_new": 3},
                   {"arrival_s": 0.7, "prompt_len": 4, "max_new": 2,
                    "priority": 1, "deadline_s": 0.25}], f)
    trace = load_trace(path)
    assert trace[0] == {"arrival_s": 0.5, "prompt_len": 8, "max_new": 3,
                        "eos_id": -1, "priority": 0, "deadline_s": None}
    assert trace[1]["priority"] == 1 and trace[1]["deadline_s"] == 0.25


# ------------------------------------------------- transfer accounting

def test_one_transfer_per_chunk_and_ceil_accounting():
    """Uniform saturated pool: decode_steps == max_new - 1 and the
    scheduler runs exactly ceil(steps / chunk) chunks, one host
    transfer each, at 100% slot occupancy."""
    cfg, model, params = _setup()
    max_new, chunk = 10, 4
    specs = [(i, 8, max_new) for i in range(4)]
    sch = Scheduler(model, params, capacity=64, slots=4, chunk=chunk)
    for r in _requests(cfg, specs):
        sch.submit(r)
    sch.run()
    steps = max_new - 1                      # tok0 comes from prefill
    assert sch.decode_steps == steps
    assert sch.chunks_run == -(-steps // chunk)
    assert sch.host_transfers == sch.chunks_run
    assert sch.slot_occupancy == 1.0


def test_eos_stops_slot_and_frees_it():
    cfg, model, params = _setup()
    prompt = jnp.zeros((4,), jnp.int32)
    from repro.serve import make_prefill_step
    pre = make_prefill_step(model, 32)
    tok, _ = pre(params, {"tokens": prompt[None]})
    eos = int(tok[0])                        # greedy's first token
    sch = Scheduler(model, params, capacity=32, slots=1, chunk=4)
    sch.submit(Request(uid=0, prompt=prompt, max_new=8, eos_id=eos))
    sch.submit(Request(uid=1, prompt=jnp.ones((4,), jnp.int32), max_new=3))
    done = sch.run()
    by_uid = {r.uid: r for r in done}
    assert len(by_uid[0].out_tokens) == 1    # tok0 == eos: stops at once
    assert len(by_uid[1].out_tokens) == 3    # slot was freed and reused


def test_idle_pool_emits_tok0_with_zero_steps():
    """max_new=1 requests never enter the decode loop: the chunk
    prologue emits the prefill token and the slot retires with zero
    decode steps (still exactly one transfer for the chunk)."""
    cfg, model, params = _setup()
    sch = Scheduler(model, params, capacity=32, slots=2, chunk=4)
    for r in _requests(cfg, [(0, 6, 1), (1, 6, 1)]):
        sch.submit(r)
    done = sch.run()
    assert all(len(r.out_tokens) == 1 for r in done)
    assert sch.decode_steps == 0
    assert sch.chunks_run == 1 == sch.host_transfers


# ------------------------------------------------- sharded slot pool

SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp
from repro import configs
from repro.dist import mesh as mesh_lib, sharding as shd
from repro.models import registry
from repro.serve import Request, Scheduler

cfg = dataclasses.replace(configs.smoke("internlm2-1.8b"),
                          dtype=jnp.float32)
model = registry.build(cfg)
params = model.init(jax.random.key(0))
key = jax.random.key(1)

def reqs():
    return [Request(uid=i,
                    prompt=jax.random.randint(jax.random.fold_in(key, i),
                                              (8,), 0, cfg.vocab_size),
                    max_new=3 + i)
            for i in range(4)]

def run(spmd_axes, rules=None, mesh=None):
    shd.set_activation_context(rules, mesh)
    sch = Scheduler(model, params, capacity=32, slots=4, chunk=3,
                    spmd_axes=spmd_axes)
    for r in reqs():
        sch.submit(r)
    return {r.uid: list(r.out_tokens) for r in sch.run()}

ref = run(None)

mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec((2, 4), ("data", "model")))
rules = shd.rules_for(cfg, "serve")
got = run(shd.slot_spmd_axes(rules, mesh, 4), rules, mesh)

print(json.dumps({"identical": got == {str(k): v for k, v in ref.items()}
                               or got == ref,
                  "devices": jax.device_count(),
                  "spmd_axes": str(shd.slot_spmd_axes(rules, mesh, 4))}))
"""


@pytest.mark.slow
def test_sharded_slot_pool_matches_unsharded():
    """The slot axis sharded over 'data' (vmap spmd_axis_name through
    dist.sharding.slot_spmd_axes) must not change a single token."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 8
    assert out["spmd_axes"] == "data"
    assert out["identical"]
