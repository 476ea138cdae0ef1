"""Paged, prefix-shared KV for the slot pool (ISSUE 5 acceptance):

  * bitwise token parity: the PagedScheduler emits identical tokens to
    the dense-pool Scheduler AND the plain batch-1 prefill/decode loop
    (tests/serve_reference.py) for mixed prompt lengths, including
    prompts that do not align to page boundaries;
  * page reuse isolation: a page freed on EOS/retire and reallocated to
    a later request never leaks stale KV (every request matches its
    solo batch-1 reference);
  * prefix sharing: pages mapped shared (hashed token prefix already in
    the pool) give IDENTICAL tokens to private copies, including
    cross-length shared prefixes; refcounts return shared pages to the
    free list only when the last reference drops;
  * capacity discipline: admission reserves pages all-or-nothing and
    DEFERS (never OOMs mid-decode) when the pool is exhausted — every
    request still completes;
  * the kv_layout plan/request seam: paged plans resolve only on
    backends declaring the capability, and a dense-only backend is
    rejected loudly;
  * attend()/flash_attention() accept PagedKV gather-views bitwise;
  * the sharded slot pool (8 fake devices) emits identical tokens
    (slow subprocess test).
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import registry
from repro.models import paged_kv
from repro.serve import PagedScheduler, Request, Scheduler
from serve_reference import reference_tokens

jax.config.update("jax_platform_name", "cpu")


def _setup(arch="internlm2-1.8b", dtype=jnp.float32, **over):
    cfg = dataclasses.replace(configs.smoke(arch), dtype=dtype, **over)
    model = registry.build(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _requests(cfg, specs):
    """specs: list of (uid, prompt_len, max_new[, eos_id]); prompt
    contents keyed by uid % 3 so repeated keys share full prompts."""
    key = jax.random.key(1)
    out = []
    for spec in specs:
        uid, plen, max_new = spec[:3]
        eos = spec[3] if len(spec) > 3 else -1
        prompt = jax.random.randint(jax.random.fold_in(key, uid % 3),
                                    (plen,), 0, cfg.vocab_size)
        out.append(Request(uid=uid, prompt=prompt, max_new=max_new,
                           eos_id=eos))
    return out


def _run(engine, reqs):
    for r in reqs:
        engine.submit(r)
    return {r.uid: list(r.out_tokens) for r in engine.run()}


# ------------------------------------------------- token parity

def test_paged_tokens_match_all_drivers():
    """PagedScheduler == dense Scheduler == the plain batch-1 loop,
    bitwise, on mixed prompt lengths (page-aligned and not) and mixed
    budgets."""
    cfg, model, params = _setup()
    specs = [(0, 8, 5), (1, 12, 3), (2, 6, 7), (3, 16, 4), (4, 9, 1)]

    paged = _run(PagedScheduler(model, params, capacity=32, slots=4,
                                chunk=3, page_size=4),
                 _requests(cfg, specs))
    dense = _run(Scheduler(model, params, capacity=32, slots=4, chunk=3),
                 _requests(cfg, specs))
    ref = reference_tokens(model, params, _requests(cfg, specs), 32)
    assert paged == dense == ref
    assert all(len(paged[uid]) == mn for uid, _, mn in specs)


def test_admission_prefill_state_holds_the_prompt_pages():
    """An admission's dense prefill state lives only until its pages are
    written into the pool, so it holds the prompt's pages, not the
    request capacity: one prefill per page count, each state that many
    positions (tokens are pinned by the parity tests above)."""
    cfg, model, params = _setup()
    eng = PagedScheduler(model, params, capacity=32, slots=4, chunk=3,
                         page_size=4)
    _run(eng, _requests(cfg, [(0, 8, 5), (1, 6, 3), (2, 9, 2)]))
    assert sorted(eng._prefills) == [8, 12]
    _, st = jax.eval_shape(eng._prefills[12], params,
                           {"tokens": jnp.zeros((1, 9), jnp.int32)})
    assert st["k"].shape[2] == 12


def test_paged_page_size_invariance():
    """The page size is a storage choice, not a semantics choice: every
    page size yields the same tokens."""
    cfg, model, params = _setup()
    specs = [(0, 10, 4), (1, 7, 6), (2, 13, 3)]
    ref = _run(Scheduler(model, params, capacity=32, slots=3, chunk=4),
               _requests(cfg, specs))
    for ps in (1, 3, 8, 32):
        got = _run(PagedScheduler(model, params, capacity=32, slots=3,
                                  chunk=4, page_size=ps),
                   _requests(cfg, specs))
        assert got == ref, f"page_size={ps}"


# ------------------------------------------------- reuse isolation

def test_recycled_pages_never_leak_stale_kv():
    """More requests than the pool can hold at once: pages freed on
    retire are reallocated to later requests.  Every request must match
    its solo batch-1 run — stale KV in a recycled page would diverge."""
    cfg, model, params = _setup()
    specs = [(i, 6 + 3 * (i % 3), 3 + (i % 4)) for i in range(8)]
    sch = PagedScheduler(model, params, capacity=32, slots=2, chunk=3,
                         page_size=4)
    got = _run(sch, _requests(cfg, specs))
    assert sorted(got) == [s[0] for s in specs]
    assert sch.pages_in_use == 0            # every page returned
    assert sch.allocator.peak_in_use > 0

    solo = reference_tokens(model, params, _requests(cfg, specs), 32)
    for uid, toks in solo.items():
        assert got[uid] == toks, f"recycled page corrupted request {uid}"


def test_eos_frees_pages_for_reuse():
    cfg, model, params = _setup()
    prompt = jnp.zeros((4,), jnp.int32)
    from repro.serve import make_prefill_step
    pre = make_prefill_step(model, 32)
    tok, _ = pre(params, {"tokens": prompt[None]})
    eos = int(tok[0])
    sch = PagedScheduler(model, params, capacity=16, slots=1, chunk=4,
                         page_size=4, num_pages=5)
    sch.submit(Request(uid=0, prompt=prompt, max_new=8, eos_id=eos))
    sch.submit(Request(uid=1, prompt=jnp.ones((4,), jnp.int32),
                       max_new=3))
    done = {r.uid: r for r in sch.run()}
    assert len(done[0].out_tokens) == 1      # tok0 == eos: stops at once
    assert len(done[1].out_tokens) == 3      # pages freed and reused
    assert sch.pages_in_use == 0


def test_pool_exhaustion_defers_admission():
    """A page pool too small for two concurrent requests serializes
    them (all-or-nothing reservation) instead of failing mid-decode."""
    cfg, model, params = _setup()
    # each request: prompt 8 + max_new 4 -> positions 0..10 -> 3 pages
    specs = [(i, 8, 4) for i in range(4)]
    sch = PagedScheduler(model, params, capacity=16, slots=4, chunk=4,
                         page_size=4, num_pages=4,      # 3 usable pages
                         share_prefix=False)
    got = _run(sch, _requests(cfg, specs))
    ref = _run(Scheduler(model, params, capacity=16, slots=4, chunk=4),
               _requests(cfg, specs))
    assert got == ref
    assert sch.allocator.peak_in_use <= 3


def test_request_exceeding_capacity_fails_loudly():
    cfg, model, params = _setup()
    sch = PagedScheduler(model, params, capacity=8, slots=1, chunk=2,
                         page_size=4)
    sch.submit(Request(uid=0, prompt=jnp.zeros((8,), jnp.int32),
                       max_new=8))
    with pytest.raises(ValueError, match="needs .* pages"):
        sch.run()


def test_request_exceeding_whole_pool_fails_loudly():
    """A request no empty pool could ever privately satisfy must raise,
    not busy-spin on deferred admission forever."""
    cfg, model, params = _setup()
    sch = PagedScheduler(model, params, capacity=32, slots=1, chunk=2,
                         page_size=4, num_pages=4)      # 3 usable pages
    sch.submit(Request(uid=0, prompt=jnp.zeros((8,), jnp.int32),
                       max_new=8))                      # needs 4 pages
    with pytest.raises(ValueError, match="usable pages"):
        sch.run()


# ------------------------------------------------- prefix sharing

def test_prefix_sharing_matches_private_copies():
    """Shared read-only pages produce the same tokens as private
    copies (share_prefix=False) and as the dense pool — and actually
    fire on identical and cross-length prefixes.  Resident KV is what
    the pool competes on: the dense pool holds every slot's whole
    capacity, the paged one at most half of it here."""
    cfg, model, params = _setup()
    base = jax.random.randint(jax.random.key(7), (12,), 0,
                              cfg.vocab_size)
    def reqs():
        return [Request(uid=0, prompt=base, max_new=6),
                Request(uid=1, prompt=base, max_new=4),
                Request(uid=2, prompt=base[:9], max_new=4),
                Request(uid=3, prompt=jnp.concatenate(
                    [base[:8], base[:4]]), max_new=3)]

    dense_pool = Scheduler(model, params, capacity=32, slots=4, chunk=4)
    dense = _run(dense_pool, reqs())
    shared = PagedScheduler(model, params, capacity=32, slots=4, chunk=4,
                            page_size=4)
    got = _run(shared, reqs())
    private = PagedScheduler(model, params, capacity=32, slots=4,
                             chunk=4, page_size=4, share_prefix=False)
    got_priv = _run(private, reqs())

    assert got == got_priv == dense
    assert shared.allocator.prefix_hits > 0
    assert private.allocator.prefix_hits == 0
    assert 0.0 < shared.prefix_hit_rate <= 1.0
    # shared pages cost the pool less than private copies
    assert shared.allocator.peak_in_use < private.allocator.peak_in_use
    assert dense_pool.kv_bytes() >= 2 * shared.kv_bytes_resident_peak
    # every reference released: the registry is empty again
    assert shared.pages_in_use == 0


def test_allocator_refcounts_and_peak():
    a = paged_kv.PageAllocator(num_pages=6, page_size=4)
    ids = a.alloc(3)
    assert ids is not None and len(set(ids)) == 3 and 0 not in ids
    assert a.pages_in_use == 3 and a.peak_in_use == 3
    assert a.alloc(3) is None                # all-or-nothing
    assert a.pages_in_use == 3               # failed alloc left no trace
    a.register_prefix(("k",), ids[0])
    assert a.lookup_prefix(("k",)) == ids[0]     # refcount 2
    assert a.lookup_prefix(("missing",)) is None
    a.release([ids[0]])
    assert a.pages_in_use == 3               # still referenced
    a.release([ids[0], ids[1], ids[2]])
    assert a.pages_in_use == 0
    assert a.lookup_prefix(("k",)) is None   # unregistered on last free
    assert a.peak_in_use == 3
    assert a.prefix_hits == 1 and a.prefix_lookups == 3


# ------------------------------------------------- kv_layout plan seam

def test_paged_plan_capability():
    from repro.kernels import (BackendSpec, plan_matmul, register_backend,
                               unregister_backend)
    p = plan_matmul((4, 64, 32), kv_layout="paged", backend="xla")
    assert p.kv_layout == "paged"
    assert p.describe()["kv_layout"] == "paged"
    # plans default to dense and the two layouts cache separately
    assert plan_matmul((4, 64, 32), backend="xla").kv_layout == "dense"
    with pytest.raises(ValueError, match=r"'dense', 'paged'"):
        plan_matmul((4, 64, 32), kv_layout="ragged")

    register_backend(BackendSpec(
        name="dense_only", ops=frozenset({"ternary"}),
        domains=frozenset({"float"}),
        packings=frozenset({"base3", "trit2"}),
        platforms=frozenset({"cpu", "tpu"}), priority=1,
        runner=lambda plan, x, w: x,
        kv_layouts=frozenset({"dense"})))
    try:
        with pytest.raises(ValueError,
                           match=r"does not support kv layout 'paged'"):
            plan_matmul((4, 64, 32), backend="dense_only",
                        kv_layout="paged")
        assert plan_matmul((4, 64, 32), kv_layout="paged").backend \
            != "dense_only"
    finally:
        unregister_backend("dense_only")


def test_paged_scheduler_resolves_paged_plans():
    """A ternary CIM config under the PagedScheduler is re-resolved
    with kv_layout='paged', so dense() plans under it carry the paged
    capability request."""
    from repro.core.cim_linear import CIMConfig, ternarize_params
    cfg, model, params = _setup()
    cim = CIMConfig(mode="ternary", packing="base3")
    pparams = ternarize_params(params, cim)
    sch = PagedScheduler(model, pparams, capacity=32, slots=2, chunk=3,
                         page_size=4, cim=cim)
    assert sch.cim.kv_layout == "paged"
    assert sch.cim.backend != "auto"
    got = _run(sch, _requests(cfg, [(0, 8, 3), (1, 6, 4)]))
    dense = _run(Scheduler(model, pparams, capacity=32, slots=2, chunk=3,
                           cim=cim), _requests(cfg, [(0, 8, 3),
                                                     (1, 6, 4)]))
    assert got == dense


# ------------------------------------------------- attend() wiring

def test_attend_accepts_paged_views_bitwise():
    from repro.models.attention import attend, flash_attention
    cfg = configs.smoke("internlm2-1.8b")
    key = jax.random.key(3)
    b, t, kvh, hd = 2, 16, cfg.num_kv_heads, cfg.hd
    ps = 4
    q = jax.random.normal(jax.random.fold_in(key, 0),
                          (b, 4, cfg.num_heads, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, t, kvh, hd),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, t, kvh, hd),
                          jnp.float32)
    # scatter the dense k/v into a shuffled page pool, per batch row
    perm = np.array([[3, 0, 2, 1], [5, 7, 4, 6]], np.int32)
    pool_k = jnp.zeros((9, ps, kvh, hd), jnp.float32)
    pool_v = jnp.zeros((9, ps, kvh, hd), jnp.float32)
    for row in range(b):
        for j in range(t // ps):
            pool_k = pool_k.at[perm[row, j]].set(
                k[row, j * ps:(j + 1) * ps])
            pool_v = pool_v.at[perm[row, j]].set(
                v[row, j * ps:(j + 1) * ps])
    pk = paged_kv.PagedKV(pool_k, jnp.asarray(perm))
    pv = paged_kv.PagedKV(pool_v, jnp.asarray(perm))

    np.testing.assert_array_equal(
        np.asarray(paged_kv.materialize(pk)), np.asarray(k))
    got = attend(q, pk, pv, cfg, causal=False)
    want = attend(q, k, v, cfg, causal=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got_f = flash_attention(q, pk, pv, cfg, causal=False, chunk=8)
    want_f = flash_attention(q, k, v, cfg, causal=False, chunk=8)
    np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want_f))


def test_non_transformer_families_reject_paged():
    cfg = configs.smoke("xlstm-125m")
    model = registry.build(dataclasses.replace(cfg, dtype=jnp.float32))
    assert not model.supports_paged_kv
    params = model.init(jax.random.key(0))
    with pytest.raises(ValueError, match="does not support paged KV"):
        PagedScheduler(model, params, capacity=16, slots=1, chunk=2)


def test_sliding_window_models_reject_paged():
    """Sliding-window decode uses a rolling cache (slot = pos % window,
    engaged only when cap == window); a page-gathered view's capacity
    would silently disarm the window mask and diverge from the dense
    pool — so those models must be refused, not mis-served."""
    cfg, model, params = _setup("mixtral-8x7b")       # sliding_window=16
    assert cfg.sliding_window > 0
    assert not model.supports_paged_kv
    with pytest.raises(ValueError, match="does not support paged KV"):
        PagedScheduler(model, params, capacity=32, slots=1, chunk=2)
    # the same config without the window pages fine
    cfg2, model2, params2 = _setup("mixtral-8x7b", sliding_window=0)
    assert model2.supports_paged_kv
    got = _run(PagedScheduler(model2, params2, capacity=32, slots=2,
                              chunk=3, page_size=4),
               _requests(cfg2, [(0, 8, 3), (1, 6, 4)]))
    ref = _run(Scheduler(model2, params2, capacity=32, slots=2, chunk=3),
               _requests(cfg2, [(0, 8, 3), (1, 6, 4)]))
    assert got == ref


# ------------------------------------------------- sharded pool

SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp
from repro import configs
from repro.dist import mesh as mesh_lib, sharding as shd
from repro.models import registry
from repro.serve import PagedScheduler, Request

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
    sch = PagedScheduler(model, params, capacity=32, slots=4, chunk=3,
                         page_size=4, spmd_axes=spmd_axes)
    for r in reqs():
        sch.submit(r)
    return {r.uid: list(r.out_tokens) for r in sch.run()}

ref = run(None)

mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec((2, 4), ("data", "model")))
rules = shd.rules_for(cfg, "serve")
got = run(shd.slot_spmd_axes(rules, mesh, 4), rules, mesh)

print(json.dumps({"identical": got == ref,
                  "devices": jax.device_count(),
                  "page_axes": str(shd.page_spmd_axes(rules, mesh, 33)),
                  "spmd_axes": str(shd.slot_spmd_axes(rules, mesh, 4))}))
"""


@pytest.mark.slow
def test_sharded_paged_pool_matches_unsharded():
    """The paged slot pool under slot-axis SPMD sharding (8 fake
    devices) must not change a single token."""
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


# ------------------------------------------------- fused attention

def test_fused_attn_token_parity():
    """fused_attn=True (the Pallas page-table read) == fused_attn=False
    (the slot_view gather) == the dense pool, bitwise, on unaligned
    prompt lengths — the ISSUE 8 regression currency."""
    cfg, model, params = _setup()
    specs = [(0, 8, 6), (1, 6, 8), (2, 13, 5)]
    fused = PagedScheduler(model, params, capacity=32, slots=2, chunk=3,
                           page_size=4, fused_attn=True)
    assert fused.attn_plan is not None
    assert fused.attn_plan.backend == "paged_attn"
    assert fused.attn_plan.describe()["kv_layout"] == "paged"
    gather = PagedScheduler(model, params, capacity=32, slots=2, chunk=3,
                            page_size=4, fused_attn=False)
    assert gather.attn_plan is None
    got_f = _run(fused, _requests(cfg, specs))
    got_g = _run(gather, _requests(cfg, specs))
    dense = _run(Scheduler(model, params, capacity=32, slots=2, chunk=3),
                 _requests(cfg, specs))
    assert got_f == got_g == dense


def test_fused_attn_cell_counters_match_hand_count(tmp_path):
    """The fused read's cell counters against a hand count, and its
    tokens against the gather path and the dense pool, with a slot that
    retires mid-chunk and is re-admitted while the other stays live.

    page_size 4, 2 slots x 8 pages; a decode step that makes token i
    reads len = prompt + i - 1 positions, ceil(len / 4) cells:
      round 1 (4 steps): uid 0 (prompt 6) lens 6, 7 then retires -> 2+2;
                         uid 1 (prompt 5) lens 5..8 -> 2+2+2+2
      round 2 (4 steps): uid 2 (prompt 9) re-admitted into slot 0,
                         lens 9..11 -> 3+3+3; uid 1 lens 9..12 -> 4 x 3
      round 3 (1 step):  uid 1 len 13 -> 4
    grid = steps x slots x pages = (4 + 4 + 1) x 2 x 8."""
    cfg, model, params = _setup()
    specs = [(0, 6, 3), (1, 5, 10), (2, 9, 4)]

    def paged(fused):
        return PagedScheduler(model, params, capacity=32, slots=2,
                              chunk=4, page_size=4, fused_attn=fused)

    fused = paged(True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        got_f = _run(fused, _requests(cfg, specs))
    finally:
        jax.profiler.stop_trace()
    assert fused.chunks_run == 3
    assert fused.attn_cells_computed == (4 + 8) + (9 + 12) + 4
    assert fused.attn_cells_grid == 9 * 2 * 8
    gather = paged(False)
    got_g = _run(gather, _requests(cfg, specs))
    assert gather.attn_cells_computed == gather.attn_cells_grid == 0
    dense = _run(Scheduler(model, params, capacity=32, slots=2, chunk=4),
                 _requests(cfg, specs))
    assert got_f == got_g == dense

    # each round's counts ride its serve.round span as args
    from jax.profiler import ProfileData
    path = next(os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
                for f in fs if f.endswith(".xplane.pb"))
    rounds = sorted((ev.start_ns, dict(ev.stats))
                    for plane in ProfileData.from_file(path).planes
                    for line in plane.lines for ev in line.events
                    if ev.name == "serve.round")
    assert [(a["computed"], a["grid"]) for _, a in rounds] == [
        (12, 64), (21, 64), (4, 16)]


def test_fused_attn_auto_falls_back_on_interpret_platform(caplog):
    """'auto' must not serve wallclock through the interpret-mode
    emulation: on a platform without a real lowering it takes the
    gather path and says why."""
    import logging
    from repro.kernels import plan_matmul
    probe = plan_matmul((16 * 2, 64, 32), "decode", op="attention",
                        domain="float", kv_layout="paged")
    if not probe.interpret:
        pytest.skip("platform lowers the fused kernel natively")
    cfg, model, params = _setup()
    with caplog.at_level(logging.INFO, "repro.serve.engine"):
        sch = PagedScheduler(model, params, capacity=32, slots=2,
                             chunk=3, page_size=4)       # fused_attn auto
    assert sch.attn_plan is None
    assert any("interpret" in r.getMessage() for r in caplog.records)


def test_fused_attn_true_rejects_incapable_pools():
    """fused_attn=True must raise loudly when no backend can serve the
    pool — int8 KV carries scale pages the fused read does not consume."""
    cfg, model, params = _setup(kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="int8 KV pool"):
        PagedScheduler(model, params, capacity=32, slots=2, chunk=3,
                       page_size=4, fused_attn=True)


def test_fused_attn_auto_moe_fallback(caplog):
    """'auto' keeps the gather graph under MoE routing (top-k amplifies
    the kernel's f32 reassociation into token divergence) — logged."""
    import logging
    cfg, model, params = _setup("mixtral-8x7b", sliding_window=0)
    assert cfg.num_experts > 0
    with caplog.at_level(logging.INFO, "repro.serve.engine"):
        sch = PagedScheduler(model, params, capacity=32, slots=2,
                             chunk=3, page_size=4)
    assert sch.attn_plan is None
    assert any("MoE" in r.getMessage() for r in caplog.records)


def test_attention_plan_capability():
    """op='attention' resolves through the registry like any other op:
    pallas wins on capable platforms, dense layout and non-float
    domains have no backend and fail loudly."""
    from repro.kernels import plan_matmul
    plan = plan_matmul((32, 64, 128), "decode", op="attention",
                       domain="float", kv_layout="paged")
    assert plan.backend == "paged_attn"
    assert plan.describe()["blocks"] is None       # needs_blocks False
    ref = plan_matmul((32, 64, 128), "decode", op="attention",
                      domain="float", kv_layout="paged",
                      backend="paged_attn_ref")
    assert ref.backend == "paged_attn_ref"
    with pytest.raises(ValueError, match="no registered backend"):
        plan_matmul((32, 64, 128), "decode", op="attention",
                    domain="float", kv_layout="dense")
    with pytest.raises(ValueError, match="no registered backend"):
        plan_matmul((32, 64, 128), "decode", op="attention",
                    domain="int8", kv_layout="paged")


@pytest.mark.parametrize("lens,nan_past_live", [
    ([29, 17, 32], False),      # every slot live, page-unaligned too
    ([0, 16, 13], True),        # dead, page-aligned, mid-page
], ids=["all_live", "skips_dead_cells"])
def test_paged_attention_kernel_matches_gather_oracle(lens, nan_past_live):
    """The fused kernel's flash statistics against the gather oracle, to
    f32 round-off.  The scores are length-hd dot products that the
    kernel's per-page einsum and the oracle's gathered einsum contract in
    different orders, so even the running max ``m`` differs by about
    hd * eps(f32) relative (~2e-6 at hd=16); ``l``/``acc`` add the
    online-vs-single-pass summation order on top.

    With ``nan_past_live`` every page past a slot's last live page (all
    of a dead slot's) holds NaN for the kernel, finite values for the
    oracle (which gathers every page): the kernel must neither fetch
    them into its sums nor compute their cells, and a dead slot
    (``len = 0``) reads ``l = 0, acc = 0``."""
    from repro.kernels import paged_attention as pa
    s, kvh, rep, hd, ps, w = 3, 2, 3, 16, 8, 4
    key = jax.random.key(11)
    q = jax.random.normal(jax.random.fold_in(key, 0), (s, kvh, rep, hd),
                          jnp.float32)
    pool_shape = (1 + s * w, ps, kvh, hd)
    k_pages = jax.random.normal(jax.random.fold_in(key, 1), pool_shape,
                                jnp.float32)
    v_pages = jax.random.normal(jax.random.fold_in(key, 2), pool_shape,
                                jnp.float32)
    table = jnp.arange(1, 1 + s * w, dtype=jnp.int32).reshape(s, w)
    pos = jnp.asarray(lens, jnp.int32)
    kv = pa.PagedAttentionKV(k_pages, v_pages, table, pos)
    acc_r, m_r, l_r = pa.paged_attention_ref(q, kv)

    if nan_past_live:
        n_live = -(-pos // ps)                          # live pages a slot
        past = np.asarray(table)[np.arange(w)[None, :]
                                 >= np.asarray(n_live)[:, None]]
        kv = kv._replace(k_pages=k_pages.at[past].set(jnp.nan),
                         v_pages=v_pages.at[past].set(jnp.nan))
    acc, m, l = pa.paged_attention(q, kv, interpret=True)
    for x in (acc, m, l):
        assert np.isfinite(np.asarray(x)).all()
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l_r),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(acc_r),
                               rtol=1e-5, atol=1e-5)
    for i in np.flatnonzero(np.asarray(pos) == 0):
        assert (np.asarray(l[i]) == 0).all()
        assert (np.asarray(acc[i]) == 0).all()
        assert (np.asarray(m[i]) == pa.NEG_INF).all()


@pytest.mark.parametrize("lens,lo", [
    ([29, 17, 32], None),       # every slot live, page-unaligned too
    ([0, 16, 13], None),        # dead, page-aligned, mid-page
    ([29, 17, 32], [12, 0, 20]),     # window layer: lo skips from below
], ids=["all_live", "dead_slot", "window"])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_paged_attention_reads_its_layer_of_the_whole_pool(layer, lens,
                                                          lo):
    """The fused decode step hands every layer the whole pool, flattened
    to ``(L*P, ...)``, and the layer's page table offset by
    ``layer * P``, both from ``paged_kv.raw_pool_view``.  Through that
    view the kernel must return the statistics of the layer's own
    pages: the same as the oracle over the layer's slab alone, and
    unlike any other layer's (each layer of the pool holds different
    values), so a wrong or missing offset cannot pass."""
    from repro.kernels import paged_attention as pa
    n_layers, s, kvh, rep, hd, ps, w = 3, 3, 2, 3, 16, 8, 4
    n_pages = 1 + s * w
    key = jax.random.key(5)
    q = jax.random.normal(jax.random.fold_in(key, 0), (s, kvh, rep, hd),
                          jnp.float32)
    shape = (n_layers, n_pages, ps, kvh, hd)
    pool = paged_kv.PagedKVCache(
        jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.float32),
        jax.random.normal(jax.random.fold_in(key, 2), shape, jnp.float32))
    table = jnp.arange(1, n_pages, dtype=jnp.int32).reshape(s, w)
    k_flat, v_flat, tables = paged_kv.raw_pool_view(pool, table)
    assert k_flat.shape == (n_layers * n_pages, ps, kvh, hd)
    np.testing.assert_array_equal(np.asarray(tables[layer]),
                                  np.asarray(table) + layer * n_pages)
    pos = jnp.asarray(lens, jnp.int32)
    lo = None if lo is None else jnp.asarray(lo, jnp.int32)
    kv = pa.PagedAttentionKV(k_flat, v_flat, tables[layer], pos, lo)

    def own(l):
        return pa.paged_attention_ref(q, pa.PagedAttentionKV(
            pool.k_pages[l], pool.v_pages[l], table, pos, lo))

    acc, m, l = pa.paged_attention(q, kv, interpret=True)
    # the oracle reads the same operand, to the bit
    for a, b in zip(pa.paged_attention_ref(q, kv), own(layer)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    acc_r, m_r, l_r = own(layer)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l_r), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(acc_r),
                               rtol=1e-5, atol=1e-5)
    live = np.asarray(pos) > 0
    for other in set(range(n_layers)) - {layer}:
        acc_o = np.asarray(own(other)[0])
        assert not np.allclose(np.asarray(acc)[live], acc_o[live],
                               rtol=1e-2, atol=1e-2)
    for i in np.flatnonzero(~live):
        assert (np.asarray(l[i]) == 0).all()
        assert (np.asarray(acc[i]) == 0).all()
