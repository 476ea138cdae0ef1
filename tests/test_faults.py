"""Device-fidelity serving: fault models, the ``device`` backend, the
fidelity plan axis, and the restore-scrub repair channel.

Covers the PR's contracts:

  * ``confusion_from_yields`` rows sum to 1 (yields validated/clamped);
  * empirical injection rate matches ``expected_trit_error_rate``;
  * fault injection is bitwise-deterministic per campaign key;
  * the ``device`` backend occupies exactly the device-fidelity cells
    of the capability lattice, and every unsupported fidelity request
    fails loudly (never a silent fall-through);
  * noise-aware routing: ``device`` requests resolve exact for prefill;
  * exact-fidelity serving is untouched by the fault machinery (inert
    hooks, unchanged transfer contract, bitwise-identical tokens);
  * the scrub gate: drift degrades the served weights measurably and
    the restore-scrub REPAIRS them (bounded by 1 - yield, not a no-op);
  * a classifier served through the device path at the MEASURED TL
    restore yield keeps its accuracy within ``ACC_DROP_MAX`` of the
    exact kernels.
"""
import dataclasses
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core.cim_linear import CIMConfig, ternarize_params
from repro.core.error_injection import (confusion_from_yields,
                                        expected_trit_error_rate,
                                        inject_trit_errors)
from repro.models import registry
from repro.serve import Request, Scheduler
from repro import faults
from repro import kernels
from repro.kernels import (execute, get_backend, plan_matmul,
                           resolve_backend, route_fidelity, shape_of)

YIELDS = (0.97, 0.995, 0.96)
# most accuracy the device path may lose against the exact kernels
ACC_DROP_MAX = 0.05


# ------------------------------------------------- confusion channel

def test_confusion_rows_sum_to_one():
    c = confusion_from_yields(jnp.asarray(YIELDS))
    assert c.shape == (3, 3)
    assert jnp.allclose(c.sum(axis=-1), 1.0, atol=1e-6)
    # diagonal is the per-state yield
    assert jnp.allclose(jnp.diagonal(c), jnp.asarray(YIELDS), atol=1e-6)


def test_confusion_validates_and_clamps():
    # Monte-Carlo yields at small sample counts can exceed 1 by eps;
    # clamped instead of producing negative error probabilities
    c = confusion_from_yields(jnp.asarray([1.0 + 1e-6, 0.5, -0.25]))
    assert jnp.allclose(c.sum(axis=-1), 1.0, atol=1e-6)
    assert float(c[0, 1]) == pytest.approx(0.0, abs=1e-6)
    assert float(c[2, 2]) == pytest.approx(0.0, abs=1e-6)
    with pytest.raises(ValueError, match="shape"):
        confusion_from_yields(jnp.asarray([0.9, 0.9]))
    with pytest.raises(ValueError, match="finite"):
        confusion_from_yields(jnp.asarray([0.9, float("nan"), 0.9]))


def test_empirical_injection_rate_matches_expected():
    prior = (0.25, 0.5, 0.25)
    key = jax.random.key(0)
    trits = (jax.random.choice(key, jnp.asarray([-1, 0, 1], jnp.int8),
                               (400_000,), p=jnp.asarray(prior)))
    out = inject_trit_errors(trits, jnp.asarray(YIELDS),
                             jax.random.key(1))
    got = float(jnp.mean(out != trits))
    want = expected_trit_error_rate(YIELDS, prior)
    assert got == pytest.approx(want, rel=0.08)


def test_injection_bitwise_deterministic_per_key():
    trits = jax.random.randint(jax.random.key(2), (64, 128), -1, 2,
                               dtype=jnp.int32).astype(jnp.int8)
    y = jnp.asarray(YIELDS)
    a = inject_trit_errors(trits, y, jax.random.key(7))
    b = inject_trit_errors(trits, y, jax.random.key(7))
    c = inject_trit_errors(trits, y, jax.random.key(8))
    assert jnp.array_equal(a, b)
    assert not jnp.array_equal(a, c)


def test_fault_model_channels_deterministic():
    fm = faults.FaultModel(seed=3, restore_yield=YIELDS, stuck_rate=0.01)
    fm2 = faults.FaultModel(seed=3, restore_yield=YIELDS, stuck_rate=0.01)
    trits = jax.random.randint(jax.random.key(4), (5, 64, 32), -1, 2,
                               dtype=jnp.int32).astype(jnp.int8)
    assert jnp.array_equal(fm.fault_trits(trits, "w"),
                           fm2.fault_trits(trits, "w"))
    assert jnp.array_equal(fm.conductance_multiplier(trits, "g"),
                           fm2.conductance_multiplier(trits, "g"))
    # a different campaign seed is a different device instance
    fm3 = dataclasses.replace(fm, seed=4)
    assert not jnp.array_equal(fm.fault_trits(trits, "w"),
                               fm3.fault_trits(trits, "w"))


# ------------------------------------------- fidelity capability axis

def test_device_backend_capability_cells():
    assert "device" in kernels.backend_names()
    spec = get_backend("device")
    assert spec.fidelities == frozenset({"device"})
    assert spec.ops == frozenset({"ternary"})
    # auto under a device request resolves the device backend...
    assert resolve_backend("ternary", "auto",
                           fidelity="device").name == "device"
    # ...and never shadows an exact request, whatever its priority
    assert resolve_backend("ternary", "auto",
                           fidelity="exact").name != "device"


def test_unsupported_fidelity_fails_loudly():
    with pytest.raises(ValueError, match="does not support fidelity"):
        resolve_backend("ternary", "pallas", fidelity="device")
    with pytest.raises(ValueError, match="does not support"):
        resolve_backend("cim", "device", fidelity="device")
    with pytest.raises(ValueError, match="no registered backend"):
        resolve_backend("cim", "auto", fidelity="device")
    with pytest.raises(ValueError, match="unknown fidelity"):
        plan_matmul((4, 64, 32), fidelity="analog")
    # float mode has no packed weights for the device model to fault
    with pytest.raises(ValueError, match="device"):
        CIMConfig(mode="float", fidelity="device").resolve()


def test_route_fidelity_prefill_exact():
    assert route_fidelity("device", "prefill") == "exact"
    assert route_fidelity("device", "decode") == "device"
    assert route_fidelity("device", "auto") == "device"
    assert route_fidelity("exact", "prefill") == "exact"
    plan = plan_matmul((4, 64, 32), "prefill", fidelity="device")
    assert plan.fidelity == "exact" and plan.backend != "device"
    plan = plan_matmul((4, 64, 32), "decode", fidelity="device")
    assert plan.fidelity == "device" and plan.backend == "device"
    assert plan.adc_bits == 5 and plan.num_trits == 5


@pytest.mark.parametrize("packing", ["base3", "trit2"])
def test_device_execute_deterministic_and_correlated(packing):
    kx, kw = jax.random.split(jax.random.key(5))
    x = jax.random.normal(kx, (8, 64))
    w = jax.random.normal(kw, (64, 48))
    pw = kernels.ops.pack_weights(w, packing)
    exact = execute(plan_matmul(shape_of(x, pw), packing=packing), x, pw)

    prev = faults.set_fault_model(faults.FaultModel(
        seed=0, restore_yield=YIELDS))
    try:
        plan = plan_matmul(shape_of(x, pw), packing=packing,
                           fidelity="device")
        y1 = execute(plan, x, pw)
        y2 = execute(plan, x, pw)
    finally:
        faults.set_fault_model(prev)
    assert jnp.array_equal(y1, y2)          # bitwise per campaign
    assert bool(jnp.all(jnp.isfinite(y1)))
    corr = jnp.corrcoef(y1.ravel(), exact.ravel())[0, 1]
    assert float(corr) > 0.8                # analog, but the same MAC

    # ... and close enough that a trained classifier served through it
    # at the measured restore yield keeps its accuracy
    from benchmarks.common import eval_mlp
    task, mlp = _classifier()

    def kernel_mm(fidelity):
        def mm(x, w):
            pw = kernels.ops.pack_weights(w, packing)
            return execute(plan_matmul(shape_of(x, pw), packing=packing,
                                       fidelity=fidelity), x, pw)
        return mm

    prev = faults.set_fault_model(faults.measured_fault_model(num_mc=1024))
    try:
        acc = {f: eval_mlp(mlp, task, matmul=kernel_mm(f), batches=2,
                           batch=256) for f in ("exact", "device")}
    finally:
        faults.set_fault_model(prev)
    assert acc["device"] >= acc["exact"] - ACC_DROP_MAX, acc


@functools.lru_cache(maxsize=None)
def _classifier():
    """The paper's small classifier (CIFAR-10 stand-in), trained once."""
    from benchmarks.common import train_mlp
    from repro.data import ClassTaskConfig
    task = ClassTaskConfig(num_classes=10, dim=128, snr=2.5, seed=0)
    return task, train_mlp(task)


# ------------------------------------------------- scrub/drift repair

def _packed_tree(packing="base3"):
    w1 = jax.random.normal(jax.random.key(6), (64, 96))
    w2 = jax.random.normal(jax.random.key(7), (96, 64))
    cfg = CIMConfig(mode="ternary", packing=packing)
    return ternarize_params({"w1": w1, "w2": w2}, cfg)


def test_drift_compounds_and_scrub_repairs():
    pristine = _packed_tree()
    key = jax.random.key(9)
    served = pristine
    rates = []
    for chunk in range(6):
        served = faults.disturb_packed_params(
            served, 0.01, jax.random.fold_in(key, chunk))
        rates.append(faults.packed_trit_error_rate(served, pristine))
    # degradation is measurable and compounds monotonically
    assert rates[0] > 0.0
    assert rates[-1] > 2.5 * rates[0]
    # scrub repairs to the restore bound, independent of drift history
    scrubbed = faults.scrub_packed_params(pristine, YIELDS,
                                          jax.random.key(10))
    post = faults.packed_trit_error_rate(scrubbed, pristine)
    assert post < rates[-1]
    bound = expected_trit_error_rate(YIELDS, (1 / 3, 1 / 3, 1 / 3))
    assert post <= 2.0 * bound
    # the scrub is a real restore, not a no-op copy: at yield < 1 the
    # repaired tree is NOT bitwise pristine
    assert post > 0.0
    # ideal restore (yield=None) IS the pristine tree
    ideal = faults.scrub_packed_params(pristine, None, jax.random.key(10))
    assert faults.packed_trit_error_rate(ideal, pristine) == 0.0


@pytest.mark.parametrize("packing", ["base3", "trit2"])
def test_packed_trit_roundtrip(packing):
    tree = _packed_tree(packing)
    leaf = tree["w1"]
    trits = faults.packed_to_trits(leaf)
    back = faults.trits_to_packed(trits, leaf)
    assert jnp.array_equal(back.data, leaf.data)
    assert back.mode == leaf.mode


# ------------------------------------------------- serving integration

def _requests(cfg, count=3, max_new=6):
    key = jax.random.key(11)
    return [Request(uid=i,
                    prompt=jax.random.randint(jax.random.fold_in(key, i),
                                              (8,), 0, cfg.vocab_size),
                    max_new=max_new)
            for i in range(count)]


def _smoke(arch="internlm2-1.8b"):
    cfg = dataclasses.replace(configs.smoke(arch), dtype=jnp.float32)
    model = registry.build(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def test_exact_serving_parity_and_inert_hooks():
    cfg, model, params = _smoke()
    cim = CIMConfig(mode="ternary", packing="base3")
    tern = ternarize_params(params, cim)
    # exact configs resolve identically for both phases: same frozen
    # config -> same jit cache entry -> bitwise-identical serving
    assert cim.resolve() == cim.resolve(phase="prefill")

    runs = []
    for _ in range(2):
        s = Scheduler(model, tern, capacity=64, slots=2, chunk=4, cim=cim)
        for r in _requests(cfg):
            s.submit(r)
        done = {r.uid: r.out_tokens for r in s.run()}
        # fault machinery is inert under exact fidelity
        assert s._fault_serving is False
        assert s._round_extras() == ()
        assert s.adc_clip_lo == 0 and s.adc_clip_hi == 0
        assert s.scrubs_run == 0
        # unchanged transfer contract: one device->host sync per chunk
        assert s.host_transfers == s.chunks_run
        runs.append(done)
    assert runs[0] == runs[1]


@pytest.mark.slow
def test_device_serving_scrub_and_transfer_contract():
    cfg, model, params = _smoke()
    cim = CIMConfig(mode="ternary", packing="base3")
    tern = ternarize_params(params, cim)
    prev = faults.set_fault_model(faults.FaultModel(
        seed=0, restore_yield=YIELDS, drift_rate=0.002))
    try:
        cimd = dataclasses.replace(cim, fidelity="device")
        s = Scheduler(model, tern, capacity=64, slots=2, chunk=2,
                      cim=cimd, scrub_every=2)
        assert s.cim.backend == "device" and s.cim.fidelity == "device"
        assert s.cim_prefill.fidelity == "exact"
        assert s.cim_prefill.backend != "device"
        for r in _requests(cfg, count=2, max_new=4):
            s.submit(r)
        done = s.run()
        assert all(len(r.out_tokens) == 4 for r in done)
        # the ADC probe scalars ride the existing per-chunk transfer
        assert s.host_transfers == s.chunks_run
        assert s.scrubs_run >= 1
        # served weights sit at the restore bound, not bitwise pristine
        err = faults.packed_trit_error_rate(s.params, s._params_pristine)
        bound = expected_trit_error_rate(YIELDS, (1 / 3, 1 / 3, 1 / 3))
        assert 0.0 < err <= 3.0 * bound
    finally:
        faults.set_fault_model(prev)


@pytest.mark.slow
def test_dryrun_device_fidelity_cell(tmp_path):
    """The launcher smoke cell: a device-fidelity decode cell lowers and
    compiles against the production mesh (subprocess — dryrun pins 512
    fake devices before jax initializes and must never be imported)."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("XLA_FLAGS", None)        # dryrun sets its own device count
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "internlm2-1.8b", "--shape", "decode_32k",
         "--packed", "base3", "--fidelity", "device",
         "--continuous", "8", "--tag", "fidelity-smoke",
         "--out-dir", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    out_files = [f for f in os.listdir(tmp_path)
                 if f.endswith("fidelity-smoke.json")]
    assert len(out_files) == 1
    with open(tmp_path / out_files[0]) as f:
        cell = json.load(f)
    assert cell["cim_backend"] == "device"
    assert cell["cim_fidelity"] == "device"
    assert cell["compile_s"] > 0
