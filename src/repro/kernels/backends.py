"""Built-in execution backends for the plan/registry API.

Each backend declares its capabilities (ops, arithmetic domains, packing
modes, platforms) through :class:`plan.BackendSpec` and provides one
``runner(plan, x, w)``.  The runner bodies are the exact dispatch paths
the pre-plan ``ops.ternary_matmul`` / ``ternary_matmul_int8`` /
``cim_matmul`` wrappers ran, so migrated call sites stay bitwise
identical to the old kwarg routing (pinned in tests/test_fastlane.py).

  pallas — kernels/ternary_matmul.py + kernels/cim_mac.py (VMEM
           dequant-on-load); the real TPU path, interpret mode on CPU.
           Block-tiled: the plan carries the resolved (bm, bn, bk).
  xla    — fused jnp dequant + dot.  The dry-run backend (Pallas TPU
           kernels cannot lower on the CPU host platform); handles
           layer-stacked weights.
  ref    — the pure-jnp oracles from kernels/ref.py, exposed as a
           backend so parity harnesses sweep (pallas, xla, ref) through
           one execute() call.  Lowest priority: never auto-selected
           while a production backend is capable.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import ops, ref
from . import cim_mac as _cim_mac_kernel
from . import ternary_matmul as _tm_kernel
from .plan import BackendSpec, register_backend

TRIT2_PER_BYTE = _tm_kernel.TRIT2_PER_BYTE


def _maybe_pad_trit2_k(x2, mode):
    """trit2 packing pads K to a byte multiple; zero-pad x to match."""
    k = x2.shape[-1]
    if mode == "trit2" and k % TRIT2_PER_BYTE:
        return jnp.pad(x2, ((0, 0), (0, -k % TRIT2_PER_BYTE)))
    return x2


# ------------------------------------------------------------- pallas

def _run_pallas(plan, x, w):
    if plan.op == "cim":
        return _run_cim_pallas(plan, x, w)
    bm, bn, bk = plan.blocks or (None, None, None)
    lead = x.shape[:-1]
    if plan.domain == "int8":
        xi, x_scale = ops.quantize_acts_int8(x)
        xi2 = _maybe_pad_trit2_k(xi.reshape(-1, xi.shape[-1]), w.mode)
        y = _tm_kernel.ternary_matmul_int8(
            xi2, x_scale.reshape(-1), w.data, w.scale, mode=w.mode,
            bm=bm, bn=bn, bk=bk, interpret=plan.interpret)
    else:
        x2 = _maybe_pad_trit2_k(x.reshape(-1, x.shape[-1]), w.mode)
        y = _tm_kernel.ternary_matmul(
            x2, w.data, w.scale, mode=w.mode, bm=bm, bn=bn, bk=bk,
            interpret=plan.interpret)
    return y.reshape(*lead, w.data.shape[-1])


def _run_cim_pallas(plan, x, w):
    from repro.core.packing import unpack_base3_to_planes
    from repro.core.ternary import encode_inputs, ternarize
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    xt = encode_inputs(x2, plan.num_trits)
    if isinstance(w, ops.PackedTernary):
        if w.mode != "base3":
            raise ValueError("cim plans need base3 (multi-trit) weights; "
                             f"got packing {w.mode!r}")
        w_trits = unpack_base3_to_planes(w.data, plan.num_trits)
        w_scale = w.scale
    else:
        # per-tensor scale: exactly mirrors core.cim.cim_matmul
        tt = ternarize(w, plan.num_trits)
        w_trits, w_scale = tt.trits, tt.scale
    bm, bn, bk = plan.blocks
    y_int = _cim_mac_kernel.cim_mac(xt.trits, w_trits,
                                    adc_bits=plan.adc_bits, bm=bm, bn=bn,
                                    bk=bk, interpret=plan.interpret)
    y = y_int.astype(jnp.float32) * xt.scale * w_scale
    return y.reshape(*lead, w_trits.shape[-1])


# ---------------------------------------------------------------- xla

def _run_xla(plan, x, w):
    if plan.domain == "int8":
        xi, x_scale = ops.quantize_acts_int8(x)
        return ops.ternary_matmul_int8_xla(xi, x_scale, w)
    return ops.ternary_matmul_xla(x, w)


# ---------------------------------------------------------------- ref

def _run_ref(plan, x, w):
    if plan.domain == "int8":
        xi, x_scale = ops.quantize_acts_int8(x)
        return ref.ternary_matmul_int8_ref(xi, x_scale, w.data, w.scale,
                                           w.mode)
    kpad = w.kdim - x.shape[-1]
    if kpad:          # trit2 packing pads K; zero rows contribute nothing
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, kpad)])
    return ref.ternary_matmul_ref(x, w.data, w.scale, w.mode)


# All built-ins are plan-aware for both KV layouts: the matmul kernels
# themselves are layout-agnostic (the paged pool's gather/scatter wraps
# AROUND the dense()/attention matmuls — models/paged_kv.py), so they
# declare {dense, paged} and a paged serving loop can be planned on any
# of them.  A future layout-specialized executor (e.g. a fused paged-
# attention kernel) would declare only the layouts it implements.
# All built-ins are exact-fidelity: the bitwise kernel contract.  The
# fault-injected analog path registers separately (repro.faults) and
# declares fidelity 'device' only, so it can never shadow an exact
# request and an exact backend never silently serves a device request.
_ALL_KV_LAYOUTS = frozenset({"dense", "paged"})
_EXACT = frozenset({"exact"})

register_backend(BackendSpec(
    name="pallas",
    ops=frozenset({"ternary", "cim"}),
    domains=frozenset({"float", "int8"}),
    packings=frozenset({"base3", "trit2"}),
    platforms=frozenset({"cpu", "tpu"}),     # cpu = interpret mode
    priority=100,
    runner=_run_pallas,
    needs_blocks=True,
    kv_layouts=_ALL_KV_LAYOUTS,
    fidelities=_EXACT,
))

register_backend(BackendSpec(
    name="xla",
    ops=frozenset({"ternary"}),
    domains=frozenset({"float", "int8"}),
    packings=frozenset({"base3", "trit2"}),
    platforms=frozenset({"cpu", "gpu", "tpu"}),
    priority=50,
    runner=_run_xla,
    kv_layouts=_ALL_KV_LAYOUTS,
    fidelities=_EXACT,
))

register_backend(BackendSpec(
    name="ref",
    ops=frozenset({"ternary"}),
    domains=frozenset({"float", "int8"}),
    packings=frozenset({"base3", "trit2"}),
    platforms=frozenset({"cpu", "gpu", "tpu"}),
    priority=10,
    runner=_run_ref,
    kv_layouts=_ALL_KV_LAYOUTS,
    fidelities=_EXACT,
))

# ----------------------------------------------------- paged attention
# The layout-specialized executors the comment above reserved: the fused
# paged-attention Pallas kernel consumes the page table in-kernel (no
# gathered dense copy), its gather-based XLA oracle materializes one.
# Both are float-KV only (int8 pools carry scale pages the fused read
# does not consume yet) and packing-agnostic — attention has no packed
# weight operand, so every packing mode a model runs under is admissible.
from . import paged_attention as _paged_attention  # noqa: E402

register_backend(BackendSpec(
    name="paged_attn",
    ops=frozenset({"attention"}),
    domains=frozenset({"float"}),
    packings=frozenset({"base3", "trit2"}),
    platforms=frozenset({"cpu", "tpu"}),     # cpu = interpret mode
    priority=100,
    runner=_paged_attention.run_pallas,
    kv_layouts=frozenset({"paged"}),
    fidelities=_EXACT,
))

register_backend(BackendSpec(
    name="paged_attn_ref",
    ops=frozenset({"attention"}),
    domains=frozenset({"float"}),
    packings=frozenset({"base3", "trit2"}),
    platforms=frozenset({"cpu", "gpu", "tpu"}),
    priority=10,
    runner=_paged_attention.run_gather,
    kv_layouts=frozenset({"paged"}),
    fidelities=_EXACT,
))

# ----------------------------------------------- grouped expert matmul
# op='ternary_grouped': expert-sorted rows against a stack of packed experts
# (kernels/grouped_matmul.py), int8 domain only.  The Pallas kernel
# reads each expert's codes at most once a call; the XLA oracle
# materializes one unpacked expert per tile.
from . import grouped_matmul as _grouped  # noqa: E402

register_backend(BackendSpec(
    name="pallas_gmm",
    ops=frozenset({"ternary_grouped"}),
    domains=frozenset({"int8"}),
    packings=frozenset({"base3", "trit2"}),
    platforms=frozenset({"cpu", "tpu"}),     # cpu = interpret mode
    priority=100,
    runner=_grouped.run_pallas,
    kv_layouts=_ALL_KV_LAYOUTS,
    fidelities=_EXACT,
))

register_backend(BackendSpec(
    name="ref_gmm",
    ops=frozenset({"ternary_grouped"}),
    domains=frozenset({"int8"}),
    packings=frozenset({"base3", "trit2"}),
    platforms=frozenset({"cpu", "gpu", "tpu"}),
    priority=10,
    runner=_grouped.run_ref,
    kv_layouts=_ALL_KV_LAYOUTS,
    fidelities=_EXACT,
))

# The device-fidelity backend (fault-injected analog MAC: sampled
# conductances + ADC transfer over a seeded FaultModel) registers from
# repro.faults.backend — imported last so the built-in registrations
# above are already in place when it joins the registry.
from repro.faults import backend as _faults_backend  # noqa: E402,F401
