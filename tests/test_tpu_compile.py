"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

Each test compiles one kernel for one chip of a described ``v5e:2x2``
topology: the TPU compiler is installed here and refuses what the chip
would refuse (tile layouts, integer ops Mosaic cannot lower, VMEM).
Nothing runs.  Widths are internlm2-1.8b's: d_model 2048 (K), d_ff 8192
(N), decode M=8 and prefill M=512; the attention read uses the serving
pool geometry of ``chip_smoke.py``.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports
this file.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import cim_mac, paged_attention as pa
from repro.kernels import grouped_matmul as gm
from repro.kernels import ternary_matmul as tm

K, N = 2048, 8192
PHASE_M = {"decode": 8, "prefill": 512}


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # entries compiled for a described chip cannot be read back without
    # one: keep them out of any persistent cache this process has on
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()    # the kernel is there
    return compiled


@pytest.mark.parametrize("phase", sorted(PHASE_M))
@pytest.mark.parametrize("mode", ["base3", "trit2"])
def test_ternary_matmul_compiles(spec, mode, phase):
    m, kw = PHASE_M[phase], K if mode == "base3" else K // tm.TRIT2_PER_BYTE
    _compile(functools.partial(tm.ternary_matmul, mode=mode),
             spec((m, K), jnp.float32), spec((kw, N), jnp.uint8),
             spec((N,), jnp.float32))


@pytest.mark.parametrize("phase", sorted(PHASE_M))
@pytest.mark.parametrize("mode", ["base3", "trit2"])
def test_ternary_matmul_bf16_compiles(spec, mode, phase):
    # the model's activations: trits decode to bf16, one MXU pass
    m, kw = PHASE_M[phase], K if mode == "base3" else K // tm.TRIT2_PER_BYTE
    _compile(functools.partial(tm.ternary_matmul, mode=mode),
             spec((m, K), jnp.bfloat16), spec((kw, N), jnp.uint8),
             spec((N,), jnp.float32))


@pytest.mark.parametrize("phase", sorted(PHASE_M))
@pytest.mark.parametrize("mode", ["base3", "trit2"])
def test_ternary_matmul_int8_compiles(spec, mode, phase):
    m, kw = PHASE_M[phase], K if mode == "base3" else K // tm.TRIT2_PER_BYTE
    _compile(functools.partial(tm.ternary_matmul_int8, mode=mode),
             spec((m, K), jnp.int8), spec((m,), jnp.float32),
             spec((kw, N), jnp.uint8), spec((N,), jnp.float32))


def _attention_operands(spec):
    # 8 slots x 16 pages of 16 rows, 8 KV heads x 2 queries, hd 128
    s, w, ps, kvh, rep, hd = 8, 16, 16, 8, 2, 128
    pool = spec((1 + s * w, ps, kvh, hd), jnp.bfloat16)
    return (spec((s, kvh, rep, hd), jnp.bfloat16),
            pa.PagedAttentionKV(pool, pool, spec((s, w), jnp.int32),
                                spec((s,), jnp.int32)))


def test_paged_attention_compiles(spec):
    _compile(pa.paged_attention, *_attention_operands(spec))


def test_paged_attention_keeps_its_name(spec):
    """Called inside a loop, as the decode step calls it once a layer,
    the kernel's custom call is still named ``paged_attention``: the
    name the device trace shows it under (unnamed, ``closed_call``)."""
    def layers(q, kv):
        def layer(carry, _):
            acc, _, _ = pa.paged_attention(q, kv)
            return carry + acc.sum(), None
        return jax.lax.scan(layer, 0.0, None, length=2)[0]
    text = _compile(layers, *_attention_operands(spec)).as_text()
    calls = [line.split(" = ", 1)[0].split()[-1]
             for line in text.splitlines()
             if "tpu_custom_call" in line and " = " in line]
    assert calls and all(c.startswith("%paged_attention.") for c in calls)


def test_windowed_paged_attention_compiles(spec):
    q, kv = _attention_operands(spec)
    _compile(pa.paged_attention, q, kv._replace(lo=spec((8,), jnp.int32)))


# Mellum2's expert matrices (w1/w3 2304 x 896, w2 896 x 2304) over its 64
# experts: a decode step's 192 routed rows in 70 tiles of 32, a 3072-token
# prefill's 24,576 in 256 tiles of 128
GMM_TILES = {"decode": (70, 32), "prefill": (256, 128)}


@pytest.mark.parametrize("phase", sorted(GMM_TILES))
@pytest.mark.parametrize("k,n", [(2304, 896), (896, 2304)])
@pytest.mark.parametrize("mode", ["base3", "trit2"])
def test_grouped_matmul_compiles(spec, mode, k, n, phase):
    tiles, bm = GMM_TILES[phase]
    kw = k if mode == "base3" else k // tm.TRIT2_PER_BYTE
    text = _compile(functools.partial(gm.ternary_gmm_int8, mode=mode),
                    spec((tiles * bm, k), jnp.int8),
                    spec((tiles * bm,), jnp.float32),
                    spec((64, kw, n), jnp.uint8), spec((64, n), jnp.float32),
                    spec((tiles,), jnp.int32), spec((1,), jnp.int32)
                    ).as_text()
    # the name the trace shows it under, apart from the dense kernel's
    assert "%ternary_gmm_int8" in text and "%ternary_matmul_int8" not in text


def test_cim_mac_compiles(spec):
    _compile(cim_mac.cim_mac, spec((5, 8, K), jnp.int8),
             spec((5, K, K), jnp.int8))
