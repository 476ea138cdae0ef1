"""Decode-path fast lane: shape-adaptive block dispatch and the int8
MXU domain.

Contracts pinned here:
  * decode shapes (M = 1/4/8) agree pallas == xla == oracle in both
    packing modes, float and int8 domains (int8 bitwise);
  * adaptive blocking cuts padded-M FLOP waste >= 8x vs fixed bm=128
    for batch <= 16 decode shapes.

Plus the ExecutionPlan migration contract (ISSUE 4 acceptance): every
(backend, domain, packing) dispatch cell is bitwise identical between
the deprecated kwarg routing and plan_matmul/execute, and plan
resolution under jit is cache-hit free of re-probing.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (execute, ops, plan_cache_clear, plan_cache_info,
                           plan_matmul, ref, shape_of)
from repro.kernels.ternary_matmul import (DEFAULT_BLOCKS, SUBLANE,
                                          _round_up, select_block_shapes,
                                          ternary_matmul_int8)

jax.config.update("jax_platform_name", "cpu")


# ------------------------------------------------------- block selection

class TestSelectBlockShapes:
    def test_prefill_keeps_mxu_tiles(self):
        assert select_block_shapes(512, 2048, 2048) == DEFAULT_BLOCKS
        assert select_block_shapes(128, 4096, 1024) == DEFAULT_BLOCKS

    @pytest.mark.parametrize("m", [1, 4, 8, 16, 64])
    def test_decode_shrinks_bm_to_sublane_multiple(self, m):
        bm, bn, bk = select_block_shapes(m, 2048, 2048)
        assert bm == -(-m // SUBLANE) * SUBLANE
        assert bm < 128 and bk >= 512     # deeper K tile for skinny M
        assert bn % 128 == 0 and bk % 128 == 0

    def test_bk_clamped_to_k_extent(self):
        _, _, bk = select_block_shapes(4, 256, 512)
        assert bk == 256                  # round_up(256, 128), not 1024

    def test_trit2_packed_tile_stays_whole(self):
        _, _, bk = select_block_shapes(8, 4096, 4096, "trit2")
        assert bk % 4 == 0

    def test_vmem_budget_shrinks_bk(self):
        _, _, bk = select_block_shapes(8, 65536, 128,
                                       vmem_budget_bytes=256 * 1024)
        assert bk <= 512

    def test_int8_domain_uses_int8_sublane(self):
        # int8 second-to-last-dim tile is 32 rows, not the f32 8
        bm, _, _ = select_block_shapes(8, 2048, 2048, domain="int8")
        assert bm == 32
        assert select_block_shapes(128, 2048, 2048,
                                   domain="int8") == DEFAULT_BLOCKS


# ------------------------------------------- decode shapes, three backends

DECODE_MS = [1, 4, 8]


class TestDecodeShapeEquivalence:
    @pytest.mark.parametrize("mode", ["base3", "trit2"])
    @pytest.mark.parametrize("m", DECODE_MS)
    def test_float_pallas_xla_oracle(self, m, mode):
        key = jax.random.PRNGKey(m)
        x = jax.random.normal(key, (m, 384), jnp.float32)
        w = 0.02 * jax.random.normal(jax.random.fold_in(key, 1), (384, 256))
        pw = ops.pack_weights(w, mode)
        mkn = shape_of(x, pw)
        y_pallas = execute(plan_matmul(mkn, packing=mode, backend="pallas",
                                       interpret=True), x, pw)  # auto blocks
        y_xla = execute(plan_matmul(mkn, packing=mode, backend="xla"),
                        x, pw)
        y_oracle = ref.ternary_matmul_ref(x, pw.data, pw.scale, mode)
        np.testing.assert_allclose(np.asarray(y_pallas), np.asarray(y_oracle),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_oracle),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("mode", ["base3", "trit2"])
    @pytest.mark.parametrize("m", DECODE_MS)
    def test_int8_domain_bitwise(self, m, mode):
        """Integer accumulation is exact: all three backends bit-match."""
        key = jax.random.PRNGKey(100 + m)
        x = jax.random.normal(key, (m, 384), jnp.float32)
        w = 0.02 * jax.random.normal(jax.random.fold_in(key, 1), (384, 256))
        pw = ops.pack_weights(w, mode)
        mkn = shape_of(x, pw)
        y_pallas = execute(plan_matmul(mkn, packing=mode, domain="int8",
                                       backend="pallas", interpret=True),
                           x, pw)
        y_xla = execute(plan_matmul(mkn, packing=mode, domain="int8",
                                    backend="xla"), x, pw)
        xi, xs = ops.quantize_acts_int8(x)
        y_oracle = ref.ternary_matmul_int8_ref(xi, xs, pw.data, pw.scale,
                                               mode)
        np.testing.assert_array_equal(np.asarray(y_pallas),
                                      np.asarray(y_xla))
        np.testing.assert_array_equal(np.asarray(y_xla),
                                      np.asarray(y_oracle))

    @pytest.mark.parametrize("mode", ["base3", "trit2"])
    def test_int8_domain_via_dispatch_and_close_to_float(self, mode):
        key = jax.random.PRNGKey(7)
        x = jax.random.normal(key, (8, 256), jnp.float32)
        w = 0.02 * jax.random.normal(jax.random.fold_in(key, 1), (256, 128))
        pw = ops.pack_weights(w, mode)
        mkn = shape_of(x, pw)
        y_int = execute(plan_matmul(mkn, packing=mode, domain="int8",
                                    backend="xla"), x, pw)
        y_f = execute(plan_matmul(mkn, packing=mode, backend="xla"), x, pw)
        rel = float(jnp.linalg.norm(y_int - y_f) /
                    (jnp.linalg.norm(y_f) + 1e-9))
        assert rel < 0.02, rel            # 7-bit activations: ~1% error
        with pytest.raises(ValueError, match="domain"):
            plan_matmul(mkn, packing=mode, domain="INT8")

    def test_int8_kernel_explicit_blocks_match_auto(self):
        key = jax.random.PRNGKey(9)
        x = jax.random.normal(key, (5, 200), jnp.float32)
        w = 0.02 * jax.random.normal(jax.random.fold_in(key, 1), (200, 96))
        pw = ops.pack_weights(w, "trit2")
        mkn = shape_of(x, pw)
        auto = execute(plan_matmul(mkn, packing="trit2", domain="int8",
                                   backend="pallas", interpret=True), x, pw)
        pinned = execute(plan_matmul(mkn, packing="trit2", domain="int8",
                                     backend="pallas", interpret=True,
                                     bm=8, bn=32, bk=64), x, pw)
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(pinned))


class TestXlaStackedWeights:
    def test_trit2_kpad_slices_contraction_axis(self):
        """Regression: layer-stacked (L, K/4, N) trit2 weights with K not
        a byte multiple — the K-padding slice must hit the K axis, not the
        leading layer axis."""
        key = jax.random.PRNGKey(3)
        k = 102                            # pads to 104 trits
        w = 0.02 * jax.random.normal(key, (2, k, 48))
        pw = ops.pack_weights(w, "trit2")
        assert pw.data.shape == (2, 26, 48)
        x = jax.random.normal(jax.random.fold_in(key, 1), (4, k))
        y = ops.ternary_matmul_xla(x, pw)          # (2, 4, 48)
        assert y.shape == (2, 4, 48)
        for layer in range(2):
            pl_ = ops.PackedTernary(pw.data[layer], pw.scale[layer], "trit2")
            np.testing.assert_allclose(np.asarray(y[layer]),
                                       np.asarray(ops.ternary_matmul_xla(
                                           x, pl_)), rtol=1e-6, atol=1e-6)


# --------------------------------------- plan API: old-vs-new dispatch

class TestPlanDispatchParity:
    """Bitwise parity of the deprecated kwarg routing vs plan/execute
    across EVERY (backend, domain, packing) dispatch cell."""

    @pytest.mark.parametrize("backend", ["pallas", "xla"])
    @pytest.mark.parametrize("domain", ["float", "int8"])
    @pytest.mark.parametrize("mode", ["base3", "trit2"])
    def test_cell_bitwise_identical(self, backend, domain, mode):
        key = jax.random.PRNGKey(42)
        x = jax.random.normal(key, (7, 384), jnp.float32)
        w = 0.02 * jax.random.normal(jax.random.fold_in(key, 1), (384, 256))
        pw = ops.pack_weights(w, mode)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            y_old = ops.ternary_matmul(x, pw, backend=backend,
                                       domain=domain)
        plan = plan_matmul(shape_of(x, pw), backend=backend, domain=domain,
                           packing=mode)
        np.testing.assert_array_equal(np.asarray(y_old),
                                      np.asarray(execute(plan, x, pw)))

    def test_pinned_blocks_parity(self):
        x, w = (jax.random.normal(jax.random.PRNGKey(1), (5, 200)),
                0.02 * jax.random.normal(jax.random.PRNGKey(2), (200, 96)))
        pw = ops.pack_weights(w, "trit2")
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            y_old = ops.ternary_matmul_int8(x, pw, interpret=True,
                                            bm=8, bn=32, bk=64)
        plan = plan_matmul(shape_of(x, pw), packing="trit2", domain="int8",
                           backend="pallas", interpret=True,
                           bm=8, bn=32, bk=64)
        assert plan.blocks == (8, 32, 64)
        np.testing.assert_array_equal(np.asarray(y_old),
                                      np.asarray(execute(plan, x, pw)))

    def test_plan_blocks_equal_adaptive_selection(self):
        # plan resolution hoists the same shape-adaptive choice the
        # kernel used to make per call (int8 lane uses its own sublane)
        p_f = plan_matmul((8, 1024, 1024), backend="pallas")
        p_i = plan_matmul((8, 1024, 1024), backend="pallas", domain="int8")
        assert p_f.blocks == select_block_shapes(8, 1024, 1024, "base3")
        assert p_i.blocks == select_block_shapes(8, 1024, 1024, "base3",
                                                 domain="int8")

    def test_plan_cache_hits_under_jit(self):
        from repro.core.cim_linear import CIMConfig, linear
        cfg = CIMConfig(mode="ternary", packing="base3").resolve()
        w = 0.02 * jax.random.normal(jax.random.PRNGKey(0), (128, 64))
        pw = ops.pack_weights(w, "base3")
        step = jax.jit(lambda x: linear(x, pw, cfg))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 128))
        plan_cache_clear()
        step(x)                               # trace: one resolution
        misses = plan_cache_info().misses
        assert misses == 1
        step(x + 1.0)                         # warm executable: no resolve
        assert plan_cache_info().misses == misses
        step(jax.random.normal(jax.random.PRNGKey(2), (6, 128)))
        assert plan_cache_info().misses == misses + 1   # new shape only


# ------------------------------------------------------- blocking waste

def padded_flops(m: int, k: int, n: int, blocks) -> int:
    """MAC-FLOPs the grid actually issues: every dim padded up to its
    block multiple (the kernel zero-pads and the MXU multiplies zeros)."""
    bm, bn, bk = blocks
    return 2 * _round_up(m, bm) * _round_up(k, bk) * _round_up(n, bn)


class TestWallclockMetrics:
    def test_decode_flop_waste_reduction_ge_8x(self):
        for m in (1, 4, 8, 16):
            for mode in ("base3", "trit2"):
                adaptive = select_block_shapes(m, 1024, 1024, mode)
                fixed = DEFAULT_BLOCKS
                red = (padded_flops(m, 1024, 1024, fixed)
                       / padded_flops(m, 1024, 1024, adaptive))
                assert red >= 8.0, (m, mode, red)
