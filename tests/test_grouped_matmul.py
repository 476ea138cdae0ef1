"""The grouped int8 expert matmul (``op='ternary_grouped'``) and the
dropless MoE serving path built on it."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cim_linear import CIMConfig
from repro.core.packing import pack_base3, pack_trits2
from repro.kernels import execute, plan_matmul, shape_of
from repro.kernels import grouped_matmul as G
from repro.kernels import ternary_matmul as tm
from repro.kernels.ops import PackedTernary, quantize_acts_int8
from repro.models import moe
from repro.models.config import ModelConfig


def _experts(packing, e, k, n, seed=0):
    rng = np.random.default_rng(seed)
    if packing == "trit2":
        c = rng.integers(-1, 2, (e, k, n)).astype(np.int8)
        data = jax.vmap(pack_trits2)(jnp.asarray(c))
    else:
        c = rng.integers(-121, 122, (e, k, n))
        data = pack_base3(jnp.asarray(c))
    scale = jnp.asarray(rng.uniform(0.5, 1.5, (e, n)), jnp.float32)
    return PackedTernary(data, scale, packing)


def _layout(counts, bm, extra_tiles=2):
    """Tile map of expert groups of ``counts`` rows, plus idle tiles."""
    tiles = [e for e, c in enumerate(counts) for _ in range(-(-c // bm))]
    live = len(tiles)
    tiles += [tiles[-1]] * extra_tiles
    starts = np.cumsum([0] + [-(-c // bm) * bm for c in counts])[:-1]
    return (jnp.asarray(tiles, jnp.int32), jnp.asarray([live], jnp.int32),
            starts)


@pytest.mark.parametrize("packing", ["trit2", "base3"])
@pytest.mark.parametrize("k,n", [(256, 384), (128, 256)])
def test_grouped_kernel_equals_per_expert_matmul(packing, k, n):
    """Every routed row of the kernel (interpret mode) and of the XLA
    oracle equals ``ternary_matmul_int8`` run on its expert's rows,
    bitwise; experts 1 and 4 get no row, expert 2 several tiles."""
    e, bm = 5, 32
    w = _experts(packing, e, k, n)
    counts = [3, 0, 70, 1, 0]
    te, nt, starts = _layout(counts, bm)
    rng = np.random.default_rng(1)
    x = np.zeros((te.shape[0] * bm, k), np.float32)
    for c, a in zip(counts, starts):
        x[a:a + c] = rng.normal(size=(c, k))
    x = jnp.asarray(x)
    gw = G.GroupedTernary(w, te, nt)
    got = execute(plan_matmul(shape_of(x, gw), op="ternary_grouped",
                              domain="int8", packing=packing), x, gw)
    ref = execute(plan_matmul(shape_of(x, gw), op="ternary_grouped",
                              domain="int8", packing=packing,
                              backend="ref_gmm"), x, gw)
    xi, xs = quantize_acts_int8(x)
    for ex, (c, a) in enumerate(zip(counts, starts)):
        if not c:
            continue
        want = tm.ternary_matmul_int8(xi[a:a + c], xs[a:a + c],
                                      w.data[ex], w.scale[ex],
                                      mode=packing, interpret=True)
        np.testing.assert_array_equal(np.asarray(got[a:a + c]),
                                      np.asarray(want))
        np.testing.assert_array_equal(np.asarray(ref[a:a + c]),
                                      np.asarray(want))


def test_grouped_plan_capability():
    """``op='ternary_grouped'`` resolves through the registry: the Pallas
    kernel wins, the oracle by name; the float domain has no backend."""
    plan = plan_matmul((64, 128, 256), op="ternary_grouped", domain="int8",
                       packing="trit2")
    assert plan.backend == "pallas_gmm" and plan.blocks is None
    assert plan_matmul((64, 128, 256), op="ternary_grouped", domain="int8",
                       packing="trit2", backend="ref_gmm").backend \
        == "ref_gmm"
    with pytest.raises(ValueError, match="no registered backend"):
        plan_matmul((64, 128, 256), op="ternary_grouped", domain="float")
    with pytest.raises(ValueError, match="does not support"):
        plan_matmul((64, 128, 256), op="ternary_grouped", domain="int8",
                    backend="pallas")


def test_block_choices():
    """Mellum's expert widths take all of N in one block, K in chunks
    that divide it; a lane-misaligned K runs as one chunk."""
    assert G.block_n(2304 // 4, 896) == 896
    assert G.block_n(896 // 4, 2304) == 2304
    assert 2304 % G.chunk_k(2304, 896) == 0
    assert 896 % G.chunk_k(896, 2304) == 0
    assert G.chunk_k(96, 128) == 96
    assert G.tile_rows(192, 64) == 32 and G.tile_rows(24576, 64) == 128


def _moe_cfg(e=8, k=2):
    return ModelConfig(name="m", family="moe", num_layers=1, d_model=128,
                       num_heads=4, num_kv_heads=2, d_ff=128,
                       vocab_size=512, head_dim=32, num_experts=e,
                       experts_per_token=k)


def _moe_weights(cfg, packing="trit2", seed=0):
    kk = jax.random.split(jax.random.key(seed), 4)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    w = {"router": jax.random.normal(kk[0], (d, e)) / np.sqrt(d)}
    for name, key, shape in (("w1", kk[1], (e, d, f)),
                             ("w3", kk[2], (e, d, f)),
                             ("w2", kk[3], (e, f, d))):
        w[name] = _experts(packing, *shape,
                           seed=int(jax.random.randint(key, (), 0, 999)))
    return w


def _per_token(x, w, cfg, cim):
    """Each token through its k experts, one dense int8 matmul each."""
    from repro.core import cim_linear
    xt = x.reshape(-1, x.shape[-1])
    logits = jnp.dot(xt.astype(jnp.float32), w["router"],
                     precision=jax.lax.Precision.HIGHEST)
    gate, idx = jax.lax.top_k(jax.nn.softmax(logits, -1),
                              cfg.experts_per_token)
    gate = gate / gate.sum(-1, keepdims=True)

    def expert(e, name):
        p = w[name]
        return PackedTernary(p.data[e], p.scale[e], p.mode)

    out = []
    for t in range(xt.shape[0]):
        acc = jnp.zeros((x.shape[-1],), jnp.float32)
        for j in range(cfg.experts_per_token):
            e = int(idx[t, j])
            row = xt[t:t + 1]

            def lin(v, name):
                return cim_linear.linear(v, expert(e, name),
                                         cim).astype(x.dtype)
            h = jax.nn.silu(lin(row, "w1")) * lin(row, "w3")
            acc = acc + gate[t, j] * lin(h, "w2")[0].astype(jnp.float32)
        out.append(acc)
    return jnp.stack(out).astype(x.dtype).reshape(x.shape), idx


def test_dropless_when_every_token_picks_the_same_expert():
    """A router that sends every token to experts 3 and 6 leaves no pair
    behind: each token equals its own two experts' dense int8 result (the
    GShard dispatch, at capacity 1.25 * T * k / E, would drop most)."""
    cfg = _moe_cfg()
    w = _moe_weights(cfg)
    bias = jnp.zeros((cfg.num_experts,)).at[3].set(50.0).at[6].set(40.0)
    # input feature 0 is at least 1 and the router maps it onto the bias
    w["router"] = (w["router"] * 1e-3).at[0].add(bias)
    x = jax.random.normal(jax.random.key(3), (2, 12, cfg.d_model),
                          jnp.float32)
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 1.0).astype(jnp.bfloat16)
    cim = CIMConfig(mode="ternary", packing="trit2", domain="int8")
    y, stats = moe.moe_serve(x, w, cfg, cim)
    want, idx = _per_token(x, w, cfg, cim)
    assert set(np.unique(np.asarray(idx)).tolist()) == {3, 6}
    assert stats.tolist() == [24 * 2, 2]
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(want, np.float32))


def test_dead_rows_are_not_routed():
    """``live`` keeps dead rows out: they read 0 and count no pair."""
    cfg = _moe_cfg(e=16, k=4)
    w = _moe_weights(cfg, "base3", seed=2)
    x = jax.random.normal(jax.random.key(5), (6, 1, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)
    live = jnp.asarray([True, False, True, True, False, True])
    cim = CIMConfig(mode="ternary", packing="base3", domain="int8")
    y, stats = moe.moe_serve(x, w, cfg, cim, live)
    y_all, _ = moe.moe_serve(x, w, cfg, cim)
    assert int(stats[0]) == 4 * 4
    assert not np.any(np.asarray(y[~np.asarray(live)], np.float32))
    np.testing.assert_array_equal(np.asarray(y[np.asarray(live)]),
                                  np.asarray(y_all[np.asarray(live)]))


def test_route_layout_tiles():
    """Groups start on tile boundaries in expert order; idle tiles repeat
    the last live tile's expert."""
    idx = jnp.asarray([[2, 0], [2, 1], [2, 0]])
    valid = jnp.asarray([[True, True], [True, False], [True, True]])
    dest, te, nt, stats = moe.route_layout(idx, valid, 4, 32, 6)
    assert int(nt[0]) == 2 and stats.tolist() == [5, 2]
    assert te.tolist() == [0, 2, 2, 2, 2, 2]
    assert dest.tolist() == [[32, 0], [33, 192], [34, 1]]


def test_serve_path_only_for_packed_int8_experts():
    cfg = _moe_cfg()
    w = _moe_weights(cfg)
    int8 = CIMConfig(mode="ternary", packing="trit2", domain="int8")
    assert moe.serves_dropless(w, int8)
    assert not moe.serves_dropless(w, dataclasses.replace(
        int8, domain="float"))
    assert not moe.serves_dropless(w, None)
    assert not moe.serves_dropless(dict(w, w1=jnp.zeros((8, 128, 128))),
                                   int8)
