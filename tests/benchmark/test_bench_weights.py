"""The benchmark's weights: the program's tree, made from the seed, and
the same codes the reference draws."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny  # noqa: F401  (puts the repo and src on the path)
from bench import harness, reference, weights, work
from repro.core.cim_linear import CIMConfig, hbm_bytes, ternarize_params
from repro.core.packing import unpack_base3, unpack_trits2
from repro.kernels.ops import PackedTernary
from repro.models import registry

PACKED = lambda x: isinstance(x, PackedTernary)  # noqa: E731


def _model():
    return registry.build(harness.model_config(tiny.CONFIG))


@pytest.mark.parametrize("packing", ["base3", "trit2"])
def test_tree_matches_the_programs(packing):
    model = _model()
    want = ternarize_params(model.init(jax.random.key(0)),
                            CIMConfig(mode="ternary", packing=packing))
    got = weights.served_params(model, packing, seed=7)
    sw = jax.tree_util.tree_structure(want, is_leaf=PACKED)
    sg = jax.tree_util.tree_structure(got, is_leaf=PACKED)
    assert sw == sg
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert hbm_bytes(got) == hbm_bytes(want)


@pytest.mark.parametrize("packing", ["base3", "trit2"])
def test_weight_bytes_from_shapes(packing):
    model = _model()
    got = weights.served_params(model, packing, seed=1)
    assert work.weight_bytes(harness.arch(tiny.CONFIG), packing) \
        == hbm_bytes(got)


@pytest.mark.parametrize("packing", ["base3", "trit2"])
def test_packed_codes_are_the_references(packing):
    model = _model()
    seed = 2 ** 33 + 3                      # above 32 bits
    p = weights.served_params(model, packing, seed)
    w = p["blocks"]["w2"]
    layer = 1
    c, s = weights.codes(weights.seed_key(seed), "blocks/w2", layer,
                         *w.shape[-2:], packing)
    data = w.data[layer]
    dec = unpack_base3(data) if packing == "base3" else unpack_trits2(data)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(c))
    np.testing.assert_array_equal(np.asarray(w.scale[layer]), np.asarray(s))


def test_seed_uses_every_bit():
    a = weights.seed_key(5)
    b = weights.seed_key(5 + 2 ** 32)
    assert not np.array_equal(jax.random.key_data(a),
                              jax.random.key_data(b))


def test_padded_vocab_is_zero():
    model = _model()
    cfg = dict(tiny.CONFIG, vocab_size=500)
    model = registry.build(harness.model_config(cfg))
    p = weights.served_params(model, "base3", seed=3)
    emb = np.asarray(p["embed"], np.float32)
    assert np.all(emb[500:] == 0) and np.any(emb[:500] != 0)
    codes = np.asarray(unpack_base3(p["unembed"].data))
    assert np.all(codes[:, 500:] == 0)


def test_quantized_matmul_is_exact_integer_arithmetic():
    x = jax.random.normal(jax.random.key(0), (3, 64))
    c, s = weights.codes(jax.random.key(1), "m", 0, 64, 32, "base3")
    y = reference._qmatmul(x, c, s, 8)
    amax = jnp.max(jnp.abs(x), -1)
    xi = np.round(np.asarray(x) / np.asarray(amax / 127)[:, None])
    exact = (xi.astype(np.int64) @ np.asarray(c, np.int64))
    want = exact * np.asarray(amax / 127)[:, None] * np.asarray(s)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-6)
