"""The architecture seam: a configuration names its architecture module,
which may be a new file alone; weights of any stack rank; the cell's own
capacity and slots."""
import json
import os
import shutil
import time

import jax
import numpy as np
import pytest

import tiny
from bench import archs, harness, reference, weights
from repro.core.packing import unpack_base3, unpack_trits2
from repro.kernels.ops import PackedTernary
from repro.models import registry
from repro.models.config import ModelConfig

DENSE = os.path.join(tiny.REPO, "bench", "archs", "dense.py")
PACKED = lambda x: isinstance(x, PackedTernary)  # noqa: E731


def _write_config(root, **extra):
    path = os.path.join(root, "bench", "configs", "tiny.json")
    with open(path, "w") as f:
        json.dump(dict(tiny.CONFIG, **extra), f)


def _write_traffic(root, **extra):
    path = os.path.join(root, "bench", "traffic", "tiny.json")
    with open(path, "w") as f:
        json.dump(dict(tiny.TRAFFIC, **extra), f)


def test_a_new_architecture_file_runs_a_cell(tmp_path, monkeypatch):
    """A module that exists only in the cell's root (a copy of ``dense``
    under another name) serves and checks a whole tiny cell; on the
    requests that run served, the ``dense`` module's reference reads the
    same gap over the same tokens."""
    root = str(tmp_path)
    name = tiny.write_cell(root)
    os.makedirs(os.path.join(root, "bench", "archs"))
    copy = os.path.join(root, "bench", "archs", "dense_copy.py")
    shutil.copy(DENSE, copy)
    _write_config(root, architecture="dense_copy")
    cell = harness.load_cell(root, name)
    assert cell.module.__file__ == copy
    assert cell.module.model_config(cell.config) \
        == harness.model_config(tiny.CONFIG)
    calls = []
    real = reference.logit_gaps

    def recorded(*args, **kwargs):
        calls.append((args, kwargs))
        return real(*args, **kwargs)
    monkeypatch.setattr(reference, "logit_gaps", recorded)
    out = harness.run_cell(cell, seed=2 ** 36 + 11, seconds=1.0,
                           trace=False, t_start=time.monotonic(),
                           on_tpu=False)
    assert out["correct"], out["checks"]
    (config, seed, seqs, rows, length), kwargs = calls[0]
    assert config["architecture"] == "dense_copy"
    assert kwargs["root"] == root
    dense = real(tiny.CONFIG, seed, seqs, rows, length)
    assert dense["served_gap"] == out["checks"]["served_gap"]["value"]
    assert dense["tokens"] == out["checks"]["checked_tokens"]["value"]


def test_unknown_architecture(tmp_path):
    root = str(tmp_path)
    name = tiny.write_cell(root)
    _write_config(root, architecture="no_such_arch")
    with pytest.raises(harness.CellError, match="no_such_arch.py"):
        harness.load_cell(root, name)


ROUTER_DRAW = '''
import jax
import jax.numpy as jnp

from bench import weights as W


def draw(key, name, spec):
    if name != "blocks/router":
        raise ValueError(name)
    return jax.lax.map(
        lambda layer: jax.random.normal(W.leaf_key(key, name, layer),
                                        spec.shape[1:], spec.dtype),
        jnp.arange(spec.shape[0]))
'''


@pytest.mark.parametrize("packing", ["base3", "trit2"])
def test_expert_stacks_are_drawn_at_the_flat_index(tmp_path, packing):
    """The program's own MoE tree: expert stacks (L, E, K, N) and an f32
    router (L, d, E), the router drawn by the architecture module."""
    os.makedirs(tmp_path / "bench" / "archs")
    (tmp_path / "bench" / "archs" / "moe_test.py").write_text(ROUTER_DRAW)
    mod = archs.load({"architecture": "moe_test"}, str(tmp_path))
    n_layers, n_experts = 2, 4
    model = registry.build(ModelConfig(
        name="moe", family="moe", num_layers=n_layers, d_model=128,
        num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=512, head_dim=32,
        num_experts=n_experts, experts_per_token=2))
    seed = 2 ** 34 + 21
    got = weights.served_params(model, packing, seed, mod.draw)
    want = weights.abstract_params(model, packing)
    assert (jax.tree_util.tree_structure(got, is_leaf=PACKED)
            == jax.tree_util.tree_structure(want, is_leaf=PACKED))
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    w1 = got["blocks"]["w1"]
    assert w1.data.ndim == 4
    unpack = unpack_base3 if packing == "base3" else unpack_trits2
    for layer, e in ((0, 0), (1, 2), (1, 3)):
        c, s = weights.codes(weights.seed_key(seed), "blocks/w1",
                             layer * n_experts + e, *w1.shape[-2:], packing)
        np.testing.assert_array_equal(
            np.asarray(unpack(w1.data[layer, e])), np.asarray(c))
        np.testing.assert_array_equal(np.asarray(w1.scale[layer, e]),
                                      np.asarray(s))
    router = np.asarray(got["blocks"]["router"])
    assert router.shape == (n_layers, 128, n_experts)
    assert np.all(np.isfinite(router)) and np.any(router[0] != router[1])
    with pytest.raises(ValueError, match="does not pack"):
        weights.served_params(model, packing, seed)


def test_cell_sets_its_own_capacity_and_slots(tmp_path):
    root = str(tmp_path)
    name = tiny.write_cell(root)
    # 3 slots of 72 positions reserve 3 x 9 pages of 8, of 39
    _write_traffic(root, capacity=72, slots=3)
    cell = harness.load_cell(root, name)
    assert cell.sched == dict(tiny.CONFIG["scheduler"], capacity=72,
                              slots=3)
    eng = harness.build(cell, seed=5, on_tpu=False)
    assert (eng.capacity, eng.slots) == (72, 3)
    assert eng.num_pages == tiny.CONFIG["scheduler"]["num_pages"]
    del eng
    out = harness.run_cell(cell, seed=2 ** 33 + 7, seconds=1.0,
                           trace=False, t_start=time.monotonic(),
                           on_tpu=False)
    assert out["correct"], out["checks"]
    # 4 slots of 80 positions would need 40 pages: the pool holds 39
    _write_traffic(root, capacity=80)
    with pytest.raises(harness.CellError, match="need 40 pages"):
        harness.load_cell(root, name)


def test_configuration_sizes_without_cell_sizes(tmp_path):
    root = str(tmp_path)
    cell = harness.load_cell(root, tiny.write_cell(root))
    assert cell.sched == tiny.CONFIG["scheduler"]
