"""The ``moe`` architecture module: a tiny cell of sparse experts and mixed
window/full layers served and checked as a real one is, and the work
counts behind ``decode.mfu`` and ``expert_matmul_roofline``."""
import importlib.util
import json
import os
import time

import jax
import pytest

import tiny
from bench import archs, harness, weights, work

SL, FU = "sliding_attention", "full_attention"
MELLUM = os.path.join(tiny.REPO, "bench", "configs",
                      "mellum2-12b-a2.5b-trit2.json")
# Mellum's block at the least widths the program packs: 64 experts,
# top-8, three window layers to one full, YaRN on the full one
CONFIG = dict(
    tiny.CONFIG, name="tiny-moe", architecture="moe",
    moe_intermediate_size=128, num_hidden_layers=4, num_experts=64,
    num_experts_per_tok=8, norm_topk_prob=True,
    layer_types=[SL, SL, SL, FU], mlp_layer_types=["sparse"] * 4,
    sliding_window=16,
    rope_parameters={
        FU: {"rope_type": "yarn", "rope_theta": 5e5, "factor": 4,
             "original_max_position_embeddings": 32, "beta_fast": 32,
             "beta_slow": 1, "attention_factor": 1.1386},
        SL: {"rope_type": "default", "rope_theta": 5e5}},
    # served_gap at this size: the program 0-0.168 over 10 seeds, the int4
    # control 0.670-1.545 over the same 10; 0.33 sits 1.96x above the one
    # and 2.03x below the other
    limits=dict(tiny.CONFIG["limits"], served_gap=0.33))


def _cell(root):
    name = tiny.write_cell(root)
    with open(os.path.join(root, "bench", "configs", "tiny.json"),
              "w") as f:
        json.dump(CONFIG, f)
    return harness.load_cell(root, name)


@pytest.mark.parametrize("control", [False, True],
                         ids=["program", "int4_control"])
def test_tiny_moe_cell(tmp_path, control):
    """Prefill then paged decode (fused read, dropless grouped experts)
    stays within the limit of the module's reference; the int4 control
    in the program's place does not."""
    cell = _cell(str(tmp_path))
    assert cell.module.__file__ == os.path.join(
        tiny.REPO, "bench", "archs", "moe.py")
    out = harness.run_cell(cell, seed=2 ** 35 + 7, seconds=1.0,
                           trace=False, t_start=time.monotonic(),
                           on_tpu=False, control=control)
    assert out["correct"] is (not control), out["checks"]
    assert out["checks"]["checked_tokens"]["value"] >= 16


def _mellum():
    with open(MELLUM) as f:
        config = json.load(f)
    return config, archs.load(config)


def test_mellum_model_config():
    """Published widths, the 3:1 window pattern and YaRN on the full
    layers, 64 experts of 896 at top-8; the whole model on one chip."""
    config, mod = _mellum()
    cfg = mod.model_config(config)
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.hd, cfg.d_ff, cfg.num_experts, cfg.experts_per_token) \
        == (28, 2304, 32, 4, 128, 896, 64, 8)
    assert cfg.layer_windows == (1024, 1024, 1024, 0) * 7
    assert cfg.rope_yarn[:4] == (16, 8192, 32, 1)
    assert config["reduced"] == []
    s = config["scheduler"]
    assert s["slots"] * -(-s["capacity"] // s["page_size"]) \
        == s["num_pages"] - 1


def test_served_bytes_match_the_tree():
    """``weight_bytes`` counts the tree the program serves, leaf by leaf
    (shapes only): 3.48 GB for Mellum in trit2."""
    from repro.kernels.ops import PackedTernary
    from repro.models import registry
    config, mod = _mellum()
    model = registry.build(mod.model_config(config))
    tree = weights.abstract_params(model, "trit2")
    leaves = jax.tree.leaves(tree, is_leaf=lambda x: isinstance(
        x, PackedTernary))
    total = sum(a.data.size + 4 * a.scale.size if isinstance(
        a, PackedTernary) else a.size * a.dtype.itemsize for a in leaves)
    assert total == mod.weight_bytes(mod.arch(config), "trit2")
    assert 3.47e9 < total < 3.49e9


def test_decode_work_counts():
    """The grouped kernel's part: ops over the routed rows, bytes every
    expert once a step (the upper bound); attention over each window
    layer's in-window positions."""
    config, mod = _mellum()
    a = mod.arch(config)
    mm = mod.decode_matmul(a, "trit2", rows=192, steps=8)
    one = sum(work.packed_bytes(k, n, "trit2")
              for k, n in ((2304, 896), (2304, 896), (896, 2304)))
    assert mm["experts"] == {"ops": 2 * 192 * 8 * 28 * 3 * 2304 * 896,
                             "bytes": 8 * 28 * 64 * one}
    assert mm["ops"] > mm["experts"]["ops"]
    att = mod.attention(a, [100, 3000])
    per_q = 4 * 32 * 128
    assert att["flops"] == per_q * (21 * (100 + 1023) + 7 * (100 + 3000))


def test_expert_roofline_reader_names_its_kernel():
    path = os.path.join(tiny.REPO, "bench", "metrics",
                        "expert_matmul_roofline.py")
    spec = importlib.util.spec_from_file_location("emr", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.is_kernel("jit_chunk_step:%ternary_gmm_int8.3 [pallas]")
    assert not mod.is_kernel(
        "jit_chunk_step:%ternary_matmul_int8.56 [pallas]")
    assert not mod.is_kernel("jit_chunk_step:%ternary_gmm_int8.3")
