"""The dense decoder (internlm2 / Qwen3): per layer a pre-norm grouped-
query attention with rotary positions (rotate-half form; Qwen3 adds an
RMSNorm on each head's q and k before the rotation) and a pre-norm
SwiGLU MLP, both added to the residual stream.  The interface is
``bench/archs/__init__.py``'s."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench import weights as W
from bench import work

ARCH_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
             "num_key_value_heads", "head_dim", "num_hidden_layers",
             "vocab_size", "rms_norm_eps", "rope_theta", "qk_norm")


def arch(config: dict) -> dict:
    """The model's sizes, under the published config.json's names."""
    return {k: config[k] for k in ARCH_KEYS}


def model_config(config: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.models.config import ModelConfig
    a = arch(config)
    return ModelConfig(
        name=config["name"], family="dense",
        num_layers=a["num_hidden_layers"], d_model=a["hidden_size"],
        num_heads=a["num_attention_heads"],
        num_kv_heads=a["num_key_value_heads"], d_ff=a["intermediate_size"],
        vocab_size=a["vocab_size"], head_dim=a["head_dim"],
        rope_theta=a["rope_theta"], qk_norm=a["qk_norm"],
        norm_eps=a["rms_norm_eps"])


# ------------------------------------------------------------ reference

def layer(x, key, layer, arch, packing, act_bits):
    """One decoder layer over ``x`` (B, T, d), one sequence at a time so
    that a batch of long rows fits beside the layer's weights."""
    d, h, kvh, hd = (arch["hidden_size"], arch["num_attention_heads"],
                     arch["num_key_value_heads"], arch["head_dim"])
    f, eps = arch["intermediate_size"], arch["rms_norm_eps"]
    t = x.shape[1]

    def mat(name, k, n):
        c, s = W.codes(key, "blocks/" + name, layer, k, n, packing)
        return lambda v: R._qmatmul(v, c, s, act_bits)

    def gain(name, n):
        return W.gains(key, "blocks/" + name, layer, n, jnp.bfloat16)

    wq, wk, wv = mat("wq", d, h * hd), mat("wk", d, kvh * hd), \
        mat("wv", d, kvh * hd)
    wo, w1, w3, w2 = mat("wo", h * hd, d), mat("w1", d, f), \
        mat("w3", d, f), mat("w2", f, d)
    g1, g2 = gain("ln1", d), gain("ln2", d)
    if arch["qk_norm"]:
        gq, gk = gain("q_norm", hd), gain("k_norm", hd)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def row(xs):                                # (T, d)
        a = R._rms(xs, g1, eps)
        q = wq(a).reshape(t, h, hd)
        k = wk(a).reshape(t, kvh, hd)
        v = wv(a).reshape(t, kvh, hd)
        if arch["qk_norm"]:
            q, k = R._rms(q, gq, eps), R._rms(k, gk, eps)
        q, k = R._rope(q, arch["rope_theta"]), R._rope(k, arch["rope_theta"])
        qg = q.reshape(t, kvh, h // kvh, hd)
        sc = jnp.einsum("tgrd,ugd->grtu", qg, k) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("grtu,ugd->tgrd", p, v).reshape(t, h * hd)
        xs = xs + wo(o)
        m = R._rms(xs, g2, eps)
        return xs + w2(jax.nn.silu(w1(m)) * w3(m))

    return jax.lax.map(row, x)


# ----------------------------------------------------------------- work

def matmuls(arch: dict) -> dict:
    """name -> (count, K, N) of every packed weight matrix."""
    d, h, kvh, hd, f = (
        arch["hidden_size"], arch["num_attention_heads"],
        arch["num_key_value_heads"], arch["head_dim"],
        arch["intermediate_size"])
    n_layers = arch["num_hidden_layers"]
    vp = work.padded_vocab(arch)
    return {"wq": (n_layers, d, h * hd), "wk": (n_layers, d, kvh * hd),
            "wv": (n_layers, d, kvh * hd), "wo": (n_layers, h * hd, d),
            "w1": (n_layers, d, f), "w3": (n_layers, d, f),
            "w2": (n_layers, f, d), "unembed": (1, d, vp)}


def weight_bytes(arch: dict, packing: str) -> int:
    """Device bytes of the served weights: packed matrices and scales,
    the bf16 embedding and the bf16 norm gains."""
    d, n_layers = arch["hidden_size"], arch["num_hidden_layers"]
    total = sum(c * work.packed_bytes(k, n, packing)
                for c, k, n in matmuls(arch).values())
    total += work.padded_vocab(arch) * d * work.BF16
    gains = n_layers * 2 * d + d
    if arch["qk_norm"]:
        gains += n_layers * 2 * arch["head_dim"]
    return total + gains * work.BF16


def decode_matmul(arch: dict, packing: str, rows: int, steps: int,
                  counters=None) -> dict:
    """Matmul work of ``steps`` decode steps that serve ``rows`` live
    rows in all: every step reads every weight once."""
    mats = matmuls(arch).values()
    return {"ops": 2 * rows * sum(c * k * n for c, k, n in mats),
            "bytes": steps * sum(c * work.packed_bytes(k, n, packing)
                                 for c, k, n in mats)}


def attention(arch: dict, contexts) -> dict:
    """Attention work of one decode query per entry of ``contexts``
    (its number of cached positions), over all layers."""
    n_layers, h, kvh, hd = (
        arch["num_hidden_layers"], arch["num_attention_heads"],
        arch["num_key_value_heads"], arch["head_dim"])
    pos = sum(contexts)
    return {"flops": 4 * h * hd * pos * n_layers,
            "bytes": 2 * kvh * hd * work.BF16 * pos * n_layers}
