"""The sparse mixture-of-experts decoder with mixed window and full
attention layers (Mellum 2): per layer a pre-norm grouped-query attention
and a pre-norm routed SwiGLU MLP, both added to the residual stream.

- Attention: ``layer_types`` names each layer ``sliding_attention``
  (keys within ``sliding_window`` positions of the query, itself
  included) or ``full_attention``.  RoPE (rotate-half) follows
  ``rope_parameters`` by layer type: sliding layers plain at their
  ``rope_theta``; full layers YaRN (arXiv:2309.00071) with the
  published ``factor``, ``original_max_position_embeddings``,
  ``beta_fast`` and ``beta_slow``, cos and sin multiplied by
  ``attention_factor``.  No q/k norm and no bias.
- MLP: an f32 router (d, E) gives logits, a softmax over the E experts,
  the top ``num_experts_per_tok`` and, with ``norm_topk_prob``, their
  probabilities renormalized to sum to one; each chosen expert's SwiGLU
  (``moe_intermediate_size`` wide) is weighted by its gate and summed.
  No shared expert.  Only routed experts are computed (``ragged_dot``
  over rows sorted by expert), dropless.

The interface is ``bench/archs/__init__.py``'s.  Weights: packed
matrices from ``bench/weights.py`` (expert ``e`` of layer ``l`` at flat
index ``l * E + e``), the router from ``draw`` below.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench import weights as W
from bench import work

SLIDING, FULL = "sliding_attention", "full_attention"
QUERY_BLOCK = 256        # queries per block of the reference's attention


def arch(config: dict) -> dict:
    """The model's sizes under the published names, with ``layer_types``
    read into per-layer windows (0 = full) and the full layers' YaRN
    parameters flattened (every value hashable: the reference's jitted
    layer takes them as static)."""
    full = config["rope_parameters"][FULL]
    sliding = config["rope_parameters"][SLIDING]
    if full.get("rope_type") != "yarn" or sliding.get(
            "rope_type", "default") != "default":
        raise ValueError("moe: full layers YaRN, sliding layers plain RoPE")
    if sliding["rope_theta"] != full["rope_theta"]:
        raise ValueError("moe: one rope_theta for both layer types")
    windows = tuple(config["sliding_window"] if t == SLIDING else 0
                    for t in config["layer_types"])
    if len(windows) != config["num_hidden_layers"] or any(
            t not in (SLIDING, FULL) for t in config["layer_types"]):
        raise ValueError("moe: one sliding/full layer type per layer")
    if any(t != "sparse" for t in config["mlp_layer_types"]):
        raise ValueError("moe: every MLP layer sparse")
    return {
        "hidden_size": config["hidden_size"],
        "moe_intermediate_size": config["moe_intermediate_size"],
        "num_attention_heads": config["num_attention_heads"],
        "num_key_value_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "num_hidden_layers": config["num_hidden_layers"],
        "vocab_size": config["vocab_size"],
        "rms_norm_eps": config["rms_norm_eps"],
        "num_experts": config["num_experts"],
        "num_experts_per_tok": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "windows": windows,
        "rope_theta": full["rope_theta"],
        "yarn": (full["factor"], full["original_max_position_embeddings"],
                 full["beta_fast"], full["beta_slow"],
                 full["attention_factor"]),
    }


def model_config(config: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.models.config import ModelConfig
    a = arch(config)
    return ModelConfig(
        name=config["name"], family="moe",
        num_layers=a["num_hidden_layers"], d_model=a["hidden_size"],
        num_heads=a["num_attention_heads"],
        num_kv_heads=a["num_key_value_heads"],
        d_ff=a["moe_intermediate_size"], vocab_size=a["vocab_size"],
        head_dim=a["head_dim"], rope_theta=a["rope_theta"],
        num_experts=a["num_experts"],
        experts_per_token=a["num_experts_per_tok"],
        layer_windows=a["windows"], rope_yarn=a["yarn"],
        norm_eps=a["rms_norm_eps"])


# ---------------------------------------------------------------- router

def _router(key, layer, d: int, experts: int):
    """Layer ``layer``'s router (d, E) f32: standard normal over
    sqrt(d), so the logits spread as a trained router's start."""
    g = jax.random.normal(W.leaf_key(key, "blocks/router", layer),
                          (d, experts), jnp.float32)
    return g * (1.0 / math.sqrt(d))


def draw(key, name, spec):
    """The served tree's unpacked leaves beyond embedding and gains: the
    stacked f32 router (L, d, E)."""
    if name != "blocks/router":
        raise ValueError(f"moe: no rule to draw {name} {spec.shape}")
    n_layers, d, experts = spec.shape
    return jax.lax.map(lambda layer: _router(key, layer, d, experts),
                       jnp.arange(n_layers)).astype(spec.dtype)


# ------------------------------------------------------------- reference

def yarn_inv_freq(hd: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies as the published ``rope_type: yarn``
    computes them (correction range floored and ceiled)."""
    def corr(rot):
        return hd * math.log(original / (rot * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    base = theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ramp = np.clip((np.arange(hd // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extrapolate = 1.0 - ramp
    return ((1.0 / (factor * base)) * (1 - extrapolate)
            + (1.0 / base) * extrapolate).astype(np.float32)


def _rope(x, inv, scale):
    """x (T, H, hd); rotate-half at positions 0..T-1 with frequencies
    ``inv`` (hd/2,), cos and sin times ``scale``."""
    hd = x.shape[-1]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None] * scale, jnp.sin(ang)[:, None] * scale
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _qragged(x, c, scale, sizes, expert, act_bits):
    """Rows ``x`` (R, K), sorted by expert with ``sizes`` (E,) rows each,
    times their expert's codes ``c`` (E, K, N): inputs quantized per row
    as ``reference._qmatmul`` does, products summed in int32."""
    qmax = 2.0 ** (act_bits - 1) - 1
    amax = jnp.max(jnp.abs(x), axis=-1)
    s = jnp.where(amax > 0, amax / qmax, 1.0)
    xi = jnp.clip(jnp.round(x / s[:, None]), -qmax, qmax).astype(jnp.int8)
    # integer products are exact at any precision; an explicit DEFAULT
    # keeps the layer's ``highest`` from reaching the int8 kernel
    acc = jax.lax.ragged_dot(xi, c, sizes, precision=jax.lax.Precision.DEFAULT,
                             preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * s[:, None] * scale[expert]


def layer(x, key, layer, arch, packing, act_bits):
    """One decoder layer over ``x`` (B, T, d), one sequence at a time;
    ``layer`` may be traced, so its window and RoPE table are selected
    from the per-layer arrays."""
    d, h, kvh, hd = (arch["hidden_size"], arch["num_attention_heads"],
                     arch["num_key_value_heads"], arch["head_dim"])
    f, e, k = (arch["moe_intermediate_size"], arch["num_experts"],
               arch["num_experts_per_tok"])
    eps, t = arch["rms_norm_eps"], x.shape[1]
    window = jnp.asarray(arch["windows"], jnp.int32)[layer]
    factor, original, fast, slow, att = arch["yarn"]
    theta = arch["rope_theta"]
    plain = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    full = window == 0
    inv = jnp.where(full, yarn_inv_freq(hd, theta, factor, original, fast,
                                        slow), plain)
    rscale = jnp.where(full, jnp.float32(att), jnp.float32(1.0))

    def mat(name, kk, n):
        c, s = W.codes(key, "blocks/" + name, layer, kk, n, packing)
        return lambda v: R._qmatmul(v, c, s, act_bits)

    def experts(name, kk, n):
        return jax.vmap(lambda i: W.codes(key, "blocks/" + name,
                                          layer * e + i, kk, n,
                                          packing))(jnp.arange(e))

    wq, wk, wv = mat("wq", d, h * hd), mat("wk", d, kvh * hd), \
        mat("wv", d, kvh * hd)
    wo = mat("wo", h * hd, d)
    (c1, s1), (c3, s3), (c2, s2) = (experts("w1", d, f),
                                    experts("w3", d, f),
                                    experts("w2", f, d))
    router = _router(key, layer, d, e)
    g1 = W.gains(key, "blocks/ln1", layer, d, jnp.bfloat16)
    g2 = W.gains(key, "blocks/ln2", layer, d, jnp.bfloat16)
    block = min(QUERY_BLOCK, t)
    kpos = jnp.arange(t)[None, :]

    def moe(m):                                 # (T, d) -> (T, d)
        probs = jax.nn.softmax(m @ router, axis=-1)
        gate, idx = jax.lax.top_k(probs, k)
        if arch["norm_topk_prob"]:
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        flat = idx.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        token, expert = order // k, flat[order]
        sizes = jnp.bincount(flat, length=e).astype(jnp.int32)
        xm = m[token]
        hmid = (jax.nn.silu(_qragged(xm, c1, s1, sizes, expert, act_bits))
                * _qragged(xm, c3, s3, sizes, expert, act_bits))
        y = _qragged(hmid, c2, s2, sizes, expert, act_bits)
        return jnp.zeros_like(m).at[token].add(
            gate.reshape(-1)[order][:, None] * y)

    def row(xs):                                # (T, d)
        a = R._rms(xs, g1, eps)
        q = _rope(wq(a).reshape(t, h, hd), inv, rscale)
        kk = _rope(wk(a).reshape(t, kvh, hd), inv, rscale)
        v = wv(a).reshape(t, kvh, hd)

        def attend(args):                       # one block of queries
            qb, q0 = args
            qpos = q0 + jnp.arange(block)[:, None]
            visible = (kpos <= qpos) & ((window <= 0)
                                        | (kpos > qpos - window))
            qg = qb.reshape(block, kvh, h // kvh, hd)
            sc = jnp.einsum("tgrd,ugd->grtu", qg, kk) / np.sqrt(hd)
            p = jax.nn.softmax(jnp.where(visible, sc, -jnp.inf), axis=-1)
            return jnp.einsum("grtu,ugd->tgrd", p, v).reshape(block,
                                                              h * hd)

        o = jax.lax.map(attend, (q.reshape(t // block, block, h, hd),
                                 jnp.arange(0, t, block)))
        xs = xs + wo(o.reshape(t, h * hd))
        return xs + moe(R._rms(xs, g2, eps))

    return jax.lax.map(row, x)


# ----------------------------------------------------------------- work

def matmuls(arch: dict) -> dict:
    """name -> (count, K, N) of every packed matrix; an expert matrix
    counts once per layer and expert."""
    d, h, kvh, hd = (arch["hidden_size"], arch["num_attention_heads"],
                     arch["num_key_value_heads"], arch["head_dim"])
    f, n_layers = arch["moe_intermediate_size"], arch["num_hidden_layers"]
    ne = n_layers * arch["num_experts"]
    return {"wq": (n_layers, d, h * hd), "wk": (n_layers, d, kvh * hd),
            "wv": (n_layers, d, kvh * hd), "wo": (n_layers, h * hd, d),
            "w1": (ne, d, f), "w3": (ne, d, f), "w2": (ne, f, d),
            "unembed": (1, d, work.padded_vocab(arch))}


EXPERT = ("w1", "w3", "w2")


def weight_bytes(arch: dict, packing: str) -> int:
    """Device bytes of the served weights: packed matrices and scales,
    the f32 routers, the bf16 embedding and the bf16 norm gains."""
    d, n_layers = arch["hidden_size"], arch["num_hidden_layers"]
    total = sum(c * work.packed_bytes(kk, n, packing)
                for c, kk, n in matmuls(arch).values())
    total += n_layers * d * arch["num_experts"] * work.F32
    total += work.padded_vocab(arch) * d * work.BF16
    return total + (n_layers * 2 * d + d) * work.BF16


def decode_matmul(arch: dict, packing: str, rows: int, steps: int,
                  counters=None) -> dict:
    """Packed-matmul work of ``steps`` decode steps serving ``rows`` live
    rows: attention projections and the output head read once a step;
    each row through its ``num_experts_per_tok`` experts.  ``experts``
    is the grouped kernel's part alone.  Its bytes count every expert
    of every layer once a step, an upper bound that holds exactly when
    each expert gets a row: with 24 live rows of 8 picks among 64, an
    expert gets none in a layer-step with probability (56/64)^24 = 4.1%
    (uniform routing).  The exact bytes need the scheduler's
    ``moe_experts_read``, which ``harness.COUNTERS`` does not pass yet.
    The f32 router is not a packed matmul and is left out."""
    mats = matmuls(arch)
    e, k = arch["num_experts"], arch["num_experts_per_tok"]
    dense = [v for name, v in mats.items() if name not in EXPERT]
    ops = 2 * rows * sum(c * kk * n for c, kk, n in dense)
    nbytes = steps * sum(c * work.packed_bytes(kk, n, packing)
                         for c, kk, n in dense)
    x_ops = 2 * rows * k * sum(mats[m][0] // e * mats[m][1] * mats[m][2]
                               for m in EXPERT)
    x_bytes = steps * sum(mats[m][0] * work.packed_bytes(*mats[m][1:],
                                                         packing)
                          for m in EXPERT)
    return {"ops": ops + x_ops, "bytes": nbytes + x_bytes,
            "experts": {"ops": x_ops, "bytes": x_bytes}}


def attention(arch: dict, contexts) -> dict:
    """Attention work of one decode query per entry of ``contexts`` (its
    cached positions), over all layers: a full layer reads all ``c``, a
    window layer the ``min(c, window - 1)`` cached positions inside its
    window (the query's own is merged apart, as in every layer)."""
    h, kvh, hd = (arch["num_attention_heads"],
                  arch["num_key_value_heads"], arch["head_dim"])
    per_window = {w: arch["windows"].count(w) for w in arch["windows"]}
    pos = sum(n * sum(c if w == 0 else min(c, w - 1) for c in contexts)
              for w, n in per_window.items())
    return {"flops": 4 * h * hd * pos,
            "bytes": 2 * kvh * hd * work.BF16 * pos}
