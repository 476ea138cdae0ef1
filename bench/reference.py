"""Plain reference of the served model, and the gap that decides ``correct``.

The model is the published decoder: token embedding, then the blocks of
its architecture module (``bench/archs/<module>.py``, ``layer``; the
dense GQA decoder of internlm2 and Qwen3 is ``dense``), then a final
RMSNorm and the output head.  Every matrix is a ternary code matrix with
per-column scales, and its input is quantized per row, as the
configuration states: ``x ~ round(x / s) * s`` with ``s = max|x| / 127``.
The products of codes are integers and are summed exactly in int32.
Everything else is float32 at ``highest`` precision.

The weights come from ``weights.codes`` and friends, from the seed: the
reference imports nothing of the program.  It runs layer by layer over a
padded batch of sequences, one sequence at a time, after the program's
state is freed.

``act_bits=4`` is the control: the same model with its matmul inputs
quantized to int4 (``max|x| / 7``), the step below the stated int8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import archs
from . import weights as W

ROW_BLOCK = 256          # positions per block of the output head


def _qmatmul(x, c, scale, act_bits):
    qmax = 2.0 ** (act_bits - 1) - 1
    amax = jnp.max(jnp.abs(x), axis=-1)
    s = jnp.where(amax > 0, amax / qmax, 1.0)
    xi = jnp.clip(jnp.round(x / s[..., None]), -qmax, qmax).astype(jnp.int8)
    acc = jax.lax.dot_general(xi, c, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * s[..., None] * scale


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, theta):
    """x (T, H, hd); rotate-half rotary embedding at positions 0..T-1."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit,
                   static_argnames=("block", "arch", "packing", "bits"))
def _layer_jit(x, key, layer, block, arch, packing, bits):
    with jax.default_matmul_precision("highest"):
        return block(x, key, layer, dict(arch), packing, bits)


@functools.partial(jax.jit, static_argnames=("arch", "packing"))
def _head(xr, xc, pos, tok, key, arch, packing):
    """Per scored position: the reference's best logit, its logit of the
    served token, and its logit of the token the control puts first."""
    arch = dict(arch)
    d, vocab = arch["hidden_size"], arch["vocab_size"]
    vp = -(-vocab // 256) * 256
    g = W.gains(key, "final_norm", 0, d, jnp.bfloat16)
    c, s = W.codes(key, "unembed", 0, d, vp, packing, vocab)
    c, s = c[:, :vocab], s[:vocab]

    def block(args):
        p, t = args
        with jax.default_matmul_precision("highest"):
            lr = _qmatmul(_rms(xr[p[:, 0], p[:, 1]], g, arch["rms_norm_eps"]),
                          c, s, 8)
            lc = (_qmatmul(_rms(xc[p[:, 0], p[:, 1]], g,
                                arch["rms_norm_eps"]), c, s, 4)
                  if xc is not None else lr)
        rows = jnp.arange(lr.shape[0])
        served = jnp.where(t < vocab, lr[rows, jnp.minimum(t, vocab - 1)],
                           -jnp.inf)
        return lr.max(-1), served, lr[rows, jnp.argmax(lc, -1)]

    nb = pos.shape[0] // ROW_BLOCK
    out = jax.lax.map(block, (pos.reshape(nb, ROW_BLOCK, 2),
                              tok.reshape(nb, ROW_BLOCK)))
    return tuple(o.reshape(-1) for o in out)


def logit_gaps(config: dict, seed: int, seqs: list, rows: int,
               length: int, control: bool = False,
               root: str | None = None) -> dict:
    """``seqs``: at most ``rows`` (prompt, served) pairs of int lists, each
    at most ``length`` tokens in all.  Returns, over every served token,
    the widest gap by which its reference logit lies below the
    reference's best (``served_gap``), and with ``control`` the same for
    the tokens the int4 control would serve (``control_gap``).  Shapes
    are padded to ``rows`` x ``length`` so that every call of a cell
    runs the same compiled programs.  The blocks are those of the
    architecture module ``config`` names (``archs.load``)."""
    mod = archs.load(config, root)
    arch, packing = mod.arch(config), config["packing"]
    key = W.seed_key(seed)
    frozen = tuple(sorted(arch.items()))
    lens = [len(p) + len(s) - 1 for p, s in seqs]
    if len(seqs) > rows or max(lens) > length:
        raise ValueError(f"{len(seqs)} sequences of up to {max(lens)} "
                         f"tokens exceed {rows} x {length}")
    t = -(-length // ROW_BLOCK) * ROW_BLOCK
    toks = np.zeros((rows, t), np.int32)
    pos, served = [], []
    for i, (p, s) in enumerate(seqs):
        full = list(p) + list(s)
        toks[i, : lens[i]] = full[:-1]
        pos += [(i, len(p) - 1 + j) for j in range(len(s))]
        served += list(s)
    pad = rows * t - len(pos)
    pos += [pos[-1]] * pad
    served += [served[-1]] * pad
    vp = -(-arch["vocab_size"] // 256) * 256
    emb = W.embedding(key, vp, arch["hidden_size"], arch["vocab_size"],
                      jnp.bfloat16)
    xr = emb[jnp.asarray(toks)].astype(jnp.float32)
    del emb
    xc = xr if control else None
    for layer in range(arch["num_hidden_layers"]):
        xr = _layer_jit(xr, key, layer, mod.layer, frozen, packing, 8)
        if control:
            xc = _layer_jit(xc, key, layer, mod.layer, frozen, packing, 4)
    best, got, ctl = jax.device_get(_head(
        xr, xc, jnp.asarray(pos, jnp.int32), jnp.asarray(served, jnp.int32),
        key, frozen, packing))
    n = len(pos) - pad
    out = {"served_gap": float(np.max(best[:n] - got[:n])),
           "tokens": n}
    if control:
        out["control_gap"] = float(np.max(best[:n] - ctl[:n]))
    return out
