"""Served weights made on the device from the seed, one layer slice at a time.

Every weight matrix is drawn directly as ternary codes with a per-column
f32 scale, the form the packed kernels serve:

- ``base3``: one 5-trit value per weight, in [-121, 121], drawn as the
  difference of two uniform integers on [0, 121] (a triangular law);
- ``trit2``: one trit per weight, 0 with probability 1/2 and +1 or -1
  with 1/4 each.

A column's scale is ``u / (code_std * sqrt(K))`` with ``u`` uniform on
[0.5, 1.5], so every matrix has the 1/sqrt(fan_in) spread of a trained
layer's weights.  Norm gains are uniform on [0.8, 1.2] and embedding rows
standard normal, both in the served bf16.  Rows and columns beyond the
published vocabulary (the program pads it to a multiple of 256) are zero.

``codes(...)`` is the one generator.  ``served_params`` packs its codes
into the program's storage layout inside one jitted call (a ``lax.map``
over the stacked matrices, so no whole float or int8 tree ever exists);
``reference.py`` calls the same generator and never sees the packing.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

CODE_STD = {"base3": math.sqrt(2 * (122 ** 2 - 1) / 12.0),
            "trit2": math.sqrt(0.5)}


def seed_key(seed: int) -> jax.Array:
    """A key that depends on every bit of ``seed``: ``jax.random.key``
    keeps only the low 32 bits of a larger seed."""
    key = jax.random.key(seed % 2 ** 32)
    return jax.random.fold_in(key, seed // 2 ** 32)


def leaf_key(key: jax.Array, name: str, layer: int | jax.Array = 0):
    return jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(
        name.encode())), layer)


def codes(key: jax.Array, name: str, layer, k: int, n: int, packing: str,
          n_valid: int | None = None):
    """Codes (K, N) int8 and per-column scales (N,) f32 of one matrix."""
    kk = leaf_key(key, name, layer)
    bits = jax.random.bits(jax.random.fold_in(kk, 0), (k, n), jnp.uint32)
    if packing == "base3":
        a = ((bits & 0xFF) * 122) >> 8
        b = (((bits >> 8) & 0xFF) * 122) >> 8
        c = a.astype(jnp.int32) - b.astype(jnp.int32)
    elif packing == "trit2":
        c = (bits & 1).astype(jnp.int32) - ((bits >> 1) & 1).astype(
            jnp.int32)
    else:
        raise ValueError(f"unknown packing {packing!r}")
    u = jax.random.uniform(jax.random.fold_in(kk, 1), (n,), jnp.float32,
                           0.5, 1.5)
    # a product, not a quotient: compiled and eager code agree on it
    scale = u * (1.0 / (CODE_STD[packing] * math.sqrt(k)))
    if n_valid is not None and n_valid < n:
        c = jnp.where(jnp.arange(n)[None, :] < n_valid, c, 0)
    return c.astype(jnp.int8), scale


def gains(key: jax.Array, name: str, layer, d: int, dtype):
    u = jax.random.uniform(leaf_key(key, name, layer), (d,), jnp.float32,
                           0.8, 1.2)
    return u.astype(dtype)


def embedding(key: jax.Array, rows: int, d: int, n_valid: int, dtype):
    e = jax.random.normal(leaf_key(key, "embed"), (rows, d), jnp.float32)
    return jnp.where(jnp.arange(rows)[:, None] < n_valid, e, 0.0).astype(
        dtype)


def _pack(c: jax.Array, packing: str) -> jax.Array:
    from repro.core.packing import pack_base3, pack_trits2
    return pack_base3(c) if packing == "base3" else pack_trits2(c)


def abstract_params(model, packing: str):
    """Shapes and dtypes of the served tree, as the program builds it."""
    from repro.core.cim_linear import CIMConfig, ternarize_params
    cim = CIMConfig(mode="ternary", packing=packing)
    return jax.eval_shape(lambda: ternarize_params(
        model.init(jax.random.key(0)), cim))


def _path_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def served_params(model, packing: str, seed: int, draw=None):
    """The served tree, made on the device in one jitted call.

    A packed leaf stacked over leading axes (layers, or layers and
    experts) is drawn one matrix at a time at the flat row-major index
    over them.  ``draw(key, name, spec)`` is the architecture module's
    (``bench/archs/``) for unpacked leaves that are neither the embedding
    nor norm gains, such as a router; without it they are an error."""
    from repro.kernels.ops import PackedTernary
    cfg = model.cfg
    shapes = abstract_params(model, packing)
    is_packed = lambda x: isinstance(x, PackedTernary)   # noqa: E731

    def make(key):
        def leaf(path, spec):
            name = _path_name(path)
            if isinstance(spec, PackedTernary):
                stack = spec.data.shape[:-2]
                k, n = spec.shape[-2:]
                n_valid = cfg.vocab_size if name == "unembed" else None

                def one(i):
                    c, s = codes(key, name, i, k, n, packing, n_valid)
                    return _pack(c, packing), s
                if stack:
                    data, scale = jax.lax.map(
                        one, jnp.arange(math.prod(stack)))
                    data = data.reshape(stack + data.shape[1:])
                    scale = scale.reshape(stack + scale.shape[1:])
                else:
                    data, scale = one(0)
                return PackedTernary(data, scale, spec.mode)
            if name == "embed":
                return embedding(key, spec.shape[0], spec.shape[1],
                                 cfg.vocab_size, spec.dtype)
            if spec.ndim > 2 or (spec.ndim == 2 and not name.startswith(
                    "blocks/")):
                if draw is not None:
                    return draw(key, name, spec)
                raise ValueError(f"{name}: a {spec.shape} matrix the "
                                 f"program does not pack")
            if spec.ndim == 2:                      # stacked norm gains
                return jax.lax.map(
                    lambda layer: gains(key, name, layer, spec.shape[1],
                                        spec.dtype),
                    jnp.arange(spec.shape[0]))
            return gains(key, name, 0, spec.shape[0], spec.dtype)
        return jax.tree_util.tree_map_with_path(leaf, shapes,
                                                is_leaf=is_packed)

    return jax.jit(make)(seed_key(seed))
