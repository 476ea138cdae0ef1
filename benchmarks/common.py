"""Shared benchmark utilities: a small trainable classifier (CIFAR-10
stand-in, §4.1) whose linear layers can be executed through every CIM
mode, plus result formatting."""
from __future__ import annotations

import json
import os
import time
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.cim import MacroConfig
from repro.core.cim_linear import CIMConfig
from repro.core.ternary import TernaryTensor, ternarize
from repro.data import ClassTaskConfig, class_batch

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "benchmarks")
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def save_json(name: str, payload: dict) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=str)
    return path


def stable_seed(*parts) -> int:
    """PYTHONHASHSEED-independent seed from a tuple of ints/strings
    (builtin hash() of str is salted per process — irreproducible).
    Canonical implementation lives in ``repro.core.seeding``; this is
    the benchmarks-facing alias the RA004 lint rule recognizes."""
    from repro.core.seeding import stable_seed as _stable_seed
    return _stable_seed(*parts)


# ------------------------------------------------------------------ MLP

def mlp_init(key, dim=128, hidden=256, classes=10):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (dim, hidden)) / jnp.sqrt(dim),
        "w2": jax.random.normal(k2, (hidden, classes)) / jnp.sqrt(hidden),
    }


def mlp_logits(params, x, matmul=None):
    mm = matmul or (lambda a, b: a @ b)
    h = jax.nn.relu(mm(x, params["w1"]))
    return mm(h, params["w2"])


def train_mlp(task: ClassTaskConfig, steps=400, batch=256, lr=3e-2, seed=0):
    params = mlp_init(jax.random.key(seed),  # lint: allow RA004 (caller passes a literal seed)
                      dim=task.dim, classes=task.num_classes)

    @jax.jit
    def step(params, i):
        b = class_batch(task, i, batch)

        def loss_fn(p):
            lg = mlp_logits(p, b["x"])
            return -jnp.mean(jax.nn.log_softmax(lg)[
                jnp.arange(batch), b["y"]])
        loss, g = jax.value_and_grad(loss_fn)(params)
        params = jax.tree.map(lambda p, gg: p - lr * gg, params, g)
        return params, loss

    for i in range(steps):
        params, loss = step(params, jnp.asarray(i))
    return params


def eval_mlp(params, task: ClassTaskConfig, matmul=None, batches=8,
             batch=512, seed_base=10_000):
    correct = total = 0
    for i in range(batches):
        b = class_batch(task, jnp.asarray(seed_base + i), batch)
        lg = mlp_logits(params, b["x"], matmul)
        correct += int(jnp.sum(jnp.argmax(lg, -1) == b["y"]))
        total += batch
    return correct / total


def quantized_matmul(scheme: str, macro: MacroConfig = MacroConfig()):
    """matmul closure that pushes the weight through a quantization scheme
    (and the bit-exact CIM macro for 'cim_*' schemes)."""
    from repro.core.cim import cim_matmul
    from repro.core.ternary import (quantize_8b, quantize_5t_direct,
                                    quantize_8b_truncate_5t)

    def dequant(qfun, x, w):
        q = qfun(w)
        return x @ (q.values.astype(jnp.float32) * q.scale)

    if scheme == "float":
        return lambda x, w: x @ w
    if scheme == "bc8":
        return partial(dequant, quantize_8b)
    if scheme == "tc5_direct":
        return partial(dequant, quantize_5t_direct)
    if scheme == "tc5_truncate":
        return partial(dequant, quantize_8b_truncate_5t)
    if scheme == "cim_exact":
        return lambda x, w: cim_matmul(x, w, macro)
    raise ValueError(scheme)


class Timer:
    def __enter__(self):
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *a):
        self.seconds = time.monotonic() - self.t0
