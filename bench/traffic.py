"""Request streams generated from a traffic file and the seed.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

    {"arrival": "poisson", "rate_per_s": 4.0,
     "prompt_lens": [[128, 0.4], [256, 0.3], ...],
     "output_lens": [[128, 0.3], ...]}

or ``"arrival": "backlog"`` with ``"requests": n``: n requests all due
when the window opens.  ``"preroll_s"`` starts a Poisson stream that
many seconds before the window, so that the window sees a system that
has filled up.  ``"source"`` says where the lengths and the rate come
from; the generator does not read it.

Every seed gets the same work in another order, so that runs differ by
the system and not by the draw: each block of ``block`` requests (the
file's ``block``, default 20) holds each length in proportion to its
weight, and the Poisson gaps of a block are the exponential law's
quantiles at the block's midpoints, scaled to the rate.  The seed
shuffles lengths and gaps within each block and draws the prompt tokens.
The arrival pattern follows ``repro.serve.trace.poisson_arrivals``
(offsets of a Poisson stream from the window's start).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Req:
    uid: int
    due_s: float
    prompt: np.ndarray
    max_new: int


def _menu_block(menu, block: int) -> list:
    """Lengths of one block: each length in proportion to its weight,
    largest remainders first, so the block's counts sum to ``block``."""
    total = sum(w for _, w in menu)
    exact = [block * w / total for _, w in menu]
    counts = [int(math.floor(e)) for e in exact]
    order = sorted(range(len(menu)), key=lambda i: counts[i] - exact[i])
    for i in order[: block - sum(counts)]:
        counts[i] += 1
    return [length for (length, _), c in zip(menu, counts)
            for _ in range(c)]


def _gap_block(rate: float, block: int) -> list:
    return [-math.log(1.0 - (i + 0.5) / block) / rate for i in range(block)]


def generate(spec: dict, seed: int, seconds: float, vocab: int) -> list:
    """Requests due in ``[-preroll_s, seconds)`` (Poisson) or the
    backlog, due at ``-preroll_s``."""
    rng = np.random.default_rng(seed)
    block = int(spec.get("block", 20))
    kind = spec["arrival"]
    if kind == "poisson":
        n_max = None
        rate = float(spec["rate_per_s"])
    elif kind == "backlog":
        n_max = int(spec["requests"])
    else:
        raise ValueError(f"unknown arrival kind {kind!r}")
    reqs = []
    t = -float(spec.get("preroll_s", 0.0))
    while True:
        prompts = _menu_block(spec["prompt_lens"], block)
        outs = _menu_block(spec["output_lens"], block)
        rng.shuffle(prompts)
        rng.shuffle(outs)
        gaps = _gap_block(rate, block) if n_max is None else [0.0] * block
        rng.shuffle(gaps)
        for p, o, g in zip(prompts, outs, gaps):
            t += g
            if (n_max is None and t >= seconds) or (
                    n_max is not None and len(reqs) >= n_max):
                return reqs
            tokens = rng.integers(0, vocab, size=p, dtype=np.int32)
            reqs.append(Req(len(reqs), t, tokens, o))


def shapes(spec: dict) -> tuple[list, int]:
    """The prompt lengths a cell can send, and the longest request
    (prompt + output), for warm-up and capacity checks."""
    prompts = sorted({int(p) for p, _ in spec["prompt_lens"]})
    longest = max(prompts) + max(int(o) for o, _ in spec["output_lens"])
    return prompts, longest
