"""A tiny cell laid out as a later change would add one: a configuration,
a traffic mix and a metric reader in files of their own, beside a
``BENCHMARK.json`` that names them.  Widths are the least the program
packs (both dimensions of every matrix at least 64)."""
from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CONFIG = {
    "name": "tiny", "source": "a test size",
    "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "num_hidden_layers": 2, "vocab_size": 512, "rms_norm_eps": 1e-6,
    "rope_theta": 1e6, "qk_norm": True, "reduced": [], "assumed": [],
    "packing": "trit2", "domain": "int8", "kv_dtype": "bfloat16",
    "scheduler": {"capacity": 64, "slots": 4, "chunk": 4, "page_size": 8,
                  "num_pages": 40},
    # served_gap at this size: the program 0-0.13 over 11 seeds, the
    # int4 control 0.44-1.84 over 10, the planted faults 1.26-7.0
    "limits": {"served_gap": 0.25, "sample_tokens": 16,
               "sample_requests": 4},
}
TRAFFIC = {"arrival": "poisson", "rate_per_s": 20.0,
           "prompt_lens": [[8, 0.5], [16, 0.5]],
           "output_lens": [[4, 0.5], [12, 0.5]], "block": 4,
           "preroll_s": 0.5}
READER = '''"""Requests that finished (a metric only this cell has)."""


def read(run):
    return float(sum(1 for r in run.requests if r.done_s == r.done_s))
'''


def write_cell(root, packing: str = "trit2") -> str:
    """Lay the cell ``tiny.t`` out under ``root``; returns its name."""
    b = os.path.join(root, "bench")
    for d in ("configs", "traffic", "metrics"):
        os.makedirs(os.path.join(b, d), exist_ok=True)
    with open(os.path.join(b, "configs", "tiny.json"), "w") as f:
        json.dump(dict(CONFIG, packing=packing), f)
    with open(os.path.join(b, "traffic", "tiny.json"), "w") as f:
        json.dump(TRAFFIC, f)
    with open(os.path.join(b, "metrics", "tiny.finished.py"), "w") as f:
        f.write(READER)
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "tiny.t", "config": "tiny",
                       "traffic": "tiny", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "tiny.finished", "unit": "requests",
                        "better": "higher", "bound": 0.01,
                        "source": "host_clock"}],
        "per_layer": [],
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return "tiny.t"
