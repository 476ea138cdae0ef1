"""Assigned-architecture registry: ``--arch <id>`` resolves here.

Each module defines CONFIG (the exact published configuration from the
assignment table) and SMOKE (a reduced same-family configuration used by
CPU smoke tests).  On the CPU, full configs are only lowered by the
dry-run (ShapeDtypeStruct; no allocation); on a TPU, ``chip_smoke.py``
serves internlm2-1.8b's full config.
"""
from __future__ import annotations

import importlib

from .shapes import SHAPES, ShapeCell, runnable  # noqa: F401

ARCHS = (
    "zamba2-7b",
    "xlstm-125m",
    "whisper-large-v3",
    "kimi-k2-1t-a32b",
    "mixtral-8x7b",
    "llama-3.2-vision-90b",
    "qwen3-14b",
    "phi3-mini-3.8b",
    "glm4-9b",
    "internlm2-1.8b",
)

_MODULES = {a: a.replace("-", "_").replace(".", "_") for a in ARCHS}


def _mod(name: str):
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; choose from {ARCHS}")
    return importlib.import_module(f"repro.configs.{_MODULES[name]}")


def get(name: str):
    """Full (paper-table) ModelConfig for an assigned architecture."""
    return _mod(name).CONFIG


def smoke(name: str):
    """Reduced same-family ModelConfig for CPU smoke tests."""
    return _mod(name).SMOKE


def cells(name: str):
    """All 4 assigned shape cells with their runnability for this arch."""
    cfg = get(name)
    return [(c, *runnable(cfg, c)) for c in SHAPES.values()]
