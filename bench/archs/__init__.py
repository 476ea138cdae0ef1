"""Architectures, one module each, named by the configuration file.

A configuration file may hold ``"architecture": "<module>"``; without the
key the module is ``dense``.  The module is the file
``bench/archs/<module>.py`` under the cell's root (or, where that has
none, under this checkout), so a new architecture lands as a new file
beside its configuration and traffic files.  It
defines what the harness, the reference and the work counts need to
know of the architecture, one function each:

- ``arch(config) -> dict``: the static sizes, under the configuration
  file's names, that ``layer``, ``decode_matmul`` and ``attention`` get.
  Every architecture also gives ``hidden_size``, ``vocab_size``,
  ``rms_norm_eps`` and ``num_hidden_layers``, which the embedding, the
  output head and the reference's loop over layers read.
- ``model_config(config) -> ModelConfig``: the program's model, built by
  the harness through ``repro.models.registry.build``.
- ``layer(x, key, layer, arch, packing, act_bits)``: the reference's
  block ``layer`` over ``x`` (B, T, d) float32, its weights drawn from
  ``key`` by ``bench/weights.py``.  ``reference.logit_gaps`` calls it once
  per layer; a period of mixed layer kinds is the module's own business.
  A packed stack over several leading axes is drawn at the flat
  row-major index over them: expert ``e`` of layer ``l`` of ``E`` is
  ``weights.codes(key, name, l * E + e, ...)``.
- ``decode_matmul(arch, packing, rows, steps, counters) -> dict``: the
  ``ops`` and ``bytes`` of the packed matmuls of ``steps`` decode steps
  that serve ``rows`` live rows in all; ``counters`` are the scheduler's
  counters over the window (``harness.COUNTERS``), for a module whose
  bytes read depend on what the steps routed.
- ``attention(arch, contexts) -> dict``: the ``flops`` and ``bytes`` of
  one decode query per entry of ``contexts`` (its cached positions),
  over all layers.
- ``draw(key, name, spec)``, optional: an unpacked leaf of the served
  tree that ``weights.served_params``'s own rules (embedding, norm gains)
  do not cover, such as a router, made on the device from ``key``.
  Without it such a leaf is an error.
"""
from __future__ import annotations

import importlib.util
import os

DEFAULT = "dense"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_loaded: dict = {}      # path -> module, so jitted callers see one function


def find(config: dict, root: str | None = None) -> str:
    """The path of the architecture module ``config`` names:
    ``bench/archs/<module>.py`` under the cell's ``root`` or, where that
    has none, this checkout's.  Imports nothing."""
    name = config.get("architecture", DEFAULT)
    paths = [os.path.join(r, "bench", "archs", name + ".py")
             for r in dict.fromkeys((root or ROOT, ROOT))]
    for path in paths:
        if os.path.isfile(path):
            return path
    from bench.harness import CellError
    raise CellError(f"architecture {name!r} has no module at "
                    + " or ".join(paths))


def load(config: dict, root: str | None = None):
    """The architecture module ``config`` names (``find``)."""
    path = find(config, root)
    if path not in _loaded:
        name = os.path.basename(path)[:-3]
        spec = importlib.util.spec_from_file_location(
            "bench_arch_" + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]
