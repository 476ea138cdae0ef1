"""The check that decides ``correct`` fails a broken served path: the
harness runs as on the chip (its look for a chip skipped), with a fault
planted where tokens are produced or where the KV cache is written."""
import time

import jax.numpy as jnp
import pytest

import tiny
from bench import harness


def _run(tmp_path, monkeypatch, plant):
    root = str(tmp_path)
    cell = harness.load_cell(root, tiny.write_cell(root))
    plant(monkeypatch)
    return harness.run_cell(cell, seed=2 ** 34 + 9, seconds=1.0,
                            trace=False, t_start=time.monotonic(),
                            on_tpu=False)


def _altered_token(monkeypatch):
    from repro.serve import engine
    real = engine.greedy_sample
    monkeypatch.setattr(engine, "greedy_sample",
                        lambda logits: (real(logits) + 1) % 512)


def _state_unchanged(monkeypatch):
    from repro.models import paged_kv
    monkeypatch.setattr(paged_kv, "append_tokens",
                        lambda pool, *a, **k: pool)


@pytest.mark.parametrize("plant", [_altered_token, _state_unchanged],
                         ids=["token_altered", "kv_append_skipped"])
def test_fault_makes_run_incorrect(tmp_path, monkeypatch, plant):
    out = _run(tmp_path, monkeypatch, plant)
    assert not out["correct"]
    gap = out["checks"]["served_gap"]
    assert gap["value"] > gap["limit"]


def test_control_fails_the_limit(tmp_path):
    """The int4 control, put in the program's place through a whole run
    at the tiny size: the check judges the tokens it puts first and reads
    not correct, while the program's own gap on the same run keeps the
    limit."""
    root = str(tmp_path)
    cell = harness.load_cell(root, tiny.write_cell(root))
    out = harness.run_cell(cell, seed=2 ** 33 + 5, seconds=1.0,
                           trace=False, t_start=time.monotonic(),
                           on_tpu=False, control=True)
    limit = tiny.CONFIG["limits"]["served_gap"]
    assert not out["correct"]
    assert out["checks"]["served_gap"]["value"] > limit
    assert out["control"]["control_gap"] > limit
    assert out["control"]["served_gap"] <= limit
    assert list(out)[-1] == "checks"
