"""A cell found from new files alone, run end to end at a tiny size on
the CPU (Pallas in interpret mode), and the command's refusal off-TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import tiny
from bench import harness


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("cell"))
    return harness.load_cell(root, tiny.write_cell(root))


def test_cell_found_from_new_files(cell):
    assert cell.config["name"] == "tiny"
    assert cell.traffic["arrival"] == "poisson"
    assert [m[0] for m in cell.metrics] == ["tiny.finished"]


def test_run_is_correct_and_reports_its_metric(cell):
    out = harness.run_cell(cell, seed=2 ** 35 + 17, seconds=1.0,
                           trace=False, t_start=time.monotonic(),
                           on_tpu=False)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["tiny.finished"]["value"] == out["attempted"]
    assert list(out)[-1] == "checks"
    assert out["checks"]["compiles_in_window"]["value"] == 0


def test_unknown_workload(cell):
    with pytest.raises(harness.CellError, match="no workload"):
        harness.load_cell(cell.root, "tiny.missing")


def test_unknown_metric_reader(tmp_path):
    root = str(tmp_path)
    name = tiny.write_cell(root)
    os.remove(os.path.join(root, "bench", "metrics", "tiny.finished.py"))
    with pytest.raises(harness.CellError, match="no reader"):
        harness.load_cell(root, name)


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "internlm2-1.8b-base3.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_command_fails_off_tpu():
    r = _command(tiny.REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_command_fails_without_the_program(tmp_path):
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path)
    for p in paths:
        shutil.copytree(os.path.join(tiny.REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _command(str(tmp_path), {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_backlog_opens_on_a_full_batch(tmp_path):
    root = str(tmp_path)
    name = tiny.write_cell(root)
    path = os.path.join(root, "bench", "traffic", "tiny.json")
    with open(path, "w") as f:
        json.dump(dict(tiny.TRAFFIC, arrival="backlog", requests=40), f)
    cell = harness.load_cell(root, name)
    out = harness.run_cell(cell, seed=3, seconds=0.5, trace=False,
                           t_start=time.monotonic(), on_tpu=False)
    assert out["correct"], out["checks"]
    # every slot was filled before the window opened
    assert out["attempted"] >= cell.config["scheduler"]["slots"]
    assert out["failed"] == 0


def test_backlog_served_out_in_the_pre_roll(tmp_path):
    """A backlog that the pre-roll serves to its end leaves an empty
    window: the run closes it as opened, and reads every request."""
    root = str(tmp_path)
    name = tiny.write_cell(root)
    path = os.path.join(root, "bench", "traffic", "tiny.json")
    slots = tiny.CONFIG["scheduler"]["slots"]
    with open(path, "w") as f:
        json.dump(dict(tiny.TRAFFIC, arrival="backlog", requests=slots,
                       preroll_s=30.0), f)
    cell = harness.load_cell(root, name)
    t0 = time.monotonic()
    out = harness.run_cell(cell, seed=5, seconds=0.5, trace=False,
                           t_start=t0, on_tpu=False)
    assert time.monotonic() - t0 < 30.0      # it did not wait for the window
    assert out["correct"], out["checks"]
    assert out["attempted"] == slots and out["failed"] == 0
