"""chip_smoke.py on the CPU: its phases pass at tiny sizes, and the script
never reports success off a TPU.

The chip sizes run only on a TPU (``python chip_smoke.py``); these tests
keep the script's phase functions and their size arguments working.
"""
from __future__ import annotations

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

SHARDED_SCRIPT = r"""
import os
from repro.launch.serve import pin_bf16_rounding
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
pin_bf16_rounding()          # as chip_smoke.main does
import chip_smoke
from repro import configs
print("FAILURES", chip_smoke.sharded_phase(
    4, arch_cfg=configs.smoke("internlm2-1.8b"), slots=4, prompt_len=8,
    max_new=3))
"""


def _run(args, **env):
    return subprocess.run([sys.executable, *args], cwd=ROOT,
                          env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
                          capture_output=True, text=True, timeout=600)


def test_kernel_phase_passes_at_tiny_sizes(capsys):
    """Every kernel meets its limit, and the one-bf16-pass controls fail
    theirs (kernel_phase reports either as a failure)."""
    assert chip_smoke.kernel_phase(k=256, n=256, ms=(8, 16),
                                   attn=(4, 2, 16, 2, 2, 128)) == []
    lines = capsys.readouterr().out.splitlines()
    assert sum(line.startswith("kernels: ") for line in lines) == 5


def test_sharded_phase_on_four_virtual_devices():
    r = _run(["-c", SHARDED_SCRIPT],
             PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    assert r.returncode == 0, r.stderr[-4000:]
    assert "FAILURES []" in r.stdout, r.stdout[-4000:]
    assert "pool_spread=True" in r.stdout


def test_main_refuses_off_tpu():
    r = _run([os.path.join(ROOT, "chip_smoke.py")])
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr
