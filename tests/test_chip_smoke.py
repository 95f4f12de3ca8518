"""``chip_smoke.py`` rehearsed on the CPU at small sizes.

The script itself refuses to run without a TPU; these tests drive its
phase functions directly (same checks, smaller streams) so the smoke
cannot rot between chip runs, check that the script fails off the chip
and outside the repo, and run its four-chip phase on four virtual CPU
devices.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, cwd, **env):
    full = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    full.update(env)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=600)


def _printed_ok(stdout: str) -> bool:
    return any('"ok": true, "device"' in line for line in stdout.splitlines())


def test_trace_and_window_phases(smoke):
    """1,000 requests per core is the smallest size at which the thesis's
    speedup ordering holds on this mix."""
    info, (batch, cells) = smoke.trace_tier(1_000)
    assert info["points"] == len(smoke.MECHS)
    assert info["weighted_speedup"]["lldram"] > 1.0
    info, _ = smoke.window_tier(batch, cells)
    assert info["points"] == 3 * len(smoke.WINDOW_MECHS)


@pytest.mark.parametrize("phase,size", [("oracle_tier", 60),
                                        ("synth_tier", 300),
                                        ("serving_tier", 64)])
def test_phase_passes_its_checks(smoke, phase, size):
    info, _ = getattr(smoke, phase)(size)
    assert info["points"] > 0 and info["sim_requests"] > 0


def test_four_chip_phase_on_virtual_devices():
    """The sharded path with padding (6 points over 4 devices), bitwise
    against one-device simulate(), rehearsed on 4 virtual CPU devices."""
    code = ("import sys, json, chip_smoke as s; "
            "info, _ = s.four_chip_tier(150); print(json.dumps(info))")
    proc = _run(["-c", code], ROOT, JAX_PLATFORMS="cpu",
                PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]),
                XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert proc.returncode == 0, proc.stderr[-4000:]
    info = json.loads(proc.stdout.strip().splitlines()[-1])
    assert info["devices_spanned"] == 4


def test_refuses_without_tpu():
    proc = _run([SCRIPT], ROOT, JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert not _printed_ok(proc.stdout)
    assert "no TPU" in proc.stderr


def test_fails_outside_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run([str(tmp_path / "chip_smoke.py")], tmp_path,
                JAX_PLATFORMS="cpu", PYTHONPATH="")
    assert proc.returncode != 0
    assert not _printed_ok(proc.stdout)
