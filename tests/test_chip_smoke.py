"""``chip_smoke.py`` at a tiny size on the CPU backend.

The script's own run needs a TPU; here its phases run at small record
counts and forest sizes in interpret mode, so a broken entry point,
query list or check is caught without the chip.  The mesh phase runs in
a child process with four virtual CPU devices (the device count can only
be forced before JAX starts).
"""

import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_to_run_without_a_tpu(smoke, capsys):
    assert smoke.main([]) == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out and "platform': 'cpu'" in out


def test_table_phase(smoke, capsys):
    smoke.table_phase(records=3000, seed=1)
    out = capsys.readouterr().out
    assert out.count("smoke reading, not a benchmark") == 6


def test_forest_phase(smoke, capsys):
    smoke.forest_phase(trees=12, batch=13, seed=2)
    assert "threshold LUT (264, 128)" in capsys.readouterr().out


def test_mesh_phase_on_four_devices_subprocess():
    code = textwrap.dedent("""
        import importlib.util, jax
        assert jax.device_count() == 4, jax.device_count()
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", "chip_smoke.py")
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        smoke.mesh_phase(records=3000, trees=12, batch=13, seed=3)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "table over 4 devices" in out.stdout
    assert "forest batch of 13 over 4 devices" in out.stdout
