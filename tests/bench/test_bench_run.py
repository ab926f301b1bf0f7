"""``bench/run.py`` refuses to run without a TPU and prints no result;
a run of a tiny cell driven past that check on the CPU is correct and
prints what the contract asks for."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from tiny import make_root

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def env(tmp_path):
    e = dict(os.environ, JAX_PLATFORMS="cpu",
             JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    e.pop("PYTHONPATH", None)
    return e


def test_run_exits_nonzero_without_a_tpu(tmp_path):
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "scan16x8.count",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env(tmp_path), capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_run_exits_nonzero_for_an_unknown_cell(tmp_path):
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "no.such",
         "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env(tmp_path), capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_root"))


def drive(root, tmp_path, cell, seed, *flags):
    p = subprocess.run(
        [sys.executable, str(HERE / "cpu_run.py"), str(root), cell,
         str(seed), "0.5", *flags],
        env=env(tmp_path), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


@pytest.mark.parametrize("cell", ["tiny_scan.count", "tiny_scan.select",
                                  "tiny_forest.batch"])
def test_sound_runs_are_correct(root, tmp_path, cell):
    result, err = drive(root, tmp_path, cell, 2**31 + 3)
    assert result["correct"] is True, err[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check ")
    assert "setup_s" in result["metrics"]


def test_traced_run_reads_per_layer_metrics(root, tmp_path):
    result, err = drive(root, tmp_path, "tiny_scan.select", 5, "--trace")
    assert result["correct"] is True, err[-3000:]
    assert "session_overhead_ms" in result["metrics"]
    assert "setup_s" not in result["metrics"]
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
