"""``bench/run.py`` refuses to run without a TPU and prints no result;
a run of a tiny cell driven past that check on the CPU is correct and
prints what the contract asks for; the window's bookkeeping keeps no
request's payload, and the roofline readers count the same bytes from
what it keeps as from the requests."""

import json
import os
import subprocess
import sys
import weakref
from dataclasses import astuple
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, kernels
from bench.trace import Interval, Trace
from test_bench_trace import LEAF, PRED
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


def _kind(name):
    return harness.load_module(ROOT / "bench" / "kinds" / f"{name}.py")


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_drive_keeps_no_request_array():
    """A forest cell's ``[4096, 8]`` int64 batches: what ``drive()``
    returns holds none of them, and only the sampler's few stay alive."""
    forest = _kind("forest")
    made = []

    def batches():
        rng = np.random.default_rng(0)
        while True:
            X = rng.integers(0, 256, (4096, 8), dtype=np.int64)
            made.append(weakref.ref(X))
            yield X
            del X

    def system(X):
        return np.zeros(len(X), np.float32), 1.0

    sampler = harness.Sampler(3, harness.stream(1, harness.SAMPLE))
    gen = batches()
    reqs, span_s = harness.drive(system, gen, 0.3, forest, sampler, False)
    gen.close()
    assert len(reqs) > 10 and span_s > 0
    for r in reqs:
        assert not any(isinstance(v, np.ndarray) for v in astuple(r))
        assert r.desc == 4096
    assert sum(ref() is not None for ref in made) == len(sampler.samples) == 3
    assert [r.i for r in reqs] == list(range(len(reqs)))


def _window(desc, label, config):
    """Two traced requests, each with one kernel launch of 20 ns."""
    tr = Trace(device={"/device:TPU:0": [Interval(10, 30, label),
                                         Interval(60, 80, label)]},
               spans=[Interval(0, 40, "bench_request", (("i", "0"),)),
                      Interval(50, 100, "bench_request", (("i", "1"),))])
    reqs = [harness.Request(i, d, 1e-3, None) for i, d in enumerate(desc)]
    return harness.Window(reqs, tr, 0, 100, SimpleNamespace(config=config),
                          "TPU v5 lite")


R = (0, 10, 20)


@pytest.mark.parametrize("metric, kind, config, requests, label", [
    ("predicate_roofline", "scan", "scan16x8",
     [("q3", *R, 1, 5, 9), ("compound", ("and", "or"),
                            (("q1", *R), ("q2", *R, *R), ("q3", *R, *R)))],
     PRED),
    ("leafbits_roofline", "forest", "forest_cb1000",
     [np.zeros((4096, 8), np.int64), np.ones((64, 8), np.int64)], LEAF),
])
def test_roofline_bytes_from_the_descriptor(metric, kind, config, requests,
                                            label):
    """A reader given the descriptors reads what the byte counts of the
    requests themselves give, as before the window kept descriptors."""
    cfg, k = _config(config), _kind(kind)
    if kind == "scan":
        want = [kernels.predicate_bytes(cfg, req) for req in requests]
        got = [kernels.predicate_bytes(cfg, k.describe(req))
               for req in requests]
    else:
        want = [kernels.leafbits_bytes(cfg, len(X)) for X in requests]
        got = [kernels.leafbits_bytes(cfg, k.describe(X)) for X in requests]
    assert got == want
    reader = harness.load_module(ROOT / "bench" / "metrics" / f"{metric}.py")
    w = _window([k.describe(req) for req in requests], label, cfg)
    assert reader.read(w) == pytest.approx(
        100.0 * sum(want) / 819e9 / 40e-9, rel=1e-12)
