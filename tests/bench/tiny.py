"""A tiny copy of the benchmark for the CPU tests: the harness's own
files with small configurations and mixes added beside them, resolved
from a temporary directory exactly as the real cells are."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

SCAN = {"kind": "scan", "records": 8192, "columns": 8, "n_bits": 16,
        "num_chunks": 4, "pud_devices": 1, "shards_per_device": 2,
        "sys_cfg": "DESKTOP", "reduced": ["records"]}
FOREST = {"kind": "forest", "trees": 40, "depth": 6, "features": 8,
          "n_bits": 8, "num_chunks": 1, "pud_devices": 1,
          "sys_cfg": "DESKTOP", "reduced": ["trees"]}
MIXES = {"tiny_count": {"generator": "scan", "check_per_kind": 8,
                        "requests": [{"kind": "q3", "weight": 1}]},
         "tiny_batch": {"generator": "forest", "batch": 16,
                        "check_per_kind": 8}}
CELLS = {"tiny_scan.count": ("tiny_scan", "tiny_count"),
         "tiny_scan.select": ("tiny_scan", "select"),
         "tiny_forest.batch": ("tiny_forest", "tiny_batch")}


def make_root(tmp: Path) -> Path:
    """``tmp`` holding ``bench/`` and a BENCHMARK.json whose cells are
    the tiny ones, with the real metrics."""
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name, cfg in (("tiny_scan", SCAN), ("tiny_forest", FOREST)):
        (tmp / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
    for name, mix in MIXES.items():
        (tmp / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [
        {"name": n, "source": "test", "file": f"bench/configs/{n}.json",
         "reduced": [], "why": "test"} for n in ("tiny_scan", "tiny_forest")]
    bench["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": 1, "why": "test"}
        for n, (c, t) in CELLS.items()]
    scans = [n for n in CELLS if n.startswith("tiny_scan")]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (scans if all(w.startswith("scan")
                                           for w in m["workloads"])
                              else ["tiny_forest.batch"]
                              if all(w.startswith("forest")
                                     for w in m["workloads"])
                              else list(CELLS))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
