"""The harness finds a cell, its configuration, its mix and its metric
readers purely from files -- ones a later change adds too -- and
BENCHMARK.json keeps to the shape the harness relies on."""

import json
import re
from pathlib import Path

import pytest

from bench import harness
from tiny import make_root

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = harness.resolve(cell)
    assert c.chips == 1
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer and set(c.readers) == {m["name"] for m in c.per_layer}
    for m in c.end_to_end:
        assert m["name"] in harness.END_TO_END
    for reader in c.readers.values():
        assert callable(reader.read)
    for key in c.config["reduced"]:
        assert key in c.config


def test_names_units_and_bounds():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in metrics:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
        assert c["reduced"] == json.loads((ROOT / c["file"]).read_text())[
            "reduced"]


def test_added_files_are_found_by_name(tmp_path):
    """A later change adds a configuration, a mix and a per-layer
    metric as new files and BENCHMARK.json entries only."""
    root = make_root(tmp_path)
    (root / "bench/configs/narrow.json").write_text(json.dumps({
        "kind": "scan", "records": 4096, "columns": 4, "n_bits": 8,
        "num_chunks": 2, "pud_devices": 1, "shards_per_device": 1,
        "sys_cfg": "DESKTOP", "reduced": []}))
    (root / "bench/traffic/ranges.json").write_text(json.dumps({
        "generator": "scan", "check_per_kind": 4,
        "requests": [{"kind": "q1", "weight": 3},
                     {"kind": "q3", "weight": 1}]}))
    (root / "bench/metrics/requests_traced.py").write_text(
        "def read(w):\n    return len(w.requests)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "narrow", "source": "test",
                             "file": "bench/configs/narrow.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "narrow.ranges", "config": "narrow",
                               "traffic": "ranges", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "requests_traced", "unit": "req",
                               "better": "higher", "source": "host_clock",
                               "layer": "session",
                               "moves": "requests_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.resolve("narrow.ranges", root)
    assert cell.config["n_bits"] == 8 and cell.mix["requests"][0] == {
        "kind": "q1", "weight": 3}
    # without a workloads key a metric is read wherever its end-to-end
    # metric is reported
    assert "requests_traced" in cell.readers
    assert cell.readers["requests_traced"].read(
        harness.Window([1, 2], None, 0, 1, cell, "x")) == 2
    assert "requests_traced" in harness.resolve("tiny_scan.count",
                                                root).readers
    reqs = cell.traffic.requests(cell.mix, cell.config,
                                 harness.stream(1, harness.TRAFFIC))
    first = [next(reqs)[0] for _ in range(8)]
    assert sorted(first) == ["q1"] * 6 + ["q3"] * 2


def test_missing_files_are_errors(tmp_path):
    root = make_root(tmp_path)
    with pytest.raises(harness.BenchError, match="unknown workload"):
        harness.resolve("no.such", root)
    (root / "bench/traffic/select.json").unlink()
    with pytest.raises(harness.BenchError, match="missing file"):
        harness.resolve("tiny_scan.select", root)
