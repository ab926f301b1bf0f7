"""With the lower-precision control in the program's place, or with
the timed path broken underneath by a fault a cell can have, a run of
a tiny cell driven past the look for a chip comes out not correct."""

import pytest

from test_bench_run import drive, root  # noqa: F401  (fixture)


@pytest.mark.parametrize("cell", ["tiny_scan.select", "tiny_forest.batch"])
def test_control_is_not_correct(root, tmp_path, cell):
    result, _ = drive(root, tmp_path, cell, 11, "--control")
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


@pytest.mark.parametrize("cell, fault, number", [
    ("tiny_scan.select", "bitmap_bit", "bitmap_bits_wrong"),
    ("tiny_scan.select", "count_off", "count_abs_err"),
    ("tiny_scan.select", "half_batch", "avg_rel_err"),
    ("tiny_scan.count", "shard_join", "count_abs_err"),
    ("tiny_forest.batch", "half_batch", "pred_max_abs_err"),
    ("tiny_forest.batch", "leaf_addr", "pred_max_abs_err"),
])
def test_faults_are_not_correct(root, tmp_path, cell, fault, number):
    result, _ = drive(root, tmp_path, cell, 12, "--fault", fault)
    assert result["correct"] is False
    c = result["checks"][number]
    assert float(c["value"]) > c["limit"]
