"""The byte counts of the two kernels against hand-worked values, and
how each kernel is told apart in a trace."""

import json
from pathlib import Path

import pytest

from bench import kernels, trace
from test_bench_trace import FUSION, LEAF, PRED

ROOT = Path(__file__).resolve().parents[2]
SCAN = json.loads((ROOT / "bench/configs/scan16x8.json").read_text())
FOREST = json.loads((ROOT / "bench/configs/forest_cb1000.json").read_text())
R = (0, 10, 20)          # a range: column 0, 10 < f < 20
WORDS = 8 * 65536 * 4    # one row across the 8 shards: 2 MiB


def test_lut_rows():
    assert kernels.lut_rows(16, 4) == 4 * 15 + 2
    assert kernels.lut_rows(8, 1) == 255 + 2
    assert kernels.lut_rows(10, 3) == 15 + 7 + 7 + 2   # chunks 4, 3, 3


@pytest.mark.parametrize("req, rows", [
    (("q3", *R, *R), 2 * 16),                       # count only
    (("q1", *R), 16 + 1),                           # + the bitmap
    (("q2", *R, *R), 32 + 1),
    (("q4", 2, *R, *R), 32 + 1),
    (("q5", 3, 2, *R, *R), 48 + 1),                 # two launches
    (("compound", ("and", "or"),
      (("q1", *R), ("q2", *R, *R), ("q3", *R, *R))), 5 * 16 + 1),
])
def test_predicate_bytes(req, rows):
    assert kernels.predicate_bytes(SCAN, req) == rows * WORDS


def test_q3_on_scan16x8_needs_64_mib():
    assert kernels.predicate_bytes(SCAN, ("q3", *R, *R)) == 67_108_864


def test_leafbits_bytes():
    words = 188                                     # ceil(6000 / 32)
    fixed = (257 + 8) * words * 4                   # LUT + masks
    assert kernels.leafbits_bytes(FOREST, 4096) == \
        fixed + 4096 * (16 + words) * 4 == 3_541_616
    assert kernels.leafbits_bytes(FOREST, 64) == fixed + 64 * (16 + words) * 4


def test_kernels_are_told_apart():
    labels = {k: trace.op_label(v) for k, v in
              (("pred", PRED), ("leaf", LEAF), ("fusion", FUSION))}
    assert [k for k, v in labels.items() if kernels.PREDICATE.search(v)] \
        == ["pred"]
    assert [k for k, v in labels.items() if kernels.LEAFBITS.search(v)] \
        == ["leaf"]
