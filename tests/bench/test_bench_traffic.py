"""The traffic generators repeat exactly for a seed, differ across
seeds, and send every seed the same work."""

import itertools
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
SCAN = json.loads((ROOT / "bench/configs/scan16x8.json").read_text())
FOREST = json.loads((ROOT / "bench/configs/forest_cb1000.json").read_text())
BIG = 2**31 + 12345


def mix(name):
    return json.loads((ROOT / f"bench/traffic/{name}.json").read_text())


def gen(name, config, seed, n):
    m = mix(name)
    mod = harness.load_module(ROOT / f"bench/traffic/{m['generator']}.py")
    it = mod.requests(m, config, harness.stream(seed, harness.TRAFFIC))
    return list(itertools.islice(it, n))


def same(a, b):
    return all(np.array_equal(x, y) if isinstance(x, np.ndarray) else x == y
               for x, y in zip(a, b))


@pytest.mark.parametrize("name, config", [
    ("count", SCAN), ("select", SCAN), ("offline", FOREST),
    ("online", FOREST)])
def test_repeats_for_a_seed_and_differs_across_seeds(name, config):
    a = gen(name, config, BIG, 25)
    assert same(a, gen(name, config, BIG, 25))
    assert not same(a, gen(name, config, BIG + 1, 25))


def test_every_seed_sends_each_kind_in_equal_shares():
    for seed in (1, 2, BIG):
        reqs = gen("select", SCAN, seed, 50)
        for block in range(10):
            kinds = Counter(r[0] for r in reqs[5 * block:5 * block + 5])
            assert kinds == {"q1": 1, "q2": 1, "q4": 1, "q5": 1,
                             "compound": 1}


def ranges(req):
    """(column, x0, x1) of every range of a scan request."""
    kind, *p = req
    if kind == "q1":
        return [tuple(p)]
    if kind in ("q2", "q3"):
        return [tuple(p[:3]), tuple(p[3:])]
    if kind == "q4":
        return ranges(("q2", *p[1:]))
    if kind == "q5":
        return ranges(("q2", *p[2:]))
    return [r for t in p[1] for r in ranges(t)]


def test_scan_ranges_are_ordered_and_pairs_distinct():
    mx = (1 << SCAN["n_bits"]) - 1
    for req in gen("select", SCAN, 7, 200) + gen("count", SCAN, 7, 200):
        for col, x0, x1 in ranges(req):
            assert 0 <= col < SCAN["columns"] and 0 <= x0 < x1 <= mx
        if req[0] in ("q3", "q2"):
            assert req[1] != req[4]


def test_forest_batches_cover_the_feature_range():
    for name, batch in (("offline", 4096), ("online", 64)):
        (X,) = gen(name, FOREST, 3, 1)
        assert X.shape == (batch, FOREST["features"])
        assert X.min() >= 0 and X.max() < 1 << FOREST["n_bits"]


def test_warm_requests_cover_each_kind_of_the_mix():
    m = mix("select")
    mod = harness.load_module(ROOT / "bench/traffic/scan.py")
    warm = mod.warm_requests(m, SCAN)
    assert [r[0] for r in warm] == [s["kind"] for s in m["requests"]]
    assert warm[-1][1] == ("and", "or")
    assert [t[0] for t in warm[-1][2]] == ["q1", "q2", "q3"]


def test_every_seed_sends_the_same_range_widths():
    """Widths set the records a range selects, and so Q4's and Q5's
    host work.  Over whole blocks of strata (960 requests: 192 of each
    kind, 12 blocks of 16) their mean agrees across seeds far closer
    than i.i.d. draws would (1/3 +- 1.7%), and matches the uniform
    ordered pair's mean width, a third of the domain."""
    mx = (1 << SCAN["n_bits"]) - 1
    means = []
    for seed in (1, 2, 3, BIG):
        widths = [x1 - x0 for req in gen("select", SCAN, seed, 960)
                  for _, x0, x1 in ranges(req)]
        means.append(np.mean(widths) / mx)
    assert max(means) - min(means) < 0.001
    assert abs(np.mean(means) - 1 / 3) < 0.001
