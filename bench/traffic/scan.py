"""Generator of scan traffic: an endless, seeded sequence of request
tuples (see ``bench/ref/scan.py``) from a mix file's parameters.

Mix parameters (``bench/traffic/<mix>.json``):

* ``requests``: list of ``{"kind", "weight"}``; kind is q1-q5 or
  ``compound`` (which also gives ``terms``, a list of q1/q2/q3, and
  ``ops``, one ``and``/``or`` per connective).  Every block of
  ``sum(weight)`` requests holds each kind exactly ``weight`` times, in
  a seeded order.
* Columns: a range's column is drawn uniformly; the two ranges of a
  q2/q3 term (and of q4/q5's WHERE) are on distinct columns; q4's and
  q5's aggregate columns are drawn uniformly from all columns.
* Bounds: each range ``x0 < f < x1`` is a uniformly drawn ordered pair
  of distinct values in ``[0, 2**n_bits - 1]``, drawn by stratified
  sampling so that every seed sends the same range widths: within each
  block of ``STRATA`` requests of a kind, the widths of its ``j``-th
  range take one point from each of ``STRATA`` equal-probability
  strata of the widths' distribution (density ``2 (1 - w)``), at a
  seeded place inside the stratum and in a seeded order; the position
  of the range is then uniform.  A range's width sets how many records
  it selects, and so the host's work in Q4 and Q5: i.i.d. widths would
  make that work differ from seed to seed.
"""

from __future__ import annotations

import math
from typing import Iterator

import numpy as np

#: Strata of each range's width per block of a kind's requests.
STRATA = 16


class _Draw:
    def __init__(self, rng: np.random.Generator, columns: int,
                 n_bits: int) -> None:
        self.rng = rng
        self.columns = columns
        self.mx = (1 << n_bits) - 1
        self.strata: dict[tuple[str, int], list[float]] = {}
        self.kind = ""
        self.slot = 0

    def col(self) -> int:
        return int(self.rng.integers(0, self.columns))

    def quantile(self) -> float:
        """The next point in [0, 1) of this kind's current range slot."""
        key = (self.kind, self.slot)
        self.slot += 1
        left = self.strata.get(key)
        if not left:
            left = list((self.rng.permutation(STRATA)
                         + self.rng.random(STRATA)) / STRATA)
            self.strata[key] = left
        return left.pop()

    def bounds(self) -> tuple[int, int]:
        u = self.quantile()
        width = min(max(1, round(self.mx * (1.0 - math.sqrt(1.0 - u)))),
                    self.mx)
        x0 = int(self.rng.integers(0, self.mx - width + 1))
        return x0, x0 + width

    def pair(self) -> tuple:
        fi, fj = (int(v) for v in self.rng.choice(self.columns, 2,
                                                  replace=False))
        return (fi, *self.bounds(), fj, *self.bounds())

    def term(self, kind: str) -> tuple:
        if kind == "q1":
            return ("q1", self.col(), *self.bounds())
        return (kind, *self.pair())

    def request(self, spec: dict) -> tuple:
        kind = spec["kind"]
        self.kind, self.slot = kind, 0
        if kind in ("q1", "q2", "q3"):
            return self.term(kind)
        if kind == "q4":
            return ("q4", self.col(), *self.pair())
        if kind == "q5":
            return ("q5", self.col(), self.col(), *self.pair())
        if kind == "compound":
            return ("compound", tuple(spec["ops"]),
                    tuple(self.term(t) for t in spec["terms"]))
        raise ValueError(f"unknown request kind {kind!r}")


def requests(mix: dict, config: dict,
             rng: np.random.Generator) -> Iterator[tuple]:
    draw = _Draw(rng, config["columns"], config["n_bits"])
    block = [spec for spec in mix["requests"]
             for _ in range(int(spec["weight"]))]
    while True:
        for k in rng.permutation(len(block)):
            yield draw.request(block[k])


def warm_requests(mix: dict, config: dict) -> list[tuple]:
    """One request of each kind in the mix, with full-width ranges (so
    that Q5's average is nonzero and its second launch runs): together
    they use every compiled shape the mix uses."""
    mx = (1 << config["n_bits"]) - 1
    full = (0, mx)
    wide = {"q1": ("q1", 0, *full),
            "q2": ("q2", 0, *full, 1, *full),
            "q3": ("q3", 0, *full, 1, *full)}
    out = []
    for spec in mix["requests"]:
        kind = spec["kind"]
        if kind in wide:
            out.append(wide[kind])
        elif kind == "q4":
            out.append(("q4", 2, *wide["q2"][1:]))
        elif kind == "q5":
            out.append(("q5", 3, 2, *wide["q3"][1:]))
        else:
            out.append(("compound", tuple(spec["ops"]),
                        tuple(wide[t] for t in spec["terms"])))
    return out
