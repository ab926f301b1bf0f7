"""Plain NumPy reference for the scan deployments: the data generator
and the semantics of Q1-Q5 and compound predicates.

Copied from the paper's generator and references so that the yardstick
does not move when the program does; it imports nothing of the program.
A request is a plain tuple:

    ("q1", fi, x0, x1)                       -> bool bitmap
    ("q2", fi, x0, x1, fj, y0, y1)           -> bool bitmap (AND)
    ("q3", fi, x0, x1, fj, y0, y1)           -> int count (OR)
    ("q4", fk, fi, x0, x1, fj, y0, y1)       -> float AVERAGE(f_k) over Q2
    ("q5", fl, fk, fi, x0, x1, fj, y0, y1)   -> int, Q5 of the paper
    ("compound", ops, terms)                 -> bool bitmap of
        terms[0] <ops[0]> terms[1] ..., left-associative; each term is a
        q1/q2/q3 tuple and stands for its WHERE clause

Bounds are exclusive: ``x0 < f < x1``.
"""

from __future__ import annotations

import numpy as np


def generate(records: int, n_bits: int, columns: int,
             rng: np.random.Generator) -> list[np.ndarray]:
    """``columns`` arrays of ``records`` values drawn uniformly from
    ``[0, 2**n_bits)`` (the paper's generator), as uint64."""
    return [rng.integers(0, 1 << n_bits, records, dtype=np.uint64)
            for _ in range(columns)]


class Reference:
    """Evaluates requests over a table held as its own copy of the
    columns.  ``shift`` > 0 compares only the top ``n_bits - shift``
    bits of values and bounds, and ``avg_dtype`` sets the precision of
    the averages: the lower-precision control is ``Reference(...,
    shift=8, avg_dtype=np.float32)``."""

    def __init__(self, columns: list[np.ndarray], n_bits: int,
                 shift: int = 0, avg_dtype=np.float64) -> None:
        narrow = np.uint16 if n_bits <= 16 else np.uint32
        self.cols = [np.asarray(c).astype(narrow) for c in columns]
        self.keys = ([c >> narrow(shift) for c in self.cols] if shift
                     else self.cols)
        self.n_bits = n_bits
        self.shift = shift
        self.avg_dtype = avg_dtype

    def where(self, fi: int, x0: int, x1: int) -> np.ndarray:
        f = self.keys[fi]
        return (f > (x0 >> self.shift)) & (f < (x1 >> self.shift))

    def term(self, t: tuple) -> np.ndarray:
        kind, *p = t
        if kind == "q1":
            return self.where(*p)
        fi, x0, x1, fj, y0, y1 = p
        a, b = self.where(fi, x0, x1), self.where(fj, y0, y1)
        return (a & b) if kind == "q2" else (a | b)

    def average(self, fk: int, mask: np.ndarray) -> float:
        vals = self.cols[fk][mask]
        if not vals.size:
            return 0.0
        return float(vals.mean(dtype=self.avg_dtype))

    def bracket(self, fl: int, avg: int) -> int:
        """Q5's count: records with ``avg < f_l < 2 * avg`` (clamped
        to the key range), 0 where that range is empty."""
        hi = min(2 * avg, (1 << self.n_bits) - 1)
        if avg >= hi:
            return 0
        return int(self.where(fl, avg, hi).sum())

    def __call__(self, req: tuple):
        kind, *p = req
        if kind in ("q1", "q2"):
            return self.term(req)
        if kind == "q3":
            return int(self.term(req).sum())
        if kind == "q4":
            fk, *q2 = p
            return self.average(fk, self.term(("q2", *q2)))
        if kind == "q5":
            fl, fk, *q3 = p
            return self.bracket(fl, int(self.average(
                fk, self.term(("q3", *q3)))))
        if kind == "compound":
            ops, terms = p
            bm = self.term(terms[0])
            for op, t in zip(ops, terms[1:]):
                nxt = self.term(t)
                bm = (bm & nxt) if op == "and" else (bm | nxt)
            return bm
        raise ValueError(f"unknown request kind {kind!r}")
