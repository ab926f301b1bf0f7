"""Drive a run of the harness on the CPU, past its look for a chip, and
print the result line: with the program as it is, with the control in
its place, or with the timed path broken underneath by one fault.

    JAX_PLATFORMS=cpu python tests/bench/cpu_run.py ROOT CELL SEED SECONDS \\
        [--trace] [--control] [--fault NAME]

Faults (each breaks what the program returns, at the harness's boundary
with it: the cell's ``Program`` of ``bench/kinds/<kind>.py``; the wrong
answer is worked out from the request, the cell's data and the NumPy
reference in ``bench/ref/``, and touches nothing inside the program):

* ``bitmap_bit``: one bit of every returned bitmap flipped;
* ``count_off``: every count off by one;
* ``half_batch``: half of the result left out -- the second half of
  every bitmap cleared, Q4's and Q5's averages taken over the records
  that remain, and the second half of every forest batch scored as the
  first;
* ``shard_join``: the join of the shards' counts left out (Q3's and
  Q5's counts over the first record shard's records only);
* ``leaf_addr``: instance 0's prediction with one address bit of tree 0
  flipped (``leaves[0, a ^ 1]`` in place of ``leaves[0, a]``).
"""

from __future__ import annotations

import argparse
import math
import sys
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def _half(mask: np.ndarray) -> np.ndarray:
    mask = mask.copy()
    mask[mask.shape[0] // 2:] = False
    return mask


class ScanFaults:
    """The wrong answers of a scan cell's faults."""

    def __init__(self, config: dict, data: dict) -> None:
        from bench.ref import scan

        cols, n = data["columns"], config["n_bits"]
        per = math.ceil(config["records"] / (config["pud_devices"]
                                             * config["shards_per_device"]))
        self.ref = scan.Reference(cols, n)
        self.first = scan.Reference([c[:per] for c in cols], n)

    def bitmap_bit(self, req, got):
        if isinstance(got, np.ndarray):
            got = got.copy()
            got[0] = not got[0]
        return got

    def count_off(self, req, got):
        return got + 1 if isinstance(got, (int, np.integer)) else got

    def half_batch(self, req, got):
        if isinstance(got, np.ndarray):
            return _half(got)
        if req[0] == "q4":
            fk, *q2 = req[1:]
            return self.ref.average(fk, _half(self.ref.term(("q2", *q2))))
        if req[0] == "q5":
            fl, fk, *q3 = req[1:]
            return self.ref.bracket(fl, int(self.ref.average(
                fk, _half(self.ref.term(("q3", *q3))))))
        return got

    def shard_join(self, req, got):
        if req[0] == "q3":
            return int(self.first.term(req).sum())
        if req[0] == "q5":
            fl, fk, *q3 = req[1:]
            return self.first.bracket(fl, int(self.ref.average(
                fk, self.ref.term(("q3", *q3)))))
        return got


class ForestFaults:
    """The wrong answers of a forest cell's faults."""

    def __init__(self, config: dict, data: dict) -> None:
        self.forest = data["forest"]

    def half_batch(self, X, got):
        got = np.array(got, copy=True)
        half = got.shape[0] // 2
        got[half:2 * half] = got[:half]
        return got

    def leaf_addr(self, X, got):
        from bench.ref import forest

        a = int(forest.leaf_addrs(self.forest, X[:1])[0, 0])
        leaves = self.forest.leaves
        got = np.array(got, copy=True)
        got[0] += leaves[0, a ^ 1] - leaves[0, a]
        return got


FAULTS = {"scan": ScanFaults, "forest": ForestFaults}


def plant(fault: str, kind) -> None:
    """Put a program whose results carry ``fault`` in place of the kind
    module's ``Program``."""
    program = kind.Program

    class Faulty(program):
        def __init__(self, config: dict, data: dict) -> None:
            super().__init__(config, data)
            faults = FAULTS[config["kind"]](config, data)
            if not hasattr(faults, fault):
                raise ValueError(f"a {config['kind']} cell has no fault "
                                 f"{fault!r}")
            self.fault = getattr(faults, fault)

        def __call__(self, req):
            got, wall = super().__call__(req)
            return self.fault(req, got), wall

    kind.Program = Faulty


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("root")
    ap.add_argument("cell")
    ap.add_argument("seed", type=int)
    ap.add_argument("seconds", type=float)
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault")
    args = ap.parse_args()

    from bench import harness

    cell = harness.resolve(args.cell, Path(args.root))
    if args.fault:
        plant(args.fault, cell.kind)
    result = harness.run(cell, args.seed, args.seconds, args.trace,
                         system="control" if args.control else "program")
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
