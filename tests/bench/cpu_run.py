"""Drive a run of the harness on the CPU, past its look for a chip, and
print the result line: with the program as it is, with the control in
its place, or with the timed path broken underneath by one fault.

    JAX_PLATFORMS=cpu python tests/bench/cpu_run.py ROOT CELL SEED SECONDS \\
        [--trace] [--control] [--fault NAME]

Faults (each breaks what the program produces, where it produces it):

* ``bitmap_bit``: one bit of every returned bitmap flipped;
* ``count_off``: every count off by one;
* ``half_batch``: half of the result left out -- the second half of
  every bitmap cleared (so Q4's and Q5's averages are taken over the
  rest) and the second half of every forest batch scored as the first;
* ``shard_join``: the join of the shards' counts left out (only the
  first shard's count returned);
* ``leaf_addr``: one leaf address bit flipped in every forest batch.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def plant(fault: str) -> None:
    from repro.kernels import fused_session as fs

    if fault in ("bitmap_bit", "half_batch"):
        orig_bitmap = fs.FusedTableExec._bitmap

        def bitmap(self, bm):
            out = orig_bitmap(self, bm).copy()
            if fault == "bitmap_bit":
                out[0] = not out[0]
            else:
                out[out.shape[0] // 2:] = False
            return out

        fs.FusedTableExec._bitmap = bitmap
    if fault == "count_off":
        orig_one = fs.FusedTableExec._one

        def one(self, q):
            got = orig_one(self, q)
            return got + 1 if isinstance(got, int) else got

        fs.FusedTableExec._one = one
    if fault == "shard_join":
        orig_fn = fs.FusedTableExec._fn

        def fn(self, num_ranges, disjunction):
            inner = orig_fn(self, num_ranges, disjunction)

            def first_shard(lut, idx):
                bm, _ = inner(lut, idx)
                return bm, inner(lut[:1], idx)[1]

            return first_shard

        fs.FusedTableExec._fn = fn
    if fault in ("half_batch", "leaf_addr"):
        orig_addrs = fs.FusedGbdtExec.leaf_addrs

        def leaf_addrs(self, X):
            out = orig_addrs(self, X).copy()
            if fault == "leaf_addr":
                out[0, 0] ^= 1
            else:
                half = out.shape[0] // 2
                out[half:2 * half] = out[:half]
            return out

        fs.FusedGbdtExec.leaf_addrs = leaf_addrs


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

    if args.fault:
        plant(args.fault)
    cell = harness.resolve(args.cell, Path(args.root))
    result = harness.run(cell, args.seed, args.seconds, args.trace,
                         system="control" if args.control else "program")
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
