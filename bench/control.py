#!/usr/bin/env python3
"""Run a cell's lower-precision control: the NumPy reference computed
one precision step below the configuration's, put in the program's
place, through the cell's own set-up, traffic and comparison.

    python3 bench/control.py --workload scan16x8.count --seeds 11,12,13 --seconds 3

Prints one line per seed with the compared numbers, and exits 0 only
when every seed's run comes out not correct: the comparison can tell a
lower-precision result from the program's.  The benchmark's own runs
never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.resolve(args.workload)
    caught = 0
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        r = harness.run(cell, seed, args.seconds, False, system="control")
        caught += not r["correct"]
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "attempted": r["attempted"],
                          "correct": r["correct"], "checks": r["checks"]}),
              flush=True)
    print(f"control: {caught} of {len(seeds)} runs came out not correct",
          file=sys.stderr)
    return 0 if caught == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
