#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload scan16x8.count --seed 7 --seconds 20 --trace 0

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs
the window under the profiler and reports its per-layer metrics, the
device's busy and window seconds and a breakdown.  Every run checks a
sample of the window's results against the NumPy reference; the
compared numbers and their limits are the last lines on standard error,
and the result object (keys ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, optionally ``breakdown``, then ``checks``) is
the last line on standard output.

Exits nonzero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, when the program cannot be imported, or when
anything compiles inside the measured window.  Set-up time counts from
the start of this script.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a cell's name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True,
                    help="seed of the data, the traffic and the sample")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    try:
        cell = harness.resolve(args.workload)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             t_origin=T0)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
