#!/usr/bin/env python3
"""Smoke run of the fused session path on a TPU.

    python chip_smoke.py              # one chip: table phase + forest phase
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

Drives ``PudSession(backend="fused")`` through its public entry points
with data made from ``--seed``, checks every result against the NumPy
references, and prints as its last line

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}

* Table phase: 16,777,216 records x 8 columns of 16-bit values, laid
  out as four simulated DESKTOP devices would hold it (8 record shards
  at 4 chunks, all on the one chip: a 2 GiB stacked LUT).  Q1-Q5 and a
  compound query, each checked exactly (Q4's average within 1e-9).
* Forest phase: an oblivious forest at CatBoost's documented defaults
  (1000 trees, depth 6, 8-bit features) scores 4096 instances; its leaf
  addresses equal ``reference_leaf_addrs`` and its predictions
  ``reference_predict`` within 1e-5.
* Mesh phase (``--chips 4``): the same table with its 8 shards over a
  4-device mesh and the forest's batch sharded over 4 devices, checked
  against the references and against a 1-device mesh in this process.

The times it prints are a smoke reading (first and second call of each
job, host clock), not a benchmark.  It exits nonzero, before any phase,
unless JAX's devices are TPUs; any failed check or exception exits
nonzero.  One process; it starts no other.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.apps.gbdt import (  # noqa: E402
    ObliviousForest,
    reference_leaf_addrs,
    reference_predict,
)
from repro.apps.predicate import Table  # noqa: E402
from repro.core import cost  # noqa: E402
from repro.dist.sharding import shard_mesh  # noqa: E402
from repro.kernels.common import enable_compile_cache  # noqa: E402
from repro.kernels.fused_session import (  # noqa: E402
    FusedGbdtExec,
    FusedTableExec,
)
from repro.pud import Q1, Q2, Q3, Q4, Q5, PudSession  # noqa: E402
from repro.pud.queries import Compound  # noqa: E402

RECORDS, COLUMNS, COLUMN_BITS = 16_777_216, 8, 16
TREES, DEPTH, FEATURES, FEATURE_BITS, BATCH = 1000, 6, 8, 8, 4096


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def session() -> PudSession:
    # four DESKTOP devices x 2 shards each: the record-shard layout
    return PudSession(sys_cfg=cost.DESKTOP, num_devices=4, backend="fused")


def queries(n_bits: int) -> list:
    mx = (1 << n_bits) - 1
    qa = dict(fi=0, x0=mx // 8, x1=mx // 2, fj=1, y0=mx // 4,
              y1=3 * mx // 4)
    return [
        Q1(fi=0, x0=mx // 8, x1=mx // 2),
        Q2(**qa),
        Q3(**qa),
        Q4(fk=2, **qa),
        Q5(fl=3, fk=2, **qa),
        Compound(terms=(Q1(fi=4, x0=mx // 10, x1=9 * mx // 10), Q2(**qa),
                        Q3(fi=5, x0=mx // 3, x1=mx // 2, fj=6, y0=0,
                           y1=mx // 5)),
                 ops=("and", "or")),
    ]


def timed(job):
    t0 = time.perf_counter()
    out = job()
    return out, time.perf_counter() - t0


def same(a, b) -> bool:
    return bool(np.array_equal(a, b)) if isinstance(a, np.ndarray) \
        else a == b


def table_phase(records: int = RECORDS, seed: int = 0) -> None:
    t, gen_s = timed(lambda: Table.generate(
        records, COLUMN_BITS, num_features=COLUMNS, seed=seed))
    s = session()
    h, place_s = timed(lambda: s.create_table(t, name="scan"))
    check(h.status == "ready", f"table status {h.status!r}")
    print(f"table: {records} records x {COLUMNS} columns of {COLUMN_BITS} "
          f"bits; generate {gen_s:.3f} s, create_table {place_s:.3f} s")
    for q in queries(COLUMN_BITS):
        first, first_s = timed(lambda: s.query(h, q).result)
        warm, warm_s = timed(lambda: s.query(h, q).result)
        name = type(q).__name__
        check(q.check(t, first) and q.check(t, warm),
              f"{name} disagrees with the NumPy reference")
        print(f"smoke reading, not a benchmark: {name} first call "
              f"{first_s * 1e3:.3f} ms, warm {warm_s * 1e3:.3f} ms")
    fx = s._fused[h.name]
    print(f"table LUT on device: {fx.lut.nbytes} bytes, shape "
          f"{tuple(fx.lut.shape)}, {fx.num_shards} shards at "
          f"{fx.num_chunks} chunks")
    s.drop(h)


def forest_and_batch(trees: int, batch: int, seed: int):
    forest = ObliviousForest.random(num_trees=trees, depth=DEPTH,
                                    num_features=FEATURES,
                                    n_bits=FEATURE_BITS, seed=seed)
    X = np.random.default_rng(seed + 1).integers(
        0, 1 << FEATURE_BITS, (batch, FEATURES), dtype=np.int64)
    return forest, X


def forest_phase(trees: int = TREES, batch: int = BATCH,
                 seed: int = 0) -> None:
    forest, X = forest_and_batch(trees, batch, seed)
    s = session()
    h = s.load_forest(forest, name="ranker")
    check(h.status == "ready", f"forest status {h.status!r}")
    want = reference_predict(forest, X)
    first, first_s = timed(lambda: s.predict(h, X).result)
    warm, warm_s = timed(lambda: s.predict(h, X).result)
    fx = s._fused[h.name]
    # the device half is exact: every (instance, tree) leaf address
    wrong = int((fx.leaf_addrs(X) != reference_leaf_addrs(forest, X)).sum())
    check(wrong == 0, f"{wrong} of {batch * trees} leaf addresses differ "
          "from reference_leaf_addrs")
    for got in (first, warm):
        check(got.shape == want.shape and bool(np.isfinite(got).all()),
              "predictions are finite, one per instance")
        check(np.allclose(got, want, rtol=0, atol=1e-5),
              "predictions match reference_predict within 1e-5 (largest "
              f"difference {float(np.abs(got - want).max())})")
    print(f"forest: {trees} trees, depth {DEPTH}, {FEATURES} features of "
          f"{FEATURE_BITS} bits; threshold LUT {tuple(fx.lut.shape)} at "
          f"{fx.num_chunks} chunk(s)")
    print(f"smoke reading, not a benchmark: predict({batch}) first call "
          f"{first_s * 1e3:.3f} ms, warm {warm_s * 1e3:.3f} ms")
    s.drop(h)


def mesh_phase(records: int = RECORDS, trees: int = TREES,
               batch: int = BATCH, seed: int = 0) -> None:
    """The table's shards and the forest's batch over every device,
    against the references and a 1-device mesh."""
    devices = jax.devices()
    one = devices[:1]
    t = Table.generate(records, COLUMN_BITS, num_features=COLUMNS,
                       seed=seed)
    s = session()
    h = s.create_table(t, name="scan")
    check(h.status == "ready", f"table status {h.status!r}")
    qs = queries(COLUMN_BITS)
    got, mesh_s = timed(lambda: s.query(h, qs).result)
    fx = s._fused[h.name]
    check(fx.mesh.shape["shards"] == len(devices),
          f"table mesh spans {fx.mesh.shape['shards']} of "
          f"{len(devices)} devices")
    solo = FusedTableExec(**s.executor(h).fused_config(),
                          mesh=shard_mesh(fx.num_shards, devices=one))
    ref, solo_s = timed(lambda: solo.run([q.to_tuple() for q in qs]))
    for q, a, b in zip(qs, got, ref):
        name = type(q).__name__
        check(q.check(t, a), f"{name} on the mesh disagrees with NumPy")
        check(same(a, b), f"{name}: {len(devices)}-device and 1-device "
              "meshes disagree")
    print(f"table over {len(devices)} devices: 6 queries agree with "
          f"NumPy and the 1-device mesh (smoke reading: {mesh_s:.3f} s "
          f"vs {solo_s:.3f} s, both including compilation)")
    s.drop(h)

    forest, X = forest_and_batch(trees, batch, seed)
    s = session()
    h = s.load_forest(forest, name="ranker")
    check(h.status == "ready", f"forest status {h.status!r}")
    preds = s.predict(h, X).result
    gx = s._fused[h.name]
    check(gx.mesh.shape["shards"] == len(devices),
          f"forest mesh spans {gx.mesh.shape['shards']} devices")
    solo_preds = FusedGbdtExec(**s.executor(h).fused_config(),
                               mesh=shard_mesh(1, devices=one)).infer(X)
    check(np.allclose(preds, reference_predict(forest, X), rtol=0,
                      atol=1e-5), "mesh predictions match reference_predict")
    check(np.array_equal(preds, solo_preds),
          "4-device and 1-device predictions are identical")
    print(f"forest batch of {batch} over {len(devices)} devices: agrees "
          "with reference_predict and the 1-device mesh")
    s.drop(h)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the table, forest and batch")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh phase, over four chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device: {device}")
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {device['count']}", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        mesh_phase(seed=args.seed)
    else:
        table_phase(seed=args.seed)
        forest_phase(seed=args.seed)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
