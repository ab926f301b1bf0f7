"""Forest deployments: an oblivious (CatBoost-style) forest scoring
batches of quantized instances through ``PudSession(backend="fused")``.

Configuration keys: ``trees``, ``depth``, ``features``, ``n_bits``,
``num_chunks``, ``pud_devices``, ``sys_cfg``.

What is compared: ``pred_max_abs_err``, the largest absolute error of a
prediction over the checked batches.  Leaf addresses are exact integers
and the reference sums the leaves in the served path's documented
float32 order, so one wrong leaf address reads as a unit-scale error.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

from bench.ref import forest as ref

#: Between what sound runs read and what the control (leaves held in
#: bfloat16) reads, as PERF.md records; above the ~1e-4 by which two
#: float32 summation orders of 1000 unit-scale leaves differ.
LIMITS = {"pred_max_abs_err": 1e-3}


def make_data(config: dict, rng: np.random.Generator) -> dict:
    return {"forest": ref.generate(config["trees"], config["depth"],
                                   config["features"], config["n_bits"],
                                   rng)}


def request_kind(req: np.ndarray) -> str:
    return "batch"


def describe(req: np.ndarray) -> int:
    """What the window keeps of a request: its batch size
    (``bench.kernels.leafbits_bytes`` reads it), not the ``[B, F]``
    instances."""
    return len(req)


class Program:
    """The system under test: a forest resource of a fused-backend
    session."""

    def __init__(self, config: dict, data: dict) -> None:
        from repro.apps.gbdt import ObliviousForest
        from repro.core import cost
        from repro.pud import PudSession

        f = data["forest"]
        self.session = PudSession(sys_cfg=getattr(cost, config["sys_cfg"]),
                                  num_devices=config["pud_devices"],
                                  backend="fused")
        forest = ObliviousForest(
            feature_idx=f.feature_idx.copy(), thresholds=f.thresholds.copy(),
            leaves=f.leaves.copy(), n_bits=f.n_bits,
            num_features=f.num_features)
        self.handle = self.session.load_forest(
            forest, name="forest", num_chunks=config["num_chunks"])

    def __call__(self, X: np.ndarray):
        job = self.session.predict(self.handle, X)
        return job.result, job.wallclock_ns

    def close(self) -> None:
        self.session.drop(self.handle)
        self.session = self.handle = None


class Control:
    """The reference in the program's place, one step below the
    configuration's float32: leaves held in bfloat16, summed in
    float32."""

    def __init__(self, config: dict, data: dict) -> None:
        self.ref = ref.Reference(data["forest"], leaf_dtype=ml_dtypes.bfloat16)

    def __call__(self, X: np.ndarray):
        return self.ref(X), None

    def close(self) -> None:
        self.ref = None


def check(config: dict, data: dict, samples: list) -> dict:
    r = ref.Reference(data["forest"])
    err = 0.0
    for X, got in samples:
        want = r(X)
        got = np.asarray(got)
        if got.shape != want.shape or not np.isfinite(got).all():
            return {"pred_max_abs_err": float("inf")}
        err = max(err, float(np.max(np.abs(got.astype(np.float64) - want))))
    return {"pred_max_abs_err": err}
