"""Scan deployments: a table of uniform unsigned columns, queried with
Q1-Q5 and compound predicates through ``PudSession(backend="fused")``.

Configuration keys: ``records``, ``columns``, ``n_bits``,
``num_chunks``, ``pud_devices``, ``shards_per_device`` (the table's
record shards are their product), ``sys_cfg``.

What is compared (each against its limit, see ``LIMITS``):

* ``bitmap_bits_wrong``: bits that differ from the reference, summed
  over the checked bitmaps (Q1, Q2, compound);
* ``count_abs_err``: the largest count error (Q3, Q5);
* ``avg_rel_err``: the largest relative error of Q4's average.
"""

from __future__ import annotations

import numpy as np

from bench.ref import scan as ref

#: Bitmaps and counts are exact.  The average is a float64 mean of the
#: selected values; the limit sits between what sound runs read and
#: what the control (float32 and 8-bit keys) reads, as PERF.md records.
LIMITS = {"bitmap_bits_wrong": 0, "count_abs_err": 0, "avg_rel_err": 1e-9}

_NUMBER = {"q1": "bitmap_bits_wrong", "q2": "bitmap_bits_wrong",
           "compound": "bitmap_bits_wrong", "q3": "count_abs_err",
           "q5": "count_abs_err", "q4": "avg_rel_err"}


def make_data(config: dict, rng: np.random.Generator) -> dict:
    return {"columns": ref.generate(config["records"], config["n_bits"],
                                    config["columns"], rng)}


def request_kind(req: tuple) -> str:
    return req[0]


def describe(req: tuple) -> tuple:
    """What the window keeps of a request: the tuple itself, a few
    integers (``bench.kernels.predicate_bytes`` reads it)."""
    return req


def _query(req: tuple):
    from repro.pud import Q1, Q2, Q3, Q4, Q5
    from repro.pud.queries import Compound

    kind, *p = req
    if kind == "compound":
        ops, terms = p
        return Compound(terms=tuple(_query(t) for t in terms),
                        ops=tuple(ops))
    return {"q1": Q1, "q2": Q2, "q3": Q3, "q4": Q4, "q5": Q5}[kind](*p)


class Program:
    """The system under test: a table resource of a fused-backend
    session, laid out as ``pud_devices`` PuD devices with
    ``shards_per_device`` record shards each would hold it."""

    def __init__(self, config: dict, data: dict) -> None:
        from repro.apps.predicate import Table
        from repro.core import cost
        from repro.pud import PudSession

        self.session = PudSession(sys_cfg=getattr(cost, config["sys_cfg"]),
                                  num_devices=config["pud_devices"],
                                  backend="fused")
        table = Table(n_bits=config["n_bits"], features=data["columns"])
        self.handle = self.session.create_table(
            table, name="scan", shards_per_device=config["shards_per_device"],
            num_chunks=config["num_chunks"])

    def __call__(self, req: tuple):
        job = self.session.query(self.handle, _query(req))
        return job.result, job.wallclock_ns

    def close(self) -> None:
        self.session.drop(self.handle)
        self.session = self.handle = None


class Control:
    """The reference in the program's place, one step below the
    configuration's precision: predicates on the top half of each
    value's bits (the int8 step for 16-bit columns) and averages in
    float32 (the step below float64)."""

    def __init__(self, config: dict, data: dict) -> None:
        n = config["n_bits"]
        self.ref = ref.Reference(data["columns"], n, shift=n // 2,
                                 avg_dtype=np.float32)

    def __call__(self, req: tuple):
        return self.ref(req), None

    def close(self) -> None:
        self.ref = None


def check(config: dict, data: dict, samples: list) -> dict:
    """``{number: value}`` over ``samples``, a list of ``(request,
    result)``: one number per kind of result the mix returns."""
    r = ref.Reference(data["columns"], config["n_bits"])
    out: dict[str, float] = {}
    for req, got in samples:
        want = r(req)
        name = _NUMBER[req[0]]
        if name == "bitmap_bits_wrong":
            got = np.asarray(got)
            err = (int(np.count_nonzero(got.astype(bool) != want))
                   if got.shape == want.shape else int(want.size))
            out[name] = out.get(name, 0) + err
            continue
        if name == "count_abs_err":
            err = abs(int(got) - want)
        else:
            err = abs(float(got) - want) / (abs(want) or 1.0)
            if err != err:  # NaN: no average at all
                err = float("inf")
        out[name] = max(out.get(name, 0), err)
    return out
