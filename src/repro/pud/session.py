"""`PudSession`: the declarative front door to the PuD substrate.

Public API
----------
Everything an application needs is on this class (re-exported as
``repro.pud.PudSession`` / ``repro.PudSession``):

    from repro import pud

    session = pud.PudSession(num_devices=2)          # a 2-device fleet
    table = session.create_table(t, name="events")   # declarative resource
    forest = session.load_forest(f, name="ranker")

    job = session.query(table, pud.Q2(fi=0, x0=1, x1=9, fj=1, y0=2, y1=8))
    job.result                                       # == NumPy reference
    job.stats.overlapped_ns                          # barrier-aware totals

    preds = session.predict(forest, X).result
    session.drop(table)                              # banks coalesce back

Resources are *declared*, not hand-placed: ``create_table`` shards
records across the fleet's devices (then across channel-spread bank
groups inside each device) and ``load_forest`` replicates the forest
per device; the session's :class:`~repro.pud.planner.Planner` owns all
bank lifetimes -- eviction of cold resources, defragmentation, and a
FIFO admission queue when a placement does not fit (``handle.status``
is ``"queued"`` until capacity frees; no exception).  Queries and
inference run as submitted jobs through the async host/PuD pipelines
and return a :class:`JobResult` carrying the merged result, the
barrier-aware :class:`~repro.apps.pipeline.PipelineStats`, and the
federated :class:`~repro.core.scheduler.Timeline`.

The host side is concurrent: per-wave merges are recorded as
reduction trees whose per-shard leaves spread over
``sys_cfg.host_lanes`` merge lanes, and ``PudSession(...,
hosts="per-device")`` gives every device its own host (local leaves,
shared cross-device joins) -- ``stats.host_utilization`` shows whether
a host lane is the pipeline ceiling.

Two backends, one contract
--------------------------
``PudSession(backend="machine")`` (default) runs jobs on the NumPy
machine simulator and returns scheduler-derived ``stats``/``timeline``
-- the DRAM-side cost oracle.  ``backend="fused"`` runs the SAME jobs
through the JAX-native fast path
(:mod:`repro.kernels.fused_session`): one jitted program per query
kind batches the Pallas kernels across every shard of the resource and
joins shard counts with a ``psum`` over a ``shard_map`` mesh.  Results
are bit-exact between the backends (tested); a fused
:class:`JobResult` carries measured ``wallclock_ns`` instead of
``stats``/``timeline`` (``None`` -- the scheduler remains the cost
oracle, the fused path is what you actually run).  Per-job override:
``session.query(table, q, backend="fused")``.  Compile-cache
invariant: fused executables are cached per ``(plan, table shape,
query kind)`` on the session resource -- scalars and feature indices
are traced operands, so repeated jobs re-trace ZERO times (regression-
tested); the cache is dropped with the resource.

Adaptive representation
-----------------------
``create_table(..., representation="auto")`` (and ``load_forest``'s
counterpart) runs the :func:`~repro.pud.planner.choose_representation`
optimizer: per column it infers the minimal bit width actually needed
by the data (plus ``headroom`` guard bits), prices every candidate
chunking through the channel scheduler, and keeps the
``(n_bits, num_chunks)`` pair minimizing predicted makespan -- never
slower and never larger than the fixed default, which is always in the
candidate set.  ``handle.representation`` reports the per-column
:class:`~repro.core.encoding.ColumnPlan`s and the LUT-row savings;
:meth:`recode_column` re-encodes one hot column in place by riding the
evict/reload path (the rebuilt layout is audited by pudlint's PL501
representation pass on the next verified job).

In-DRAM data movement
---------------------
Bulk data movement inside a session never round-trips the host when a
RowClone-class path exists: ``load_forest(replicate="rowclone")`` (the
default) host-loads only the FIRST replica per (device, channel) and
clones the remaining replicas' LUT planes and mask rows with
RowClone / multi-row-ACT waves; planner defragmentation relocates
evicted-and-rebuilt groups with RowClone copy waves instead of
READ/WRITE streams; and :class:`~repro.pud.queries.Compound`
predicates (``merge="dram"``) combine term bitmaps with Ambit AND/OR
waves inside the banks so only the final bitmap (or its popcount)
crosses the pins.  ``sys_cfg.multi_row_act`` > 1 lets one activation
clone that many rows per wave (PULSAR-style), collapsing clone command
counts.

This replaces direct construction of ``PudQueryEngine`` /
``GbdtPudEngine`` and the PR-4 pipeline classes, which are internal
executors behind the session.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Sequence

import numpy as np

from repro.core import cost
from repro.core.device import PuDDevice
from repro.core.machine import PuDArch
from repro.core.scheduler import Timeline

from .executors import GbdtBatchExecutor, QueryBatchExecutor
from .planner import Planner
from .queries import Q1, Q2, Q3, Q4, Q5, Compound


def _session_span():
    """The profiler span around one fused job, ``clutch.session``
    (imported here: only the fused backend needs JAX)."""
    from jax.profiler import TraceAnnotation

    from repro.kernels.fused_session import SPAN_SESSION

    return TraceAnnotation(SPAN_SESSION)


@dataclass
class JobResult:
    """One submitted job's outcome: the merged result, plus the cost
    accounting of whichever backend ran it.  Machine-backend jobs carry
    the barrier-aware pipeline ``stats`` and the federated device
    ``timeline`` (the DRAM-side cost oracle); fused-backend jobs carry
    the measured ``wallclock_ns`` instead (``stats``/``timeline`` are
    ``None``) -- ``backend`` says which."""

    result: Any
    stats: Any = None          # repro.apps.pipeline.PipelineStats | None
    timeline: Timeline | None = None
    wallclock_ns: float | None = None
    backend: str = "machine"

    @property
    def makespan_ns(self) -> float:
        """Modeled makespan for machine jobs; measured wall-clock for
        fused jobs (the only clock the fused path has)."""
        if self.stats is not None:
            return self.stats.makespan_ns
        return self.wallclock_ns


@dataclass
class ResourceHandle:
    """Opaque handle to a session resource; ``status`` tracks the
    planner lifetime: ``ready`` / ``queued`` / ``evicted``, plus
    ``failed`` (a queued build whose recipe turned out broken when it
    was finally attempted -- drop and re-create) and ``dropped`` (the
    resource has been released)."""

    name: str
    session: "PudSession" = field(repr=False)

    @property
    def status(self) -> str:
        r = self.session.planner.resources.get(self.name)
        return r.state if r is not None else "dropped"


@dataclass
class TableHandle(ResourceHandle):
    num_records: int = 0
    n_bits: int = 0

    @property
    def representation(self) -> dict:
        """Per-column representation report: the active
        :class:`~repro.core.encoding.ColumnPlan`s (inferred widths and
        chunk counts) and the LUT-row footprint versus the fixed
        uniform default.  ``status`` stays the planner lifecycle
        string; this is the representation view."""
        return self.session.representation_report(self)


@dataclass
class ForestHandle(ResourceHandle):
    num_trees: int = 0
    depth: int = 0


class PudSession:
    """A session over a fleet of PuD devices: declarative resources,
    planned placement, federated query/inference jobs.

    ``verify`` runs the :mod:`repro.analysis` static verifier (pudlint)
    over every machine-backend job's streams and scheduled timeline:
    ``"strict"`` raises :class:`repro.analysis.PudLintError` on any
    error-severity diagnostic, ``"warn"`` emits a warning, ``"off"``
    skips linting.  ``None`` takes the class default
    (:data:`DEFAULT_VERIFY`, normally ``"off"``; the test suite flips
    it to ``"strict"``)."""

    #: Session-wide default for the ``verify`` knob (``None`` in a
    #: constructor call resolves to this).  Process-wide override
    #: point: the repo's conftest sets it to ``"strict"`` so every
    #: tier-1 job is linted.
    DEFAULT_VERIFY: str = "off"

    def __init__(self, sys_cfg=cost.DESKTOP, devices=None,
                 num_devices: int = 1, arch: PuDArch = PuDArch.MODIFIED,
                 num_rows: int = 1024, seed: int = 0,
                 hosts: str = "shared", backend: str = "machine",
                 verify: str | None = None) -> None:
        if hosts not in ("shared", "per-device"):
            raise ValueError(
                f"hosts must be 'shared' or 'per-device', got {hosts!r}")
        if backend not in ("machine", "fused"):
            raise ValueError(
                f"backend must be 'machine' or 'fused', got {backend!r}")
        if verify is None:
            verify = self.DEFAULT_VERIFY
        if verify not in ("strict", "warn", "off"):
            raise ValueError(
                f"verify must be 'strict', 'warn' or 'off', got {verify!r}")
        self.verify = verify
        self.sys_cfg = sys_cfg
        #: Default execution backend for jobs: "machine" (NumPy
        #: simulator + scheduled cost model) or "fused" (JAX-native
        #: one-jit path, measured wall-clock).  Overridable per job.
        self.backend = backend
        # Fused executors cached per resource name (compile caches live
        # inside them); invalidated on drop/evict.
        self._fused: dict[str, Any] = {}
        #: Fleet host model: "shared" = one host (with
        #: ``sys_cfg.host_lanes`` merge lanes) drives every device;
        #: "per-device" = each device schedules its merges on its OWN
        #: host's lanes, with only cross-device reduction joins on the
        #: shared host.
        self.hosts = hosts
        if devices is not None:
            self.devices = list(devices)
            archs = {d.arch for d in self.devices}
            if len(archs) != 1:
                raise ValueError(f"devices disagree on arch: {archs}")
            self.arch = next(iter(archs))
        else:
            self.arch = arch
            self.devices = [
                PuDDevice.from_system(sys_cfg, arch, num_rows=num_rows)
                for _ in range(num_devices)
            ]
            for i, d in enumerate(self.devices):
                d._seed = None if seed is None else seed + 1000 * i
        if not self.devices:
            raise ValueError("need at least one device")
        self.planner = Planner(self.devices)
        self._auto = 0
        # Adaptive-representation state, keyed by resource name: the
        # per-column ColumnPlans (mutable -- recode_column edits them
        # in place) plus the source data the plans were derived from
        # (recode validation re-checks value ranges against it).  Build
        # closures read these LATE, so an evict/reload rebuild picks up
        # recoded plans.
        self._plans: dict[str, list] = {}
        self._tables: dict[str, Any] = {}
        self._forest_plans: dict[str, Any] = {}

    # ------------------------------------------------------------------ #
    # Declarative resources
    # ------------------------------------------------------------------ #
    def _auto_name(self, prefix: str) -> str:
        self._auto += 1
        return f"{prefix}{self._auto}"

    def create_table(self, data, name: str | None = None,
                     n_bits: int | None = None,
                     shards_per_device: int = 2, method: str = "clutch",
                     num_chunks: int | None = None,
                     cols_per_bank: int = 65536,
                     channels="auto",
                     representation: str = "fixed", headroom: int = 0,
                     pinned: bool = False) -> TableHandle:
        """Register a table resource and (when capacity allows) load it
        across the fleet.  ``data`` is a
        :class:`~repro.apps.predicate.Table`, or a ``[records,
        features]`` integer array with ``n_bits`` giving the feature
        width.  Records shard across devices, then across
        ``shards_per_device`` channel-spread bank groups per device.
        Returns immediately with a handle; ``handle.status`` is
        ``"queued"`` when the placement is waiting for capacity.

        ``representation="auto"`` (clutch only) runs the
        :func:`~repro.pud.planner.choose_representation` optimizer:
        each column gets the ``(n_bits, num_chunks)`` pair minimizing
        predicted makespan given its observed value range (plus
        ``headroom`` guard bits above the observed maximum), never
        slower or larger than the fixed default.  ``"fixed"`` keeps the
        declared uniform width/chunking."""
        from repro.apps.predicate import Table

        if representation not in ("fixed", "auto"):
            raise ValueError(
                f"representation must be 'fixed' or 'auto', "
                f"got {representation!r}")
        if not isinstance(data, Table):
            arr = np.asarray(data)
            if n_bits is None:
                raise ValueError(
                    "n_bits is required when data is a raw array")
            data = Table(n_bits=n_bits,
                         features=[np.ascontiguousarray(arr[:, f],
                                                        dtype=np.uint64)
                                   for f in range(arr.shape[1])])
        name = name or self._auto_name("table")
        self._tables[name] = data
        if representation == "auto":
            if method != "clutch":
                raise ValueError(
                    "representation='auto' requires method='clutch' "
                    "(bit-serial tables have no chunk plan to optimize)")
            from .planner import choose_representation

            self._plans[name] = choose_representation(
                data, self.arch,
                num_rows=min(d.num_rows for d in self.devices),
                sys_cfg=self.sys_cfg, headroom=headroom,
                num_chunks=num_chunks)

        def build():
            # read the plan set LATE: recode_column mutates it and
            # rides this rebuild on the evict/reload path
            plans = self._plans.get(name)
            return QueryBatchExecutor(
                data, self.arch, self.devices,
                shards_per_device=shards_per_device, method=method,
                num_chunks=num_chunks, cols_per_bank=cols_per_bank,
                channels=channels, hosts=self.hosts,
                plans=tuple(plans) if plans is not None else None)

        self.planner.admit(name, "table", build, pinned=pinned)
        return TableHandle(name=name, session=self,
                           num_records=data.num_records,
                           n_bits=data.n_bits)

    def load_forest(self, forest, name: str | None = None,
                    groups_per_device: int = 2, banks_per_group: int = 4,
                    num_chunks: int | None = None,
                    channels="auto", replicate: str = "rowclone",
                    representation: str = "fixed", headroom: int = 0,
                    pinned: bool = False) -> ForestHandle:
        """Register an oblivious forest (thresholds + one-hot masks
        replicated into ``groups_per_device`` channel-spread groups on
        every device) and return its handle; placement queues when it
        does not fit.  ``replicate="rowclone"`` (default) host-loads
        only each channel's first replica and clones the rest in-DRAM
        (RowClone/MRACT waves, zero host bytes per extra replica);
        ``"host"`` re-loads every replica over the pins (the
        baseline).  ``representation="auto"`` sizes the threshold LUT
        to the observed threshold range via
        :func:`~repro.pud.planner.choose_forest_plan` (priced with the
        ``>``-only probe inference actually issues)."""
        if representation not in ("fixed", "auto"):
            raise ValueError(
                f"representation must be 'fixed' or 'auto', "
                f"got {representation!r}")
        name = name or self._auto_name("forest")
        if representation == "auto":
            from .planner import choose_forest_plan

            self._forest_plans[name] = choose_forest_plan(
                forest, self.arch,
                num_rows=min(d.num_rows for d in self.devices),
                sys_cfg=self.sys_cfg, headroom=headroom,
                num_chunks=num_chunks)

        def build():
            return GbdtBatchExecutor(
                forest, self.arch, self.devices,
                groups_per_device=groups_per_device,
                banks_per_group=banks_per_group, num_chunks=num_chunks,
                channels=channels, hosts=self.hosts,
                replicate=replicate,
                plan=self._forest_plans.get(name))

        self.planner.admit(name, "forest", build, pinned=pinned)
        return ForestHandle(name=name, session=self,
                            num_trees=forest.num_trees, depth=forest.depth)

    def drop(self, handle: ResourceHandle) -> None:
        """Release a resource: its banks coalesce back into each
        device's free map (and its fused compile cache is dropped) and
        the admission queue drains FIFO."""
        self.planner.release(handle.name)
        self._fused.pop(handle.name, None)
        self._plans.pop(handle.name, None)
        self._tables.pop(handle.name, None)
        self._forest_plans.pop(handle.name, None)

    def evict(self, handle: ResourceHandle) -> None:
        """Reclaim a resource's banks now; it reloads on next use.
        The fused cache is reclaimed with it."""
        self.planner.evict(handle.name)
        self._fused.pop(handle.name, None)

    # ------------------------------------------------------------------ #
    # Adaptive representation
    # ------------------------------------------------------------------ #
    def recode_column(self, handle: TableHandle, column: int,
                      n_bits: int | None = None,
                      num_chunks: int | None = None):
        """Re-encode one table column under a new ``(n_bits,
        num_chunks)`` representation, riding the existing evict/reload
        path: the resource's banks are reclaimed now, and the next job
        rebuilds every shard with the updated per-column plan (the
        rebuilt layout is audited by pudlint's PL501 representation
        pass).  Omitted arguments keep the column's current value.
        Returns the new :class:`~repro.core.encoding.ColumnPlan`."""
        from repro.core.encoding import ColumnPlan
        from repro.core.machine import BankedSubarray, PuDArch

        name = handle.name
        table = self._tables.get(name)
        if table is None:
            raise KeyError(f"unknown table {handle.name!r} "
                           "(dropped, or from another session?)")
        n_feat = len(table.features)
        if not 0 <= column < n_feat:
            raise IndexError(
                f"column {column} out of range for {n_feat}-feature table")
        num_rows = min(d.num_rows for d in self.devices)
        plans = self._plans.get(name)
        if plans is None:
            # fixed-representation table: seed declared-width plans so a
            # single column can move without disturbing the others
            from .planner import _default_uniform_chunks

            c_def = _default_uniform_chunks(
                table.n_bits, self.arch, n_feat, num_rows)
            plans = [ColumnPlan(table.n_bits, c_def)
                     for _ in range(n_feat)]
            self._plans[name] = plans
        old = plans[column]
        bits = old.n_bits if n_bits is None else int(n_bits)
        vals = table.features[column]
        if vals.size and int(vals.max()) >= (1 << bits):
            raise ValueError(
                f"column {column}: values reach {int(vals.max())}, which "
                f"overflows a {bits}-bit recode "
                f"(representable range [0, {(1 << bits) - 1}])")
        chunks = (min(old.num_chunks, bits) if num_chunks is None
                  else int(num_chunks))
        new = ColumnPlan(bits, chunks)
        plans[column] = new
        # pre-flight the budget the rebuild will check, so a bad recode
        # fails HERE (state rolled back) instead of wedging the resource
        mult = 2 if self.arch is PuDArch.UNMODIFIED else 1
        need = 2 + 4 + 2 + mult * sum(p.rows_required for p in plans)
        budget = num_rows - BankedSubarray.NUM_RESERVED
        if need > budget:
            plans[column] = old
            raise MemoryError(
                f"recode to {new} needs {need} rows > budget {budget} "
                f"({num_rows}-row subarray); pick more chunks or fewer "
                "bits")
        r = self.planner.resources.get(name)
        if r is not None and r.state == "ready":
            self.planner.evict(name)
        self._fused.pop(name, None)
        return new

    def representation_report(self, handle: TableHandle) -> dict:
        """Per-column representation view of a table resource: the
        active plans (``mode="auto"`` after the optimizer or a recode;
        ``"fixed"`` otherwise) and the LUT-row footprint next to the
        fixed uniform default -- ``saved_rows`` is the optimizer's
        win."""
        from repro.core.encoding import column_footprint_rows
        from repro.core.machine import PuDArch
        from .planner import _default_uniform_chunks

        name = handle.name
        table = self._tables.get(name)
        if table is None:
            raise KeyError(f"unknown table {handle.name!r} "
                           "(dropped, or from another session?)")
        n_feat = len(table.features)
        num_rows = min(d.num_rows for d in self.devices)
        mult = 2 if self.arch is PuDArch.UNMODIFIED else 1
        c_def = _default_uniform_chunks(
            table.n_bits, self.arch, n_feat, num_rows)
        fixed_col = column_footprint_rows(table.n_bits, c_def) * mult
        plans = self._plans.get(name)
        columns = []
        total = 0
        for i in range(n_feat):
            if plans is not None:
                p = plans[i]
                rows = p.rows_required * mult
                columns.append({"column": i, "n_bits": p.n_bits,
                                "num_chunks": p.num_chunks,
                                "lut_rows": rows})
            else:
                rows = fixed_col
                columns.append({"column": i, "n_bits": table.n_bits,
                                "num_chunks": c_def, "lut_rows": rows})
            total += rows
        fixed_total = n_feat * fixed_col
        return {"mode": "auto" if plans is not None else "fixed",
                "columns": columns, "lut_rows": total,
                "fixed_lut_rows": fixed_total,
                "saved_rows": fixed_total - total}

    # ------------------------------------------------------------------ #
    # Serving hooks (autoscaler knobs)
    # ------------------------------------------------------------------ #
    def set_host_lanes(self, k: int) -> None:
        """Re-provision the session's host merge lanes (the autoscaler's
        grow/shrink knob).  Takes effect on the next scheduled job --
        recorded streams are lane-agnostic, lanes are assigned at
        schedule time."""
        from dataclasses import replace

        if k < 1:
            raise ValueError(f"host_lanes must be >= 1, got {k}")
        self.sys_cfg = replace(self.sys_cfg, host_lanes=k)

    def set_hosts(self, mode: str) -> None:
        """Switch the fleet host model (``"shared"`` / ``"per-device"``)
        for subsequent jobs.  Ready executors are re-pointed in place;
        queued/evicted resources pick the mode up on rebuild."""
        if mode not in ("shared", "per-device"):
            raise ValueError(
                f"hosts must be 'shared' or 'per-device', got {mode!r}")
        self.hosts = mode
        for r in self.planner.resources.values():
            if r.executor is not None:
                r.executor.hosts = mode

    # ------------------------------------------------------------------ #
    # Jobs
    # ------------------------------------------------------------------ #
    def _executor(self, handle: ResourceHandle, kind: str):
        r = self.planner.resources.get(handle.name)
        if r is None:
            raise KeyError(f"unknown resource {handle.name!r} "
                           "(dropped, or from another session?)")
        if r.kind != kind:
            raise TypeError(
                f"resource {handle.name!r} is a {r.kind}, not a {kind}")
        return self.planner.ensure_ready(handle.name)

    def _fused_exec(self, handle: ResourceHandle, ex, kind: str):
        """The resource's cached fused executor, built from the machine
        executor's own layout recipe (same table/forest, shard count
        and chunk plan) so both backends evaluate identical shapes."""
        fx = self._fused.get(handle.name)
        if fx is None:
            from repro.kernels.fused_session import (
                FusedGbdtExec,
                FusedTableExec,
            )

            cls = FusedTableExec if kind == "table" else FusedGbdtExec
            fx = cls(**ex.fused_config())
            self._fused[handle.name] = fx
        return fx

    def _lint_job(self, ex, timeline: Timeline) -> None:
        """Run pudlint over a machine job's trimmed streams + scheduled
        timeline (plus each device's clone-confinement rule), applying
        the session's ``verify`` mode."""
        if self.verify == "off":
            return
        from repro.analysis import pudlint

        report = pudlint.lint_timeline(
            timeline, sys_cfg=self.sys_cfg, streams=ex._job_streams())
        for dev in dict.fromkeys(d for d, _ in ex.placements):
            report.diagnostics.extend(
                pudlint.clone_confinement_diags(dev))
        # PL501 representation audit: every shard's encoded LUT layouts
        # must match the declared per-column plans (catches stale planes
        # after a recode_column that skipped the rebuild)
        plans = getattr(ex, "plans", None)
        if plans is not None:
            for eng in ex.engines:
                report.diagnostics.extend(pudlint.representation_diags(
                    eng.engines, plans, group=eng.label))
        plan = getattr(ex, "plan", None)
        if plan is not None:
            for eng in ex.engines:
                report.diagnostics.extend(pudlint.representation_diags(
                    [eng.engine], [plan], group=eng.label))
        pudlint.enforce(report, self.verify, where="PudSession job")

    def query(self, table: TableHandle,
              queries: "Q1 | Q2 | Q3 | Q4 | Q5 | Compound | Sequence",
              backend: str | None = None) -> JobResult:
        """Run one query (or a batch -- batches pipeline back-to-back
        and overlap host merges with PuD execution) against a table.
        Returns a :class:`JobResult`; for a single query ``result`` is
        that query's value, for a batch it is the list of values, in
        order, bit-exact against the NumPy references.
        :class:`~repro.pud.queries.Compound` queries merge their term
        bitmaps in-DRAM by default (``merge="host"`` selects the
        read-every-term baseline).  ``backend`` overrides the session
        default for this job; the fused backend returns measured
        ``wallclock_ns`` instead of scheduler stats."""
        single = isinstance(queries, (Q1, Q2, Q3, Q4, Q5, Compound))
        batch = [queries] if single else list(queries)
        if (backend or self.backend) == "fused":
            with _session_span():
                fx = self._fused_exec(
                    table, self._executor(table, "table"), "table")
                t0 = time.perf_counter()
                results = fx.run([q.to_tuple() for q in batch])
                wall = (time.perf_counter() - t0) * 1e9
                return JobResult(result=results[0] if single else results,
                                 wallclock_ns=wall, backend="fused")
        ex = self._executor(table, "table")
        results = ex.run([q.to_tuple() for q in batch])
        timeline = ex.schedule(self.sys_cfg)
        self._lint_job(ex, timeline)
        stats = ex.last_stats(self.sys_cfg, timeline=timeline)
        return JobResult(result=results[0] if single else results,
                         stats=stats, timeline=timeline)

    def predict(self, forest: ForestHandle, X: np.ndarray,
                backend: str | None = None) -> JobResult:
        """Batched GBDT inference: instances spread over every device's
        forest replicas wave by wave; predictions come back in input
        order with the batch's barrier-aware pipeline stats (machine
        backend) or measured ``wallclock_ns`` (fused backend --
        bit-exact predictions, one kernel launch for the whole
        batch)."""
        if (backend or self.backend) == "fused":
            with _session_span():
                fx = self._fused_exec(
                    forest, self._executor(forest, "forest"), "forest")
                t0 = time.perf_counter()
                preds = fx.infer(np.asarray(X))
                wall = (time.perf_counter() - t0) * 1e9
                return JobResult(result=preds, wallclock_ns=wall,
                                 backend="fused")
        ex = self._executor(forest, "forest")
        preds = ex.infer(np.asarray(X))
        timeline = ex.schedule(self.sys_cfg)
        self._lint_job(ex, timeline)
        stats = ex.last_stats(self.sys_cfg, timeline=timeline)
        return JobResult(result=preds, stats=stats, timeline=timeline)

    # ------------------------------------------------------------------ #
    # Introspection
    # ------------------------------------------------------------------ #
    def executor(self, handle: ResourceHandle):
        """The resource's live executor (engines, ``wave_width``,
        ``placements``) -- the supported accessor for benchmarks and
        tools that need engine-level introspection (op counts, chunk
        plans, recorded traces).  Transparently reloads an evicted
        resource, like a job would."""
        return self.planner.ensure_ready(handle.name)

    def clear_traces(self, handle: ResourceHandle) -> None:
        """Forget a resource's recorded command streams (e.g. drop LUT
        loading from a cost-model histogram before measuring a job).
        Job timelines are already job-scoped; this is for callers
        reading raw traces (``cost.trace_cost``) or device-level
        schedules."""
        for eng in self.executor(handle).engines:
            eng.sub.trace.clear()

    def schedule(self) -> Timeline:
        """Jointly scheduled timeline of every device's full recorded
        streams -- the session-lifetime view (LUT loads and all jobs;
        each :class:`JobResult` carries its own job-scoped timeline).
        Device channels are re-keyed into per-device namespaces; host
        events land on the session's host model (one shared host's
        lanes, or per-device hosts with cross-device joins shared)."""
        from repro.core.scheduler import ChannelScheduler, rekey_stream

        stride = max(d.channels for d in self.devices)
        streams = [
            rekey_stream(st, di, stride,
                         host=di if self.hosts == "per-device" else 0)
            for di, d in enumerate(self.devices)
            for st in d.streams()]
        return ChannelScheduler(self.sys_cfg).schedule(streams)

    def cost_summary(self) -> dict:
        """Per-device cost summaries plus the federated makespan."""
        per_dev = [d.cost_summary(self.sys_cfg) for d in self.devices]
        fed = self.schedule()
        return {
            "devices": per_dev,
            "time_scheduled_ns": fed.makespan_ns,
            "time_device_ns": fed.device_span_ns,
            "energy_nj": sum(s["energy_nj"] for s in per_dev),
        }

    def planner_stats(self) -> dict:
        """Placement-planner counters (resource states, queue, defrag,
        evictions, free-map shape per device)."""
        return self.planner.stats()
