"""JAX-native fused execution backend for :class:`repro.pud.PudSession`.

Public API
----------
``PudSession(backend="fused")`` routes ``query``/``predict`` jobs here
instead of through the NumPy machine executors.  Two executors mirror
the machine path's semantics exactly:

* :class:`FusedTableExec` -- Q1-Q5 over a record-sharded table.  Every
  feature's normal AND complement LUT planes for every record shard are
  stacked into ONE ``[shards, rows * Wp / 128, 128]`` array at build
  time, each shard's rows laid out as contiguous slabs of whole tiles
  (:func:`repro.kernels.fused_query.row_slabs`, ``Wp`` = words rounded
  up to 1024); a query then runs as ONE jitted program: a single
  :func:`repro.kernels.fused_query.fused_predicate_banked` grid over
  *(shard, word block)* gathers from HBM only the LUT rows the query's
  index lanes name and evaluates the whole WHERE clause (both range
  sides, AND/OR combination, per-shard popcount) and a ``psum`` over a
  ``shard_map`` mesh (built from :func:`repro.dist.sharding.shard_mesh`)
  joins the shard counts -- the PR-5 merge tree's leaves become the
  kernel's vectorized popcounts and its root join becomes the
  collective.  No per-group Python loop, no per-wave host round trip
  for pure-device segments.  Compound predicates run through
  :func:`~repro.kernels.fused_query.fused_compound_banked` -- one
  launch per compound, the register-level mirror of the machine path's
  in-bank Ambit AND/OR merge (one executable per compound *shape*).
* :class:`FusedGbdtExec` -- GBDT inference.  The forest's threshold LUT
  and one-hot feature masks are device-resident; one
  :func:`~repro.kernels.fused_query.gbdt_leafbits_banked` grid over
  *(instance, word block)* folds every feature comparison into each
  instance's leaf-address bitmap, sharded over the mesh on the instance
  axis.

Bit-exact parity contract (tested in ``tests/test_fused_session.py``):
bitmaps, counts and leaf addresses are exact integer/boolean math on
device; the few FLOAT aggregates (Q4/Q5 averages, GBDT leaf sums) are
finished HOST-side with the same NumPy expressions the machine
executors use (:func:`repro.apps.gbdt.assemble_leaves` is shared), so
summation order -- and therefore every result -- is identical to
``backend="machine"``.

Compile-cache invariant: feature indices and scalars are resolved to
row-index *arrays* (host-side, memoized via
:func:`repro.kernels.ops.resolve_indices`) and passed as traced
operands, so ONE compiled executable per ``(plan, table shape, query
kind)`` serves every (feature, scalar) combination.  ``trace_counts``
exposes the per-kind trace counter the zero-retrace regression test
asserts on; ``lut_rows_read`` the rows a kind's launch gathers per
shard against the rows a shard holds (a Q3 at 4 chunks: 32);
``FusedGbdtExec.leafbits_launch`` what one leaf-bits launch covers.

Heterogeneous per-column plans: ``plans`` (one
:class:`~repro.core.encoding.ColumnPlan` per feature) stacks RAGGED
per-feature LUT blocks -- each feature's planes are exactly as tall as
its own ``(n_bits, num_chunks)`` requires, and the recorded per-block
base offsets replace the uniform ``f * r_pad`` arithmetic.  The
kernels stay UNCHANGED and run at the static chunk count ``C_max =
max(num_chunks)``: a narrower feature's index rows are padded from its
own ``C_f`` up to ``C_max`` with identity lanes ``(lt=zero_row,
le=one_row)`` -- ``maj3(acc, 0, 1) == acc``, and the kernel never
reads ``le[0]`` -- inside that feature's own block, so every lane
stays in-block and machine/fused bit-exactness is preserved.  Scalars
beyond a narrow column's range clamp exactly like the machine path's
``ClutchEngine(clamp=True)``: the gt-side scalar saturates to the
column max, an lt-side bound past the max resolves every lane to the
complement block's constant-one row (always true on valid columns).
Uniform plans are the degenerate case: the stacked layout and index
arithmetic reduce to the original byte-identical form.

Profiler spans: each request opens ``jax.profiler.TraceAnnotation``
spans named by the ``SPAN_*`` constants below, so that a profiler trace
(``jax.profiler.trace``) puts every host phase on the same clock as the
device's operations.  ``SPAN_SESSION`` is opened by
:class:`repro.pud.PudSession` around a fused job; inside it the
executors open, per launch, ``SPAN_RESOLVE`` (Algorithm 1 row indices on
the host), ``SPAN_DISPATCH`` (indices to the device and the async
launch), ``SPAN_READBACK`` (the first blocking read of a result) and,
where a bitmap comes back, ``SPAN_UNPACK`` (words to bits) and
``SPAN_FINISH`` (Q4/Q5 averages, leaf addresses and leaf sums).  With
the profiler off a span costs under a microsecond of host time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.encoding import ChunkPlan, ColumnPlan, make_plan
from repro.core.machine import pack_bits, unpack_bits
from repro.dist.sharding import shard_mesh

from .common import SUBLANES, round_up
from .fused_query import (
    fused_compound_banked,
    fused_predicate_banked,
    gbdt_leafbits_banked,
    row_slabs,
)
from .ops import (
    encode_lut,
    lut_offsets,
    resolve_indices,
    resolve_indices_banked,
)

SPAN_SESSION = "clutch.session"
SPAN_RESOLVE = "clutch.resolve"
SPAN_DISPATCH = "clutch.dispatch"
SPAN_READBACK = "clutch.readback"
SPAN_UNPACK = "clutch.unpack"
SPAN_FINISH = "clutch.finish"

#: Bytes of the int64 product the leaf-address sum forms per block of
#: instances: a few MiB the heap hands back every block, so that no
#: ``[B, T, depth]`` product (196 MB for 4096 instances of 1000 depth-6
#: trees) is mapped and page-faulted afresh every request.
FINISH_BLOCK_BYTES = 8 << 20


class FusedTableExec:
    """One-jit Q1-Q5 execution over a record-sharded table.

    ``table`` is duck-typed (``n_bits``, ``features``, ``num_records``
    -- a :class:`repro.apps.predicate.Table` or equivalent).  Records
    shard exactly like :class:`repro.pud.executors.QueryBatchExecutor`
    (``per = ceil(n / num_shards)`` contiguous records per shard), so
    bitmap order matches the machine path bit for bit.  Padding columns
    encode ``B = 0``; the gt-side of every range predicate is 0 there
    (scalars are non-negative), the AND kills the complement side, and
    popcounts need no masking.  The words :func:`row_slabs` pads each
    row with are zero in every plane, so every range is false there too.
    """

    def __init__(self, table, num_shards: int, num_chunks: int,
                 mesh=None, plans=None) -> None:
        self.table = table
        self.plan: ChunkPlan = make_plan(table.n_bits, num_chunks)
        self.num_features = len(table.features)
        self.num_shards = num_shards
        self.mx = (1 << table.n_bits) - 1
        #: per-column plans; uniform `(table.n_bits, num_chunks)` for
        #: every feature when none are supplied (the degenerate case --
        #: layout and index math reduce to the original uniform form).
        self.plans = (tuple(plans) if plans is not None else tuple(
            ColumnPlan(table.n_bits, self.plan.num_chunks)
            for _ in table.features))
        if len(self.plans) != self.num_features:
            raise ValueError(
                f"need one ColumnPlan per feature: got {len(self.plans)} "
                f"plans for {self.num_features} features")
        if plans is not None:
            for i, (p, f) in enumerate(zip(self.plans, table.features)):
                arr = np.asarray(f, np.uint64)
                if arr.size and int(arr.max()) > p.max_value:
                    raise ValueError(
                        f"column {i}: values reach {int(arr.max())}, "
                        f"which overflows the plan's {p.n_bits}-bit "
                        "width")
        # kernels run at the static max chunk count; narrower features'
        # index rows pad up to it with in-block identity lanes
        self.num_chunks = max(p.num_chunks for p in self.plans)
        self._cplans = [p.chunk_plan for p in self.plans]
        n = table.num_records
        self.per = math.ceil(n / num_shards)
        self.mesh = mesh if mesh is not None else shard_mesh(num_shards)
        # Per shard: every feature's normal LUT block, then every
        # feature's complement block.  Blocks are ragged -- each is as
        # tall as its own plan's planes (+2 const rows, tile-padded) --
        # and `base[(comp, f)]` records where each begins.  Each shard
        # is laid out as row slabs before the stack, so the stacked
        # array is never copied whole.
        shards = []
        base: list[int] = []
        for s in range(num_shards):
            lo = s * self.per
            cols = []
            off = 0
            for comp in (False, True):
                for f, cp in zip(table.features, self._cplans):
                    v = np.zeros(self.per, np.uint32)
                    chunk = np.asarray(f[lo:lo + self.per], np.uint64)
                    v[:chunk.shape[0]] = chunk.astype(np.uint32)
                    blk = encode_lut(jnp.asarray(v), cp, complement=comp)
                    if s == 0:
                        base.append(off)
                        off += int(blk.shape[0])
                    cols.append(blk)
            shards.append(row_slabs(jnp.concatenate(cols, axis=0)))
        #: LUT rows and words of one shard
        self.rows = sum(int(blk.shape[0]) for blk in cols)
        self.words = int(cols[0].shape[1])
        # [S, rows * Wp / 128, 128], each device holding its own shards
        self.lut = jax.device_put(
            jnp.stack(shards), NamedSharding(self.mesh, P("shards")))
        self._base_n = base[:self.num_features]
        self._base_c = base[self.num_features:]
        #: traces per query kind -- the zero-retrace test's probe.
        self.trace_counts: dict[tuple, int] = {}
        #: per query kind, (LUT rows a launch gathers per shard, rows a
        #: shard holds), set when the kind is traced.
        self.lut_rows_read: dict[tuple, tuple[int, int]] = {}
        self._fns: dict[tuple, object] = {}
        self._idx_cache: dict[tuple, np.ndarray] = {}

    # ---------------------------- compiled fns ------------------------- #
    def _traced(self, key: tuple, idx) -> None:
        """Trace-time bookkeeping of one query kind: its trace count
        and the LUT rows its launch gathers against a shard's rows."""
        self.trace_counts[key] = self.trace_counts.get(key, 0) + 1
        self.lut_rows_read[key] = (int(idx.shape[0]), self.rows)

    def _fn(self, num_ranges: int, disjunction: bool):
        """The compiled executable for one query kind: kernel sweep over
        every shard + ``psum`` root join, under one ``jit``.  Cached per
        ``(num_ranges, disjunction)``; scalars/features arrive as the
        traced ``idx`` operand, so repeated queries of a kind re-trace
        zero times."""
        key = (num_ranges, disjunction)
        fn = self._fns.get(key)
        if fn is None:
            c, axis = self.num_chunks, "shards"

            def local(lut, idx):
                # executes at trace time only -> counts (re)traces
                self._traced(key, idx)
                bm, cnt = fused_predicate_banked(
                    lut, idx, c, num_ranges, disjunction, words=self.words)
                return bm, jax.lax.psum(cnt.sum(), axis)

            # check_vma=False: pallas_call has no replication rule; the
            # psum output is genuinely replicated regardless.
            fn = jax.jit(jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(P(axis), P()), out_specs=(P(axis), P()),
                check_vma=False))
            self._fns[key] = fn
        return fn

    def _compound_fn(self, term_ranges: tuple, term_disj: tuple,
                     conn_disj: tuple):
        """Compiled executable for one compound SHAPE (per-term range
        counts, per-term internal ops, connective chain) -- scalars and
        feature indices stay traced operands, so every compound of the
        same shape reuses one executable."""
        key = ("compound", term_ranges, term_disj, conn_disj)
        fn = self._fns.get(key)
        if fn is None:
            c, axis = self.num_chunks, "shards"

            def local(lut, idx):
                self._traced(key, idx)
                bm, cnt = fused_compound_banked(
                    lut, idx, c, term_ranges, term_disj, conn_disj,
                    words=self.words)
                return bm, jax.lax.psum(cnt.sum(), axis)

            fn = jax.jit(jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(P(axis), P()), out_specs=(P(axis), P()),
                check_vma=False))
            self._fns[key] = fn
        return fn

    # ---------------------------- index plumbing ----------------------- #
    def _range_idx(self, fi: int, x0: int, x1: int) -> np.ndarray:
        """Algorithm 1 row indices for ``x0 < f_fi < x1`` inside the
        stacked LUT: gt-side on feature ``fi``'s normal block, lt-side
        on its complement block with scalar ``MAX_f - x1`` (the NOT-free
        rewrite: ``B < x1  <=>  MAX_f-x1 < MAX_f-B``), where ``MAX_f``
        is feature ``fi``'s OWN plan max.  Scalars past a narrow
        column's range clamp like the machine path: the gt scalar
        saturates to ``MAX_f`` (``B > MAX_f`` is vacuously false --
        same bitmap), and ``x1 > MAX_f`` resolves the whole lt-side to
        the complement block's constant-one row (vacuously true).
        Narrower features pad their ``C_f`` index rows up to the
        kernel's static ``C_max`` with in-block identity lanes
        ``(zero_row, one_row)``."""
        key = (fi, x0, x1)
        idx = self._idx_cache.get(key)
        if idx is None:
            plan = self._cplans[fi]
            mx_f = self.plans[fi].max_value
            pad = self.num_chunks - plan.num_chunks
            _, zero, one = lut_offsets(plan)
            bn, bc = self._base_n[fi], self._base_c[fi]

            def lanes(lt, le, b):
                lt = np.concatenate([lt, np.full(pad, zero, np.int32)])
                le = np.concatenate([le, np.full(pad, one, np.int32)])
                return [lt + np.int32(b), le + np.int32(b)]

            gt = lanes(*resolve_indices(plan, min(x0, mx_f)), bn)
            if x1 > mx_f:
                allc = np.full(self.num_chunks, one, np.int32)
                lt = [allc + np.int32(bc), allc + np.int32(bc)]
            else:
                lt = lanes(*resolve_indices(plan, mx_f - x1), bc)
            idx = np.concatenate(gt + lt).astype(np.int32)
            self._idx_cache[key] = idx
        return idx

    def _indices(self, ranges: list[tuple[int, int, int]]) -> np.ndarray:
        with TraceAnnotation(SPAN_RESOLVE):
            return np.concatenate([self._range_idx(*r) for r in ranges])

    def _launch(self, fn, idx: np.ndarray):
        with TraceAnnotation(SPAN_DISPATCH):
            return fn(self.lut, jnp.asarray(idx))

    def _predicate(self, ranges: list[tuple[int, int, int]],
                   disjunction: bool):
        return self._launch(self._fn(len(ranges), disjunction),
                            self._indices(ranges))

    @staticmethod
    def _count(total: jnp.ndarray) -> int:
        with TraceAnnotation(SPAN_READBACK):
            return int(total)

    def _bitmap(self, bm: jnp.ndarray) -> np.ndarray:
        """[S, W] packed words -> bool [num_records] in table order."""
        with TraceAnnotation(SPAN_READBACK):
            words = np.asarray(bm)
        with TraceAnnotation(SPAN_UNPACK):
            bits = unpack_bits(words, self.per)              # [S, per]
            return bits.reshape(-1)[: self.table.num_records].astype(bool)

    def _mean(self, fk: int, bm: jnp.ndarray) -> float:
        """Host-side float finish of Q4/Q5: the mean of feature ``fk``
        over the selection, the machine path's same expression."""
        sel = self._bitmap(bm)
        with TraceAnnotation(SPAN_FINISH):
            vals = self.table.features[fk][sel]
            return float(vals.mean()) if vals.size else 0.0

    # ------------------------------- queries --------------------------- #
    def run(self, queries: list[tuple]) -> list:
        """Execute a batch of executor-format query tuples; returns one
        result per query, bit-exact vs ``QueryBatchExecutor.run``."""
        return [self._one(q) for q in queries]

    def _one(self, q: tuple):
        name, *p = q
        if name == "q1":
            bm, _ = self._predicate([tuple(p)], False)
            return self._bitmap(bm)
        if name == "q2":
            fi, x0, x1, fj, y0, y1 = p
            bm, _ = self._predicate([(fi, x0, x1), (fj, y0, y1)], False)
            return self._bitmap(bm)
        if name == "q3":
            fi, x0, x1, fj, y0, y1 = p
            _, total = self._predicate([(fi, x0, x1), (fj, y0, y1)], True)
            return self._count(total)
        if name == "q4":
            fk, fi, x0, x1, fj, y0, y1 = p
            bm, _ = self._predicate([(fi, x0, x1), (fj, y0, y1)], False)
            return self._mean(fk, bm)
        if name == "q5":
            fl, fk, fi, x0, x1, fj, y0, y1 = p
            bm, _ = self._predicate([(fi, x0, x1), (fj, y0, y1)], True)
            avg = int(self._mean(fk, bm))
            hi = min(2 * avg, self.mx)
            if avg >= hi:
                return 0
            # phase 2 reuses the (1, False) executable -- new scalars,
            # zero new traces
            _, total = self._predicate([(fl, avg, hi)], False)
            return self._count(total)
        if name == "compound":
            # (count, merge, ops, term tuples); `merge` picks the
            # machine path's in-DRAM vs host combine -- the fused
            # backend's single launch computes the identical result
            # either way, so it is accepted and ignored here
            count, _merge_mode, ops, terms = p
            ranges: list[tuple[int, int, int]] = []
            t_nr: list[int] = []
            t_disj: list[bool] = []
            for term in terms:
                tk, *tp = term
                if tk == "q1":
                    ranges.append(tuple(tp))
                    t_nr.append(1)
                    t_disj.append(False)
                elif tk in ("q2", "q3"):
                    fi, x0, x1, fj, y0, y1 = tp
                    ranges += [(fi, x0, x1), (fj, y0, y1)]
                    t_nr.append(2)
                    t_disj.append(tk == "q3")
                else:
                    raise ValueError(f"unsupported compound term {tk!r}")
            conn = tuple(op == "or" for op in ops)
            bm, total = self._launch(
                self._compound_fn(tuple(t_nr), tuple(t_disj), conn),
                self._indices(ranges))
            return self._count(total) if count else self._bitmap(bm)
        raise ValueError(f"unknown query {name!r}")


class FusedGbdtExec:
    """One-jit GBDT leaf-address computation for a whole batch.

    ``forest`` is duck-typed (``thresholds``, ``feature_idx``,
    ``leaves``, ``n_bits``, ``num_features``, ``num_trees``, ``depth``).
    The device half (comparisons, masking, OR-accumulation into the
    leaf-address bitmap) is exact integer math in one kernel grid over
    *(instance, word block)*, sharded over the mesh on the instance
    axis; leaf gathering/summation reuses the machine path's
    :func:`repro.apps.gbdt.assemble_leaves` so predictions are
    bit-exact vs ``backend="machine"``."""

    def __init__(self, forest, num_chunks: int, mesh=None,
                 plan=None) -> None:
        self.forest = forest
        thr = np.asarray(forest.thresholds, np.uint64).reshape(-1)
        if plan is not None:
            # adaptive threshold representation: LUT sized to the plan's
            # own width; instance values clamp to the plan max (exactly
            # the machine path's ClutchEngine(clamp=True) semantics --
            # thr > x is vacuously false past the threshold range)
            if thr.size and int(thr.max()) > plan.max_value:
                raise ValueError(
                    f"thresholds reach {int(thr.max())}, which overflows "
                    f"the plan's {plan.n_bits}-bit width")
            self.plan = plan.chunk_plan
            self.mx = plan.max_value
            self._clamp = True
        else:
            self.plan = make_plan(forest.n_bits, num_chunks)
            self.mx = (1 << forest.n_bits) - 1
            self._clamp = False
        self.num_chunks = self.plan.num_chunks
        self.n_nodes = forest.num_trees * forest.depth
        self.lut = encode_lut(jnp.asarray(thr.astype(np.uint32)), self.plan)
        f = forest.num_features
        flat_feat = np.asarray(forest.feature_idx).reshape(-1)
        mask_bits = (flat_feat[None, :] ==
                     np.arange(f)[:, None]).astype(np.uint8)
        words = pack_bits(mask_bits)                     # [F, ceil(n/32)]
        f_pad, w = round_up(f, SUBLANES), int(self.lut.shape[1])
        masks = np.zeros((f_pad, w), np.uint32)
        masks[:f, :words.shape[1]] = words
        self.mesh = mesh if mesh is not None else shard_mesh(
            max(jax.device_count(), 1))
        # the LUT and masks are replicated on every device of the mesh
        rep = NamedSharding(self.mesh, P())
        self.lut = jax.device_put(self.lut, rep)
        self.masks = jax.device_put(jnp.asarray(masks), rep)
        self.trace_counts: dict[tuple, int] = {}
        #: (padded instances a device's launch scores, leaf-bit words
        #: ``ceil(trees * depth / 32)``, LUT rows with the constant
        #: rows, features) of the launch, set when it is traced: a new
        #: batch shape retraces and replaces it.
        self.leafbits_launch: dict[str, tuple[int, int, int, int]] = {}
        self._fn_cached = None

    def _fn(self):
        if self._fn_cached is None:
            c, f = self.num_chunks, self.forest.num_features
            words = math.ceil(self.n_nodes / 32)
            rows = self.plan.rows_required + 2

            def local(lut, masks, idx):
                self.trace_counts["gbdt"] = \
                    self.trace_counts.get("gbdt", 0) + 1
                self.leafbits_launch["gbdt"] = (int(idx.shape[0]), words,
                                                rows, f)
                return gbdt_leafbits_banked(lut, masks, idx, c, f)

            axis = "shards"
            self._fn_cached = jax.jit(jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(P(), P(), P(axis)), out_specs=P(axis),
                check_vma=False))
        return self._fn_cached

    def leaf_addrs(self, X: np.ndarray) -> np.ndarray:
        """[B, F] quantized instances -> [B, T] int32 leaf addresses
        (exact; the whole device half of inference)."""
        forest, plan = self.forest, self.plan
        X = np.asarray(X)
        b = X.shape[0]
        # whole 8-instance kernel blocks on every device
        d = self.mesh.shape["shards"]
        b_pad = round_up(max(b, 1), SUBLANES * d)
        with TraceAnnotation(SPAN_RESOLVE):
            if self._clamp:
                X = np.minimum(X.astype(np.int64), self.mx)
            if b_pad != b:
                X = np.concatenate([X, np.repeat(X[:1], b_pad - b, axis=0)])
            cols = []
            for f in range(forest.num_features):
                lt, le = resolve_indices_banked(plan,
                                                X[:, f].astype(np.int64))
                cols += [lt, le]
            idx = np.concatenate(cols, axis=1).astype(np.int32)
        with TraceAnnotation(SPAN_DISPATCH):
            bm = self._fn()(self.lut, self.masks, jnp.asarray(idx))
        with TraceAnnotation(SPAN_READBACK):
            words = np.asarray(bm)
        with TraceAnnotation(SPAN_UNPACK):
            bits = unpack_bits(words, self.n_nodes)        # [B_pad, nodes]
            bits = bits.reshape(b_pad, forest.num_trees, forest.depth)
        with TraceAnnotation(SPAN_FINISH):
            weights = 1 << np.arange(forest.depth)[::-1]
            bits = bits[:b]
            addrs = np.empty((b, forest.num_trees), np.int32)
            step = max(1, FINISH_BLOCK_BYTES // (8 * self.n_nodes))
            for i in range(0, b, step):
                addrs[i:i + step] = (bits[i:i + step] * weights).sum(-1)
            return addrs

    def infer(self, X: np.ndarray) -> np.ndarray:
        """[B, F] -> [B] float32 predictions, bit-exact vs the machine
        executors (shared host-side leaf assembly)."""
        from repro.apps.gbdt import assemble_leaves

        X = np.asarray(X)
        if X.shape[0] == 0:
            return np.empty((0,), np.float32)
        addrs = self.leaf_addrs(X)
        with TraceAnnotation(SPAN_FINISH):
            return assemble_leaves(self.forest.leaves, addrs)
