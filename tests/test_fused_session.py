"""Fused-backend parity suite (ISSUE 6 acceptance).

``PudSession(backend="fused")`` must be *bit-exact* against
``backend="machine"`` for Q1-Q5 and GBDT inference -- the machine path
stays the DRAM-side cost oracle, the fused path is what actually runs.
Covered here:

* property-style parity of :class:`FusedTableExec` /
  :class:`FusedGbdtExec` over random plans, chunk counts, shard counts
  and table sizes (hypothesis, CPU interpret mode);
* session-level machine-vs-fused equality for every query kind and for
  predictions (predictions exact vs machine -- shared
  ``assemble_leaves`` float summation order -- and allclose vs
  ``reference_predict``, whose axis order differs);
* the compile-cache invariant: repeated jobs -- including Q5's phase-2
  re-query with brand-new scalars -- re-trace ZERO times;
* host-side resolver memoization (``resolve_indices`` lru cache, the
  vectorized ``resolve_indices_banked``);
* a multi-shard ``shard_map`` run on a REAL 2-device mesh in a
  subprocess (``XLA_FLAGS=--xla_force_host_platform_device_count`` is
  never set in-process -- conftest must stay device-count-neutral);
* the serving front end on a fused session, and fused-cache
  invalidation on drop/evict;
* CatBoost's default forest shape (1000 depth-6 trees, 8-bit features)
  against the benchmark's NumPy reference, and the leaf-bits launch
  counter against the benchmark's byte count.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.apps import gbdt as G
from repro.apps import predicate as P
from repro.core import cost
from repro.core.encoding import make_plan
from repro.kernels import ops
from repro.kernels import fused_session as FS
from repro.kernels.fused_session import FusedGbdtExec, FusedTableExec
from repro.pud import Q1, Q2, Q3, Q4, Q5, PudSession
from repro.serve.pud_service import PudRequest, PudService

# the benchmark's package (``bench``) lives at the repository's root
ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness, kernels  # noqa: E402
from bench.ref import forest as bench_forest  # noqa: E402

MX = 255
QA = dict(fi=0, x0=MX // 8, x1=MX // 2, fj=1, y0=MX // 4, y1=3 * MX // 4)


def session(backend="machine"):
    return PudSession(sys_cfg=cost.DESKTOP, num_devices=2,
                      backend=backend)


# --------------------------------------------------------------------- #
# Property-style executor parity
# --------------------------------------------------------------------- #

@settings(deadline=None, max_examples=6)
@given(st.integers(0, 10_000))
def test_fused_table_exec_q1_q5_parity_property(seed):
    """Random (n_bits, chunks, shards, records, scalars): every query
    kind matches the NumPy references exactly -- including Q4's float
    finish and Q5's host-barrier phase 2."""
    rng = np.random.default_rng(seed)
    n_bits = int(rng.choice([8, 12, 16]))
    chunks = int(rng.integers(max(1, n_bits // 8), 5))
    shards = int(rng.integers(1, 4))
    n = int(rng.integers(40, 2500))
    t = P.Table.generate(n, n_bits, num_features=3, seed=seed)
    ex = FusedTableExec(t, num_shards=shards, num_chunks=chunks)
    mx = (1 << n_bits) - 1

    def span():
        a, b = sorted(int(x) for x in rng.integers(0, mx + 1, 2))
        return a, max(b, a + 1)

    x0, x1 = span()
    y0, y1 = span()
    qs = [("q1", 0, x0, x1),
          ("q2", 0, x0, x1, 1, y0, y1),
          ("q3", 0, x0, x1, 1, y0, y1),
          ("q4", 2, 0, x0, x1, 1, y0, y1),
          ("q5", 2, 1, 0, x0, x1, 1, y0, y1)]
    r1, r2, r3, r4, r5 = ex.run(qs)
    np.testing.assert_array_equal(r1, P.reference_q1(t, 0, x0, x1))
    np.testing.assert_array_equal(
        r2, P.reference_q2(t, 0, x0, x1, 1, y0, y1))
    assert r3 == P.reference_q3(t, 0, x0, x1, 1, y0, y1)
    assert r4 == P.reference_q4(t, 2, 0, x0, x1, 1, y0, y1)
    assert r5 == P.reference_q5(t, 2, 1, 0, x0, x1, 1, y0, y1)


@settings(deadline=None, max_examples=6)
@given(st.integers(0, 10_000))
def test_fused_gbdt_exec_parity_property(seed):
    """Random forest shapes: leaf addresses are exact vs the NumPy
    reference; predictions match ``reference_predict`` to float32
    rounding (exactness vs the MACHINE path is asserted at session
    level -- the reference sums over the other axis)."""
    rng = np.random.default_rng(seed)
    n_bits = int(rng.choice([8, 16]))
    forest = G.ObliviousForest.random(
        num_trees=int(rng.integers(2, 20)),
        depth=int(rng.integers(2, 7)),
        num_features=int(rng.integers(2, 6)),
        n_bits=n_bits, seed=seed)
    ex = FusedGbdtExec(forest, num_chunks=max(1, n_bits // 8))
    X = rng.integers(0, 1 << n_bits,
                     (int(rng.integers(1, 40)), forest.num_features),
                     dtype=np.int64)
    np.testing.assert_array_equal(ex.leaf_addrs(X),
                                  G.reference_leaf_addrs(forest, X))
    np.testing.assert_allclose(ex.infer(X),
                               G.reference_predict(forest, X), atol=1e-5)


@pytest.mark.parametrize("depth,rows", [(1, 1), (9, 5), (12, 64)])
def test_fused_leaf_addrs_in_blocks(monkeypatch, depth, rows):
    """The leaf-address sum runs ``rows`` instances a block: one row a
    block, a ragged last block, and one block for the whole batch, over
    a lone level and addresses wider than a byte."""
    monkeypatch.setattr(FS, "FINISH_BLOCK_BYTES", 8 * 3 * depth * rows)
    forest = G.ObliviousForest.random(num_trees=3, depth=depth,
                                      num_features=4, n_bits=8, seed=depth)
    X = np.random.default_rng(depth).integers(0, 256, (13, 4),
                                              dtype=np.int64)
    addrs = FusedGbdtExec(forest, num_chunks=1).leaf_addrs(X)
    assert addrs.dtype == np.int32
    np.testing.assert_array_equal(addrs, G.reference_leaf_addrs(forest, X))


def test_fused_table_exec_empty_selection_and_always_true():
    t = P.Table.generate(500, 8, num_features=2, seed=1)
    ex = FusedTableExec(t, num_shards=2, num_chunks=2)
    # empty WHERE -> Q4 average of nothing is 0.0, matching the machine
    assert ex.run([("q4", 1, 0, 5, 4, 1, 0, 255)])[0] == 0.0
    # boundary scalars exercise every chunk's const-row substitution
    bm = ex.run([("q1", 0, 0, 255)])[0]
    np.testing.assert_array_equal(bm, P.reference_q1(t, 0, 0, 255))


def test_lut_rows_read_counts_the_gathered_rows():
    """A Q3 at 4 chunks gathers its 2 ranges x 4 x C = 32 index lanes'
    rows of each shard, whatever the shard holds; a compound of 5
    ranges gathers 80, more than this small table's rows."""
    t = P.Table.generate(3000, 16, num_features=3, seed=2)
    ex = FusedTableExec(t, num_shards=2, num_chunks=4)
    # 3 features x (normal + complement) x (4 x 15 planes + 2, tiled)
    assert ex.rows == 3 * 2 * 64
    got = ex.run([("q3", 0, 100, 40000, 1, 2000, 60000)])[0]
    assert got == P.reference_q3(t, 0, 100, 40000, 1, 2000, 60000)
    assert ex.lut_rows_read == {(2, True): (32, ex.rows)}
    q1, q2 = ("q1", 2, 5, 9000), ("q2", 0, 1, 30000, 1, 7, 65000)
    ex.run([("compound", True, None, ("or", "and"), (q1, q2, q2))])
    assert ex.lut_rows_read[
        ("compound", (1, 2, 2), (False, False, False), (True, False))
    ] == (80, ex.rows)


# --------------------------------------------------------------------- #
# Session-level backend parity
# --------------------------------------------------------------------- #

def test_session_fused_backend_matches_machine_bit_exactly():
    t = P.Table.generate(30_000, 8, seed=11)
    qs = [Q1(fi=0, x0=MX // 8, x1=MX // 2), Q2(**QA), Q3(**QA),
          Q4(fk=2, **QA), Q5(fl=3, fk=2, **QA)]
    s = session()
    h = s.create_table(t, name="t")
    machine = s.query(h, qs)
    fused = s.query(h, qs, backend="fused")
    assert machine.backend == "machine" and fused.backend == "fused"
    for q, m, f in zip(qs, machine.result, fused.result):
        if isinstance(m, np.ndarray):
            np.testing.assert_array_equal(f, m)
        else:
            assert f == m            # ints exact; Q4 float finish shares
            #                          the machine path's expression
        assert q.check(t, f)
    # machine jobs carry scheduler stats, fused jobs wall-clock
    assert machine.stats is not None and machine.wallclock_ns is None
    assert fused.stats is None and fused.wallclock_ns > 0
    assert fused.makespan_ns == fused.wallclock_ns


def test_session_fused_predict_exact_vs_machine():
    forest = G.ObliviousForest.random(num_trees=16, depth=4,
                                      num_features=4, n_bits=8, seed=3)
    s = session(backend="fused")
    h = s.load_forest(forest, name="f", banks_per_group=2)
    X = np.random.default_rng(9).integers(0, 256, (33, 4),
                                          dtype=np.uint64)
    fused = s.predict(h, X)
    machine = s.predict(h, X, backend="machine")
    # exact vs machine (shared assemble_leaves summation order) ...
    np.testing.assert_array_equal(fused.result, machine.result)
    # ... and correct vs the reference up to float32 re-association
    np.testing.assert_allclose(fused.result,
                               G.reference_predict(forest, X), atol=1e-5)
    assert fused.backend == "fused" and fused.wallclock_ns > 0


def test_session_default_backend_and_per_job_override():
    t = P.Table.generate(4000, 8, seed=2)
    s = session(backend="fused")
    h = s.create_table(t, name="t")
    q = Q1(fi=0, x0=10, x1=200)
    assert s.query(h, q).backend == "fused"
    assert s.query(h, q, backend="machine").backend == "machine"
    with pytest.raises(ValueError, match="backend"):
        PudSession(sys_cfg=cost.DESKTOP, backend="warp")


# --------------------------------------------------------------------- #
# Compile-cache invariant: zero retraces on repeated jobs
# --------------------------------------------------------------------- #

def test_repeated_queries_retrace_zero_times():
    t = P.Table.generate(6000, 8, seed=5)
    s = session(backend="fused")
    h = s.create_table(t, name="t")
    qs = [Q1(fi=0, x0=MX // 8, x1=MX // 2), Q2(**QA), Q3(**QA),
          Q4(fk=2, **QA), Q5(fl=3, fk=2, **QA)]
    s.query(h, qs)
    fx = s._fused["t"]
    # three executables cover all five kinds (Q5 phase 2 reuses q1's)
    first = dict(fx.trace_counts)
    assert set(first) == {(1, False), (2, False), (2, True)}
    assert all(v == 1 for v in first.values())
    # NEW scalars and features, same kinds: zero new traces
    s.query(h, [Q1(fi=2, x0=3, x1=77), Q3(fi=1, x0=9, x1=99, fj=2,
                                          y0=1, y1=50),
                Q5(fl=1, fk=3, **QA)])
    assert dict(fx.trace_counts) == first


def test_repeated_predict_retraces_zero_times():
    forest = G.ObliviousForest.random(num_trees=8, depth=3,
                                      num_features=3, n_bits=8, seed=2)
    s = session(backend="fused")
    h = s.load_forest(forest, name="f", banks_per_group=2)
    rng = np.random.default_rng(4)
    s.predict(h, rng.integers(0, 256, (6, 3), dtype=np.uint64))
    fx = s._fused["f"]
    assert fx.trace_counts == {"gbdt": 1}
    # same padded batch shape, new values -> zero new traces
    s.predict(h, rng.integers(0, 256, (6, 3), dtype=np.uint64))
    assert fx.trace_counts == {"gbdt": 1}


# --------------------------------------------------------------------- #
# CatBoost's default forest shape, and the leaf-bits launch counter
# --------------------------------------------------------------------- #

def fused_forest(trees, depth, features, n_bits, seed):
    """A fused session holding a random forest at 1 chunk, as the
    forest cells load it: (session, handle, forest)."""
    forest = G.ObliviousForest.random(trees, depth, features, n_bits,
                                      seed=seed)
    s = session(backend="fused")
    return s, s.load_forest(forest, name="f", num_chunks=1), forest


@pytest.fixture(scope="module")
def catboost_default_forest():
    """CatBoost's training defaults: 1000 oblivious trees of depth 6
    over 8-bit (``border_count=254``) features; 8 features."""
    return fused_forest(1000, 6, 8, 8, seed=17)


@pytest.mark.parametrize("batch", [8, 13])
def test_catboost_default_forest_matches_references(catboost_default_forest,
                                                    batch):
    """A whole 8-instance block and a ragged one at the published
    shape: leaf addresses exact, predictions against both references."""
    s, h, forest = catboost_default_forest
    X = np.random.default_rng(batch).integers(0, 256, (batch, 8),
                                              dtype=np.int64)
    job = s.predict(h, X)
    np.testing.assert_array_equal(s._fused["f"].leaf_addrs(X),
                                  G.reference_leaf_addrs(forest, X))
    # the benchmark's limit (bench/kinds/forest.py): above the ~1e-4 by
    # which two float32 orders of 1000 unit-scale leaves differ
    want = bench_forest.Reference(forest)(X)
    np.testing.assert_allclose(job.result, want, rtol=0, atol=1e-3)
    # reference_predict sums each instance's leaves in the served
    # path's own float32 order, so only rounding of equal sums is left
    np.testing.assert_allclose(job.result, G.reference_predict(forest, X),
                               rtol=0, atol=1e-5)


def test_leafbits_launch_counts_what_the_roofline_reads():
    """One predict of a ragged batch at the benchmark's tiny forest
    size: the counter holds the padded batch, the leaf-bit words, the
    LUT rows and the features, and the bytes they give are what
    ``bench.kernels.leafbits_bytes`` counts for that batch."""
    cfg = harness.load_module(ROOT / "tests" / "bench" / "tiny.py").FOREST
    s, h, _ = fused_forest(cfg["trees"], cfg["depth"], cfg["features"],
                           cfg["n_bits"], seed=3)
    X = np.random.default_rng(4).integers(0, 256, (13, cfg["features"]),
                                          dtype=np.int64)
    s.predict(h, X)
    launch = s._fused["f"].leafbits_launch
    # 40 trees x 6 levels = 240 bits in 8 words; 255 planes + 2 rows
    assert launch == {"gbdt": (16, 8, 257, 8)}
    batch, words, rows, features = launch["gbdt"]
    idx = batch * features * 2 * cfg["num_chunks"]
    need = (rows * words + features * words + idx + batch * words) * 4
    assert need == kernels.leafbits_bytes(cfg, batch)


def test_drop_and_evict_invalidate_fused_cache():
    t = P.Table.generate(4000, 8, seed=7)
    s = session(backend="fused")
    h = s.create_table(t, name="t")
    q = Q1(fi=0, x0=10, x1=200)
    s.query(h, q)
    assert "t" in s._fused
    s.evict(h)
    assert "t" not in s._fused          # stale LUTs never survive evict
    s.query(h, q)                       # reload rebuilds transparently
    assert "t" in s._fused
    s.drop(h)
    assert "t" not in s._fused


def test_bitserial_table_rejects_fused_backend():
    t = P.Table.generate(4000, 8, seed=7)
    s = session()
    h = s.create_table(t, name="t", method="bitserial")
    with pytest.raises(TypeError, match="clutch"):
        s.query(h, Q1(fi=0, x0=10, x1=200), backend="fused")


# --------------------------------------------------------------------- #
# Host-side resolver memoization (satellite a)
# --------------------------------------------------------------------- #

def test_resolve_indices_is_memoized_per_plan_and_scalar():
    plan = make_plan(16, 4)
    ops._resolve_scalar_cached.cache_clear()
    a1 = ops.resolve_indices(plan, 12345)
    before = ops._resolve_scalar_cached.cache_info()
    a2 = ops.resolve_indices(plan, 12345)
    after = ops._resolve_scalar_cached.cache_info()
    assert after.hits == before.hits + 1
    assert after.misses == before.misses
    np.testing.assert_array_equal(a1[0], a2[0])
    np.testing.assert_array_equal(a1[1], a2[1])
    # a different plan with equal chunk widths is the same cache key
    # only if it compares equal (frozen dataclass): distinct scalars miss
    ops.resolve_indices(plan, 12346)
    assert ops._resolve_scalar_cached.cache_info().misses == \
        after.misses + 1


@settings(deadline=None, max_examples=10)
@given(st.integers(0, 5000))
def test_resolve_indices_banked_matches_scalar_resolver(seed):
    rng = np.random.default_rng(seed)
    n_bits = int(rng.choice([8, 12, 16, 32]))
    chunks = int(rng.integers(max(1, n_bits // 8), 5))
    plan = make_plan(n_bits, chunks)
    a = rng.integers(0, 1 << n_bits, 17).astype(np.int64)
    a[rng.integers(0, 17)] = -1          # always-true sentinel lane
    lt, le = ops.resolve_indices_banked(plan, a)
    _, _, one_row = ops.lut_offsets(plan)
    for i, s in enumerate(a):
        if s < 0:
            # banked-only convention: -1 pins both lookups to const-one
            assert (lt[i] == one_row).all() and (le[i] == one_row).all()
            continue
        slt, sle = ops.resolve_indices(plan, int(s))
        np.testing.assert_array_equal(lt[i], slt)
        np.testing.assert_array_equal(le[i], sle)


def test_resolve_indices_banked_rejects_out_of_range():
    plan = make_plan(8, 2)
    with pytest.raises(ValueError):
        ops.resolve_indices_banked(plan, np.array([3, 256], np.int64))


# --------------------------------------------------------------------- #
# Multi-device shard_map (subprocess: conftest stays device-neutral)
# --------------------------------------------------------------------- #

def test_fused_parity_on_real_two_device_mesh_subprocess():
    """The shard_map root join must hold on an actual multi-device
    mesh, not just the 1-device degenerate case.  The device count can
    only be forced before jax initializes, so this runs in a child
    process (XLA_FLAGS is NEVER set by conftest, per spec)."""
    src = str(Path(__file__).resolve().parents[1] / "src")
    code = textwrap.dedent("""
        import jax, numpy as np
        assert jax.device_count() == 2, jax.device_count()
        from repro.apps import gbdt as G
        from repro.apps import predicate as P
        from repro.kernels.fused_session import FusedGbdtExec, \\
            FusedTableExec
        t = P.Table.generate(5000, 8, num_features=3, seed=3)
        ex = FusedTableExec(t, num_shards=4, num_chunks=2)
        assert ex.mesh.shape["shards"] == 2       # 4 shards, 2 devices
        r1, r3 = ex.run([("q1", 0, 10, 200),
                         ("q3", 0, 10, 200, 1, 30, 220)])
        assert (r1 == P.reference_q1(t, 0, 10, 200)).all()
        assert r3 == P.reference_q3(t, 0, 10, 200, 1, 30, 220)
        f = G.ObliviousForest.random(num_trees=8, depth=3,
                                     num_features=3, n_bits=8, seed=2)
        gx = FusedGbdtExec(f, num_chunks=1)
        assert gx.mesh.shape["shards"] == 2
        X = np.random.default_rng(0).integers(0, 256, (9, 3),
                                              dtype=np.int64)
        assert (gx.leaf_addrs(X) == G.reference_leaf_addrs(f, X)).all()
        print("MESH-PARITY-OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=2")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    assert "MESH-PARITY-OK" in out.stdout


# --------------------------------------------------------------------- #
# Serving front end on a fused session
# --------------------------------------------------------------------- #

def test_pud_service_runs_on_fused_session():
    t = P.Table.generate(5000, 8, seed=8)
    svc = PudService(session(backend="fused"))
    svc.session.create_table(t, name="events")
    forest = G.ObliviousForest.random(num_trees=8, depth=3,
                                      num_features=3, n_bits=8, seed=5)
    svc.session.load_forest(forest, name="ranker", banks_per_group=2)
    X = np.random.default_rng(6).integers(0, 256, (4, 3),
                                          dtype=np.uint64)
    svc.submit(PudRequest(rid=1, resource="events",
                          query=Q1(fi=0, x0=10, x1=200)))
    svc.submit(PudRequest(rid=2, resource="ranker", X=X))
    svc.submit(PudRequest(rid=3, resource="events", query=Q3(**QA)))
    rs = svc.flush()
    assert [r.rid for r in rs] == [1, 2, 3]
    np.testing.assert_array_equal(rs[0].result,
                                  P.reference_q1(t, 0, 10, 200))
    assert rs[2].result == P.reference_q3(t, **QA)
    np.testing.assert_allclose(rs[1].result,
                               G.reference_predict(forest, X), atol=1e-5)
    # fused jobs have no scheduled timeline: latency falls back to the
    # measured batch wall-clock for every member
    assert all(r.stats is None for r in rs)
    assert rs[0].latency_ns == rs[2].latency_ns > 0
    assert rs[1].latency_ns > 0
