"""Per-kernel shape/dtype sweeps: every Pallas kernel (interpret mode on
CPU) against its pure-jnp ref.py oracle, plus hypothesis properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.encoding import make_plan
from repro.kernels import common, ops, ref
from repro.kernels.common import (
    float_to_monotonic_u32,
    unpack_bits_jnp,
)

RNG = np.random.default_rng(7)


# ------------------------- clutch_merge ------------------------------ #

@pytest.mark.parametrize("n_bits,chunks", [(8, 1), (8, 2), (16, 2),
                                           (16, 4), (32, 5), (32, 8),
                                           (12, 3), (24, 6)])
@pytest.mark.parametrize("n", [100, 4096, 5000])
def test_clutch_merge_sweep(n_bits, chunks, n):
    plan = make_plan(n_bits, chunks)
    vals = jnp.asarray(RNG.integers(0, 1 << n_bits, n, dtype=np.uint32))
    a = int(RNG.integers(0, 1 << n_bits))
    got = ops.clutch_compare(vals, a, plan)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(vals) > a)


def test_clutch_merge_kernel_equals_ref():
    plan = make_plan(16, 4)
    vals = jnp.asarray(RNG.integers(0, 1 << 16, 3000, dtype=np.uint32))
    lut = ops.encode_lut(vals, plan)
    lt, le = ops.resolve_indices(plan, 12345)
    k = ops.compare_gt_scalar(lut, jnp.asarray(lt), jnp.asarray(le))
    r = ref.clutch_merge_ref(lut, jnp.asarray(lt), jnp.asarray(le))
    np.testing.assert_array_equal(np.asarray(k), np.asarray(r))


@settings(deadline=None, max_examples=15)
@given(st.integers(0, 2**16 - 1), st.integers(1, 5))
def test_clutch_merge_hypothesis(a, chunks):
    plan = make_plan(16, chunks)
    vals = jnp.asarray(RNG.integers(0, 1 << 16, 512, dtype=np.uint32))
    got = ops.clutch_compare(vals, a, plan)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(vals) > a)


# ------------------------ temporal_encode ---------------------------- #

@pytest.mark.parametrize("k", [1, 3, 6, 8])
def test_temporal_encode_vs_ref(k):
    n = 2048
    vals = jnp.asarray(RNG.integers(0, 1 << k, n, dtype=np.uint32))
    plan = make_plan(k, 1)
    lut = ops.encode_lut(vals, plan)
    want = ref.temporal_encode_ref(vals, k)
    np.testing.assert_array_equal(
        np.asarray(lut[: (1 << k) - 1, : want.shape[1]]), np.asarray(want))


# ------------------------- bitserial_cmp ----------------------------- #

@pytest.mark.parametrize("n_bits", [4, 8, 16, 32])
@pytest.mark.parametrize("n", [77, 4096])
def test_bitserial_kernel_sweep(n_bits, n):
    vals = jnp.asarray(RNG.integers(0, 1 << n_bits, n, dtype=np.uint32))
    planes = ops.encode_bitplanes(vals, n_bits)
    a = int(RNG.integers(0, 1 << n_bits))
    words = ops.bitserial_compare(planes, a, n_bits)
    got = unpack_bits_jnp(words, n).astype(bool)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(vals) > a)
    r = ref.bitserial_cmp_ref(planes[:n_bits], np.uint32(a), n_bits)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(words))


# ------------------------- fused_query -------------------------------- #

@pytest.mark.parametrize("n_bits,chunks", [(8, 2), (16, 4), (32, 8)])
def test_fused_range_count(n_bits, chunks):
    plan = make_plan(n_bits, chunks)
    n = 3333
    vals = jnp.asarray(RNG.integers(0, 1 << n_bits, n, dtype=np.uint32))
    lut = ops.encode_lut(vals, plan)
    lut_c = ops.encode_lut(vals, plan, complement=True)
    mx = (1 << n_bits) - 1
    x0, x1 = mx // 5, 4 * mx // 5
    gt = ops.resolve_indices(plan, x0)
    lt = ops.resolve_indices(plan, mx - x1)
    idx = jnp.asarray(np.concatenate([gt[0], gt[1], lt[0], lt[1]]))
    bm, cnt = ops.range_count(lut, lut_c, idx, chunks)
    got = unpack_bits_jnp(bm, n).astype(bool)
    want = (np.asarray(vals) > x0) & (np.asarray(vals) < x1)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert int(cnt) == int(want.sum())


# ---------------- fused_predicate_banked / gbdt_leafbits -------------- #

@pytest.mark.parametrize("n_bits,chunks,shards", [(8, 2, 1), (16, 4, 3),
                                                  (32, 8, 2)])
@pytest.mark.parametrize("num_ranges,disjunction", [(1, False), (2, False),
                                                    (2, True)])
def test_fused_predicate_banked_vs_ref(n_bits, chunks, shards, num_ranges,
                                       disjunction):
    from repro.kernels.fused_query import fused_predicate_banked, row_slabs

    plan = make_plan(n_bits, chunks)
    n, feats = 900, 3
    mx = (1 << n_bits) - 1
    vals = RNG.integers(0, 1 << n_bits, (shards, feats, n), dtype=np.uint32)
    # stacked layout: per shard, every feature's normal block then every
    # feature's complement block (what FusedTableExec builds)
    lut = jnp.stack([jnp.concatenate(
        [ops.encode_lut(jnp.asarray(vals[s, f]), plan, complement=c)
         for c in (False, True) for f in range(feats)], axis=0)
        for s in range(shards)])
    r_pad = lut.shape[1] // (2 * feats)
    ranges = [(0, mx // 7, 5 * mx // 7), (1, mx // 3, 9 * mx // 10)]
    parts = []
    for fi, x0, x1 in ranges[:num_ranges]:
        g = ops.resolve_indices(plan, x0)
        lt = ops.resolve_indices(plan, mx - x1)
        parts += [g[0] + fi * r_pad, g[1] + fi * r_pad,
                  lt[0] + (feats + fi) * r_pad,
                  lt[1] + (feats + fi) * r_pad]
    idx = jnp.asarray(np.concatenate(parts).astype(np.int32))
    bm, cnt = fused_predicate_banked(row_slabs(lut), idx, chunks,
                                     num_ranges, disjunction,
                                     words=lut.shape[2])
    rbm, rcnt = ref.fused_predicate_banked_ref(lut, idx, chunks,
                                               num_ranges, disjunction)
    np.testing.assert_array_equal(np.asarray(bm), np.asarray(rbm))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(rcnt))
    # and against plain numpy semantics
    def rmask(s, fi, x0, x1):
        v = vals[s, fi].astype(np.int64)
        return (v > x0) & (v < x1)
    for s in range(shards):
        want = rmask(s, *ranges[0])
        if num_ranges == 2:
            m2 = rmask(s, *ranges[1])
            want = want | m2 if disjunction else want & m2
        got = unpack_bits_jnp(bm[s], n).astype(bool)
        np.testing.assert_array_equal(np.asarray(got), want)
        assert int(cnt[s]) == int(want.sum())


# Gather edge cases, through the executor's own layout and index lanes:
# (n_bits, chunks, per-column plans, records, shards, ranges, OR,
# VMEM budget for the gather buffers, None for the default).
_GATHER_CASES = {
    # one feature twice: every lane repeats; x0 = 0 and x1 = MAX
    # resolve to constant rows
    "repeated_and_const_rows": (16, 4, None, 900, 2,
                                [(0, 0, 65535), (0, 0, 65535)], False,
                                None),
    # a 2-chunk 8-bit column padded to 4 chunks with identity lanes,
    # its x1 past the column's max clamped to the constant-one row
    "clamped_narrow_plan": (16, 4, [(8, 2), (16, 4)], 900, 2,
                            [(0, 10, 40000), (1, 9000, 50000)], True,
                            None),
    # W = 384 words = 128 x 3, padded to a 1024-word slab
    "odd_width": (16, 4, None, 12000, 1,
                  [(0, 3000, 60000), (1, 100, 30000)], False, None),
    # a budget of one 1024-word block for K = 16 lanes: two blocks a
    # shard, so the double buffer crosses shards
    "three_shards_two_blocks": (8, 2, None, 120000, 3,
                                [(0, 30, 200), (1, 77, 250)], True,
                                2 * 16 * 1024 * 4),
    # one column: K = 24 lanes gather more rows than a shard's 16
    "lanes_exceed_rows": (2, 2, [(2, 2)], 700, 2,
                          [(0, 0, 2), (0, 1, 3), (0, 2, 3)], True, None),
}


@pytest.mark.parametrize("case", list(_GATHER_CASES))
def test_fused_predicate_banked_gather_cases(case, monkeypatch):
    from repro.apps.predicate import Table
    from repro.core.encoding import ColumnPlan
    from repro.kernels import fused_query
    from repro.kernels.fused_session import FusedTableExec

    (n_bits, chunks, plans, n, shards, ranges, disj,
     budget) = _GATHER_CASES[case]
    if budget is not None:
        monkeypatch.setattr(fused_query, "VMEM_TILE_BYTES", budget)
    feats = 2 if plans is None else len(plans)
    cols = [RNG.integers(0, 1 << (n_bits if plans is None else p[0]), n,
                         dtype=np.uint32) for p in (plans or [None] * feats)]
    ex = FusedTableExec(Table(n_bits=n_bits, features=cols),
                        num_shards=shards, num_chunks=chunks,
                        plans=None if plans is None else
                        [ColumnPlan(*p) for p in plans])
    idx = jnp.asarray(ex._indices(ranges))
    # the oracle reads the plain [S, R, W] stack the slabs were made of
    s, w = ex.num_shards, ex.words
    lut = ex.lut.reshape(s, ex.rows, -1)[:, :, :w]
    bm, cnt = fused_query.fused_predicate_banked(
        ex.lut, idx, ex.num_chunks, len(ranges), disj, words=w)
    rbm, rcnt = ref.fused_predicate_banked_ref(lut, idx, ex.num_chunks,
                                               len(ranges), disj)
    np.testing.assert_array_equal(np.asarray(bm), np.asarray(rbm))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(rcnt))
    want = None
    for fi, x0, x1 in ranges:
        v = cols[fi].astype(np.int64)
        m = (v > x0) & (v < x1)
        want = m if want is None else (want | m if disj else want & m)
    got = unpack_bits_jnp(bm, ex.per).reshape(-1)[:n].astype(bool)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert int(cnt.sum()) == int(want.sum())


@pytest.mark.parametrize("n_bits,chunks", [(8, 1), (16, 2), (32, 5)])
def test_gbdt_leafbits_banked_vs_ref(n_bits, chunks):
    from repro.kernels.common import SUBLANES, round_up
    from repro.kernels.fused_query import gbdt_leafbits_banked

    plan = make_plan(n_bits, chunks)
    feats, nodes, b = 5, 333, 7
    thr = RNG.integers(0, 1 << n_bits, nodes, dtype=np.uint32)
    feat_of = RNG.integers(0, feats, nodes)
    lut = ops.encode_lut(jnp.asarray(thr), plan)
    mask_bits = (feat_of[None, :] == np.arange(feats)[:, None]
                 ).astype(np.uint8)
    from repro.core.machine import pack_bits
    words = pack_bits(mask_bits)
    masks = np.zeros((round_up(feats, SUBLANES), lut.shape[1]), np.uint32)
    masks[:feats, :words.shape[1]] = words
    X = RNG.integers(0, 1 << n_bits, (b, feats), dtype=np.int64)
    cols = []
    for f in range(feats):
        lt, le = ops.resolve_indices_banked(plan, X[:, f])
        cols += [lt, le]
    idx = jnp.asarray(np.concatenate(cols, axis=1).astype(np.int32))
    got = gbdt_leafbits_banked(lut, jnp.asarray(masks), idx, chunks, feats)
    want = ref.gbdt_leafbits_banked_ref(lut, jnp.asarray(masks), idx,
                                        chunks, feats)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # numpy semantics: node j's bit for instance i == (X[i, feat] < thr_j)
    bits = unpack_bits_jnp(got, nodes)
    sem = (X[:, feat_of] < thr[None, :].astype(np.int64))
    np.testing.assert_array_equal(np.asarray(bits).astype(bool), sem)


# ------------------------- leaf_gather -------------------------------- #

@pytest.mark.parametrize("b,t,depth", [(8, 16, 4), (100, 64, 6),
                                       (256, 128, 8), (33, 7, 5)])
def test_leaf_gather_sweep(b, t, depth):
    addrs = jnp.asarray(RNG.integers(0, 1 << depth, (b, t), dtype=np.int32))
    leaves = jnp.asarray(
        RNG.normal(size=(t, 1 << depth)).astype(np.float32))
    got = ops.gbdt_leaf_sum(addrs, leaves)
    want = ref.leaf_gather_ref(addrs, leaves)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# -------------------------- minp_mask --------------------------------- #

def test_monotonic_u32_is_order_preserving():
    x = jnp.asarray(np.float32([-1e30, -5.5, -0.0, 0.0, 1e-9, 3.14, 2e30]))
    u = np.asarray(float_to_monotonic_u32(x))
    assert (np.diff(u.astype(np.int64)) >= 0).all()


@pytest.mark.parametrize("b,v", [(1, 100), (4, 1024), (8, 50000), (3, 7)])
def test_minp_mask_sweep(b, v):
    logits = jnp.asarray(RNG.normal(size=(b, v)).astype(np.float32) * 8)
    tau = jnp.asarray(RNG.normal(size=(b,)).astype(np.float32))
    got = ops.sample_threshold_mask(logits, tau)
    want = ref.minp_mask_ref(logits, tau)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(deadline=None, max_examples=20)
@given(st.floats(-100, 100, width=32), st.integers(1, 4))
def test_minp_mask_hypothesis(tau_val, b):
    v = 300
    logits = jnp.asarray(RNG.normal(size=(b, v)).astype(np.float32) * 50)
    tau = jnp.full((b,), tau_val, jnp.float32)
    got = ops.sample_threshold_mask(logits, tau)
    want = ref.minp_mask_ref(logits, tau)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------- banked clutch_merge --------------------------- #

@pytest.mark.parametrize("n_bits,chunks,banks", [(8, 2, 3), (16, 4, 4),
                                                 (16, 2, 1), (32, 5, 2)])
def test_clutch_compare_banked_sweep(n_bits, chunks, banks):
    """One kernel program per bank shard == per-bank numpy comparisons,
    including boundary scalars and the always-true -1 encoding."""
    plan = make_plan(n_bits, chunks)
    n = 700
    vals = RNG.integers(0, 1 << n_bits, (banks, n), dtype=np.uint32)
    mx = (1 << n_bits) - 1
    pool = [0, mx, -1, 123 % mx, int(RNG.integers(0, mx))]
    a = np.array(pool[:banks], np.int64)
    got = ops.clutch_compare_banked(jnp.asarray(vals), a, plan)
    want = vals.astype(np.int64) > a[:, None]   # -1 < everything
    np.testing.assert_array_equal(np.asarray(got), want)


def test_clutch_compare_banked_matches_machine():
    """The banked kernel and the banked PuD machine produce identical
    bitmaps from the same per-bank shards and per-bank scalars."""
    from repro.core.clutch import ClutchEngine
    from repro.core.machine import BankedSubarray, PuDArch

    banks, n, n_bits, chunks = 5, 1000, 16, 4
    vals = RNG.integers(0, 1 << n_bits, (banks, n), dtype=np.uint64)
    scalars = np.array([0, (1 << n_bits) - 1, 777, 12345,
                        int(vals[4, 0])], np.int64)
    plan = make_plan(n_bits, chunks)

    sub = BankedSubarray(num_banks=banks, num_rows=1024, num_cols=1024,
                         arch=PuDArch.MODIFIED)
    eng = ClutchEngine(sub, vals, n_bits, plan=plan, support_negated=False)
    machine_bm = eng.read_bitmap(eng.predicate(">", scalars).row)

    kernel_bm = np.asarray(ops.clutch_compare_banked(
        jnp.asarray(vals.astype(np.uint32)), scalars, plan))
    np.testing.assert_array_equal(machine_bm, kernel_bm[:, :n])


# ----------------- cross-substrate agreement -------------------------- #

def test_machine_and_kernel_agree():
    """The PuD machine simulation and the TPU kernel compute the same
    bitmaps from the same encoded data."""
    from repro.core.clutch import ClutchEngine
    from repro.core.machine import PuDArch, Subarray

    n_bits, chunks, n = 16, 4, 1000
    vals_np = RNG.integers(0, 1 << n_bits, n, dtype=np.uint64)
    plan = make_plan(n_bits, chunks)
    a = int(RNG.integers(0, 1 << n_bits))
    sub = Subarray(num_rows=1024, num_cols=1024, arch=PuDArch.MODIFIED)
    eng = ClutchEngine(sub, vals_np, n_bits, plan=plan)
    machine_bm = eng.read_bitmap(eng.predicate(">", a).row)
    kernel_bm = np.asarray(ops.clutch_compare(
        jnp.asarray(vals_np.astype(np.uint32)), a, plan))
    np.testing.assert_array_equal(machine_bm, kernel_bm)


@pytest.fixture
def backend_is(monkeypatch):
    """Make ``jax.default_backend()`` report a chosen platform while
    :func:`use_interpret` decides, with its memo cleared around it."""
    def set_backend(name):
        monkeypatch.setattr(jax, "default_backend", lambda: name)
        common.use_interpret.cache_clear()

    yield set_backend
    common.use_interpret.cache_clear()


def test_use_interpret_on_cpu_and_tpu(backend_is):
    backend_is("cpu")
    assert common.use_interpret() is True
    backend_is("tpu")
    assert common.use_interpret() is False


@pytest.mark.parametrize("backend", ["gpu", "rocm", "METAL"])
def test_use_interpret_refuses_other_backends(backend_is, backend):
    # a chip that failed to come up must not pass for a run on it
    backend_is(backend)
    with pytest.raises(RuntimeError, match=backend):
        common.use_interpret()


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert common.compile_cache_dir() == str(tmp_path)


def test_compile_cache_dir_defaults_to_fixed_checkout_path(monkeypatch):
    from pathlib import Path

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = common.compile_cache_dir()
    assert first == common.compile_cache_dir()
    root = Path(__file__).resolve().parents[1]
    assert Path(first) == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


def test_enable_compile_cache_sets_jax_only_without_env(monkeypatch,
                                                        tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert common.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = common.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("n", [100_000, 4096 + 128 * 32, 33 * 32])
def test_clutch_merge_nondividing_word_counts(n):
    """Regression: word counts that don't divide the preferred block size
    must still process every block (bug: last 128-word block skipped)."""
    plan = make_plan(16, 4)
    vals = jnp.asarray(RNG.integers(0, 1 << 16, n, dtype=np.uint32))
    a = int(RNG.integers(0, 1 << 16))
    got = ops.clutch_compare(vals, a, plan)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(vals) > a)
