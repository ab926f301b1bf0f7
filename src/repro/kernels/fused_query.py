"""Pallas TPU kernels: fused predicates + popcount (beyond-paper).

``fused_compound_banked`` is the fused backend's one predicate kernel:
one ``pallas_call`` grid over *(shard, word block)* evaluates a whole
WHERE clause against a stacked LUT holding every feature's
normal+complement planes for every record shard.  Each range ``x0 < B
< x1`` runs its ``>``-side merge on the normal planes and its ``<``-side
on the complement planes (the NOT-free rewrite Unmodified PuD uses);
ranges combine with their term's AND/OR, terms fold through the
connective chain (``Q1 AND Q2 OR Q3``) in registers, and a per-shard
popcount accumulates across the word blocks -- the entire device half
of a Q1-Q5 or compound query in ONE launch, the register-level mirror
of the machine path's in-bank Ambit AND/OR merge, bit-exact against
it.  ``fused_predicate_banked`` (one term) and ``fused_range_count``
(one range over separate normal/complement LUTs) are its special cases.

The merge loop never reads ``le[0]`` and ``maj3(acc, zero_row,
one_row) == acc``, so callers with heterogeneous per-column chunk
counts (:class:`repro.kernels.fused_session.FusedTableExec` with
``plans``) can pad a narrower column's index rows up to the static
``num_chunks`` with ``(lt=zero_row, le=one_row)`` identity lanes --
the kernels themselves are chunk-count-uniform and unchanged.

``gbdt_leafbits_banked`` is the GBDT counterpart: one grid over
*(8-instance block, word block)* folds every feature's per-instance
threshold comparison (per-instance gather indices, like the banked
machine's broadcast wave with per-bank lookups) through the one-hot
feature masks into the leaf-address bitmap rows -- the whole per-wave
compute loop of :class:`repro.apps.gbdt.GbdtPudEngine` as one kernel.

Memory placement: the LUT tile ``(rows, bw)`` sits in VMEM with ``bw``
from :func:`~repro.kernels.common.vmem_block`; the row indices sit in
SMEM, where a scalar read that feeds a dynamic sublane offset is legal.
A predicate's indices are one small vector (whole array in SMEM); a
GBDT batch's are read an ``(8, F*2*C)`` block per grid step, so any
batch size fits SMEM's 1 MiB.  A shard's bitmap row leaves as a
``(1, 1, bw)`` block of an ``[S, 1, W]`` array, its popcount as an
int32 ``(1, 1, 128)`` tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (
    LANES,
    SUBLANES,
    clutch_fold,
    round_up,
    use_interpret,
    vmem_block,
)

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _compound_kernel(idx_ref, lut_ref, bm_ref, cnt_ref, *,
                     num_chunks: int, term_ranges: tuple,
                     term_disj: tuple, conn_disj: tuple):
    """Evaluate each TERM's bitmap (its own ranges combined with its
    own internal AND/OR), then fold the term bitmaps left-associatively
    through the connectives."""
    c = num_chunks

    def row(i):
        # dynamic one-sublane load from the shard's VMEM-resident tile
        return lut_ref[0, pl.ds(i, 1), :]

    def merge(off):
        # Algorithm 1 over idx[off:off+C] (lt) / idx[off+C:off+2C] (le)
        return clutch_fold(row, lambda j: idx_ref[off + j],
                           lambda j: idx_ref[off + c + j], c)

    def range_bm(rix):
        # gt-side on the normal planes, lt-side on the complement planes
        off = rix * 4 * c
        return merge(off) & merge(off + 2 * c)

    rix = 0
    acc = None
    for t, (nr, disj) in enumerate(zip(term_ranges, term_disj)):
        tb = range_bm(rix)
        rix += 1
        for _ in range(1, nr):
            nxt = range_bm(rix)
            rix += 1
            tb = (tb | nxt) if disj else (tb & nxt)
        if acc is None:
            acc = tb
        else:
            acc = (acc | tb) if conn_disj[t - 1] else (acc & tb)
    bm_ref[0] = acc

    # per-shard popcount accumulated across the word-block grid axis
    # (TPU grids run sequentially per core; interpret mode likewise)
    @pl.when(pl.program_id(1) == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    # int32: Mosaic has no unsigned reductions
    cnt_ref[...] += jax.lax.population_count(acc).astype(jnp.int32).sum()


def fused_compound_banked(lut: jnp.ndarray, idx: jnp.ndarray,
                          num_chunks: int, term_ranges: tuple,
                          term_disj: tuple, conn_disj: tuple,
                          block_words: int = 1024
                          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One-launch compound predicate (``term0 <op0> term1 ...``) over a
    whole sharded resource.

    lut: [S, R, W] uint32 -- per record shard, every feature's stacked
    normal planes followed by every feature's complement planes (row
    offsets are the caller's business; see
    :class:`repro.kernels.fused_session.FusedTableExec`).
    idx: [sum(term_ranges) * 4 * C] int32 -- per range, in term order,
    the concatenation (gt_lt, gt_le, lt_lt, lt_le) of Algorithm 1 row
    indices, already offset to the right feature block.  Static
    structure (the compile-cache key upstream): ``term_ranges[t]``
    ranges per term, combined with that term's internal
    ``term_disj[t]`` (True = OR), then the term bitmaps folded through
    ``conn_disj`` (one entry per connective, True = OR,
    left-associative).  Returns (bitmap [S, W] uint32, per-shard
    popcount [S] int32) -- the whole WHERE clause and its COUNT leave
    the kernel in one pass, matching the machine path's in-DRAM merge
    contract of one-readout-per-compound."""
    s, r, w = lut.shape
    total_ranges = sum(term_ranges)
    assert len(term_disj) == len(term_ranges)
    assert len(conn_disj) == len(term_ranges) - 1
    assert r % SUBLANES == 0 and w % LANES == 0, (r, w)
    assert idx.shape == (total_ranges * 4 * num_chunks,), idx.shape
    bw = vmem_block(r, w, block_words)
    kernel = functools.partial(_compound_kernel, num_chunks=num_chunks,
                               term_ranges=tuple(term_ranges),
                               term_disj=tuple(term_disj),
                               conn_disj=tuple(conn_disj))
    bm, cnt = pl.pallas_call(
        kernel,
        grid=(s, w // bw),
        in_specs=[
            _SMEM,
            pl.BlockSpec((1, r, bw), lambda si, i: (si, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bw), lambda si, i: (si, 0, i)),
            pl.BlockSpec((1, 1, LANES), lambda si, i: (si, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, 1, w), jnp.uint32),
            jax.ShapeDtypeStruct((s, 1, LANES), jnp.int32),
        ],
        interpret=use_interpret(),
        name="clutch_predicate",
    )(idx.astype(jnp.int32), lut)
    return bm.reshape(s, w), cnt[:, 0, 0]


def fused_predicate_banked(lut: jnp.ndarray, idx: jnp.ndarray,
                           num_chunks: int, num_ranges: int,
                           disjunction: bool = False,
                           block_words: int = 1024
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One-launch Q1-Q3-shaped predicate over a whole sharded resource:
    :func:`fused_compound_banked` with one term of ``num_ranges`` (1 or
    2) ranges combined with AND (``disjunction=False``) or OR.  Returns
    (bitmap [S, W] uint32, per-shard popcount [S] int32)."""
    return fused_compound_banked(lut, idx, num_chunks, (num_ranges,),
                                 (disjunction,), (), block_words)


def fused_range_count(lut: jnp.ndarray, lut_c: jnp.ndarray,
                      idx: jnp.ndarray, num_chunks: int,
                      block_words: int = 1024
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``x0 < B < x1`` bitmap + COUNT in one pass.  lut/lut_c: [R, W]
    uint32 normal / complement planes; idx: [4*C] int32 =
    concat(gt_lt, gt_le, lt_lt, lt_le) row indices, the lt-side ones
    into ``lut_c``.  Returns (bitmap [W] uint32, count [1] int32)."""
    r, _ = lut.shape
    assert lut_c.shape == lut.shape
    c = num_chunks
    # one stacked shard: the complement planes follow the normal ones
    stacked = jnp.concatenate([lut, lut_c])[None]
    idx = idx.astype(jnp.int32).at[2 * c:].add(r)
    bm, cnt = fused_predicate_banked(stacked, idx, c, 1,
                                     block_words=block_words)
    return bm[0], cnt


def _leafbits_kernel(idx_ref, lut_ref, mask_ref, bm_ref, *,
                     num_chunks: int, num_features: int):
    c = num_chunks

    def row(i):
        return lut_ref[pl.ds(i, 1), :]

    for b in range(SUBLANES):
        acc = jnp.zeros((1, bm_ref.shape[1]), jnp.uint32)
        for f in range(num_features):
            # cmp = Clutch(v_f < thresholds); acc |= cmp AND mask_f
            off = f * 2 * c
            cmp = clutch_fold(row, lambda j: idx_ref[b, off + j],
                              lambda j: idx_ref[b, off + c + j], c)
            acc = acc | (cmp & mask_ref[f:f + 1, :])
        bm_ref[b:b + 1, :] = acc


def gbdt_leafbits_banked(lut: jnp.ndarray, masks: jnp.ndarray,
                         idx: jnp.ndarray, num_chunks: int,
                         num_features: int, block_words: int = 1024
                         ) -> jnp.ndarray:
    """One-launch GBDT leaf-address bitmap for a whole instance batch.

    lut: [R, W] uint32 -- the forest's threshold LUT planes (shared by
    every instance, like the machine's broadcast wave).  masks:
    [F_pad, W] uint32 packed one-hot feature masks (rows past
    ``num_features`` are padding).  idx: [B, F * 2 * C] int32 --
    per instance, per feature, (lt, le) Algorithm 1 row indices for
    that instance's feature value (the per-bank gather of the machine
    model).  Returns the leaf-address bitmap [B, W] uint32.  Each grid
    step takes 8 instances; a batch that is not a multiple of 8 is
    padded here and the padding rows dropped.
    """
    r, w = lut.shape
    fp, wm = masks.shape
    b, k = idx.shape
    assert wm == w and r % SUBLANES == 0 and w % LANES == 0, (r, w, fp)
    assert fp % SUBLANES == 0 and fp >= num_features
    assert k == num_features * 2 * num_chunks, idx.shape
    b_pad = round_up(max(b, 1), SUBLANES)
    idx = jnp.pad(idx.astype(jnp.int32), ((0, b_pad - b), (0, 0)))
    bw = vmem_block(r + fp, w, block_words)
    kernel = functools.partial(_leafbits_kernel, num_chunks=num_chunks,
                               num_features=num_features)
    bm = pl.pallas_call(
        kernel,
        grid=(b_pad // SUBLANES, w // bw),
        in_specs=[
            pl.BlockSpec((SUBLANES, k), lambda bi, i: (bi, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((r, bw), lambda bi, i: (0, i)),
            pl.BlockSpec((fp, bw), lambda bi, i: (0, i)),
        ],
        out_specs=pl.BlockSpec((SUBLANES, bw), lambda bi, i: (bi, i)),
        out_shape=jax.ShapeDtypeStruct((b_pad, w), jnp.uint32),
        interpret=use_interpret(),
        name="clutch_leafbits",
    )(idx, lut, masks)
    return bm[:b]
