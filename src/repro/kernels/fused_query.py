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

Memory placement.  The predicate kernel's stacked LUT stays in HBM
(``memory_space=pl.ANY``) as :func:`row_slabs` lays it out: row ``i``
of a shard is a contiguous slab of whole ``(8, 128)`` tiles, so one
DMA can fetch any row's block.  Its index vector is scalar-prefetched
into SMEM; each grid step DMAs the ``bw``-word block of every row an
index lane names into that lane's slot of a double-buffered ``(2, K,
bw/128, 128)`` VMEM scratch (``bw`` from the ``VMEM_TILE_BYTES``
budget) and folds the slots, static lane by lane, one dense vreg at a
time.  So a launch reads ``K = 4 * C * ranges`` rows of each shard,
not all of them.  A shard's bitmap leaves as ``(1, bw/128, 128)``
blocks of an ``[S, Wp/128, 128]`` array (reshaped and sliced to ``[S,
W]``), its popcount as an int32 ``(1, 1, 128)`` tile.  The GBDT
kernel's LUT tile ``(rows, bw)`` sits in VMEM with ``bw`` from
:func:`~repro.kernels.common.vmem_block`, and its indices are read an
``(8, F*2*C)`` SMEM block per grid step, so any batch size fits SMEM's
1 MiB; a scalar read there may feed a dynamic sublane offset.
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
    VMEM_TILE_BYTES,
    clutch_fold,
    round_up,
    use_interpret,
    vmem_block,
)

#: Words in one ``(8, 128)`` uint32 tile: a LUT row's slab is a whole
#: number of these, so every row starts on a tile boundary in HBM.
SLAB_WORDS = SUBLANES * LANES


def row_slabs(lut: jnp.ndarray) -> jnp.ndarray:
    """Lay LUT rows out for the predicate kernel's row gather: ``[..., R,
    W]`` uint32 -> ``[..., R * Wp / 128, 128]``, ``Wp`` = ``W`` rounded
    up to :data:`SLAB_WORDS`.  Row ``i`` becomes the contiguous slab of
    sublane rows ``[i * Wp / 128, (i + 1) * Wp / 128)``, whole ``(8,
    128)`` tiles that one DMA can address.  Pad words are zero in every
    plane, the constant-one row included, so every range is false there
    and popcounts stay exact.  Apply it per shard before stacking: on a
    stacked LUT it is a copy of the whole array."""
    *lead, r, w = lut.shape
    wp = round_up(w, SLAB_WORDS)
    lut = jnp.pad(lut, [(0, 0)] * (lut.ndim - 1) + [(0, wp - w)])
    return lut.reshape(*lead, r * wp // LANES, LANES)


def _gather_block(k: int, wp: int) -> int:
    """Words per grid step: the largest multiple of :data:`SLAB_WORDS`
    dividing ``wp`` whose two ``k``-row buffers fit
    :data:`~repro.kernels.common.VMEM_TILE_BYTES`; one tile at least."""
    units = wp // SLAB_WORDS
    best = 1
    for d in range(2, units + 1):
        if units % d == 0 and 2 * k * d * SLAB_WORDS * 4 <= VMEM_TILE_BYTES:
            best = d
    return best * SLAB_WORDS


def _where(row, c: int, term_ranges: tuple, term_disj: tuple,
           conn_disj: tuple):
    """The WHERE clause over gathered rows: ``row(j)`` is index lane
    ``j``'s row.  Each TERM's bitmap combines its own ranges with its
    own AND/OR; the term bitmaps fold left-associatively through the
    connectives."""
    def merge(off):
        # Algorithm 1 over lanes off..off+C (lt) / off+C..off+2C (le)
        return clutch_fold(row, lambda j: off + j, lambda j: off + c + j, c)

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
    return acc


def _compound_kernel(idx_ref, lut_hbm, bm_ref, cnt_ref, buf, sem, *,
                     row_tiles: int, where):
    """One grid step (shard, word block): gather the block of every row
    ``idx`` names from HBM into a VMEM slot per index lane, fold the
    WHERE clause one ``(8, 128)`` tile at a time, and add the block's
    popcount to its shard's.  The gather is double-buffered across the
    whole grid: step ``n + 1``'s copies, possibly of the next shard,
    start before step ``n`` waits on its own."""
    k, tiles = buf.shape[1], buf.shape[2]
    nb = pl.num_programs(1)
    step = pl.program_id(0) * nb + pl.program_id(1)
    slot = step % 2

    def copies(n, sl):
        s, b = n // nb, n % nb
        return [pltpu.make_async_copy(
            lut_hbm.at[s, pl.ds(pl.multiple_of(
                idx_ref[j] * row_tiles + b * tiles, SUBLANES), tiles)],
            buf.at[sl, j], sem.at[sl]) for j in range(k)]

    @pl.when(step == 0)
    def _first():
        for cp in copies(step, slot):
            cp.start()

    @pl.when(step + 1 < pl.num_programs(0) * nb)
    def _prefetch():
        for cp in copies(step + 1, 1 - slot):
            cp.start()

    for cp in copies(step, slot):
        cp.wait()

    def tile(g, cnt):
        rows = pl.ds(pl.multiple_of(g * SUBLANES, SUBLANES), SUBLANES)
        acc = where(lambda j: buf[slot, j, rows, :])
        bm_ref[0, rows, :] = acc
        return cnt + jax.lax.population_count(acc).astype(jnp.int32)

    cnt = jax.lax.fori_loop(0, tiles // SUBLANES, tile,
                            jnp.zeros((SUBLANES, LANES), jnp.int32))

    # per-shard popcount accumulated across the word-block grid axis
    # (TPU grids run sequentially per core; interpret mode likewise), in
    # int32: Mosaic has no unsigned reductions
    @pl.when(pl.program_id(1) == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    cnt_ref[...] += cnt.sum()


def fused_compound_banked(lut: jnp.ndarray, idx: jnp.ndarray,
                          num_chunks: int, term_ranges: tuple,
                          term_disj: tuple, conn_disj: tuple, *,
                          words: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One-launch compound predicate (``term0 <op0> term1 ...``) over a
    whole sharded resource.

    lut: [S, R * Wp / 128, 128] uint32 -- :func:`row_slabs` of the
    per-shard LUT of ``words`` words a row: every feature's stacked
    normal planes followed by every feature's complement planes (row
    offsets are the caller's business; see
    :class:`repro.kernels.fused_session.FusedTableExec`).
    idx: [K] int32, ``K = sum(term_ranges) * 4 * C`` -- per range, in
    term order, the concatenation (gt_lt, gt_le, lt_lt, lt_le) of
    Algorithm 1 row indices, already offset to the right feature block.
    Each grid step copies the rows of all ``K`` lanes, repeats and the
    unread ``le[0]`` included, and no others: ``K`` rows of each shard
    are read, whatever ``R``.  Static structure (the compile-cache key
    upstream): ``term_ranges[t]`` ranges per term, combined with that
    term's internal ``term_disj[t]`` (True = OR), then the term bitmaps
    folded through ``conn_disj`` (one entry per connective, True = OR,
    left-associative).  Returns (bitmap [S, words] uint32, per-shard
    popcount [S] int32) -- the whole WHERE clause and its COUNT leave
    the kernel in one pass, matching the machine path's in-DRAM merge
    contract of one-readout-per-compound."""
    s, n, lanes = lut.shape
    wp = round_up(words, SLAB_WORDS)
    row_tiles = wp // LANES
    k = sum(term_ranges) * 4 * num_chunks
    assert len(term_disj) == len(term_ranges)
    assert len(conn_disj) == len(term_ranges) - 1
    assert lanes == LANES and n % row_tiles == 0, (lut.shape, words)
    assert idx.shape == (k,), idx.shape
    bw = _gather_block(k, wp)
    tiles = bw // LANES
    where = functools.partial(_where, c=num_chunks,
                              term_ranges=tuple(term_ranges),
                              term_disj=tuple(term_disj),
                              conn_disj=tuple(conn_disj))
    kernel = functools.partial(_compound_kernel, row_tiles=row_tiles,
                               where=where)
    bm, cnt = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, wp // bw),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                pl.BlockSpec((1, tiles, LANES),
                             lambda si, i, idx: (si, i, 0)),
                pl.BlockSpec((1, 1, LANES), lambda si, i, idx: (si, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, k, tiles, LANES), jnp.uint32),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((s, row_tiles, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((s, 1, LANES), jnp.int32),
        ],
        # the double buffer carries state from step to step
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=use_interpret(),
        name="clutch_predicate",
    )(idx.astype(jnp.int32), lut)
    return bm.reshape(s, wp)[:, :words], cnt[:, 0, 0]


def fused_predicate_banked(lut: jnp.ndarray, idx: jnp.ndarray,
                           num_chunks: int, num_ranges: int,
                           disjunction: bool = False, *, words: int
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One-launch Q1-Q3-shaped predicate over a whole sharded resource:
    :func:`fused_compound_banked` with one term of ``num_ranges`` (1 or
    2) ranges combined with AND (``disjunction=False``) or OR.  Returns
    (bitmap [S, words] uint32, per-shard popcount [S] int32)."""
    return fused_compound_banked(lut, idx, num_chunks, (num_ranges,),
                                 (disjunction,), (), words=words)


def fused_range_count(lut: jnp.ndarray, lut_c: jnp.ndarray,
                      idx: jnp.ndarray, num_chunks: int
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``x0 < B < x1`` bitmap + COUNT in one pass.  lut/lut_c: [R, W]
    uint32 normal / complement planes; idx: [4*C] int32 =
    concat(gt_lt, gt_le, lt_lt, lt_le) row indices, the lt-side ones
    into ``lut_c``.  Returns (bitmap [W] uint32, count [1] int32)."""
    r, w = lut.shape
    assert lut_c.shape == lut.shape
    c = num_chunks
    # one stacked shard: the complement planes follow the normal ones
    stacked = row_slabs(jnp.concatenate([lut, lut_c]))[None]
    idx = idx.astype(jnp.int32).at[2 * c:].add(r)
    bm, cnt = fused_predicate_banked(stacked, idx, c, 1, words=w)
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
