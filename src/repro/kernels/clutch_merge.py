"""Pallas TPU kernel: Clutch chunk-merge (Algorithm 1) over packed planes.

One grid program per *(bank shard, word block)* processes a ``(R, bw)``
VMEM tile of that bank's stacked LUT: it loads the ``lt``/``le`` planes
of every chunk with dynamic one-sublane loads (the TPU analogue of row
activation), their indices read from SMEM, and folds them with the
NOT-free MAJ3 recurrence, so per-chunk intermediates never leave VMEM --
mirroring how Clutch keeps per-chunk bitmaps inside the DRAM subarray.

VMEM budget: ``bw`` comes from :func:`~repro.kernels.common.vmem_block`,
which keeps the R x bw x 4-byte LUT tile within 4 MiB (e.g. 448 rows x
2048 words = 3.5 MiB); the output is one ``(1, 1, bw)`` word row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import LANES, SUBLANES, clutch_fold, use_interpret, vmem_block


def _banked_kernel(idx_ref, lut_ref, out_ref, *, num_chunks: int):
    # idx_ref: the whole [B, 2C] (lt | le) index array in SMEM; the LUT
    # and output refs carry a leading singleton bank axis
    bi = pl.program_id(0)
    c = num_chunks
    out_ref[0] = clutch_fold(lambda i: lut_ref[0, pl.ds(i, 1), :],
                             lambda j: idx_ref[bi, j],
                             lambda j: idx_ref[bi, c + j], c)


def clutch_merge_banked(lut: jnp.ndarray, lt_idx: jnp.ndarray,
                        le_idx: jnp.ndarray,
                        block_words: int = 1024) -> jnp.ndarray:
    """Bank-batched Clutch merge: one grid program per (bank shard,
    word block), mirroring how the banked machine runs one broadcast
    stream whose per-bank lookups differ.

    lut: [B, R, W] uint32 (per-bank stacked LUT planes); lt_idx/le_idx:
    [B, C] int32 per-bank Algorithm 1 row indices (each bank compares
    its own scalar).  Returns [B, W] uint32 bitmaps of ``a_b < B_b``.
    """
    b, r, w = lut.shape
    assert r % SUBLANES == 0 and w % LANES == 0, (r, w)
    assert lt_idx.shape == le_idx.shape == (b, lt_idx.shape[1])
    c = lt_idx.shape[1]
    bw = vmem_block(r, w, block_words)
    idx = jnp.concatenate([lt_idx, le_idx], axis=1).astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_banked_kernel, num_chunks=c),
        grid=(b, w // bw),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, r, bw), lambda bi, i: (bi, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, bw), lambda bi, i: (bi, 0, i)),
        out_shape=jax.ShapeDtypeStruct((b, 1, w), jnp.uint32),
        interpret=use_interpret(),
    )(idx, lut)
    return out.reshape(b, w)


def clutch_merge(lut: jnp.ndarray, lt_idx: jnp.ndarray, le_idx: jnp.ndarray,
                 block_words: int = 1024) -> jnp.ndarray:
    """lut: [R, W] uint32 (R % 8 == 0, W % 128 == 0); lt_idx/le_idx: [C]
    int32.  Returns [W] uint32 bitmap of ``a < B`` (the one-bank case
    of :func:`clutch_merge_banked`)."""
    return clutch_merge_banked(lut[None], lt_idx[None], le_idx[None],
                               block_words)[0]
