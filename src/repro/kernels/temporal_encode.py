"""Pallas TPU kernel: binary -> temporal-coding LUT plane construction.

Builds, for one k-bit chunk, the ``2^k - 1`` packed bit-planes where plane
``r`` bit ``i`` equals ``r < v_i``.  This is the one-time conversion the
paper amortizes (Fig. 18a / 21); on TPU it is the bulk encoder used when
loading vectors into the bit-sliced layout.

Layout trick: the 32 values packed into an output word must sit along the
*lane* dimension for the VPU, so ops.py reshapes values to [W, 32] and the
kernel reduces the 32-wide trailing dim with shift-or after the compare:
    word[r, w] = sum_i (r < v[w, i]) << i
summed in int32 and bitcast to the uint32 word.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import WORD_BITS, use_interpret


def _kernel(vals_ref, out_ref, *, block_rows: int):
    # int32 throughout (chunk values sit far below 2^31; Mosaic has no
    # unsigned reductions); the shift-sum of distinct bits wraps into
    # bit 31 exactly, and the bitcast hands back the uint32 word
    r0 = pl.program_id(0) * block_rows
    vals = vals_ref[...].astype(jnp.int32)                 # [BW, 32]
    rows = (r0 + jax.lax.broadcasted_iota(jnp.int32, (block_rows, 1, 1), 0))
    bits = (rows < vals[None]).astype(jnp.int32)           # [BR, BW, 32]
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 1, WORD_BITS), 2)
    out_ref[...] = jax.lax.bitcast_convert_type(
        (bits << shifts).sum(axis=-1), jnp.uint32)


def temporal_encode(vals: jnp.ndarray, k: int, block_rows: int = 8,
                    block_words: int = 512) -> jnp.ndarray:
    """vals: [W, 32] uint32 chunk values (W % 128 == 0).  Returns
    [R_pad, W] uint32 planes with R_pad = roundup(2^k - 1, block_rows);
    ops.py slices off the padding rows."""
    w = vals.shape[0]
    assert vals.shape[1] == WORD_BITS and w % 128 == 0
    r = (1 << k) - 1
    r_pad = (r + block_rows - 1) // block_rows * block_rows
    from .common import choose_block
    bw = choose_block(w, min(block_words, w))
    kernel = functools.partial(_kernel, block_rows=block_rows)
    return pl.pallas_call(
        kernel,
        grid=(r_pad // block_rows, w // bw),
        in_specs=[pl.BlockSpec((bw, WORD_BITS), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((block_rows, bw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r_pad, w), jnp.uint32),
        interpret=use_interpret(),
    )(vals)
