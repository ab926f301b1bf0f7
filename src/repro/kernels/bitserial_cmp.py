"""Pallas TPU kernel: bit-serial borrow-chain comparison (the baseline).

Computes ``a < B`` over binary bit-planes with the MAJ3 borrow recurrence
(unrolled over the static bit-width).  Exists so the TPU-side benchmark can
compare Clutch's O(C) merge against the O(n) baseline on identical layouts,
mirroring the paper's Fig. 10 kernel comparison.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import SUBLANES, maj3, use_interpret


def _kernel(nota_ref, planes_ref, out_ref, *, n_bits: int):
    borrow = jnp.zeros_like(out_ref[...])
    for i in range(n_bits):
        not_a = nota_ref[i]                       # 0x0 or 0xFFFFFFFF
        plane = planes_ref[i]
        borrow = maj3(jnp.broadcast_to(not_a, borrow.shape), plane, borrow)
    out_ref[...] = borrow


def bitserial_cmp(planes: jnp.ndarray, not_a_words: jnp.ndarray,
                  block_words: int = 2048) -> jnp.ndarray:
    """planes: [n_pad, W] uint32 (LSB first, n_pad % 8 == 0);
    not_a_words: [n_bits] uint32 with 0xFFFFFFFF where the scalar bit is 0.
    Returns [W] uint32 bitmap of ``a < B``."""
    n_pad, w = planes.shape
    n_bits = not_a_words.shape[0]
    assert n_pad % SUBLANES == 0 and w % 128 == 0
    from .common import choose_block
    bw = choose_block(w, min(block_words, w))
    kernel = functools.partial(_kernel, n_bits=n_bits)
    return pl.pallas_call(
        kernel,
        grid=(w // bw,),
        in_specs=[
            pl.BlockSpec((n_bits,), lambda i: (0,)),
            pl.BlockSpec((n_pad, bw), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((bw,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((w,), jnp.uint32),
        interpret=use_interpret(),
    )(not_a_words, planes)
