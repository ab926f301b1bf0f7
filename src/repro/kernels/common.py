"""Shared helpers for the TPU-native Clutch kernels.

TPU adaptation of the PuD substrate: a "DRAM row across 64K columns"
becomes a packed ``uint32`` word-vector tile resident in VMEM; the
charge-sharing MAJ3 becomes five VPU logical ops; the LUT "row activation"
becomes a dynamic one-sublane load from a VMEM-resident bit-plane tile,
its row index read from SMEM.

Conventions:
  * bitmaps are packed little-endian: element ``i`` -> bit ``i % 32`` of
    word ``i // 32`` (matches ``repro.core.machine.pack_bits``).
  * 2-D word arrays are [rows, W] with W padded to a multiple of 128 lanes
    and row counts padded to a multiple of 8 sublanes (int32 tiling).
  * every block's last two dimensions are (8k, 128m) or the array's own,
    so kernels that emit one word row per grid program write into a
    ``[N, 1, W]`` array; popcounts reduce in int32 (Mosaic has no
    unsigned reductions).
"""

from __future__ import annotations

import functools
import os
from pathlib import Path

import jax
import jax.numpy as jnp

WORD_BITS = 32
LANES = 128
SUBLANES = 8


#: VMEM bytes for a kernel's LUT blocks: one tile that the pipeline
#: double-buffers (the GBDT kernel), or both gather buffers (the
#: predicate kernel) -- well inside the 16 MiB scoped default of a v5e
#: core either way.
VMEM_TILE_BYTES = 4 << 20

#: Checkout root: ``<root>/src/repro/kernels/common.py``.
_CHECKOUT = Path(__file__).resolve().parents[3]


@functools.cache
def use_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode.  Only the CPU
    backend interprets (the test suite's backend); a TPU compiles to
    Mosaic; any other backend is an error, so a machine whose
    accelerator failed to come up cannot pass for one that ran."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Clutch kernels run on a TPU, or interpreted on the CPU "
        f"backend; JAX's default backend is {backend!r}")


def compile_cache_dir() -> str:
    """JAX's persistent compilation cache directory: the one
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``
    (fixed, so a later run of the same checkout finds its entries)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        _CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX at :func:`compile_cache_dir` (entry points call this
    before their first compile); returns the directory.  JAX reads
    ``JAX_COMPILATION_CACHE_DIR`` itself, so it is left alone when set."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def maj3(a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Bitwise 3-input majority -- NOT-free, exactly as in-DRAM MAJ3."""
    return (a & b) | (b & c) | (a & c)


def clutch_fold(row, lt, le, num_chunks: int):
    """Algorithm 1's chunk merge, the one gather-and-fold every kernel
    shares: ``row(i)`` loads LUT plane ``i``; ``lt(j)``/``le(j)`` give
    chunk ``j``'s plane indices.  ``le(0)`` is never read."""
    acc = row(lt(0))
    for j in range(1, num_chunks):
        acc = maj3(acc, row(lt(j)), row(le(j)))
    return acc


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def choose_block(w: int, preferred: int) -> int:
    """Largest power-of-two block <= preferred that divides w (w is
    always a multiple of 128 lanes, so 128 always qualifies)."""
    c = preferred
    while c > 128 and w % c:
        c //= 2
    assert w % c == 0, (w, c)
    return c


def vmem_block(rows: int, w: int, preferred: int = 1024,
               budget_bytes: int = VMEM_TILE_BYTES) -> int:
    """Block width keeping an (rows, bw) uint32 LUT tile under the VMEM
    budget.  The full width wins whenever the tile fits -- W is often
    128 * odd (no power-of-two divisor above the lane count), and
    falling back to 128-word blocks there would multiply grid steps by
    W/128 for no locality gain.  Otherwise the largest power-of-two
    divisor under budget (>= 128 lanes -- tiny tiles always fit)."""
    if rows * w * 4 <= budget_bytes:
        return w
    bw = choose_block(w, min(preferred, w))
    while bw > 128 and rows * bw * 4 > budget_bytes:
        bw //= 2
    assert w % bw == 0, (w, bw)
    return bw


def pack_bits_jnp(bits: jnp.ndarray) -> jnp.ndarray:
    """[..., N] 0/1 -> [..., ceil(N/32)] uint32 (little-endian per word)."""
    n = bits.shape[-1]
    pad = (-n) % WORD_BITS
    if pad:
        bits = jnp.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
    b = bits.reshape(*bits.shape[:-1], -1, WORD_BITS).astype(jnp.uint32)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    return (b << shifts).sum(axis=-1).astype(jnp.uint32)


def unpack_bits_jnp(words: jnp.ndarray, n: int) -> jnp.ndarray:
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*words.shape[:-1], -1)[..., :n].astype(jnp.uint8)


def float_to_monotonic_u32(x: jnp.ndarray) -> jnp.ndarray:
    """Map float32 bit patterns to uint32 preserving total order:
    ``x < y  <=>  m(x) < m(y)`` (IEEE-754 sign-magnitude fix-up).  This is
    how the serving sampler feeds logits to the integer Clutch comparator."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    sign = bits >> 31
    flip = jnp.where(sign == 1, jnp.uint32(0xFFFFFFFF), jnp.uint32(0x80000000))
    return bits ^ flip


def pad2d(words: jnp.ndarray, row_mult: int = SUBLANES,
          col_mult: int = LANES) -> jnp.ndarray:
    r, w = words.shape
    return jnp.pad(words, ((0, round_up(r, row_mult) - r),
                           (0, round_up(w, col_mult) - w)))
