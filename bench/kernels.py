"""The kernels the per-layer metrics read: how each is found in a
device trace, and the bytes its work needs at the least.

The bytes are what Algorithm 1 of the Clutch paper has to move for a
request, at unpadded sizes, whatever implements it: a kernel that moves
more (a whole LUT tile where a range reads a few rows of it, padding,
repeated loads) reads as a lower share of its roofline, and a later
change that stops moving it reads higher under this same yardstick.
"""

from __future__ import annotations

import math
import re

#: The predicate kernel (``fused_compound_banked``): a Pallas call that
#: reads the stacked ``[shards, rows, words]`` LUT, a rank-3 uint32
#: operand.
PREDICATE = re.compile(r"^tpu_custom_call .*<- \(.*u32\[\d+,\d+,\d+\]")

#: The leaf-bits kernel (``gbdt_leafbits_banked``): a Pallas call from
#: per-instance row indices, the threshold LUT and the feature masks to
#: one leaf-bit row per instance.
LEAFBITS = re.compile(
    r"^tpu_custom_call u32\[\d+,\d+\] <- "
    r"\(s32\[\d+,\d+\], u32\[\d+,\d+\], u32\[\d+,\d+\]\)")

WORD = 4  # bytes of a uint32 word


def lut_rows(n_bits: int, num_chunks: int) -> int:
    """Rows of one Clutch LUT: ``2**k - 1`` temporal-code planes per
    chunk of ``k`` bits (chunks as even as possible) plus the constant
    zero and one rows."""
    base, extra = divmod(n_bits, num_chunks)
    widths = [base + (1 if j < extra else 0) for j in range(num_chunks)]
    return sum((1 << k) - 1 for k in widths) + 2


def _ranges(req: tuple) -> tuple[int, bool]:
    """(ranges the request's launches evaluate, whether a bitmap is
    needed back).  Q5's second launch (one range, count only) is
    counted; it is skipped only when the first selection's average is 0
    or the column maximum."""
    kind = req[0]
    if kind == "q1":
        return 1, True
    if kind in ("q2", "q4"):
        return 2, True
    if kind == "q3":
        return 2, False
    if kind == "q5":
        return 3, True
    if kind == "compound":
        return sum(1 if t[0] == "q1" else 2 for t in req[2]), True
    raise ValueError(f"unknown request kind {kind!r}")


def predicate_bytes(config: dict, req: tuple) -> int:
    """Bytes a scan request's predicate launches need: per range, the
    ``4 * C`` LUT rows Algorithm 1 reads (``C`` per side for each of
    its ``lt`` and ``le`` lookups, both sides of the range) across every
    shard's words, plus the result bitmap when one leaves the device."""
    shards = config["pud_devices"] * config["shards_per_device"]
    words = math.ceil(math.ceil(config["records"] / shards) / 32)
    ranges, bitmap = _ranges(req)
    rows = ranges * 4 * config["num_chunks"]
    return (rows + (1 if bitmap else 0)) * shards * words * WORD


def leafbits_bytes(config: dict, batch: int) -> int:
    """Bytes one leaf-bits launch over ``batch`` instances needs: the
    threshold LUT once, the one-hot feature masks, the per-instance row
    indices (an ``lt`` and an ``le`` index per chunk per feature) and
    the leaf-bit rows written back."""
    words = math.ceil(config["trees"] * config["depth"] / 32)
    c, f = config["num_chunks"], config["features"]
    lut = lut_rows(config["n_bits"], c) * words
    masks = f * words
    idx = batch * f * 2 * c
    out = batch * words
    return (lut + masks + idx + out) * WORD
