"""Plain NumPy reference for the forest deployments: the generator of
an oblivious (CatBoost-style) forest and its scoring semantics.

Copied from the paper's GBDT mapping so that the yardstick does not
move when the program does; it imports nothing of the program.  Every
node at depth ``k`` of tree ``t`` tests ``x[feature_idx[t, k]] <
thresholds[t, k]``; the test bits, depth 0 as the most significant,
form the tree's leaf address, and a prediction is the float32 sum of
the addressed leaves over the trees, summed as one contiguous row per
instance (the order the served path documents: float32 sums of 1000
unit-scale leaves in two orders differ by about 1e-4).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Forest:
    feature_idx: np.ndarray   # [T, D] int32 in [0, F)
    thresholds: np.ndarray    # [T, D] uint64 in [0, 2**n_bits)
    leaves: np.ndarray        # [T, 2**D] float32
    n_bits: int
    num_features: int


def generate(trees: int, depth: int, features: int, n_bits: int,
             rng: np.random.Generator) -> Forest:
    """A forest with uniform features and thresholds and standard
    normal float32 leaves."""
    return Forest(
        feature_idx=rng.integers(0, features, (trees, depth),
                                 dtype=np.int32),
        thresholds=rng.integers(0, 1 << n_bits, (trees, depth),
                                dtype=np.uint64),
        leaves=rng.normal(size=(trees, 1 << depth)).astype(np.float32),
        n_bits=n_bits,
        num_features=features)


def leaf_addrs(forest: Forest, X: np.ndarray) -> np.ndarray:
    """[B, F] -> [B, T] int32 leaf addresses."""
    bits = X[:, forest.feature_idx] < forest.thresholds[None]   # [B, T, D]
    depth = forest.feature_idx.shape[1]
    weights = 1 << np.arange(depth)[::-1]
    return (bits * weights).sum(-1).astype(np.int32)


class Reference:
    """Scores batches.  ``leaf_dtype`` is the precision the leaves are
    held in before the float32 sum: the lower-precision control is
    ``Reference(forest, leaf_dtype=bfloat16)``."""

    def __init__(self, forest: Forest, leaf_dtype=np.float32) -> None:
        self.forest = forest
        self.leaves = np.asarray(forest.leaves).astype(leaf_dtype).astype(
            np.float32)

    def __call__(self, X: np.ndarray) -> np.ndarray:
        addrs = leaf_addrs(self.forest, np.asarray(X))
        per_tree = np.take_along_axis(self.leaves, addrs.T, axis=1)  # [T, B]
        return np.ascontiguousarray(per_tree.T).sum(-1).astype(np.float32)
