"""Generator of forest traffic: an endless, seeded sequence of
instance batches from a mix file's parameters.

Mix parameters (``bench/traffic/<mix>.json``): ``batch``, the instances
per request.  Features are drawn uniformly from ``[0, 2**n_bits)``, so
every request of a mix does the same work.
"""

from __future__ import annotations

from typing import Iterator

import numpy as np


def requests(mix: dict, config: dict,
             rng: np.random.Generator) -> Iterator[np.ndarray]:
    shape = (int(mix["batch"]), config["features"])
    while True:
        yield rng.integers(0, 1 << config["n_bits"], shape, dtype=np.int64)


def warm_requests(mix: dict, config: dict) -> list[np.ndarray]:
    """One batch of the mix's size: the only shape it uses."""
    return [np.zeros((int(mix["batch"]), config["features"]), np.int64)]
