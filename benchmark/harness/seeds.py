"""Seeded numpy streams: the same seed gives the same inputs."""

from __future__ import annotations

import numpy as np

# one independent numpy stream per use of the seed
INVENTORY, CHURN, SAMPLE = 1, 2, 4
# An inventory's objects come from this fixed seed, and the run's seed
# permutes them over the names: every seed then holds the same multiset
# of objects, so the same padded sizes and compiled programs, in
# another order.
CONTENT = 0


def rng(seed: int, stream: int) -> np.random.Generator:
    """Any whole number is a seed, negative and past 32 bits included."""
    return np.random.default_rng([int(seed) % 2**64, stream])
