"""Generators from ``--seed``: any whole number, the driver's pass 2**31."""

from __future__ import annotations

import numpy as np

MASK = (1 << 63) - 1


def rng_for(seed: int, *stream) -> np.random.Generator:
    """A generator for one named stream of one seed."""
    return np.random.default_rng([int(seed) & MASK, *map(int, stream)])
