"""Seeded random streams for the generators and drivers."""

from __future__ import annotations

import numpy as np


def stream(seed: int, *tags: int) -> np.random.Generator:
    """A generator keyed by ``seed`` and integer ``tags``.

    Any whole ``seed`` is accepted, negative or above 64 bits: it enters the
    entropy as its sign and magnitude.
    """
    seed = int(seed)
    return np.random.default_rng([abs(seed), int(seed < 0), *map(int, tags)])
