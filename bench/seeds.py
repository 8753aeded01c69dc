"""Seeded streams: ``stream(seed, k)`` is the k-th independent generator
drawn from a run's ``--seed`` (any whole number, negative ones too)."""

from __future__ import annotations

import numpy as np


def stream(seed: int, *k: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), *k])
