"""Seeds derived from the run's ``--seed``: any whole number, however large."""
from __future__ import annotations

import numpy as np


def derive(seed: int, *path: int) -> int:
    """A 31-bit seed for the stream named by ``path`` under ``seed``.

    31 bits keep every consumer happy: ``np.random.default_rng(s + rep)``,
    ``jax.random.key(s)`` and sequence numbers alike."""
    words = [int(seed) % (1 << 63), int(seed) >> 63, *(int(p) % (1 << 32) for p in path)]
    return int(np.random.SeedSequence(words).generate_state(1)[0] & 0x7FFFFFFF)


def rng(seed: int, *path: int) -> np.random.Generator:
    return np.random.default_rng(derive(seed, *path))
