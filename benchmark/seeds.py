"""Seeds: every input of a run is drawn from ``--seed``."""

from __future__ import annotations

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator for one stream (a caller, the payload
    pool, the sampler) of one seed; any non-negative seed, 64-bit too."""
    return np.random.default_rng([int(seed), *stream])


def key32(seed: int, *stream: int) -> int:
    """A 32-bit integer for ``jax.random.key`` from the same streams."""
    ss = np.random.SeedSequence([int(seed), *stream])
    return int(ss.generate_state(1, np.uint32)[0])
