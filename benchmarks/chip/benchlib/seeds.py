"""Seeds: any whole number (seeds may exceed 32 bits) becomes a numpy
generator and a JAX key, both fixed by the seed alone."""

from __future__ import annotations

import numpy as np


def rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), *tags])


def key(seed: int, *tags: int):
    import jax
    word = np.random.SeedSequence([int(seed) & (2**64 - 1), *tags])
    return jax.random.PRNGKey(int(word.generate_state(1, np.uint32)[0]))
