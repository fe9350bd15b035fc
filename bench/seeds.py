"""Keys and generators from a run's ``--seed`` (any whole number, also past
32 bits), salted so that each use draws its own stream."""

from __future__ import annotations

import numpy as np

__all__ = ["key_words", "jax_key", "rng"]


def key_words(seed: int, *salt: int) -> np.ndarray:
    return np.random.SeedSequence([int(seed), *map(int, salt)]) \
        .generate_state(2, np.uint32)


def jax_key(seed: int, *salt: int):
    import jax
    return jax.random.wrap_key_data(key_words(seed, *salt),
                                    impl="threefry2x32")


def rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *map(int, salt)])
