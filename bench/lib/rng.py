"""Seeds: any whole number, also one wider than 32 bits."""
import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator for ``(seed, *stream)``."""
    return np.random.default_rng([int(seed) % 2**63, *map(int, stream)])


def key_for(seed: int, *stream: int) -> int:
    """A 32-bit JAX PRNG seed for ``(seed, *stream)``."""
    return int(np.random.SeedSequence(
        [int(seed) % 2**63, *map(int, stream)]).generate_state(1)[0])
