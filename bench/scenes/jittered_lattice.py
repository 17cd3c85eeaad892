"""A fluid block at rest: one particle per lattice site, at
``(index + 0.5) * spacing`` from the origin, each moved by up to
``jitter * spacing`` per axis so that no two neighbours tie at the
radius."""
import numpy as np


def generate(rng: np.random.Generator, *, lattice, spacing: float,
             jitter: float) -> np.ndarray:
    grid = np.stack(np.meshgrid(*[np.arange(s) for s in lattice],
                                indexing="ij"), -1).reshape(-1, 3)
    off = rng.uniform(-jitter, jitter, grid.shape)
    return ((grid + 0.5 + off) * spacing).astype(np.float32)
