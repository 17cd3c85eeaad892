"""A LiDAR-like frame: points over the unit square with a radial density
falloff from the sensor at its centre, in a thin z slab. The distribution
of ``repro.data.pointclouds.kitti_like_cloud``, copied so that the
yardstick does not move with the program."""
import numpy as np


def generate(rng: np.random.Generator, *, points: int,
             z_range: float) -> np.ndarray:
    xy = rng.random((points, 2), dtype=np.float32)
    z = rng.random((points, 1), dtype=np.float32) * np.float32(z_range)
    r = np.sqrt(rng.random((points, 1), dtype=np.float32))
    xy = 0.5 + (xy - 0.5) * r
    return np.concatenate([xy, z], axis=1).astype(np.float32)
