"""Point-cloud processing pipeline: KNN normal estimation on a KITTI-like
LiDAR frame — the perception workload class (PCL) the paper's KNN serves.

For every point: find K nearest neighbors, fit a local plane (PCA of the
neighborhood covariance), output the normal. Runs the full RTNN pipeline
(schedule + partition + bundle) and cross-checks a sample against brute
force.

  PYTHONPATH=src python examples/pointcloud_pipeline.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import NeighborSearch, SearchOpts, SearchParams
from repro.data.pointclouds import kitti_like_cloud
from repro.kernels.ref import brute_force_search
from repro.launch.cache import configure_compile_cache

K = 16
R = 0.03


@jax.jit
def estimate_normals(points, nbr_idx):
    valid = (nbr_idx >= 0)[..., None]
    nbrs = points[jnp.clip(nbr_idx, 0)]                     # [N, K, 3]
    cnt = jnp.maximum(valid.sum(axis=1), 1)
    mean = jnp.sum(jnp.where(valid, nbrs, 0), axis=1) / cnt
    centered = jnp.where(valid, nbrs - mean[:, None], 0)
    cov = jnp.einsum("nki,nkj->nij", centered, centered) / cnt[..., None]
    # normal = eigenvector of the smallest eigenvalue
    w, v = jnp.linalg.eigh(cov)
    return v[..., 0]


def main():
    configure_compile_cache()
    pts = kitti_like_cloud(60_000, seed=3)
    t0 = time.perf_counter()
    ns = NeighborSearch(pts, SearchParams(radius=R, k=K))
    res = ns.query(pts)
    t_search = time.perf_counter() - t0
    normals = estimate_normals(jnp.asarray(pts), res.indices)
    print(f"searched {len(pts)} points in {t_search:.2f}s "
          f"({t_search / len(pts) * 1e6:.1f} us/query, "
          f"{ns.report.num_partitions} partitions)")

    # verify sample vs brute force
    oi, od, oc = brute_force_search(jnp.asarray(pts), jnp.asarray(pts[:200]),
                                    R, K)
    got = np.asarray(res.distances2[:200])
    want = np.asarray(od)
    match = np.allclose(np.where(np.isinf(got), -1, got),
                        np.where(np.isinf(want), -1, want), atol=1e-5)
    print("sample oracle match:", match)
    # normals on a flat slab should be mostly vertical
    vertical = np.abs(np.asarray(normals)[:, 2]) > 0.9
    print(f"vertical normals: {vertical.mean() * 100:.0f}% "
          "(KITTI-like ground slab)")
    assert match


if __name__ == "__main__":
    main()
