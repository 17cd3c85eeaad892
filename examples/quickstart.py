"""Quickstart: RTNN-style neighbor search, functional-first.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.api import SearchParams
from repro.launch.cache import configure_compile_cache

configure_compile_cache()
rng = np.random.default_rng(0)
points = rng.random((50_000, 3)).astype(np.float32)   # your point cloud
queries = rng.random((5_000, 3)).astype(np.float32)   # where to search

# K-nearest-neighbor search, bounded by a radius (the paper's unified
# (r, K) interface, section 2.1). The index is a pytree; query is a pure
# function — jit it, vmap it, close over it in your own step function.
index = api.build_index(points, SearchParams(radius=0.05, k=8))
result = jax.jit(api.query)(index, queries)

print("indices   ", result.indices.shape, "(-1 padded)")
print("distances2", result.distances2.shape, "(inf padded)")
print("counts    ", np.asarray(result.counts)[:10])

# moving points? update_index re-bins into the frozen spec, on device
moved = np.clip(points + rng.normal(0, 1e-3, points.shape),
                0, 1).astype(np.float32)
index2, stats = api.update_index(index, moved)
print("update    ", "max_disp2=%.2e" % float(stats.max_disp2),
      "oob=%d" % int(stats.oob))

# batch of independent same-spec scenes == vmap (multi-scene batching)
scenes = jnp.stack([jnp.asarray(points), jnp.asarray(moved)])
batch_q = jnp.stack([jnp.asarray(queries)] * 2)
stacked = jax.vmap(
    lambda p: api.build_index(p, SearchParams(radius=0.05, k=8),
                              spec=index.spec))(scenes)
batch = jax.jit(jax.vmap(api.query))(stacked, batch_q)
print("batched   ", batch.indices.shape, "(2 scenes, one compiled program)")

# the eager class surface is a shim over the same core, with the
# host-planned executor (cost-model bundling) as its optimizing path
from repro.core import NeighborSearch, SearchOpts

searcher = NeighborSearch(points, SearchParams(radius=0.05, k=8))
res_eager = searcher.query(queries)
assert np.array_equal(np.asarray(res_eager.counts),
                      np.asarray(result.counts))
print(f"eager     partitions={searcher.report.num_partitions} "
      f"bundles={len(searcher.report.bundles)} "
      f"t_search={searcher.report.t_search * 1e3:.1f}ms")

# fixed-radius ("range") search with the same structure: first-K within r
range_result = NeighborSearch(
    points, SearchParams(radius=0.05, k=16, mode="range"),
    SearchOpts(bundle=True)).query(queries)
print("range counts", np.asarray(range_result.counts)[:10])
