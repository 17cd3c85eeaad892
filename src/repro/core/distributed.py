"""Distributed neighbor search — thin shim over the sharded-scene
subsystem (``core/shards.py``, DESIGN.md section 6).

The original implementation in this module routed points and queries on
the host (``np.digitize`` bucketing + Python scatter loops) on every call
and ran a bespoke full-window search inside ``shard_map``, bypassing the
functional core entirely. All of that now lives — traced — in
``core/shards.py``: on-device slab routing (padded scatter), O(surface)
halo exchange via ``ppermute`` inside ``shard_map(api.query)``, one shared
static ``GridSpec`` across slabs, and the traced inverse scatter. This
module keeps the legacy one-shot convenience surface.
"""
from __future__ import annotations

import dataclasses

from jax.sharding import Mesh

from .shards import STATIC_SCENE_OPTS, shard_scene
from .types import SearchOpts, SearchParams, SearchResult


def distributed_neighbor_search(mesh: Mesh, points, queries,
                                params: SearchParams,
                                slab_axis: str = "data",
                                query_axis: str = "model",
                                cell_size: float | None = None,
                                opts: SearchOpts = SearchOpts()
                                ) -> SearchResult:
    """One-shot sharded search: plan, route, search, un-route.

    Results come back in query order with *global* point indices, exactly
    as before — but routing and un-routing are now traced device scatters
    and the per-slab search is ``api.query`` over the slab's functional
    ``NeighborIndex`` (megacell partitioning and the Pallas path compose).

    KNN keeps this surface's historical exactness contract: the
    approximate-by-design heuristic window is upgraded to the paper's
    conservative exact window (the legacy implementation always searched
    the full-radius window, so it was exact regardless of ``knn_window``).
    """
    if params.mode == "knn" and params.knn_window != "exact":
        params = dataclasses.replace(params, knn_window="exact")
    index = shard_scene(points, params, mesh=mesh, opts=opts,
                        shopts=STATIC_SCENE_OPTS, queries=queries,
                        cell_size=cell_size, slab_axis=slab_axis,
                        query_axis=query_axis)
    return index.query(queries)
