"""Top-level neighbor search — paper Listings 1-3 as a JAX pipeline.

Pipeline (host orchestration mirrors the paper's host code):
  1. build the cell grid over the points              (Listing 1, buildBVH)
  2. schedule: Morton-order the queries               (section 4, Listing 2)
  3. partition: megacells -> per-query window         (section 5.1, Listing 3)
  4. bundle: cost-model launch plan                   (section 5.2)
  5. per bundle: tiled window search (jnp path or Pallas kernel path),
     scatter back through the inverse permutations.

Static-shape discipline: each bundle launch is jitted under a static
(window, skip, K, padded-N) signature; bundle query counts are padded to
power-of-two buckets so recompilation is bounded (DESIGN.md "padded-bucket
partitions").
"""
from __future__ import annotations

import dataclasses
import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# "topk" = partial selection (lax.top_k) on the candidate axis; "sort" =
# stable full argsort (oracle-identical tie order). Perf iteration 5.
_SELECTION = os.environ.get("REPRO_SELECTION", "topk")

from . import bundle as bundle_mod
from .partition import (MegacellStatics, PartitionPlan, compute_megacells,
                        megacell_statics, plan_partitions, trivial_plan)
from .schedule import schedule_queries
from .types import (Array, CellGrid, GridSpec, SearchOpts, SearchParams,
                    SearchResult)
from ..kernels.ref import pairwise_d2, topk_select


# ---------------------------------------------------------------------------
# per-bundle window search (jnp path; the Pallas path lives in kernels/ops)
# ---------------------------------------------------------------------------

def window_tile_search(
    grid: CellGrid,
    points: Array,
    qt: Array,
    spec: GridSpec,
    w: int,
    radius: float,
    k: int,
    skip_test: bool,
    origin: Array | None = None,
) -> tuple[Array, Array, Array]:
    """One query tile ``qt`` [T, 3] against the (2w+1)^3 window around each
    query's cell: ([T, k] d2, [T, k] idx, [T] cnt).

    The per-tile unit shared by the jitted ``window_search`` path and the
    traced launch-ladder branches of the functional core (``core/api.py``):
    both paths run the identical ops, so their results are bit-identical
    for the same ``w``/``skip_test`` signature.

    Step 1 (paper: ray-AABB on RT cores) is the regular window gather —
    pure index arithmetic: the window's candidate ids from ``grid.dense``
    and their coordinates from ``grid.coords``, each as one slice per
    query. Step 2 (paper: IS shader sphere test) is the tiled
    pairwise-distance + bounded-K selection; with ``skip_test`` the r^2
    filter is elided (paper's megacell-inscribed range-search case).
    ``points`` is not read here: it stays in the searcher signature that
    ``window_search`` shares with the Pallas path
    (``NeighborSearch._searcher``).
    """
    # per-axis window, clamped to the grid (thin-slab datasets like KITTI
    # have near-degenerate axes whose whole extent fits inside the window)
    ws = tuple(min(2 * w + 1, d) for d in spec.dims)
    cap = spec.capacity
    r2 = jnp.float32(radius) ** 2
    dims = jnp.asarray(spec.dims, jnp.int32)
    ws_arr = jnp.asarray(ws, jnp.int32)

    # each stage runs under a scope of its own, so a device trace of any
    # path that runs this tile (api.execute_plan, the executor's launcher,
    # the host loop) attributes its time by the same four names
    with jax.named_scope("repro.search.window_gather"):
        ccoord = spec.cell_of(qt, origin)                # [T, 3]
        start = jnp.clip(ccoord - w, 0, dims - ws_arr)   # [T, 3]

        def gather_one(st):
            blk = jax.lax.dynamic_slice(
                grid.dense, (st[0], st[1], st[2], 0),
                (*ws, cap))
            return blk.reshape(-1)

        cand = jax.vmap(gather_one)(start)               # [T, W^3*C]
    # the same window of the coordinate table, whose minor dimension is
    # the z-run of cells times their 3 coordinates times their slots
    # (CellGrid.coords): slot order matches cand's, and an empty slot's
    # 0.0 is masked by cand < 0
    with jax.named_scope("repro.search.row_gather"):
        def coords_one(st):
            blk = jax.lax.dynamic_slice(
                grid.coords, (st[0], st[1], st[2] * 3 * cap),
                (ws[0], ws[1], ws[2] * 3 * cap))
            return blk.reshape(*ws, 3, cap)

        cand_pos = jnp.moveaxis(jax.vmap(coords_one)(start), 4, 1)
        cand_pos = cand_pos.reshape(qt.shape[0], 3, -1)  # [T, 3, W^3*C]
    with jax.named_scope("repro.search.distance"):
        d2 = _tile_d2(qt, cand_pos)                      # [T, W^3*C]
    with jax.named_scope("repro.search.select"):
        return _select(d2, cand, k, r2, skip_test)


def _select(d2: Array, cand: Array, k: int, r2, skip_test: bool):
    """Mask the out-of-range and empty candidates, then keep the ``k``
    nearest: ([T, k] d2, [T, k] idx, [T] cnt)."""
    invalid = cand < 0
    if not skip_test:
        invalid = invalid | (d2 > r2)
    d2 = jnp.where(invalid, jnp.inf, d2)
    idx = jnp.where(invalid, -1, cand)
    if _SELECTION == "topk":
        # partial selection O(M*K) instead of full argsort O(M log M)
        # over the candidate axis (Perf iteration 5, EXPERIMENTS.md)
        m = d2.shape[-1]
        kk = min(k, m)
        negd, sel = jax.lax.top_k(-d2, kk)
        d2k = jnp.pad(-negd, ((0, 0), (0, k - kk)),
                      constant_values=jnp.inf)
        idxk = jnp.pad(jnp.take_along_axis(idx, sel, axis=-1),
                       ((0, 0), (0, k - kk)), constant_values=-1)
        idxk = jnp.where(jnp.isinf(d2k), -1, idxk)
    else:
        d2k, idxk = topk_select(d2, idx, k)
    cnt = jnp.sum((idxk >= 0).astype(jnp.int32), axis=-1)
    return d2k, idxk, cnt


@partial(jax.jit,
         static_argnames=("spec", "w", "k", "skip_test", "tile"))
def window_search(
    grid: CellGrid,
    points: Array,
    queries: Array,
    spec: GridSpec,
    w: int,
    radius: float,
    k: int,
    skip_test: bool,
    tile: int = 256,
    origin: Array | None = None,
) -> tuple[Array, Array, Array]:
    """Search each query against the (2w+1)^3 cell window around its cell.

    Tiled driver over :func:`window_tile_search`. Padded rows are
    edge-replicates of the last real query (matching the host loop and the
    Pallas path) so they search that query's own window instead of all
    collapsing into the origin cell's window — zero-padding wasted gathers
    and distorted the Pallas tile-window anchors.
    """
    nq = queries.shape[0]
    npad = (-nq) % tile
    if npad:
        queries = jnp.pad(queries, ((0, npad), (0, 0)), mode="edge")

    def one_tile(qt):
        return window_tile_search(grid, points, qt, spec, w, radius, k,
                                  skip_test, origin)

    d2c, idxc, cntc = jax.lax.map(one_tile, queries.reshape(-1, tile, 3))
    return (idxc.reshape(-1, k)[:nq], d2c.reshape(-1, k)[:nq],
            cntc.reshape(-1)[:nq])


def _tile_d2(q: Array, cand_pos: Array) -> Array:
    """[T, 3] x [T, 3, M] (planar) -> [T, M] squared distances (batched MXU
    form; ``HIGHEST`` keeps f32 products on the TPU too)."""
    qn = jnp.sum(q * q, axis=-1, keepdims=True)              # [T, 1]
    pn = jnp.sum(cand_pos * cand_pos, axis=1)                # [T, M]
    cross = jnp.einsum("td,tdm->tm", q, cand_pos,
                       precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(qn + pn - 2.0 * cross, 0.0)


def _pad_bucket(n: int, tile: int) -> int:
    """Next power-of-two multiple of ``tile`` >= n (recompile bounding)."""
    base = max(tile, int(2 ** math.ceil(math.log2(max(n, 1)))))
    return int(math.ceil(base / tile) * tile)


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SearchReport:
    """Execution breakdown mirroring paper Fig. 12 categories, plus the
    executor's dispatch/sync counters (DESIGN.md section 3)."""

    t_opt: float = 0.0         # Opt   (schedule + partition + bundle planning)
    t_search: float = 0.0      # Search
    bundles: list = dataclasses.field(default_factory=list)
    num_partitions: int = 0
    launches: int = 0          # device dispatches in the last query
    host_syncs: int = 0        # blocking result materializations (executor: 1)
    plan_fetches: int = 0      # small plan-metadata transfers (executor: <=1)


class NeighborSearch:
    """RTNN-style neighbor search over a fixed point set.

    >>> ns = NeighborSearch(points, SearchParams(radius=0.1, k=8))
    >>> res = ns.query(queries)          # SearchResult in query order
    """

    def __init__(
        self,
        points,
        params: SearchParams,
        opts: SearchOpts = SearchOpts(),
        spec: GridSpec | None = None,
        cost_model: bundle_mod.CostModel | None = None,
    ):
        from .api import build_index
        self.params = params
        self.opts = opts
        self.cost_model = cost_model or bundle_mod.CostModel()
        # thin shim over the functional core: the structure is a
        # NeighborIndex (core/api.py); the executor below is the
        # host-planned optimizing path over the same leaves
        self.index = build_index(points, params, opts, spec=spec)
        self.spec = self.index.spec
        self.points = self.index.points
        self.grid = self.index.grid
        self.statics = self.index.statics
        self.report = SearchReport()
        from .executor import QueryExecutor
        self.executor = QueryExecutor(self)

    # -- pipeline stages ----------------------------------------------------

    def _schedule(self, queries: Array) -> tuple[Array, Array]:
        if not self.opts.schedule:
            n = queries.shape[0]
            eye = jnp.arange(n, dtype=jnp.int32)
            return eye, eye
        return schedule_queries(self.spec, queries)

    def _partition(self, queries_s: Array) -> PartitionPlan:
        nq = queries_s.shape[0]
        if not self.opts.partition or not self.statics.has_megacells:
            return trivial_plan(nq, self.statics.w_full)
        w_search, skip, rho = compute_megacells(
            self.grid, queries_s, self.statics, self.params)
        return plan_partitions(w_search, skip, rho, self.statics.w_full)

    def _bundle(self, plan: PartitionPlan) -> list[bundle_mod.Bundle]:
        return bundle_mod.plan_bundles(
            plan.partitions, self.cost_model,
            n_points=int(self.points.shape[0]),
            cell_size=self.spec.cell_size,
            mode=self.params.mode, k=self.params.k,
            w_sph=self.statics.w_sph,
            enable=self.opts.bundle,
        )

    # -- execution ----------------------------------------------------------

    def query(self, queries) -> SearchResult:
        """Search ``queries`` [Nq, 3]; results come back in query order.

        Default path is the device-resident ``QueryExecutor`` (async
        signature-batched launches, on-device scatter, one host sync —
        DESIGN.md section 3); ``SearchOpts(executor=False)`` keeps the
        legacy per-bundle host loop for A/B benchmarking.
        """
        if self.opts.executor:
            return self.executor.execute(queries)
        return self._query_host_loop(queries)

    def _query_host_loop(self, queries) -> SearchResult:
        import time
        queries = jnp.asarray(queries, jnp.float32)
        nq = queries.shape[0]
        k = self.params.k

        t0 = time.perf_counter()
        perm, inv = self._schedule(queries)
        queries_s = jnp.asarray(queries)[perm]
        plan = self._partition(queries_s)
        bundles = self._bundle(plan)
        self.report.t_opt = time.perf_counter() - t0
        self.report.num_partitions = plan.num_partitions
        self.report.bundles = bundles

        out_idx = np.full((nq, k), -1, np.int32)
        out_d2 = np.full((nq, k), np.inf, np.float32)
        out_cnt = np.zeros((nq,), np.int32)
        perm_np = np.asarray(jax.device_get(perm))

        t0 = time.perf_counter()
        for b in bundles:
            sel_sched = bundle_mod.bundle_query_sel(plan, b)
            qb = queries_s[jnp.asarray(sel_sched)]
            pad_n = _pad_bucket(qb.shape[0], self.opts.query_tile)
            # edge-replicate padding: padded rows are copies of a real query
            # so tile window anchors (pallas path) are not distorted
            qb = jnp.pad(qb, ((0, pad_n - qb.shape[0]), (0, 0)), mode="edge")
            searcher = self._searcher()
            idx, d2, cnt = searcher(
                self.grid, self.points, qb, self.spec,
                int(b.w_search), self.params.radius, k,
                bool(b.skip_test), self.opts.query_tile)
            n_b = sel_sched.shape[0]
            orig = perm_np[sel_sched]
            out_idx[orig] = np.asarray(jax.device_get(idx))[:n_b]
            out_d2[orig] = np.asarray(jax.device_get(d2))[:n_b]
            out_cnt[orig] = np.asarray(jax.device_get(cnt))[:n_b]
        self.report.t_search = time.perf_counter() - t0
        self.report.launches = len(bundles)
        # per bundle: 3 blocking result transfers; +1 for the perm fetch
        self.report.host_syncs = 3 * len(bundles) + 1
        self.report.plan_fetches = 3 if (self.opts.partition and
                                         self.statics.has_megacells) else 0

        return SearchResult(indices=jnp.asarray(out_idx),
                            distances2=jnp.asarray(out_d2),
                            counts=jnp.asarray(out_cnt))

    def _searcher(self):
        # both searchers are pure traced JAX with the same positional
        # signature; the Pallas one runs the level-segmented fused schedule
        # (device tile anchors by scalar prefetch, kernels/ops), so the
        # executor compiles either into its one-program launch schedule
        if self.opts.use_pallas:
            from ..kernels.ops import window_search_pallas
            return window_search_pallas
        return window_search


def neighbor_search(points, queries, radius: float, k: int,
                    mode: str = "knn",
                    opts: SearchOpts = SearchOpts(),
                    knn_window: str = "exact") -> SearchResult:
    """One-shot search (builds the structure and searches).

    Routed through the keyed index cache of the functional core
    (``api.cached_searcher``): repeated one-shot calls over the same point
    set reuse the built grid and every plan/compile cache instead of
    discarding them per call.
    """
    from .api import cached_searcher
    params = SearchParams(radius=radius, k=k, mode=mode,
                          knn_window=knn_window)
    return cached_searcher(points, params, opts).query(queries)
