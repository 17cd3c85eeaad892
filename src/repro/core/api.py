"""Functional pytree-first core: ``build_index / query / update_index`` as
pure, traceable JAX (DESIGN.md section 8).

The host-orchestrated surfaces (``NeighborSearch``, ``SimulationSession``,
``distributed_neighbor_search``) cannot be called from inside a user's
jitted step function: their planning fetches partition metadata to the host
mid-pipeline. This module is the pure core they are shims over — the whole
search is a traceable JAX value, so

  * ``jax.jit(query)`` runs the full schedule→partition→search pipeline as
    one program with zero mid-trace host syncs;
  * ``jax.vmap(query)`` over a stacked batch of same-spec scenes IS
    multi-scene batching (the ROADMAP's "multi-session batching" item);
  * ``shard_map`` over stacked scene leaves distributes it;
  * ``lax.cond`` over ``update_index`` + ``plan_query``/``execute_plan``
    is the dynamic session's device-resident staleness branch
    (``core/dynamic.py``).

**Static-signature tracing contract.** The host executor plans
data-dependent launch groups (fetch megacell metadata, group bundles by
``(w_search, skip_test)``, pad to buckets). A traced query cannot shape
launches from data, so the traced path enumerates, host-statically, every
launch signature a query could be assigned — the megacell rings
``0..w_loop`` mapped through the paper's window sizing plus the
full-radius fallback (``partition.launch_signatures``) — sorts queries by
``(signature level, Morton)`` on device, and dispatches each query *tile*
through ``lax.switch`` to its signature's branch. Each tile pays only its
own window's gather cost (the partition win), every branch has static
shapes, and the signature set is bounded exactly like the executor's
padded-bucket signatures. The eager host-planned executor remains the
optimizing path (it additionally folds bundles by the cost model);
``SearchOpts.w_ladder`` coarsens the traced ladder explicitly.

``use_pallas`` now composes with the traced path: the fused kernel's
tile-window anchors are computed on device (a traced per-tile min/max over
the scheduled queries' cell coords, delivered to the kernel by scalar
prefetch), and the per-tile ``lax.switch`` is replaced by **level-segmented
launches** — ``schedule_by_level`` makes each ladder level's tiles a
contiguous run, and ``kernels/ops.window_search_segmented`` runs ONE
masked fused-kernel launch per level, with off-level tiles predicated off
inside the kernel (``@pl.when``). Under ``vmap`` this keeps the partition
win: a batched ``lax.switch`` lowers to execute-all-branches, while the
masked launches stream only each tile's own window. The Pallas *update*
kernel is likewise traced by ``update_index``. ``REPRO_SEGMENT_LAUNCHES=0``
falls back to the jnp ``lax.switch`` path (DESIGN.md section 4).
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..reliability.errors import QueryError
from .grid import (build_cell_grid, choose_grid_spec, parked_mask,
                   update_cell_grid_traced)
from .partition import (MegacellStatics, compute_megacells, launch_signatures,
                        megacell_statics, signature_levels)
from .schedule import schedule_by_level
from .search import window_tile_search
from .types import (PARK_THRESHOLD, Array, CellGrid, GridSpec, SearchOpts,
                    SearchParams, SearchResult, UpdateStats)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class NeighborIndex:
    """The built search structure as a registered pytree.

    Spec-static aux (hashable, shared by every scene in a vmap batch):
    ``params``, ``opts``, ``statics``; the ``GridSpec`` rides in the
    ``CellGrid`` subtree's own aux. Leaves: ``points`` [N, 3], the grid
    arrays, ``anchor_points`` — the positions the current plan was
    captured at (the staleness statistic of ``update_index`` is measured
    against them; ``with_anchor`` re-anchors after a replan) — and
    ``origin``, an optional dynamic [3] override of the spec origin: the
    sharded slabs (``core/shards.py``) share ONE static spec across the
    mesh while each slab's local frame differs, so the frame must be a
    leaf, not aux (None = use the static ``spec.origin``).
    """

    params: SearchParams
    opts: SearchOpts
    statics: MegacellStatics
    points: Array
    grid: CellGrid
    anchor_points: Array
    origin: Array | None = None

    @property
    def spec(self) -> GridSpec:
        return self.grid.spec

    def with_anchor(self, anchor_points: Array) -> "NeighborIndex":
        return dataclasses.replace(self, anchor_points=anchor_points)

    def tree_flatten(self):
        return ((self.points, self.grid, self.anchor_points, self.origin),
                (self.params, self.opts, self.statics))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        params, opts, statics = aux
        points, grid, anchor, origin = leaves
        return cls(params=params, opts=opts, statics=statics,
                   points=points, grid=grid, anchor_points=anchor,
                   origin=origin)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QueryPlan:
    """A device-resident, replayable schedule∘partition plan.

    Static aux: query count ``nq``, tile size, and the launch-signature
    ``ladder`` the levels index into. Leaves: ``perm`` — the composed
    (level, Morton) permutation, edge-padded to a tile multiple (padded
    slots repeat the last scheduled query, so duplicate scatter writes are
    idempotent) — and ``tile_levels``, each tile's ``lax.switch`` branch.
    Both branches of the session's staleness ``lax.cond`` return one of
    these, which is what makes plan replay a device decision.
    """

    nq: int
    tile: int
    ladder: tuple
    perm: Array          # [Np] int32, Np % tile == 0
    tile_levels: Array   # [Np // tile] int32

    def tree_flatten(self):
        return ((self.perm, self.tile_levels),
                (self.nq, self.tile, self.ladder))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        nq, tile, ladder = aux
        perm, tile_levels = leaves
        return cls(nq=nq, tile=tile, ladder=ladder, perm=perm,
                   tile_levels=tile_levels)


# ---------------------------------------------------------------------------
# build / update
# ---------------------------------------------------------------------------

def build_index(points, params: SearchParams,
                opts: SearchOpts = SearchOpts(), *,
                spec: GridSpec | None = None,
                origin=None) -> NeighborIndex:
    """Build a :class:`NeighborIndex` over ``points`` [N, 3].

    Pure and traceable when ``spec`` is given (the grid build is a bin +
    stable-rank scatter). Without a spec the grid parameters are planned on
    the host from the concrete points (``choose_grid_spec``) — that is
    data-dependent host work, so under ``jit``/``vmap`` an explicit spec is
    required (and is what makes a batch of scenes share one trace).

    ``origin`` [3] dynamically overrides ``spec.origin`` for every cell
    lookup (build, update, and query planning) — the sharded slabs' shared
    static spec with per-slab frames. With ``opts.mask_parked`` rows parked
    at the padding sentinel are dropped from the grid entirely instead of
    binned into the clamped corner cell (which would pollute megacell
    counts near the grid's high corner).

    ``SearchOpts(use_pallas=True)`` raises ``NotImplementedError`` on a
    TPU backend, where the fused kernel's in-kernel gathers do not compile
    (``kernels/ops.check_fused_path``).
    """
    if opts.use_pallas:
        from ..kernels.ops import check_fused_path
        check_fused_path()
    if spec is None:
        if isinstance(points, jax.core.Tracer):
            raise TypeError(
                "build_index called under jit/vmap without a GridSpec: grid "
                "planning (choose_grid_spec) is host-side data-dependent "
                "work. Plan the spec eagerly and pass spec=...")
        # np.asarray is free for host inputs and one fetch for device
        # inputs; converting before the upload below avoids a host->device
        # ->host round-trip of the full cloud
        spec = choose_grid_spec(np.asarray(points, np.float32),
                                params.radius)
    with jax.named_scope("repro.build_index"):
        points = jnp.asarray(points, jnp.float32)
        if origin is not None:
            origin = jnp.asarray(origin, jnp.float32)
        valid = jnp.logical_not(parked_mask(points)) if opts.mask_parked \
            else None
        grid = build_cell_grid(points, spec, origin, valid)
        statics = megacell_statics(spec.cell_size, params, opts.w_max)
        return NeighborIndex(params=params, opts=opts, statics=statics,
                             points=points, grid=grid, anchor_points=points,
                             origin=origin)


def update_index(index: NeighborIndex,
                 new_points) -> tuple[NeighborIndex, UpdateStats]:
    """Re-bin moved points into the index's frozen spec (pure, traceable).

    Returns the updated index and on-device :class:`UpdateStats` —
    ``overflow`` / ``oob`` counters (nonzero means the frozen spec can no
    longer represent the scene exactly; the session's host respec fallback
    handles that) and ``max_disp2`` vs ``anchor_points`` (the staleness
    statistic). The anchor is deliberately NOT advanced: re-anchoring is
    the replan branch's job (``with_anchor``), typically under the
    session's ``lax.cond``.
    """
    with jax.named_scope("repro.update_index"):
        pts = jnp.asarray(new_points, jnp.float32)
        grid, stats, _ccoord = update_cell_grid_traced(
            index.grid, pts, index.anchor_points,
            use_pallas=index.opts.use_pallas, origin=index.origin,
            mask_parked=index.opts.mask_parked)
        return (dataclasses.replace(index, points=pts, grid=grid), stats)


# ---------------------------------------------------------------------------
# plan / execute / query
# ---------------------------------------------------------------------------

def plan_query(index: NeighborIndex, queries, *,
               margin: int = 0) -> QueryPlan:
    """Schedule + partition ``queries`` into a replayable :class:`QueryPlan`
    (pure, traceable).

    ``margin`` bakes the staleness allowance into every window (the traced
    counterpart of ``partition.inflate_plan_inputs``): windows inflate by
    ``margin`` cells clamped to the full-radius window, and the sphere-test
    skip is revoked for any window pushed past the inscribed ring — so a
    captured plan stays exact while drift remains under the session
    threshold.
    """
    with jax.named_scope("repro.plan_query"):
        queries = jnp.asarray(queries, jnp.float32)
        params, opts, statics = index.params, index.opts, index.statics
        spec = index.spec
        nq = queries.shape[0]
        tile = opts.query_tile
        partitioned = opts.partition and statics.has_megacells
        ladder = launch_signatures(statics, params, margin=margin,
                                   enabled=partitioned,
                                   w_ladder=opts.w_ladder)
        ccoord = spec.cell_of(queries, index.origin)
        if partitioned:
            w_search, skip, _rho = compute_megacells(index.grid, queries,
                                                     statics, params,
                                                     index.origin)
            if margin:
                w_search = jnp.minimum(w_search + jnp.int32(margin),
                                       jnp.int32(statics.w_full))
                skip = skip & (w_search <= statics.w_sph)
            levels = signature_levels(w_search, skip, ladder)
        else:
            levels = jnp.zeros((nq,), jnp.int32)
        perm = schedule_by_level(ccoord, levels, morton=opts.schedule)
        npad = (-nq) % tile
        # edge-replicate padding (same discipline as the executor's padded
        # selections): padded slots repeat the last scheduled query
        take = jnp.minimum(jnp.arange(nq + npad), nq - 1)
        perm_p = perm[take].astype(jnp.int32)
        tile_levels = jnp.max(levels[perm_p].reshape(-1, tile), axis=1)
        return QueryPlan(nq=nq, tile=tile, ladder=ladder, perm=perm_p,
                         tile_levels=tile_levels)


def _segment_launches() -> bool:
    """Safety valve: 0 falls the traced fused path back to the per-tile
    lax.switch jnp dispatch even when use_pallas is set (DESIGN.md
    section 4). Read at trace time (not import time), so toggling it
    after import affects every NEW trace — programs already compiled and
    cached under jit keep the path they were traced with until their
    cache is cleared or a fresh jit wrapper is made."""
    return os.environ.get("REPRO_SEGMENT_LAUNCHES", "1") != "0"


def execute_plan(index: NeighborIndex, queries,
                 plan: QueryPlan) -> SearchResult:
    """Run ``queries`` through a captured plan (pure, traceable).

    jnp path: one ``lax.map`` over query tiles; each tile dispatches
    through ``lax.switch`` to its launch signature's ``window_tile_search``
    branch — identical per-tile ops to the executor's launches, so results
    are exact. Fused path (``SearchOpts(use_pallas=True)``): the plan's
    (level, Morton)-contiguous tile order feeds the level-segmented
    Pallas schedule (``kernels/ops.window_search_segmented``) — device
    tile anchors by scalar prefetch, one masked fused-kernel launch per
    ladder level. Either way the scatter back through ``perm`` happens on
    device and the whole call is one traced program.
    """
    with jax.named_scope("repro.execute_plan"):
        return _execute_plan_scoped(index, queries, plan)


def _execute_plan_scoped(index, queries, plan):
    queries = jnp.asarray(queries, jnp.float32)
    params = index.params
    k, tile, nq = params.k, plan.tile, plan.nq
    grid, points, spec = index.grid, index.points, index.spec
    qs = queries[plan.perm]

    if index.opts.use_pallas and _segment_launches():
        from ..kernels.ops import window_search_segmented
        d2t, idxt, cntt = window_search_segmented(
            grid, points, qs, spec, plan.ladder, plan.tile_levels,
            params.radius, k, tile, origin=index.origin)
    else:
        def _branch(e, w, skip):
            def run(qt):
                # the Pallas path's per-level scope names (kernels/ops), so
                # a device trace gives each ladder level its own time
                with jax.named_scope(f"repro.launch.level{e}_w{w}"):
                    return window_tile_search(grid, points, qt, spec, w,
                                              params.radius, k, skip,
                                              origin=index.origin)
            return run

        branches = [_branch(e, w, s)
                    for e, (w, s) in enumerate(plan.ladder)]

        def one_tile(args):
            qt, lvl = args
            if len(branches) == 1:
                return branches[0](qt)
            return jax.lax.switch(jnp.clip(lvl, 0, len(branches) - 1),
                                  branches, qt)

        d2t, idxt, cntt = jax.lax.map(
            one_tile, (qs.reshape(-1, tile, 3), plan.tile_levels))
    # padded slots repeat the last real query, so duplicate writes below
    # carry identical rows and the scatter is idempotent
    out_idx = jnp.full((nq, k), -1, jnp.int32).at[plan.perm].set(
        idxt.reshape(-1, k))
    out_d2 = jnp.full((nq, k), jnp.inf, jnp.float32).at[plan.perm].set(
        d2t.reshape(-1, k))
    out_cnt = jnp.zeros((nq,), jnp.int32).at[plan.perm].set(
        cntt.reshape(-1))
    return SearchResult(indices=out_idx, distances2=out_d2, counts=out_cnt)


def _validate_enabled() -> bool:
    """`REPRO_VALIDATE=1` validates host-side query inputs inside
    ``query`` (DESIGN.md sections 4/11). Read per call, not at import."""
    return os.environ.get("REPRO_VALIDATE", "0") not in ("", "0")


def validate_queries(queries, *, lo=None, hi=None,
                     max_rows: int = 8):
    """Reject unservable query inputs with a structured
    :class:`~repro.reliability.QueryError` — the serving layer's
    graceful-degradation gate (DESIGN.md section 11).

    Checks NaN, inf, and out-of-domain rows. The default domain check
    only catches coordinates whose magnitude reaches the parked-row
    sentinel threshold (``types.PARK_THRESHOLD`` — such rows would be
    silently dropped from grids built with ``mask_parked``); explicit
    ``lo``/``hi`` bounds (per-axis or scalar) tighten it to a real
    domain. ``max_rows`` bounds the offending-row list on the error.

    Contract-preserving by construction: under tracing it is a no-op
    (tracers pass through — the jaxpr of ``query`` is identical with
    validation on or off), and device-resident arrays pass through
    unfetched (the one-host-sync contract owns the only transfer), so
    only host-side inputs — the serving admission path, eager callers —
    are actually inspected. Returns ``queries`` unchanged when clean.
    """
    if isinstance(queries, jax.core.Tracer) or isinstance(queries,
                                                          jax.Array):
        return queries
    q = np.asarray(queries, np.float32)
    nan = np.isnan(q).any(axis=-1)
    inf = np.isinf(q).any(axis=-1)
    finite = ~(nan | inf)
    oob = finite & (np.abs(q) >= PARK_THRESHOLD).any(axis=-1)
    if lo is not None:
        oob |= finite & (q < np.asarray(lo, np.float32)).any(axis=-1)
    if hi is not None:
        oob |= finite & (q > np.asarray(hi, np.float32)).any(axis=-1)
    bad = nan | inf | oob
    if bad.any():
        reasons = {}
        for name, mask in (("nan", nan), ("inf", inf), ("oob", oob)):
            n = int(mask.sum())
            if n:
                reasons[name] = n
        rows = np.flatnonzero(bad.reshape(-1))[:max_rows].tolist()
        raise QueryError(reasons, rows, int(np.prod(bad.shape)))
    return queries


def query(index: NeighborIndex, queries) -> SearchResult:
    """Pure neighbor search: ``execute_plan(plan_query(...))``.

    Traceable end-to-end — composes under ``jax.jit``, ``jax.vmap`` (stack
    same-spec scenes and batch both arguments), and ``shard_map``. Results
    are in query order and exact (knn distances/counts identical to the
    eager ``NeighborSearch.query``; range mode returns a valid bounded-K
    in-radius subset per the paper's interface).

    With ``REPRO_VALIDATE=1``, host-side ``queries`` are validated
    (:func:`validate_queries`) before upload; tracers and device arrays
    pass through untouched, so jaxprs and sync counts are unchanged.
    """
    if _validate_enabled():
        queries = validate_queries(queries)
    return execute_plan(index, queries, plan_query(index, queries))


def query_concat(index: NeighborIndex, queries_list) -> list[SearchResult]:
    """Batch-concat entry point: many requests' queries against one index
    as ONE ``plan_query`` + ``execute_plan`` launch, split back per request.

    This is the serving layer's drain contract (``repro.serve``,
    DESIGN.md section 10): B requests sharing a scene and search signature
    cost one traced program — one schedule/partition pass over the
    concatenated rows, one launch schedule, one result sync — instead of B.
    Exactness is per query: each row's launch-ladder level depends only on
    its own megacell statistics, and a knn query searched at a widened
    window (a tile it shares with a larger-window neighbor) still returns
    the identical k-nearest set, so per-request results are bitwise what
    ``query`` returns for that request alone. Pure and traceable (the
    split offsets are host-static shapes).
    """
    sizes = [q.shape[0] for q in queries_list]
    if not sizes:
        return []
    cat = jnp.concatenate(
        [jnp.asarray(q, jnp.float32) for q in queries_list], axis=0)
    res = query(index, cat)
    out, off = [], 0
    for n in sizes:
        out.append(SearchResult(indices=res.indices[off:off + n],
                                distances2=res.distances2[off:off + n],
                                counts=res.counts[off:off + n]))
        off += n
    return out


# ---------------------------------------------------------------------------
# keyed index cache (one-shot surface)
# ---------------------------------------------------------------------------

_SEARCHER_CACHE: collections.OrderedDict = collections.OrderedDict()
_SEARCHER_CACHE_MAX = 8


def cached_searcher(points, params: SearchParams,
                    opts: SearchOpts = SearchOpts()):
    """Keyed cache behind the one-shot ``neighbor_search``.

    The legacy one-shot path constructed a fresh ``NeighborSearch`` +
    executor per call, discarding every plan/compile cache each time.
    Here the searcher is cached by a value fingerprint of (points, params,
    opts), so repeated one-shot calls over the same point set — the
    benchmark/test pattern — reuse the built grid, partition plans, and
    compiled launch schedules. LRU-bounded at ``_SEARCHER_CACHE_MAX``;
    the entries pin their device grids until evicted, so memory-sensitive
    streaming callers should use :func:`searcher_cache_clear` (or build a
    ``NeighborSearch`` directly, which was always the uncached path).
    """
    from .search import NeighborSearch
    # np.asarray fetches device arrays and is free on host arrays (the
    # common one-shot case) — no gratuitous upload/download round-trip
    pts_np = np.asarray(points, np.float32)
    digest = hashlib.sha1(np.ascontiguousarray(pts_np).tobytes()).digest()
    key = (pts_np.shape, digest, params, opts)
    hit = _SEARCHER_CACHE.get(key)
    if hit is not None:
        _SEARCHER_CACHE.move_to_end(key)
        return hit
    ns = NeighborSearch(pts_np, params, opts)
    _SEARCHER_CACHE[key] = ns
    if len(_SEARCHER_CACHE) > _SEARCHER_CACHE_MAX:
        _SEARCHER_CACHE.popitem(last=False)
    return ns


def searcher_cache_stats() -> dict:
    """Size of the one-shot searcher cache (tests assert hit behavior by
    identity of the returned searcher)."""
    return {"entries": len(_SEARCHER_CACHE),
            "max_entries": _SEARCHER_CACHE_MAX}


def searcher_cache_clear() -> None:
    _SEARCHER_CACHE.clear()


__all__ = [
    "GridSpec",
    "NeighborIndex",
    "QueryError",
    "QueryPlan",
    "SearchOpts",
    "SearchParams",
    "SearchResult",
    "UpdateStats",
    "build_index",
    "cached_searcher",
    "execute_plan",
    "launch_signatures",
    "plan_query",
    "query",
    "query_concat",
    "searcher_cache_clear",
    "searcher_cache_stats",
    "update_index",
    "validate_queries",
]
