"""Sharded scenes: slab-resident distributed sessions on the functional
core (DESIGN.md section 6).

The paper is a single-GPU system; its host code routes queries to one
device. This module maps the whole pipeline onto a JAX device mesh by
porting the spatial x-slab decomposition onto the pytree core
(``core/api.py``), so scale-out composes with everything the functional
core already composes with (jit, the Pallas pipeline, sessions):

* **Traced slab routing.** The legacy distributed path bucketed points and
  queries on the host (``np.digitize`` + Python loops) on EVERY call. Here
  routing is pure traced JAX — slab-of-x bucketing, a stable rank within
  each slab, and a padded scatter into fixed-capacity per-slab buffers
  (:func:`route_points` / :func:`route_queries`) — and the inverse scatter
  (:func:`unroute_results`) is traced too, so a distributed query is ONE
  compiled program with zero host-side routing.
* **One shared static spec.** Every slab uses the same static
  :class:`~.types.GridSpec`; only the frame differs per slab — a dynamic
  ``origin`` leaf on the slab's :class:`~.api.NeighborIndex`
  (``layout.origin_of(axis_index)``). A single trace therefore serves the
  whole mesh; slabs are SPMD.
* **O(surface) halo exchange.** Inside ``shard_map``, each slab sends the
  points within ``radius`` of its faces to its two spatial neighbors via
  ``jax.lax.ppermute`` (static per-face caps), then runs plain
  ``api.query`` over owned + halo points — communication scales with the
  slab surface, not the volume.
* **Parked-row convention.** Fixed-capacity buffers pad with
  ``types.PARK_SENTINEL`` positions and id -1; ``SearchOpts.mask_parked``
  makes the functional core drop parked rows from the grid (they must not
  pollute megacell counts) and from the update statistics.
* **Slab-resident stepping** (:class:`ShardedSession`). The dynamic-scene
  session of DESIGN.md section 7, per slab: frozen shared spec, per-slab
  ``api.update_index`` over the halo-extended rows, a per-slab staleness
  ``lax.cond`` replaying the captured per-slab :class:`~.api.QueryPlan`,
  and cross-boundary particle **migration** — rows whose new position left
  the slab travel to the neighbor by ``ppermute`` under a static per-face
  cap and merge into free rows. Steady-state steps perform ZERO host-side
  routing (``stats()["host_routings"]`` counts the only host routing
  events: construction and the respec-style fallback). Any cap overflow —
  migration cap, halo cap, cell capacity, out-of-bounds, a multi-slab hop
  — raises a device flag and falls back to a host re-plan/re-route with
  geometrically growing headroom (the respec hysteresis of section 7).

``distributed_neighbor_search`` (``core/distributed.py``) is now a thin
shim over :func:`shard_scene` + :meth:`ShardedIndex.query`.
"""
from __future__ import annotations

import collections
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .. import obs
from . import api
from .dynamic import SessionOpts, validate_session_opts
from .types import (PARK_SENTINEL, Array, GridSpec, SearchOpts, SearchParams,
                    SearchResult)

_FLAG_REPLANNED = 1     # some slab's staleness cond took the replan branch
_FLAG_EXHAUSTED = 2     # a cap overflowed: layout can no longer hold scene


@dataclasses.dataclass(frozen=True)
class ShardOpts:
    """Static knobs of the slab decomposition.

    The ``*_slack`` factors size the fixed-capacity per-slab buffers above
    the observed distribution so rows can migrate/drift between host
    re-plans; ``migrate_frac`` caps the per-face per-step migration volume
    (static shape of the ``ppermute`` payload). ``reroute_growth`` is the
    hysteresis of the host fallback: every re-route multiplies all
    headroom by the accumulated boost, so a workload that keeps exhausting
    the layout pays O(log frames) re-routes (mirrors
    ``SessionOpts.respec_growth``).
    """

    point_slack: float = 1.6
    halo_slack: float = 1.6
    migrate_frac: float = 0.2
    query_slack: float = 1.5
    capacity_slack: float = 1.5
    domain_margin_radii: float = 1.0
    max_dim: int = 128
    auto_reroute: bool = True
    reroute_growth: float = 2.0
    reroute_boost_max: float = 64.0


# the one-shot path (distributed_neighbor_search) decomposes a STATIC
# scene: exact caps, no drift headroom
STATIC_SCENE_OPTS = ShardOpts(point_slack=1.0, halo_slack=1.0,
                              query_slack=1.0, capacity_slack=1.0,
                              domain_margin_radii=0.0)


@dataclasses.dataclass(frozen=True)
class SlabLayout:
    """Host-planned static layout of the spatial decomposition (hashable:
    jitted programs specialize on it).

    ``spec`` is the ONE static grid spec shared by every slab;
    ``spec.origin`` is slab 0's local frame and :meth:`origin_of` shifts it
    per slab — the only per-slab quantity, and it is a traced value, which
    is what lets a single trace serve the whole mesh.
    """

    n_slabs: int
    n_qsplit: int
    lo_x: float
    slab_width: float
    halo: float             # world-units halo width (= search radius)
    point_cap: int          # owned-row slots per slab
    halo_cap: int           # per-face halo-exchange payload rows
    migrate_cap: int        # per-face per-step migration payload rows
    query_cap: int          # rows per (slab, qsplit) routing cell
    spec: GridSpec

    @property
    def total_rows(self) -> int:
        """Rows of the halo-extended per-slab point buffer."""
        return self.point_cap + 2 * self.halo_cap

    def origin_of(self, sidx: Array) -> Array:
        """Local grid origin of slab ``sidx`` (traced)."""
        ox = (jnp.float32(self.spec.origin[0])
              + sidx.astype(jnp.float32) * jnp.float32(self.slab_width))
        return jnp.stack([ox, jnp.float32(self.spec.origin[1]),
                          jnp.float32(self.spec.origin[2])])

    def slab_of(self, x: Array) -> Array:
        """Slab id of x-coordinates (traced; clipped to the edge slabs)."""
        s = jnp.floor((x - jnp.float32(self.lo_x))
                      / jnp.float32(self.slab_width)).astype(jnp.int32)
        return jnp.clip(s, 0, self.n_slabs - 1)

    def slab_bounds(self, sidx: Array) -> tuple[Array, Array]:
        lo = (jnp.float32(self.lo_x)
              + sidx.astype(jnp.float32) * jnp.float32(self.slab_width))
        return lo, lo + jnp.float32(self.slab_width)


def plan_layout(points, params: SearchParams, n_slabs: int, *,
                n_qsplit: int = 1, queries=None,
                shopts: ShardOpts = ShardOpts(),
                cell_size: float | None = None,
                boost: float = 1.0) -> SlabLayout:
    """Host-side planning of the slab decomposition (the ONLY host routing
    work; everything downstream is traced).

    Equal-width x-slabs over the (margin-padded) point extent; the shared
    local spec covers one slab + halo + the one-cell clamp pad, with cell
    capacity measured EXACTLY per slab (each slab's owned + halo points
    binned in its own frame) times the slack. ``boost`` is the re-route
    hysteresis multiplier applied to every headroom knob.
    """
    pts = np.asarray(points, np.float32)
    n = pts.shape[0]
    r = float(params.radius)
    margin = shopts.domain_margin_radii * r * boost
    lo = pts.min(axis=0) - margin
    hi = pts.max(axis=0) + margin
    lo_x = float(lo[0])
    width = max((float(hi[0]) - lo_x) / n_slabs, 1e-6)
    halo = r

    ex = width + 2.0 * halo
    ey = max(float(hi[1] - lo[1]), r)
    ez = max(float(hi[2] - lo[2]), r)
    if cell_size is not None:
        cell = float(cell_size)
    else:
        # same policy as choose_grid_spec: cells finer than the radius so
        # megacells exist, bounded by the dense-array budget per axis
        cell = float(max(r / 4.0, max(ex, ey, ez) / shopts.max_dim))
    dims = tuple(min(int(math.ceil(e / cell)) + 3, shopts.max_dim + 3)
                 for e in (ex, ey, ez))
    origin0 = (lo_x - halo - cell, float(lo[1]) - cell, float(lo[2]) - cell)

    slab = np.clip(((pts[:, 0] - np.float32(lo_x))
                    / np.float32(width)).astype(np.int64), 0, n_slabs - 1)
    p_cnt = np.bincount(slab, minlength=n_slabs)
    relx = pts[:, 0] - (lo_x + slab * width)
    # domain-edge outer faces ship nothing (no neighbor) — size the caps
    # from the interior faces only
    nb_l = np.bincount(slab[(relx <= halo) & (slab > 0)],
                       minlength=n_slabs)
    nb_r = np.bincount(slab[(width - relx <= halo)
                            & (slab < n_slabs - 1)], minlength=n_slabs)

    point_cap = int(min(n, max(8, math.ceil(
        p_cnt.max() * shopts.point_slack * boost))))
    halo_cap = int(min(n, max(1, math.ceil(
        max(nb_l.max(), nb_r.max(), 1) * shopts.halo_slack * boost))))
    migrate_cap = int(min(max(1, point_cap // 2),
                          max(8, math.ceil(point_cap
                                           * shopts.migrate_frac))))

    # exact worst-case cell occupancy across the per-slab frames (the
    # frames are shifted by slab_width, which is not a cell multiple, so a
    # global-grid estimate would not bound them)
    occ_max = 1
    dims_a = np.asarray(dims)
    for s in range(n_slabs):
        xlo = lo_x + s * width - halo
        xhi = lo_x + (s + 1) * width + halo
        sel = pts[(pts[:, 0] >= xlo) & (pts[:, 0] <= xhi)]
        if not len(sel):
            continue
        o_s = np.asarray([xlo - cell, origin0[1], origin0[2]], np.float32)
        cc = np.clip(np.floor((sel - o_s) / cell).astype(np.int64), 0,
                     dims_a - 1)
        flat = (cc[:, 0] * dims[1] + cc[:, 1]) * dims[2] + cc[:, 2]
        _u, occ = np.unique(flat, return_counts=True)
        occ_max = max(occ_max, int(occ.max()))
    capacity = int(max(1, math.ceil(
        occ_max * shopts.capacity_slack * boost)))

    if queries is not None:
        qs = np.asarray(queries, np.float32)
        q_slab = np.clip(((qs[:, 0] - np.float32(lo_x))
                          / np.float32(width)).astype(np.int64), 0,
                         n_slabs - 1)
        q_cnt = np.bincount(q_slab, minlength=n_slabs)
        query_cap = int(max(1, math.ceil(
            q_cnt.max() / n_qsplit * shopts.query_slack * boost)))
    else:
        query_cap = int(max(1, math.ceil(point_cap / n_qsplit)))

    return SlabLayout(
        n_slabs=int(n_slabs), n_qsplit=int(n_qsplit), lo_x=lo_x,
        slab_width=float(width), halo=float(halo), point_cap=point_cap,
        halo_cap=halo_cap, migrate_cap=migrate_cap, query_cap=query_cap,
        spec=GridSpec(origin=origin0, cell_size=cell, dims=dims,
                      capacity=capacity))


# ---------------------------------------------------------------------------
# traced routing (replaces the host np.digitize round-trip)
# ---------------------------------------------------------------------------

def _rank_within(key: Array, n: int) -> Array:
    """Stable rank of each element among equal keys, in input order."""
    order = jnp.argsort(key, stable=True)
    ks = key[order]
    first = jnp.searchsorted(ks, ks, side="left")
    rank_sorted = jnp.arange(n, dtype=jnp.int32) - first.astype(jnp.int32)
    return jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)


def route_points(layout: SlabLayout, points: Array,
                 ids: Array | None = None
                 ) -> tuple[Array, Array, Array]:
    """Traced slab routing of ``points`` [N, 3] into fixed-capacity
    per-slab buffers.

    Returns ``(pts [S, P, 3], ids [S, P], overflow)``: parked rows carry
    the sentinel position and id -1; ``overflow`` counts points dropped
    because their slab's ``point_cap`` was exceeded (nonzero means the
    layout must be re-planned — it cannot happen when the layout was
    planned over these points).
    """
    n = points.shape[0]
    s_slabs, cap = layout.n_slabs, layout.point_cap
    gids = (jnp.arange(n, dtype=jnp.int32) if ids is None
            else ids.astype(jnp.int32))
    slab = layout.slab_of(points[:, 0])
    rank = _rank_within(slab, n)
    keep = rank < cap
    slot = jnp.where(keep, slab * cap + rank, s_slabs * cap)
    pts = (jnp.full((s_slabs * cap, 3), PARK_SENTINEL, jnp.float32)
           .at[slot].set(points.astype(jnp.float32), mode="drop")
           .reshape(s_slabs, cap, 3))
    out_ids = (jnp.full((s_slabs * cap,), -1, jnp.int32)
               .at[slot].set(gids, mode="drop").reshape(s_slabs, cap))
    return pts, out_ids, jnp.sum(jnp.logical_not(keep).astype(jnp.int32))


def route_queries(layout: SlabLayout, queries: Array
                  ) -> tuple[Array, Array, Array]:
    """Traced query routing into ``[S, C, Q, 3]`` buffers (C =
    ``n_qsplit`` round-robin columns per slab, the "model"-axis query
    split). Returns ``(qs, qid [S, C, Q], overflow)``.
    """
    nq = queries.shape[0]
    s_slabs, c, cap = layout.n_slabs, layout.n_qsplit, layout.query_cap
    slab = layout.slab_of(queries[:, 0])
    rank = _rank_within(slab, nq)
    col = rank % c
    pos = rank // c
    keep = pos < cap
    slot = jnp.where(keep, (slab * c + col) * cap + pos, s_slabs * c * cap)
    qs = (jnp.full((s_slabs * c * cap, 3), PARK_SENTINEL, jnp.float32)
          .at[slot].set(queries.astype(jnp.float32), mode="drop")
          .reshape(s_slabs, c, cap, 3))
    qid = (jnp.full((s_slabs * c * cap,), -1, jnp.int32)
           .at[slot].set(jnp.arange(nq, dtype=jnp.int32), mode="drop")
           .reshape(s_slabs, c, cap))
    return qs, qid, jnp.sum(jnp.logical_not(keep).astype(jnp.int32))


def unroute_results(qid: Array, gidx: Array, d2: Array, cnt: Array,
                    nq: int) -> tuple[Array, Array, Array]:
    """Traced inverse of the routing scatter: per-slab results back into
    original query order (rows with qid -1 — padding — are dropped)."""
    k = gidx.shape[-1]
    flat_q = qid.reshape(-1)
    safe = jnp.where(flat_q >= 0, flat_q, nq)       # nq is out of range
    oi = (jnp.full((nq, k), -1, jnp.int32)
          .at[safe].set(gidx.reshape(-1, k), mode="drop"))
    od = (jnp.full((nq, k), jnp.inf, jnp.float32)
          .at[safe].set(d2.reshape(-1, k), mode="drop"))
    oc = (jnp.zeros((nq,), jnp.int32)
          .at[safe].set(cnt.reshape(-1), mode="drop"))
    return oi, od, oc


# ---------------------------------------------------------------------------
# halo exchange + migration primitives (inside shard_map)
# ---------------------------------------------------------------------------

def _select_rows(pts: Array, ids: Array, mask: Array, cap: int
                 ) -> tuple[Array, Array, Array]:
    """First ``cap`` rows where ``mask`` (stable row order, static shape).

    Returns ``(p [cap, 3], i [cap], n_masked)`` — ``n_masked`` is the TRUE
    masked count, so the caller can flag ``n_masked > cap`` overflow
    instead of silently truncating.
    """
    order = jnp.argsort(jnp.where(mask, 0, 1), stable=True)[:cap]
    valid = mask[order]
    sel_p = jnp.where(valid[:, None], pts[order], PARK_SENTINEL)
    sel_i = jnp.where(valid, ids[order], -1)
    return sel_p, sel_i, jnp.sum(mask.astype(jnp.int32))


def _pack(p: Array, i: Array) -> Array:
    # ids shifted +1 so a zero-filled (mesh-edge) permute decodes to -1
    return jnp.concatenate([p, (i + 1)[:, None].astype(jnp.float32)],
                           axis=1)


def _unpack(buf: Array) -> tuple[Array, Array]:
    i = buf[:, 3].astype(jnp.int32) - 1
    p = jnp.where((i >= 0)[:, None], buf[:, :3], PARK_SENTINEL)
    return p, i


def _neighbor_perms(n_slabs: int):
    right = [(i, i + 1) for i in range(n_slabs - 1)]
    left = [(i + 1, i) for i in range(n_slabs - 1)]
    return right, left


def _with_halo(layout: SlabLayout, pts: Array, ids: Array, sidx: Array,
               slab_axis: str) -> tuple[Array, Array, Array]:
    """O(surface) halo exchange: each slab ships the rows within ``halo``
    of its two faces to the spatial neighbors (``ppermute``) and returns
    the halo-extended ``(all_p [P + 2H, 3], all_i [P + 2H], overflow)``.
    """
    slab_lo, slab_hi = layout.slab_bounds(sidx)
    valid = ids >= 0
    # domain-edge faces have no neighbor: nothing to ship, and points
    # piling against the domain boundary must not trip the halo cap
    has_left = sidx > 0
    has_right = sidx < layout.n_slabs - 1
    near_l = valid & (pts[:, 0] - slab_lo <= layout.halo) & has_left
    near_r = valid & (slab_hi - pts[:, 0] <= layout.halo) & has_right
    send_l_p, send_l_i, n_l = _select_rows(pts, ids, near_l,
                                           layout.halo_cap)
    send_r_p, send_r_i, n_r = _select_rows(pts, ids, near_r,
                                           layout.halo_cap)
    ovf = (jnp.maximum(n_l - layout.halo_cap, 0)
           + jnp.maximum(n_r - layout.halo_cap, 0))
    right_perm, left_perm = _neighbor_perms(layout.n_slabs)
    from_left = jax.lax.ppermute(_pack(send_r_p, send_r_i), slab_axis,
                                 right_perm)
    from_right = jax.lax.ppermute(_pack(send_l_p, send_l_i), slab_axis,
                                  left_perm)
    halo_l_p, halo_l_i = _unpack(from_left)
    halo_r_p, halo_r_i = _unpack(from_right)
    all_p = jnp.concatenate([pts, halo_l_p, halo_r_p], axis=0)
    all_i = jnp.concatenate([ids, halo_l_i, halo_r_i], axis=0)
    return all_p, all_i, ovf


def _migrate(layout: SlabLayout, pts: Array, ids: Array, sidx: Array,
             slab_axis: str) -> tuple[Array, Array, Array, Array]:
    """Cross-boundary particle migration (static per-face caps).

    Rows whose position left the slab travel to the adjacent slab via
    ``ppermute`` and merge into free rows there. Returns
    ``(pts', ids', n_migrated, overflow)`` — overflow is nonzero when a
    face cap overflowed, an arrival found no free row, or a row tried to
    hop more than one slab in a single step; all three mean the layout's
    static headroom is exhausted and trigger the host re-route fallback.
    """
    m_cap = layout.migrate_cap
    valid = ids >= 0
    tgt = layout.slab_of(pts[:, 0])
    delta = jnp.where(valid, tgt - sidx, 0)
    go_l = delta < 0
    go_r = delta > 0
    far = jnp.sum((jnp.abs(delta) > 1).astype(jnp.int32))

    send_l_p, send_l_i, n_l = _select_rows(pts, ids, go_l, m_cap)
    send_r_p, send_r_i, n_r = _select_rows(pts, ids, go_r, m_cap)
    ovf = (jnp.maximum(n_l - m_cap, 0) + jnp.maximum(n_r - m_cap, 0)
           + far)

    # vacate every mover's row (under overflow some movers are dropped —
    # the flag forces a full host re-route, so the state is discarded)
    gone = go_l | go_r
    pts1 = jnp.where(gone[:, None], PARK_SENTINEL, pts)
    ids1 = jnp.where(gone, -1, ids)

    right_perm, left_perm = _neighbor_perms(layout.n_slabs)
    from_left = jax.lax.ppermute(_pack(send_r_p, send_r_i), slab_axis,
                                 right_perm)
    from_right = jax.lax.ppermute(_pack(send_l_p, send_l_i), slab_axis,
                                  left_perm)
    in_p_l, in_i_l = _unpack(from_left)
    in_p_r, in_i_r = _unpack(from_right)
    in_p = jnp.concatenate([in_p_l, in_p_r], axis=0)        # [2M, 3]
    in_i = jnp.concatenate([in_i_l, in_i_r], axis=0)
    arriving = in_i >= 0

    # merge arrivals into the first free rows (stable order): the k-th
    # ARRIVAL (not the k-th buffer slot — right-neighbor arrivals sit in
    # the second half of the buffer) takes the k-th free row
    free = ids1 < 0
    n_free = jnp.sum(free.astype(jnp.int32))
    free_rows = jnp.argsort(jnp.where(free, 0, 1), stable=True)
    rank = jnp.cumsum(arriving.astype(jnp.int32)) - 1     # [2M]
    ok = arriving & (rank < n_free)
    # accepted arrivals target distinct free rows; everything else is
    # routed to the out-of-range row and scatter-dropped (a shared
    # in-range dummy would race accepted writes under duplicate indices)
    n_rows = ids1.shape[0]
    dest = jnp.where(ok, free_rows[jnp.clip(rank, 0, n_rows - 1)],
                     n_rows)
    ovf = ovf + jnp.sum(arriving.astype(jnp.int32)) \
        - jnp.sum(ok.astype(jnp.int32))
    pts2 = pts1.at[dest].set(in_p, mode="drop")
    ids2 = ids1.at[dest].set(in_i, mode="drop")
    n_migrated = n_l + n_r
    return pts2, ids2, n_migrated, ovf


# ---------------------------------------------------------------------------
# sharded one-shot query (ShardedIndex / shard_scene)
# ---------------------------------------------------------------------------

def _local_query_fn(layout: SlabLayout, params: SearchParams,
                    opts: SearchOpts, slab_axis: str):
    """Per-slab body of the sharded query: halo exchange -> build the
    slab's NeighborIndex on the shared spec (per-slab origin) ->
    ``api.query`` -> local row -> global id."""
    spec = layout.spec

    def local_fn(pts, ids, qs):
        pts, ids, qs = pts[0], ids[0], qs[0, 0]
        sidx = jax.lax.axis_index(slab_axis)
        origin = layout.origin_of(sidx)
        all_p, all_i, _ovf = _with_halo(layout, pts, ids, sidx, slab_axis)
        index = api.build_index(all_p, params, opts, spec=spec,
                                origin=origin)
        res = api.query(index, qs)
        gidx = jnp.where(res.indices >= 0,
                         all_i[jnp.clip(res.indices, 0)], -1)
        d2 = jnp.where(gidx >= 0, res.distances2, jnp.inf)
        cnt = jnp.sum((gidx >= 0).astype(jnp.int32), axis=-1)
        return gidx[None, None], d2[None, None], cnt[None, None]

    return local_fn


# LRU-bounded: every distinct layout (i.e. every one-shot decomposition of
# a fresh point set) compiles its own program; unbounded growth would pin
# every compiled schedule a long-lived process ever built
_QUERY_FN_CACHE: collections.OrderedDict = collections.OrderedDict()
_QUERY_FN_CACHE_MAX = 16


def make_sharded_query(mesh: Mesh, layout: SlabLayout,
                       params: SearchParams, opts: SearchOpts,
                       slab_axis: str = "data",
                       query_axis: str | None = None):
    """Jitted end-to-end sharded query program over ``mesh``:
    ``(pts [S,P,3], ids [S,P], queries [Nq,3]) -> (oi, od, oc, qovf)`` —
    traced query routing, ``shard_map(api.query)`` with halo exchange, and
    the traced inverse scatter, as ONE compiled program. Cached by
    ``(mesh, layout, params, opts, axes)``.
    """
    opts = dataclasses.replace(opts, mask_parked=True)
    key = (mesh, layout, params, opts, slab_axis, query_axis)
    hit = _QUERY_FN_CACHE.get(key)
    if hit is not None:
        _QUERY_FN_CACHE.move_to_end(key)
        return hit

    local_fn = _local_query_fn(layout, params, opts, slab_axis)
    q_spec = (P(slab_axis, query_axis) if query_axis is not None
              else P(slab_axis))
    fn = jax.shard_map(local_fn, mesh=mesh,
                    in_specs=(P(slab_axis), P(slab_axis), q_spec),
                    out_specs=(q_spec, q_spec, q_spec), check_vma=False)

    @jax.jit
    def run(pts, ids, queries):
        qs, qid, qovf = route_queries(layout, queries)
        gidx, d2, cnt = fn(pts, ids, qs)
        oi, od, oc = unroute_results(qid, gidx, d2, cnt,
                                     queries.shape[0])
        return oi, od, oc, qovf

    _QUERY_FN_CACHE[key] = run
    if len(_QUERY_FN_CACHE) > _QUERY_FN_CACHE_MAX:
        _QUERY_FN_CACHE.popitem(last=False)
    return run


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardedIndex:
    """A scene decomposed into device-resident slabs (a registered pytree:
    the routed buffers are the leaves; layout/mesh/params are aux).

    Built by :func:`shard_scene`; ``query(queries)`` runs the one-program
    sharded search (traced route -> shard_map(api.query) with halo
    exchange -> traced unroute) and returns results in query order with
    GLOBAL point indices.
    """

    layout: SlabLayout
    params: SearchParams
    opts: SearchOpts
    mesh: Mesh
    slab_axis: str
    query_axis: str | None
    pts: Array              # [S, P, 3] owned rows (sentinel-parked pads)
    ids: Array              # [S, P] global ids (-1 pads)

    def tree_flatten(self):
        return ((self.pts, self.ids),
                (self.layout, self.params, self.opts, self.mesh,
                 self.slab_axis, self.query_axis))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        layout, params, opts, mesh, slab_axis, query_axis = aux
        pts, ids = leaves
        return cls(layout=layout, params=params, opts=opts, mesh=mesh,
                   slab_axis=slab_axis, query_axis=query_axis, pts=pts,
                   ids=ids)

    def query(self, queries) -> SearchResult:
        queries = jnp.asarray(queries, jnp.float32)
        fn = make_sharded_query(self.mesh, self.layout, self.params,
                                self.opts, self.slab_axis,
                                self.query_axis)
        oi, od, oc, qovf = fn(self.pts, self.ids, queries)
        if int(qovf):
            raise RuntimeError(
                f"query routing overflowed the layout's query_cap="
                f"{self.layout.query_cap} ({int(qovf)} dropped); re-plan "
                "with shard_scene(..., queries=...) sized for this batch")
        return SearchResult(indices=oi, distances2=od, counts=oc)


def shard_scene(points, params: SearchParams, *,
                mesh: Mesh | None = None, n_slabs: int | None = None,
                opts: SearchOpts = SearchOpts(),
                shopts: ShardOpts = ShardOpts(),
                queries=None, cell_size: float | None = None,
                slab_axis: str = "data",
                query_axis: str | None = None) -> ShardedIndex:
    """Decompose a scene into device-resident slabs.

    Host work is the layout *planning* only (:func:`plan_layout`); the
    routing itself is the traced padded scatter. ``queries`` optionally
    sizes the query routing caps; ``mesh`` defaults to a 1-D slab mesh
    over all local devices (``launch.mesh.make_slab_mesh``).
    """
    if mesh is None:
        from ..launch.mesh import make_slab_mesh
        mesh = make_slab_mesh(n_slabs, axis=slab_axis)
    n_slabs = int(mesh.shape[slab_axis])
    n_qsplit = int(mesh.shape[query_axis]) if query_axis else 1
    opts = dataclasses.replace(opts, mask_parked=True)
    pts_np = np.asarray(jax.device_get(jnp.asarray(points, jnp.float32)))
    layout = plan_layout(pts_np, params, n_slabs, n_qsplit=n_qsplit,
                         queries=queries, shopts=shopts,
                         cell_size=cell_size)
    pts, ids, ovf = route_points(layout, jnp.asarray(points, jnp.float32))
    if int(ovf):        # cannot happen for a layout planned over `points`
        raise RuntimeError("slab routing overflowed its own layout")
    return ShardedIndex(layout=layout, params=params, opts=opts, mesh=mesh,
                        slab_axis=slab_axis, query_axis=query_axis,
                        pts=pts, ids=ids)


# ---------------------------------------------------------------------------
# slab-resident distributed session
# ---------------------------------------------------------------------------

def _local_init_fn(layout: SlabLayout, params: SearchParams,
                   opts: SearchOpts, margin: int, slab_axis: str):
    """Per-slab session bootstrap: halo exchange, index build on the shared
    spec, and the initial per-slab plan capture."""

    def local_fn(pts, ids):
        pts, ids = pts[0], ids[0]
        sidx = jax.lax.axis_index(slab_axis)
        origin = layout.origin_of(sidx)
        all_p, all_i, _ovf = _with_halo(layout, pts, ids, sidx, slab_axis)
        index = api.build_index(all_p, params, opts, spec=layout.spec,
                                origin=origin)
        plan = api.plan_query(index, pts, margin=margin)
        # pts/ids/mig pass THROUGH the shard_map so every piece of session
        # state carries the same NamedSharding the step program's outputs
        # will have — otherwise the second step recompiles on the sharding
        # change alone
        return jax.tree.map(lambda x: x[None],
                            (pts, ids, index, plan, jnp.int32(0)))

    return local_fn


def _local_step_fn(layout: SlabLayout, params: SearchParams,
                   opts: SearchOpts, thr2: float, margin: int,
                   slab_axis: str):
    """Per-slab body of the fused sharded step:

    gather (rows' new positions from the replicated frame, by resident
    global id — no routing) -> migrate -> halo exchange -> update_index ->
    per-slab staleness ``lax.cond`` (replan | replay) -> execute_plan ->
    global ids. Entirely device-resident; the caps raise flags instead of
    host decisions.
    """

    def local_fn(pts, ids, index, plan, mig_total, pg):
        pts, ids = pts[0], ids[0]
        index, plan = jax.tree.map(lambda x: x[0], (index, plan))
        mig_total = mig_total[0]
        sidx = jax.lax.axis_index(slab_axis)

        valid = ids >= 0
        new = jnp.where(valid[:, None], pg[jnp.clip(ids, 0)],
                        PARK_SENTINEL)
        pts2, ids2, n_mig, mig_ovf = _migrate(layout, new, ids, sidx,
                                              slab_axis)
        all_p, all_i, halo_ovf = _with_halo(layout, pts2, ids2, sidx,
                                            slab_axis)

        index2, stats = api.update_index(index, all_p)
        bad = ((stats.overflow > 0) | (stats.oob > 0) | (mig_ovf > 0)
               | (halo_ovf > 0))
        stale = stats.max_disp2 > jnp.float32(thr2)

        q = pts2                       # self-query: owned rows

        def replan(_):
            return api.plan_query(index2, q, margin=margin), all_p

        def replay(_):
            return plan, index2.anchor_points

        plan2, anchor2 = jax.lax.cond(stale, replan, replay, None)
        index3 = index2.with_anchor(anchor2)
        res = api.execute_plan(index3, q, plan2)
        gidx = jnp.where(res.indices >= 0,
                         all_i[jnp.clip(res.indices, 0)], -1)
        d2 = jnp.where(gidx >= 0, res.distances2, jnp.inf)
        cnt = jnp.sum((gidx >= 0).astype(jnp.int32), axis=-1)
        flags = (stale.astype(jnp.int32) * _FLAG_REPLANNED
                 + bad.astype(jnp.int32) * _FLAG_EXHAUSTED)
        # per-slab telemetry, split by cross-slab reduction: tel_i slot 0
        # (flags) reduces by max, the rest by sum — overflow, oob, rows
        # migrated this step, halo volume (occupied halo rows received),
        # and the per-ladder-level occupancy histogram. tel_f is the
        # max-reduced staleness statistic. step_prog reduces + packs them
        # into the ONE per-step transfer (obs/device.py).
        halo_vol = jnp.sum((all_i[pts2.shape[0]:] >= 0).astype(jnp.int32))
        occ = obs.level_occupancy(plan2.tile_levels, len(plan2.ladder))
        tel_i = jnp.concatenate([
            jnp.stack([flags, stats.overflow.astype(jnp.int32),
                       stats.oob.astype(jnp.int32),
                       n_mig.astype(jnp.int32), halo_vol]), occ])
        tel_f = stats.max_disp2.reshape(1)
        out_state = jax.tree.map(lambda x: x[None],
                                 (index3, plan2, mig_total + n_mig))
        return (pts2[None], ids2[None], *out_state, gidx[None], d2[None],
                cnt[None], tel_i[None], tel_f[None])

    return local_fn


class ShardedSession:
    """Slab-resident distributed :class:`~.dynamic.SimulationSession`.

    >>> sess = ShardedSession(points, SearchParams(radius=0.1, k=8),
    ...                       mesh=make_slab_mesh(4))
    >>> for _ in range(steps):
    ...     res = sess.step(points)          # global order, global ids
    ...     points = integrate(points, res)

    ``step(points)`` takes the frame's positions in GLOBAL id order
    [N, 3]; each slab gathers its own rows' new positions by resident id
    (a traced gather from the replicated frame — no routing), migrates
    rows across faces, halo-exchanges, incrementally re-bins its frozen
    local grid, and replays or replans its captured plan on device.
    Results are oracle-equal to a single-device session on the identical
    trajectory. The ONLY host-side routing events are construction and
    the (rare) exhausted-layout fallback — counted in
    ``stats()["host_routings"]``; steady-state steps fetch one packed
    flags scalar, nothing else.
    """

    def __init__(self, points, params: SearchParams,
                 opts: SearchOpts = SearchOpts(),
                 sopts: SessionOpts = SessionOpts(),
                 shopts: ShardOpts = ShardOpts(),
                 mesh: Mesh | None = None, n_slabs: int | None = None,
                 slab_axis: str = "data"):
        validate_session_opts(sopts)
        if mesh is None:
            from ..launch.mesh import make_slab_mesh
            mesh = make_slab_mesh(n_slabs, axis=slab_axis)
        self._mesh = mesh
        self._axis = slab_axis
        self._n_slabs = int(mesh.shape[slab_axis])
        self.params = params
        self.opts = dataclasses.replace(opts, mask_parked=True)
        self.sopts = sopts
        self.shopts = shopts
        self._boost = 1.0
        # lifecycle counters + step-latency histogram in the unified
        # registry (repro.obs)
        self._metrics = obs.metric_set("sharded_session")
        self.last_flags = 0
        self.last_compiles = 0
        self._t_last = 0.0
        pts_np = np.asarray(jax.device_get(jnp.asarray(points,
                                                       jnp.float32)))
        self._n = int(pts_np.shape[0])
        self._reroute(pts_np)

    # -- surface ------------------------------------------------------------

    @property
    def layout(self) -> SlabLayout:
        return self._layout

    @property
    def spec(self) -> GridSpec:
        return self._layout.spec

    def stats(self) -> dict:
        counters = dict(steps=0, fast_steps=0, replans=0, reroutes=0,
                        host_routings=0, host_syncs=0, compiles=0)
        counters.update(self._metrics.counters())
        return {
            **counters,
            "migrated": int(jnp.sum(self._mig_total)),
            "last_flags": int(self.last_flags),
            "last_compiles": int(self.last_compiles),
            "boost": float(self._boost),
            "t_step": float(self._t_last),   # wall time of the last step
        }

    # -- lifecycle ----------------------------------------------------------

    def _reroute(self, pts_np: np.ndarray) -> None:
        """Host fallback (and bootstrap): re-plan the layout from current
        positions, re-route every row, rebuild the per-slab indexes, and
        recapture the per-slab plans. The ONLY host routing in the
        session's life — counted, and asserted zero across steady-state
        steps in the tests."""
        self._metrics.count("host_routings")
        layout = plan_layout(pts_np, self.params, self._n_slabs,
                             shopts=self.shopts, boost=self._boost)
        self._layout = layout
        margin = int(self.sopts.reuse_margin_cells)
        thr2 = float((self.sopts.displacement_frac
                      * layout.spec.cell_size) ** 2)
        pts, ids, ovf = route_points(layout, jnp.asarray(pts_np))
        if int(ovf):    # pragma: no cover — caps planned from same data
            raise RuntimeError("slab routing overflowed its own layout")

        ax = self._axis
        init_fn = jax.shard_map(
            _local_init_fn(layout, self.params, self.opts, margin, ax),
            mesh=self._mesh, in_specs=(P(ax), P(ax)),
            out_specs=(P(ax),) * 5, check_vma=False)
        (self._pts, self._ids, self._index, self._plan,
         self._mig_total) = jax.jit(init_fn)(pts, ids)

        local = _local_step_fn(layout, self.params, self.opts, thr2,
                               margin, ax)
        step_inner = jax.shard_map(
            local, mesh=self._mesh,
            in_specs=(P(ax), P(ax), P(ax), P(ax), P(ax), P()),
            out_specs=(P(ax),) * 10, check_vma=False)
        n = self._n

        def step_prog(pts, ids, index, plan, mig_total, pg):
            out = step_inner(pts, ids, index, plan, mig_total, pg)
            (pts2, ids2, index3, plan2, mig2, gidx, d2, cnt,
             tel_i, tel_f) = out
            # owned rows ARE the self-queries, so their global ids are the
            # routing ids and the one-shot inverse scatter applies as-is
            oi, od, oc = unroute_results(ids2, gidx, d2, cnt, n)
            # reduce the per-slab telemetry (slot-wise: flags by max, the
            # counters by sum, staleness by max) and pack the one per-step
            # transfer
            sums = jnp.sum(tel_i[:, 1:], axis=0)
            telem = obs.pack_step_telemetry(
                jnp.max(tel_i[:, 0]), overflow=sums[0], oob=sums[1],
                max_disp2=jnp.max(tel_f), occupancy=sums[4:],
                migrated=sums[2], halo=sums[3])
            return (pts2, ids2, index3, plan2, mig2, oi, od, oc, telem)

        # per-reroute jit: a re-route changes the (static) layout, so the
        # old variants are released with the old program
        self._step_fn = jax.jit(step_prog)

    def step(self, points) -> SearchResult:
        """Advance every slab to the frame ``points`` [N, 3] (global id
        order) and self-query. One fused device program; the packed
        telemetry vector (flags + device counters, obs/device.py) is the
        only per-step host transfer."""
        m = self._metrics
        compiles0 = obs.thread_compiles()
        with obs.span("step", slabs=self._n_slabs) as sp_step:
            pg = jnp.asarray(points, jnp.float32)
            with obs.span("plan"):
                if pg.shape != (self._n, 3):
                    # particle count changed: the layout's static caps are
                    # stale
                    self._n = int(pg.shape[0])
                    self._reroute(np.asarray(jax.device_get(pg)))
            out, tel = self._dispatch_synced(pg)
            fl = tel["flags"]

            if fl & _FLAG_EXHAUSTED:
                if not self.shopts.auto_reroute:
                    raise RuntimeError(
                        "sharded layout exhausted (migration/halo/capacity/"
                        "bounds) and auto_reroute is disabled")
                # respec-style fallback with hysteresis: geometrically more
                # headroom per re-route, so adversarial drift costs O(log
                # frames) re-routes
                m.count("reroutes")
                self._boost = min(self._boost * self.shopts.reroute_growth,
                                  self.shopts.reroute_boost_max)
                self._reroute(np.asarray(jax.device_get(pg)))
                out, tel = self._dispatch_synced(pg)
                fl = tel["flags"]
                if fl & _FLAG_EXHAUSTED:        # pragma: no cover
                    raise RuntimeError(
                        "re-route failed to absorb the scene")

            (self._pts, self._ids, self._index, self._plan,
             self._mig_total, oi, od, oc, _telem) = out
            self.last_flags = fl
            m.count("steps")
            if fl & _FLAG_REPLANNED:
                m.count("replans")
            else:
                m.count("fast_steps")
            m.count("migrated_rows", tel["migrated"])
            m.count("halo_rows", tel["halo"])
            m.count("overflow_points", tel["overflow"])
            m.count("oob_points", tel["oob"])
            for lvl, occ in enumerate(tel["occupancy"]):
                m.count(f"level_occ_{lvl}", occ)
            m.gauge("staleness_disp2", tel["max_disp2"])
            m.gauge("boost", self._boost)
            self.last_compiles = obs.thread_compiles() - compiles0
            m.count("compiles", self.last_compiles)
            sp_step.set(compiles=self.last_compiles)
        self._t_last = sp_step.duration
        m.observe("step_s", self._t_last)
        return SearchResult(indices=oi, distances2=od, counts=oc)

    def _dispatch(self, pg):
        return self._step_fn(self._pts, self._ids, self._index,
                             self._plan, self._mig_total, pg)

    def _dispatch_synced(self, pg):
        """Launch the fused sharded step and fetch the packed telemetry
        vector — still ONE blocking transfer per step; a compile of the
        step program shows as a ``compile`` span nested under the launch
        (``obs/compiles.py``)."""
        with obs.span("launch"):
            out = self._dispatch(pg)
        with obs.span("sync"):
            tel = obs.unpack_step_telemetry(
                np.asarray(jax.device_get(out[-1])))
        self._metrics.count("host_syncs")
        return out, tel


__all__ = [
    "STATIC_SCENE_OPTS",
    "ShardOpts",
    "ShardedIndex",
    "ShardedSession",
    "SlabLayout",
    "make_sharded_query",
    "plan_layout",
    "route_points",
    "route_queries",
    "shard_scene",
    "unroute_results",
]
