"""jit'd wrappers around the Pallas kernels + the drop-in search path.

``window_search_pallas`` matches ``core.search.window_search``'s signature
so `SearchOpts(use_pallas=True)` swaps the jnp tile path for the fused
kernel path. The kernel mode follows the backend (``kernels.
resolve_interpret``): the interpreter off-TPU, Mosaic on a TPU. Mosaic
refuses the fused kNN kernel's in-kernel gathers, so on a TPU backend
``build_index`` refuses the fused path up front (:func:`check_fused_path`)
instead of interpreting it or taking the jnp path behind the caller's back.

Tile-window semantics: each Morton-contiguous query tile gathers ONE shared
cell window (the union of its members' windows) — that is the coherence
payoff of the paper's section-4 scheduling: neighbors of adjacent queries
come from the same VMEM-resident candidate tile.

Single-program schedule (DESIGN.md section 3): the whole
anchor→gather→distance→top-K pipeline is traced JAX — no host metadata in
the loop. Window *shapes* must still be static, so the data-dependent tile
spread is bounded by a host-static ladder (:func:`segment_levels`): the
launch-signature windows of ``partition.launch_signatures`` extended with
geometrically growing escalation sizes capped at the grid dims (the
whole-grid window always fits, so assignment is total). Each tile is
assigned, on device, the smallest ladder entry that covers the union of
its members' windows; per entry ONE masked :func:`~.knn_tile.knn_tile_anchored`
launch runs over the (level, Morton)-contiguous tile order, with off-level
tiles predicated off inside the kernel. Anchors are a traced per-tile
min/max reduction over the queries' cell coords, delivered to the kernel
by scalar prefetch.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp

from .distance_tile import distance_tile
from .knn_tile import knn_tile, knn_tile_anchored
from .range_tile import range_count
from .update_tile import bin_disp_tile

FUSED_PATH_REFUSAL = (
    "SearchOpts(use_pallas=True) cannot run on a TPU backend: the fused kNN "
    "kernel gathers inside the kernel (jnp.take of candidate rows from the "
    "coordinate table, and of candidate ids from the flattened dense grid "
    "in knn_tile_anchored), and the TPU Pallas compiler (Mosaic) refuses "
    "both gathers. Use the default jnp path (use_pallas=False); the fused "
    "path needs a cell-sorted layout with DMA window fetches first "
    "(DESIGN.md section 2).")


def check_fused_path() -> None:
    """Refuse the fused search path where it cannot compile (a TPU
    backend), naming the refused in-kernel gathers. Called by
    ``build_index`` for ``SearchOpts(use_pallas=True)``; off-TPU the
    kernels run in the Pallas interpreter and the path is allowed."""
    if jax.default_backend() == "tpu":
        raise NotImplementedError(FUSED_PATH_REFUSAL)


@lru_cache(maxsize=512)
def segment_levels(
    ladder: tuple,              # ((w, skip), ...) launch signatures
    dims: tuple,                # grid dims (static)
) -> tuple:
    """Host-static Pallas launch ladder: ``((ws3, skip), ...)`` entries,
    ascending window volume.

    Base sizes come from the launch-signature ladder (``2w+1`` per
    signature — the paper's per-partition AABB widths); because a
    Morton-contiguous query *tile* spans more than one cell, a tile's
    shared window can need more than its signature's ``2w+1``, so the size
    set is extended with geometrically growing escalations capped at the
    grid dims. The final whole-grid entry always fits, which makes the
    on-device first-fit assignment total. Every size is crossed with the
    skip flags present in the signature ladder: escalating a
    skip-sphere-test tile to a larger window stays exact (the megacell
    that held >= K in-sphere points is still inside the window, so the
    streamed top-K distances are bounded by its K-th distance).
    """
    sizes = sorted({2 * int(w) + 1 for w, _ in ladder})
    dmax = max(dims)
    s = sizes[-1]
    while s < dmax:
        # jump straight to the whole-grid size once doubling would land
        # within a cell of it — two near-identical top rungs would double
        # the cost of the most expensive tier for nothing
        s = dmax if 2 * s + 1 >= dmax - 1 else 2 * s + 1
        sizes.append(s)
    skips = sorted({bool(sk) for _, sk in ladder})
    entries, seen = [], set()
    for s in sizes:
        ws = tuple(min(s, d) for d in dims)
        for sk in skips:
            if (ws, sk) not in seen:
                seen.add((ws, sk))
                entries.append((ws, sk))
    return tuple(entries)


def assign_tile_levels(
    qcells: jax.Array,          # [n_tiles, tile, 3] i32 member cell coords
    tile_levels: jax.Array,     # [n_tiles] i32 index into ``ladder``
    ladder: tuple,
    entries: tuple,             # segment_levels(ladder, dims)
    dims: tuple,
) -> tuple[jax.Array, jax.Array]:
    """Traced per-tile (launch level, window anchor) assignment.

    The anchor/spread computation that used to be host ``np`` metadata:
    per tile, the min/max cell coords of its members plus the signature
    window radius give the union window; the tile takes the smallest
    ladder entry (matching skip flag) that covers it, clamped to the grid.
    Returns ``(plevel [n_tiles], anchors [n_tiles, 3])``.
    """
    dims_a = jnp.asarray(dims, jnp.int32)
    lo = jnp.min(qcells, axis=1)                          # [n_tiles, 3]
    hi = jnp.max(qcells, axis=1)
    lvl = jnp.clip(tile_levels, 0, len(ladder) - 1)
    w_arr = jnp.asarray([int(w) for w, _ in ladder], jnp.int32)
    s_arr = jnp.asarray([bool(s) for _, s in ladder], jnp.bool_)
    tile_w = w_arr[lvl][:, None]                          # [n_tiles, 1]
    tile_skip = s_arr[lvl]
    need = jnp.minimum(hi - lo + 1 + 2 * tile_w, dims_a)  # per-axis cells

    # first fit, ascending volume; the defensive fallback mirrors
    # signature_levels: never land a no-skip tile on a skip entry (eliding
    # the r^2 filter is only sound for true megacell signatures)
    no_skip = [i for i, (_, sk) in enumerate(entries) if not sk]
    fb = no_skip[-1] if no_skip else len(entries) - 1
    plevel = jnp.full(tile_skip.shape, fb, jnp.int32)
    assigned = jnp.zeros(tile_skip.shape, bool)
    for e, (ws, sk) in enumerate(entries):
        fits = (jnp.all(need <= jnp.asarray(ws, jnp.int32), axis=-1)
                & (tile_skip == sk))
        hit = jnp.logical_not(assigned) & fits
        plevel = jnp.where(hit, jnp.int32(e), plevel)
        assigned = assigned | hit

    ws_table = jnp.asarray([ws for ws, _ in entries], jnp.int32)
    ws_tile = ws_table[plevel]                            # [n_tiles, 3]
    anchors = jnp.clip(lo - tile_w, 0, dims_a - ws_tile).astype(jnp.int32)
    return plevel, anchors


def window_search_segmented(
    grid,                 # core.types.CellGrid
    points: jax.Array,
    queries: jax.Array,   # [Nq, 3], Nq % tile == 0 (caller pads)
    spec,                 # core.types.GridSpec
    ladder: tuple,        # ((w, skip), ...) launch signatures
    tile_levels: jax.Array,   # [Nq // tile] i32 per-tile signature level
    radius: float,
    k: int,
    tile: int,
    interpret: bool | None = None,
    origin: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Level-segmented fused search: one masked kernel launch per ladder
    entry over the (level, Morton)-ordered query tiles (pure, traceable).

    Returns ``(d2 [Nq, k], idx [Nq, k], cnt [Nq])`` in the scheduled query
    order (``window_tile_search``'s convention). ``origin`` overrides the
    static spec origin for the cell lookup (sharded slabs); the kernel
    itself works purely in cell space, so only the anchor computation here
    needs the frame.
    """
    nq = queries.shape[0]
    n_tiles = nq // tile
    assert n_tiles * tile == nq, (nq, tile)
    dims, cap = spec.dims, spec.capacity
    entries = segment_levels(tuple(ladder), tuple(dims))
    qc = spec.cell_of(queries, origin).reshape(n_tiles, tile, 3)
    plevel, anchors = assign_tile_levels(qc, tile_levels, tuple(ladder),
                                         entries, dims)
    dense_flat = grid.dense.reshape(-1)
    out_d2 = jnp.full((nq, k), jnp.inf, jnp.float32)
    out_idx = jnp.full((nq, k), -1, jnp.int32)
    for e, (ws, sk) in enumerate(entries):
        def _launch(carry, e=e, ws=ws, sk=sk):
            out_d2, out_idx = carry
            d2_e, idx_e = knn_tile_anchored(
                queries, points, dense_flat, anchors, plevel,
                level=e, ws=ws, dims=tuple(dims), cap=cap, k=k,
                r2=float(radius) ** 2, skip_test=sk, tq=tile,
                interpret=interpret)
            # off-level rows came back neutral; one select folds it in
            rows = jnp.repeat(plevel == e, tile)[:, None]
            return (jnp.where(rows, d2_e, out_d2),
                    jnp.where(rows, idx_e, out_idx))

        # most ladder entries own zero tiles on a typical query (the
        # escalation rungs exist for totality): skip their launches at
        # runtime with shapes still static. Under vmap the cond lowers to
        # select-and-execute-both — no worse than the unconditional launch
        with jax.named_scope(f"repro.launch.level{e}_w{ws}"):
            out_d2, out_idx = jax.lax.cond(
                jnp.any(plevel == e), _launch, lambda c: c,
                (out_d2, out_idx))
    cnt = jnp.sum((out_idx >= 0).astype(jnp.int32), axis=1)
    return out_d2, out_idx, cnt


def window_search_pallas(
    grid,                 # core.types.CellGrid
    points: jax.Array,
    queries: jax.Array,   # [Nq, 3]
    spec,                 # core.types.GridSpec
    w: int,
    radius: float,
    k: int,
    skip_test: bool,
    tile: int = 256,
    origin: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Drop-in fused-path counterpart of ``core.search.window_search``
    (single uniform launch signature). Pure and traceable: anchors are
    computed on device and the ``skip_test`` flag is honored by the kernel
    (sound because each query's megacell stays inside the shared tile
    window — see ``segment_levels``)."""
    nq = queries.shape[0]
    npad = (-nq) % tile
    if npad:
        # edge-replicate to the tile multiple (same padding discipline as
        # window_search and the executor's selections): padded rows repeat
        # the last real query, so they cannot distort the shared tile-window
        # anchors the way zero rows (origin cell) would
        queries = jnp.pad(queries, ((0, npad), (0, 0)), mode="edge")
    n_tiles = (nq + npad) // tile
    ladder = ((int(w), bool(skip_test)),)
    tile_levels = jnp.zeros((n_tiles,), jnp.int32)
    d2, idx, cnt = window_search_segmented(
        grid, points, queries, spec, ladder, tile_levels, radius, k, tile,
        origin=origin)
    return idx[:nq], d2[:nq], cnt[:nq]


__all__ = ["bin_disp_tile", "distance_tile", "knn_tile",
           "knn_tile_anchored", "range_count", "segment_levels",
           "assign_tile_levels", "window_search_segmented",
           "window_search_pallas", "check_fused_path"]
