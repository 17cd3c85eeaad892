"""Uniform cell grid build — the TPU-native acceleration structure.

Replaces the paper's BVH build (which on the GPU is opaque, linear in the
number of AABBs, Fig. 15). Our build is a bin + scatter, also linear in N,
and — like the paper's per-partition BVHs — can be *re-fitted* with a
partition-specific cell size (see partition.py / bundle.py) to shrink the
candidate window quantization overfetch.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .types import (PARK_THRESHOLD, Array, CellGrid, GridSpec, UpdateStats)


def parked_mask(points: Array) -> Array:
    """Rows parked at the slab-padding sentinel (``types.PARK_SENTINEL``):
    any coordinate with magnitude >= ``PARK_THRESHOLD`` marks the row as an
    empty fixed-capacity slot, not a point (core/shards.py)."""
    return jnp.any(jnp.abs(points) >= jnp.float32(PARK_THRESHOLD), axis=-1)


def choose_grid_spec(
    points: np.ndarray,
    radius: float,
    *,
    cell_size: float | None = None,
    max_dim: int = 256,
    capacity: int | None = None,
    capacity_slack: float = 1.0,
    domain_margin: float = 0.0,
) -> GridSpec:
    """Host-side planning of the static grid parameters.

    Mirrors the paper's "smallest cell size allowed by the GPU memory
    capacity" policy (section 5.1): default cell edge = search radius (so the
    full-radius window is 3^3 cells), refined down while the dense array stays
    within ``max_dim`` per axis. ``capacity`` is the max cell occupancy, read
    from the data exactly like JAX-MD capacity planning; the build reports
    overflow if exceeded (asserted zero in tests).

    ``domain_margin`` pads the bounding box by that many world units on every
    side before sizing — dynamic scenes (``core/dynamic.py``) use it so points
    can drift without leaving the frozen grid. Degenerate extents (identical
    or coplanar point sets) are clamped to ``radius`` per axis so cells never
    collapse to zero size and dims stay finite.
    """
    points = np.asarray(points, dtype=np.float32)
    lo = points.min(axis=0) - domain_margin
    hi = points.max(axis=0) + domain_margin
    extent = np.maximum(hi - lo, max(float(radius), 1e-6))
    if cell_size is None:
        # cells finer than the radius (paper: smallest cell size memory
        # allows) so megacells exist: w_sph >= 1 needs cell <= r/(2*sqrt(3))
        cell_size = float(max(radius / 4.0, extent.max() / max_dim))
    # pad the domain by one cell on each side so window clamping at the
    # boundary never loses a candidate cell
    origin = lo - cell_size
    dims = tuple(int(d) for d in np.ceil(extent / cell_size).astype(int) + 3)
    dims = tuple(min(int(d), max_dim + 3) for d in dims)
    if capacity is None:
        cc = np.floor((points - origin) / cell_size).astype(np.int64)
        cc = np.clip(cc, 0, np.asarray(dims) - 1)
        flat = (cc[:, 0] * dims[1] + cc[:, 1]) * dims[2] + cc[:, 2]
        occ = np.bincount(flat, minlength=dims[0] * dims[1] * dims[2])
        capacity = int(max(1, np.ceil(occ.max() * capacity_slack)))
    return GridSpec(
        origin=tuple(float(o) for o in origin),
        cell_size=float(cell_size),
        dims=dims,
        capacity=int(capacity),
    )


@partial(jax.jit, static_argnames=("spec",))
def build_cell_grid(points: Array, spec: GridSpec,
                    origin: Array | None = None,
                    valid: Array | None = None) -> CellGrid:
    """Bin ``points`` [N, 3] into the dense fixed-capacity cell list.

    Deterministic scatter: points are ranked within their cell by a stable
    sort over flat cell id, so the slot of each point is its rank among
    same-cell points in input order. Points beyond ``capacity`` are dropped
    and counted in ``overflow``. ``origin`` optionally overrides the static
    spec origin (distributed slabs). ``valid`` [N] optionally drops rows
    from the grid entirely — parked padding slots of the sharded slabs must
    not pollute cell counts/SAT (they would inflate megacell occupancy and
    shrink windows below exactness).
    """
    ccoord = spec.cell_of(points, origin)
    flat = spec.flat_cell(ccoord)
    if valid is not None:
        flat = jnp.where(valid, flat, spec.num_cells)   # scatter-dropped
    return _grid_from_flat(flat, points, spec)


def _grid_from_flat(flat: Array, points: Array, spec: GridSpec) -> CellGrid:
    """Dense grid + coordinate table + counts + SAT from precomputed flat
    cell ids (shared by the static build and the dynamic update path)."""
    n = points.shape[0]
    order = jnp.argsort(flat, stable=True)
    flat_sorted = flat[order]
    # rank within cell = position - first position of this cell id
    first_of_cell = jnp.searchsorted(flat_sorted, flat_sorted, side="left")
    rank_sorted = (jnp.arange(n, dtype=jnp.int32)
                   - first_of_cell.astype(jnp.int32))
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)

    keep = rank < spec.capacity
    dx, dy, dz = spec.dims
    dense = jnp.full((dx * dy * dz, spec.capacity), -1, jnp.int32)
    slot = jnp.where(keep, flat * spec.capacity + rank, dx * dy * dz * spec.capacity)
    dense = (
        dense.reshape(-1)
        .at[slot]
        .set(jnp.arange(n, dtype=jnp.int32), mode="drop")
        .reshape(dx * dy * dz, spec.capacity)
    )
    # the same slots' coordinates, interleaved per cell: the x of its C
    # slots, then their y, then their z (CellGrid.coords); a slot that
    # holds no point keeps the finite 0.0 (dense marks it -1)
    cap = spec.capacity
    base = jnp.where(keep, flat * 3 * cap + rank, 3 * dx * dy * dz * cap)
    coords = (
        jnp.zeros((3 * dx * dy * dz * cap,), jnp.float32)
        .at[jnp.concatenate([base + a * cap for a in range(3)])]
        .set(points.T.reshape(-1).astype(jnp.float32), mode="drop")
        .reshape(dx, dy, dz * 3 * cap)
    )

    # mode="drop": rows routed to the out-of-range id num_cells (invalid /
    # parked slots) contribute to no cell
    counts_full = jnp.zeros((dx * dy * dz,), jnp.int32).at[flat].add(
        1, mode="drop")
    counts = jnp.minimum(counts_full, spec.capacity).reshape(dx, dy, dz)
    overflow = jnp.sum(counts_full - jnp.minimum(counts_full, spec.capacity))

    sat = _summed_area_table(counts)
    return CellGrid(
        spec=spec,
        dense=dense.reshape(dx, dy, dz, spec.capacity),
        coords=coords,
        counts=counts,
        sat=sat,
        overflow=overflow,
    )


# ---------------------------------------------------------------------------
# dynamic-scene incremental update (core/dynamic.py; DESIGN.md section 7)
# ---------------------------------------------------------------------------

def _bin_and_stats(spec: GridSpec, points: Array, anchor_points: Array,
                   origin: Array | None = None,
                   valid: Array | None = None
                   ) -> tuple[Array, Array, Array]:
    """Unclamped binning + motion statistics (jnp path).

    Returns (ccoord [N,3] clipped, oob, max_disp2): ``oob`` counts points
    whose true cell lies outside the frozen grid (clamping them would bin
    them into a wrong border cell, losing exactness — the session respecs
    instead), ``max_disp2`` is the max squared displacement vs the positions
    the current plan was captured at (the temporal-coherence statistic).
    ``origin`` overrides the static spec origin (sharded slabs); ``valid``
    [N] excludes parked padding rows from both statistics (a parked slot is
    not out of bounds, and a parked→parked row contributes 0 displacement —
    while a row whose occupant changed blows the statistic up, which is the
    conservative replan trigger the sharded session relies on).
    """
    o = (jnp.asarray(spec.origin, points.dtype) if origin is None
         else origin.astype(points.dtype))
    c = jnp.floor((points - o) / spec.cell_size).astype(jnp.int32)
    hi = jnp.asarray([d - 1 for d in spec.dims], jnp.int32)
    escaped = jnp.any((c < 0) | (c > hi), axis=-1)
    d2 = jnp.sum((points - anchor_points) ** 2, axis=-1)
    if valid is not None:
        escaped = escaped & valid
        d2 = jnp.where(valid, d2, 0.0)
    oob = jnp.sum(escaped.astype(jnp.int32))
    return jnp.clip(c, 0, hi), oob, jnp.max(d2)


def _update_impl(grid: CellGrid, points: Array, anchor_points: Array,
                 use_pallas: bool, origin: Array | None = None,
                 mask_parked: bool = False):
    spec = grid.spec
    valid = jnp.logical_not(parked_mask(points)) if mask_parked else None
    if use_pallas:
        from ..kernels.update_tile import bin_disp_tile
        ccoord, oob, max_d2 = bin_disp_tile(points, anchor_points, spec,
                                            origin=origin,
                                            mask_parked=mask_parked)
    else:
        ccoord, oob, max_d2 = _bin_and_stats(spec, points, anchor_points,
                                             origin, valid)
    flat = spec.flat_cell(ccoord)
    if valid is not None:
        flat = jnp.where(valid, flat, spec.num_cells)
    new = _grid_from_flat(flat, points, spec)
    stats = UpdateStats(overflow=new.overflow, oob=oob, max_disp2=max_d2)
    return new, stats, ccoord


_update_donated = partial(jax.jit,
                          static_argnames=("use_pallas", "mask_parked"),
                          donate_argnums=(0,))(_update_impl)
_update_plain = partial(jax.jit,
                        static_argnames=("use_pallas", "mask_parked"))(
                            _update_impl)


def update_cell_grid(
    grid: CellGrid,
    points: Array,
    anchor_points: Array,
    *,
    use_pallas: bool = False,
    donate: bool | None = None,
    origin: Array | None = None,
    mask_parked: bool = False,
) -> tuple[CellGrid, UpdateStats, Array]:
    """Re-bin moved ``points`` into the *frozen* spec of ``grid``.

    One fused device program replacing the per-frame teardown/rebuild of the
    static path: binning, overflow/out-of-bounds counters, and the
    max-displacement statistic come out of a single dispatch, and the old
    grid's buffers are donated (``donate=None`` auto-enables off-CPU; the CPU
    backend ignores donation and would warn) so the dense array is updated
    in place at the XLA level rather than double-allocated.

    Returns ``(grid', stats, ccoord)`` — ``ccoord`` is the per-point cell
    assignment, shared with query scheduling on the self-query fast path
    (``schedule_cells``) so it is computed exactly once per step.
    """
    if donate is None:
        donate = jax.default_backend() != "cpu"
    fn = _update_donated if donate else _update_plain
    return fn(grid, points, anchor_points, use_pallas, origin,
              mask_parked=mask_parked)


def update_cell_grid_traced(
    grid: CellGrid,
    points: Array,
    anchor_points: Array,
    *,
    use_pallas: bool = False,
    origin: Array | None = None,
    mask_parked: bool = False,
) -> tuple[CellGrid, UpdateStats, Array]:
    """Un-jitted core of :func:`update_cell_grid`, for composition inside
    larger traced programs: the functional core's ``update_index``
    (``core/api.py``) and the session's fused ``lax.cond`` step
    (``core/dynamic.py``) inline it into their own jitted bodies, where a
    nested donating jit would be meaningless."""
    return _update_impl(grid, points, anchor_points, use_pallas, origin,
                        mask_parked)


def _summed_area_table(counts: Array) -> Array:
    """3-D inclusive summed-area table with a zero border at index 0."""
    s = jnp.cumsum(jnp.cumsum(jnp.cumsum(counts, 0), 1), 2)
    return jnp.pad(s, ((1, 0), (1, 0), (1, 0)))


def box_count(sat: Array, lo: Array, hi: Array) -> Array:
    """Number of points with cell coords in the inclusive box [lo, hi].

    ``lo``/``hi`` are int32 [..., 3]; clamping to the grid is the caller's
    job (see partition.py). Classic 8-corner inclusion-exclusion on the SAT.
    """
    x0, y0, z0 = lo[..., 0], lo[..., 1], lo[..., 2]
    x1, y1, z1 = hi[..., 0] + 1, hi[..., 1] + 1, hi[..., 2] + 1
    g = lambda a, b, c: sat[a, b, c]
    return (
        g(x1, y1, z1)
        - g(x0, y1, z1) - g(x1, y0, z1) - g(x1, y1, z0)
        + g(x0, y0, z1) + g(x0, y1, z0) + g(x1, y0, z0)
        - g(x0, y0, z0)
    )


def clamp_box(spec: GridSpec, center: Array, w) -> tuple[Array, Array]:
    """Inclusive cell box of half-width ``w`` around ``center``, clamped."""
    hi_lim = jnp.asarray([d - 1 for d in spec.dims], jnp.int32)
    lo = jnp.clip(center - w, 0, hi_lim)
    hi = jnp.clip(center + w, 0, hi_lim)
    return lo, hi
