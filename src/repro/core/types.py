"""Core datatypes for the RTNN-on-TPU neighbor search library.

Static-shape discipline: everything that determines an array shape (grid
dims, cell capacity, K, window radius, tile sizes) is a Python int held in a
hashable spec object, so jitted functions specialize per spec. Everything
data-dependent (point positions, counts, permutations) lives in arrays.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

Array = jax.Array

# Padding convention of the sharded slabs (core/shards.py): rows "parked" at
# PARK_SENTINEL are empty slots of a fixed-capacity buffer. Any position with
# a coordinate magnitude >= PARK_THRESHOLD is treated as parked by the
# functional core when ``SearchOpts.mask_parked`` is set: dropped from the
# grid entirely and excluded from the update statistics.
PARK_SENTINEL = 1e30
PARK_THRESHOLD = 1e29


@dataclasses.dataclass(frozen=True)
class GridSpec:
    """Static description of a uniform cell grid over the point domain.

    The grid is the TPU-native acceleration structure replacing the paper's
    BVH (DESIGN.md section 2): points are binned into cells of edge
    ``cell_size``; a search with window radius ``w`` (in cells) gathers the
    ``(2w+1)**3`` cell neighborhood, the analogue of the set of AABBs whose
    width the paper tunes.
    """

    origin: tuple[float, float, float]
    cell_size: float
    dims: tuple[int, int, int]          # number of cells per axis (static)
    capacity: int                        # max points stored per cell (static)

    @property
    def num_cells(self) -> int:
        dx, dy, dz = self.dims
        return dx * dy * dz

    def cell_of(self, pos: Array, origin: Array | None = None) -> Array:
        """Integer cell coordinates of positions ``pos`` [..., 3].

        ``origin`` optionally overrides the static origin with a dynamic
        array — used by the distributed slabs, whose local frames differ
        per shard while the spec (shapes) is shared.
        """
        o = (jnp.asarray(self.origin, dtype=pos.dtype) if origin is None
             else origin.astype(pos.dtype))
        c = jnp.floor((pos - o) / self.cell_size).astype(jnp.int32)
        hi = jnp.asarray([d - 1 for d in self.dims], dtype=jnp.int32)
        return jnp.clip(c, 0, hi)

    def flat_cell(self, ccoord: Array) -> Array:
        """Flatten [..., 3] integer cell coords to a scalar cell id."""
        _, dy, dz = self.dims
        return (ccoord[..., 0] * dy + ccoord[..., 1]) * dz + ccoord[..., 2]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CellGrid:
    """The built acceleration structure.

    ``dense``    [Dx, Dy, Dz, C]  int32 point indices, -1 padded.
    ``coords``   [Dx, Dy, Dz*3*C] f32 coordinate table: for each cell, the
                 x of its C slots, then their y, then their z, of the point
                 ``dense`` names there (0.0 in empty slots, which ``dense``
                 marks -1). A query's window is one ``(wx, wy, wz*3*C)``
                 slice, at the cells its ids come from in ``dense``, so the
                 search reads its candidates' coordinates as one window
                 slice per query, not as one row gather per candidate. The
                 minor dimension is the run a window reads, cells times
                 coordinates times slots: a minor dimension of the 3
                 coordinates pads to 128 lanes on the TPU (DESIGN.md
                 section 2).
    ``counts``   [Dx, Dy, Dz]     int32 points per cell (clipped to C).
    ``sat``      [Dx+1, Dy+1, Dz+1] int32 3-D summed-area table of counts;
                 box sums in O(1) for the megacell growth of paper section 5.1.
    ``overflow`` scalar int32: number of points dropped because their cell
                 exceeded capacity (0 in a correctly-capacity-planned build;
                 asserted in tests).
    """

    spec: GridSpec
    dense: Array
    coords: Array
    counts: Array
    sat: Array
    overflow: Array

    def tree_flatten(self):
        return ((self.dense, self.coords, self.counts, self.sat,
                 self.overflow), self.spec)

    @classmethod
    def tree_unflatten(cls, spec, leaves):
        dense, coords, counts, sat, overflow = leaves
        return cls(spec=spec, dense=dense, coords=coords, counts=counts,
                   sat=sat, overflow=overflow)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class UpdateStats:
    """On-device counters of one incremental grid update (all scalar int32 /
    f32 device arrays; fetched in ONE fused transfer per step by the
    session).

    ``overflow``   points dropped because their cell exceeded capacity.
    ``oob``        points whose true cell lies outside the frozen grid —
                   binning them clamped would lose exactness, so any nonzero
                   value triggers the session's respec-and-rebuild fallback.
    ``max_disp2``  max squared displacement vs the plan-anchor positions;
                   compared against the staleness threshold to decide
                   whether the cached schedule/partition plan is reusable.
    """

    overflow: Array
    oob: Array
    max_disp2: Array

    def tree_flatten(self):
        return (self.overflow, self.oob, self.max_disp2), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)


@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Static parameters of one neighbor search call.

    ``radius=math.inf`` is exact kNN with no radius (knn mode only): every
    query gets the min(K, N) nearest points of its own cloud, found by
    searching the whole cloud, with no grid; ``knn_window`` is not read
    (DESIGN.md section 2)."""

    radius: float
    k: int
    mode: str = "knn"                  # "knn" | "range"
    knn_window: str = "heuristic"      # "heuristic" | "exact" (paper 5.1)

    def __post_init__(self):
        if math.isinf(self.radius) and self.mode != "knn":
            raise ValueError(
                f"mode={self.mode!r} needs a finite radius: a range search "
                f"with no radius would return every point; use mode='knn' "
                f"for exact kNN with radius=math.inf")

    @property
    def bounded(self) -> bool:
        """False for exact kNN with no radius (``radius=math.inf``)."""
        return not math.isinf(self.radius)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SearchResult:
    """indices [Nq, K] int32 (-1 pad), distances2 [Nq, K] f32 (inf pad),
    counts [Nq] int32."""

    indices: Array
    distances2: Array
    counts: Array

    def tree_flatten(self):
        return (self.indices, self.distances2, self.counts), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)


@dataclasses.dataclass(frozen=True)
class SearchOpts:
    """Which paper optimizations are enabled (benchmark ablation knobs,
    mirroring Fig. 13: NoOpt / Sched / +Partition / +Bundle)."""

    schedule: bool = True              # section 4: Morton query ordering
    partition: bool = True             # section 5.1: megacell partitioning
    bundle: bool = True                # section 5.2: cost-model bundling
    use_pallas: bool = False           # fused kernels (Pallas interpreter
    #                                    off-TPU; refused on a TPU backend)
    query_tile: int = 256              # queries per jnp/kernel tile
    w_max: int = 6                     # max megacell growth rings examined
    executor: bool = True              # device-resident QueryExecutor path
    #                                    (False: legacy per-bundle host loop,
    #                                    kept for A/B benchmarking)
    w_ladder: tuple[int, ...] | None = None
    #                                    explicit window ladder for the traced
    #                                    functional path (core/api.py): queries
    #                                    round UP to the nearest ladder window
    #                                    (always exact, sphere test always on);
    #                                    None derives the ladder from the
    #                                    megacell statics. Bounds the traced
    #                                    lax.switch branch count.
    mask_parked: bool = False          # rows parked at PARK_SENTINEL (fixed-
    #                                    capacity slab padding, core/shards.py)
    #                                    are absent: dropped from the grid and
    #                                    excluded from oob/displacement stats
