"""Pallas TPU kernel: fused gather → distance → streaming top-K selection.

This is the fused hot loop of the search (paper: BVH traversal + IS shader +
priority queue; here: candidate streaming + MXU distance + VPU selection,
DESIGN.md section 2):

  grid = (query_tiles, candidate_tiles)   # candidate axis is minor/stream
  per step:  p  = points[clip(idx_tile)]                (in-kernel gather)
             d2 = ||q||^2 + ||p||^2 - 2 q.p^T           (MXU, [TQ, TM])
             merge into running best-K held in VMEM scratch
  last step: emit [TQ, K] distances + indices

Two entry points share the loop body:

* :func:`knn_tile` — the candidate-id stream ([n_tiles, M] int32) is
  assembled by the caller (an XLA dynamic-slice gather over the dense
  grid). This is the legacy eager path.
* :func:`knn_tile_anchored` — the whole window gather moves INSIDE the
  kernel: per-tile window anchors and launch levels arrive as
  scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``), the dense
  cell grid stays resident (flattened, constant index map), and each grid
  step derives its TM candidate ids from pure index arithmetic on the
  prefetched anchor before gathering positions. Tiles whose prefetched
  level does not match the launch's level are skipped wholesale
  (``@pl.when``) — the masked per-level launch of the level-segmented
  schedule (DESIGN.md section 3), which is what lets the traced functional
  path (``core/api.py``) run the fused kernel as one compiled program with
  no host metadata in the loop.

The candidate *positions* are never materialized in HBM: the kernel
receives only the int32 candidate-id stream ([n_tiles, M], 4 B/candidate)
plus the coordinate table ([N, 8] f32, resident once), and gathers each TM
sub-tile of positions inside VMEM. The legacy layout shipped a
[n_tiles, M, 8] f32 window-position array (32 B/candidate) through HBM —
8x the traffic, duplicated across overlapping windows.

The merge uses K-pass extraction over [TQ, K + TM] with a one-hot argmin
(vectorizes on the VPU; no per-row gathers). A per-step threshold guard
(@pl.when) skips the merge entirely once no tile candidate beats any row's
current K-th best — the TPU analogue of the paper's AH-shader early ray
termination.

Lane-width discipline: every block whose minor dimension is K (the output
and best-K scratch blocks) is padded to a multiple of the 128-lane register
width, and the candidate-chunk width TM is rounded to a lane multiple, so
arbitrary K values (k=8, k=5, ...) lower cleanly on real TPU instead of
tripping Mosaic's tiling constraints. The wrappers keep the *logical* K:
pad columns ride as the _BIG/-1 neutral element through the merge (the
K-pass extraction only ever writes the first K columns) and are sliced off
before returning, so padded and unpadded results are bit-identical — the
same code path runs in interpret mode on CPU CI. The query-tile sublane
dimension TQ must be a multiple of 8 (asserted).

TPU status: Mosaic (the TPU Pallas compiler) refuses both in-kernel
gathers — the row gather of candidate positions from the coordinate table
(``jnp.take(pts, idx, axis=0)``: "Shape mismatch in input, indices and
output") and the 1-D gather of candidate ids from the flattened dense grid
in :func:`knn_tile_anchored` ("Only 2D gather is supported"). The fused
path therefore runs only in the Pallas interpreter (CPU), and
``build_index`` refuses ``SearchOpts(use_pallas=True)`` on a TPU backend
(``kernels/ops.check_fused_path``). Making it compile needs a different
data layout — cell-sorted points with the tables kept in HBM and each
tile's window fetched by DMA (DESIGN.md section 2). The same rewrite lifts
the VMEM-resident table limit on scene size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

DEFAULT_TQ = 256
DEFAULT_TM = 512
COORD_PAD = 8
LANE = 128               # TPU register lane width (minor-dim tile multiple)
SUBLANE = 8              # f32 sublane multiple (second-to-minor dim)
_BIG = 3.4e38            # sentinel "invalid/evicted" distance (plain float:
_NEG_I32 = -(2**31) + 1  # jnp scalars here would be captured tracer consts)


def _pad_lane(n: int) -> int:
    """Round ``n`` up to the 128-lane register width multiple."""
    return ((int(n) + LANE - 1) // LANE) * LANE


def _merge_topk(best_d2, best_idx, d2, idx, k: int):
    """Merge candidate tile (d2, idx) into running best (ascending)."""
    tq = best_d2.shape[0]
    md2 = jnp.concatenate([best_d2, d2], axis=1)          # [TQ, K+TM]
    midx = jnp.concatenate([best_idx, idx], axis=1)
    width = md2.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (tq, width), 1)
    out_d2 = jnp.full_like(best_d2, _BIG)
    out_idx = jnp.full_like(best_idx, -1)

    def body(j, carry):
        md2, out_d2, out_idx = carry
        dmin = jnp.min(md2, axis=1, keepdims=True)        # [TQ, 1]
        # first occurrence one-hot of the row min: the lowest lane holding
        # it (a lane min; Mosaic has no cumsum lowering)
        first = jnp.min(jnp.where(md2 == dmin, lane, width), axis=1,
                        keepdims=True)
        oh = lane == first
        imin = jnp.max(jnp.where(oh, midx, _NEG_I32), axis=1, keepdims=True)
        col = jax.lax.broadcasted_iota(jnp.int32, out_d2.shape, 1)
        out_d2 = jnp.where(col == j, dmin, out_d2)
        out_idx = jnp.where(col == j, imin, out_idx)
        md2 = jnp.where(oh, _BIG, md2)
        return md2, out_d2, out_idx

    _, out_d2, out_idx = jax.lax.fori_loop(
        0, k, body, (md2, out_d2, out_idx))
    out_idx = jnp.where(out_d2 >= _BIG, -1, out_idx)
    return out_d2, out_idx


def _stream_candidates(q, pts, idx, best_d2, best_idx, *, k: int, r2: float,
                       skip_test: bool, n_pts: int):
    """One candidate-tile step of the streaming top-K (shared by both
    kernels): gather positions from the resident coordinate table, distance
    on the MXU, merge into the running best-K scratch behind the
    threshold guard."""
    # fused gather: candidate positions pulled from the VMEM-resident
    # coordinate table; invalid slots (-1) clip to row 0 and are masked below
    p = jnp.take(pts, jnp.clip(idx, 0, n_pts - 1), axis=0)  # [TM, 8]
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    pn = jnp.sum(p * p, axis=1)[None, :]
    cross = jnp.dot(q, p.T, preferred_element_type=jnp.float32)
    d2 = jnp.maximum(qn + pn - 2.0 * cross, 0.0)          # [TQ, TM]

    invalid = jnp.broadcast_to((idx < 0)[None, :], d2.shape)
    if not skip_test:
        invalid = invalid | (d2 > r2)
    d2 = jnp.where(invalid, _BIG, d2)
    idx_b = jnp.where(invalid, -1, jnp.broadcast_to(idx[None, :], d2.shape))

    # threshold guard: does any candidate beat any row's current K-th best?
    # (only the first k columns are live — the lane-pad columns stay _BIG
    # forever and would otherwise pin the guard open)
    row_kth = jnp.max(best_d2[...][:, :k], axis=1)        # [TQ]
    row_min = jnp.min(d2, axis=1)                         # [TQ]
    beats = jnp.any(row_min < row_kth)

    @pl.when(beats)
    def _merge():
        nd2, nidx = _merge_topk(best_d2[...], best_idx[...], d2, idx_b, k)
        best_d2[...] = nd2
        best_idx[...] = nidx


def _emit_best(out_d2_ref, out_idx_ref, best_d2, best_idx):
    out_d2_ref[...] = jnp.where(best_d2[...] >= _BIG, jnp.inf, best_d2[...])
    out_idx_ref[...] = best_idx[...]


def _knn_kernel(q_ref, pts_ref, idx_ref, out_d2_ref, out_idx_ref,
                best_d2, best_idx, *, k: int, r2: float, skip_test: bool,
                n_m: int, n_pts: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_d2[...] = jnp.full_like(best_d2, _BIG)
        best_idx[...] = jnp.full_like(best_idx, -1)

    _stream_candidates(q_ref[...], pts_ref[...], idx_ref[0, 0], best_d2,
                       best_idx, k=k, r2=r2, skip_test=skip_test,
                       n_pts=n_pts)

    @pl.when(j == n_m - 1)
    def _emit():
        _emit_best(out_d2_ref, out_idx_ref, best_d2, best_idx)


@functools.partial(
    jax.jit,
    static_argnames=("k", "r2", "skip_test", "tq", "tm", "interpret"))
def knn_tile(
    q: jax.Array,          # [Nq, 3] f32, Nq % tq == 0 per query tile group
    points: jax.Array,     # [N, 3] f32 coordinate table (gathered in-kernel)
    wnd_idx: jax.Array,    # [n_tiles, M] int32 candidate ids (-1 invalid)
    *,
    k: int,
    r2: float,
    skip_test: bool = False,
    tq: int = DEFAULT_TQ,
    tm: int = DEFAULT_TM,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Streaming top-K of each query against its tile's candidate id window.

    Returns (d2 [Nq, k] ascending inf-padded, idx [Nq, k] -1-padded).
    """
    n_tiles, m = wnd_idx.shape
    assert q.shape[0] == n_tiles * tq, (q.shape, n_tiles, tq)
    assert tq % SUBLANE == 0, f"query tile {tq} must be a multiple of 8"
    tm = _pad_lane(tm)
    kp = _pad_lane(k)        # block minor dim; logical K sliced off below
    n_pts = points.shape[0]
    m_pad = (-m) % tm
    # [n_tiles, 1, M]: the (1, tm) id block then spans the array's own
    # unit sublane dimension (a (1, tm) block of [n_tiles, M] is refused)
    wnd_idx = jnp.pad(wnd_idx, ((0, 0), (0, m_pad)),
                      constant_values=-1)[:, None, :]
    # coordinate table: coords padded to the register width, rows padded to
    # the sublane multiple; pad rows park far away (never selected: gather
    # indices are clipped to n_pts-1 and -1 slots are masked)
    n_row_pad = (-n_pts) % 8
    pts8 = jnp.pad(points.astype(jnp.float32),
                   ((0, n_row_pad), (0, COORD_PAD - 3)),
                   constant_values=0.0)
    qp = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, COORD_PAD - 3)))
    n_m = wnd_idx.shape[2] // tm

    kernel = functools.partial(_knn_kernel, k=k, r2=float(r2),
                               skip_test=bool(skip_test), n_m=n_m,
                               n_pts=n_pts)
    out_d2, out_idx = pl.pallas_call(
        kernel,
        grid=(n_tiles, n_m),
        in_specs=[
            pl.BlockSpec((tq, COORD_PAD), lambda i, j: (i, 0)),
            # full table, constant index map: stays VMEM-resident across
            # the candidate stream instead of re-fetching per step
            pl.BlockSpec((n_pts + n_row_pad, COORD_PAD), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1, tm), lambda i, j: (i, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((tq, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((tq, kp), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles * tq, kp), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles * tq, kp), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tq, kp), jnp.float32),
            pltpu.VMEM((tq, kp), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(qp, pts8, wnd_idx)
    return out_d2[:, :k], out_idx[:, :k]


def _knn_anchored_kernel(anchors_ref, levels_ref, q_ref, pts_ref, dense_ref,
                         out_d2_ref, out_idx_ref, best_d2, best_idx, *,
                         k: int, r2: float, skip_test: bool, level: int,
                         n_m: int, n_pts: int, m: int, tm: int,
                         ws: tuple, dims: tuple, cap: int):
    """Level-masked anchored variant: the window-candidate gather happens
    here, from the resident flattened dense grid, using the scalar-prefetched
    per-tile anchor. Tiles whose prefetched level != ``level`` skip both the
    gather and the merge (their output rows are written neutral at the last
    step so the caller's per-level combine is a plain select)."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    mine = levels_ref[i] == level

    @pl.when(mine & (j == 0))
    def _init():
        best_d2[...] = jnp.full_like(best_d2, _BIG)
        best_idx[...] = jnp.full_like(best_idx, -1)

    @pl.when(mine)
    def _step():
        ax = anchors_ref[i, 0]
        ay = anchors_ref[i, 1]
        az = anchors_ref[i, 2]
        # candidate ids for chunk j from pure index arithmetic on the
        # prefetched anchor: flat window position -> (window cell, slot) ->
        # global cell -> position in the flattened dense grid. The anchor is
        # pre-clipped so every window cell is in bounds; only the m..m_pad
        # tail (candidate positions past the window) needs masking.
        c = j * tm + jax.lax.broadcasted_iota(jnp.int32, (1, tm), 1)[0]
        slot = c % cap
        cell = c // cap
        iz = cell % ws[2]
        iy = (cell // ws[2]) % ws[1]
        ix = cell // (ws[2] * ws[1])
        flat = (((ax + ix) * dims[1] + (ay + iy)) * dims[2]
                + (az + iz)) * cap + slot
        n_flat = dims[0] * dims[1] * dims[2] * cap
        cand = jnp.take(dense_ref[...], jnp.clip(flat, 0, n_flat - 1))
        idx = jnp.where(c < m, cand, -1)                  # [TM]
        _stream_candidates(q_ref[...], pts_ref[...], idx, best_d2, best_idx,
                           k=k, r2=r2, skip_test=skip_test, n_pts=n_pts)

    @pl.when(mine & (j == n_m - 1))
    def _emit():
        _emit_best(out_d2_ref, out_idx_ref, best_d2, best_idx)

    @pl.when(jnp.logical_not(mine) & (j == n_m - 1))
    def _emit_neutral():
        out_d2_ref[...] = jnp.full_like(out_d2_ref, jnp.inf)
        out_idx_ref[...] = jnp.full_like(out_idx_ref, -1)


@functools.partial(
    jax.jit,
    static_argnames=("level", "ws", "dims", "cap", "k", "r2", "skip_test",
                     "tq", "tm", "interpret"))
def knn_tile_anchored(
    q: jax.Array,          # [Nq, 3] f32, Nq == n_tiles * tq
    points: jax.Array,     # [N, 3] f32 coordinate table (gathered in-kernel)
    dense_flat: jax.Array,  # [Dx*Dy*Dz*cap] i32 flattened cell grid
    anchors: jax.Array,    # [n_tiles, 3] i32 window anchors (scalar prefetch)
    levels: jax.Array,     # [n_tiles] i32 per-tile launch level
    *,
    level: int,            # this launch's level; other tiles are masked
    ws: tuple,             # (wx, wy, wz) static window size in cells
    dims: tuple,           # grid dims (static)
    cap: int,              # cell capacity (static)
    k: int,
    r2: float,
    skip_test: bool = False,
    tq: int = DEFAULT_TQ,
    tm: int = DEFAULT_TM,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One masked launch of the level-segmented schedule: every query tile
    whose ``levels`` entry equals ``level`` streams its anchored
    ``ws[0]*ws[1]*ws[2]*cap`` candidate window through the fused
    gather→distance→top-K loop; all other tiles are skipped and emit
    neutral rows (inf / -1). Fully traced — anchors and levels are device
    arrays delivered by scalar prefetch, so the caller composes under
    ``jit`` and ``vmap`` with zero host metadata.

    Returns (d2 [Nq, k] ascending inf-padded, idx [Nq, k] -1-padded).
    """
    n_tiles = anchors.shape[0]
    assert q.shape[0] == n_tiles * tq, (q.shape, n_tiles, tq)
    assert tq % SUBLANE == 0, f"query tile {tq} must be a multiple of 8"
    n_pts = points.shape[0]
    m = ws[0] * ws[1] * ws[2] * cap
    # candidate-chunk width: lane-multiple so the in-kernel iota/gather
    # vectors tile cleanly; the c < m mask already handles the tail
    tm = _pad_lane(min(tm, max(1, m)))
    kp = _pad_lane(k)        # block minor dim; logical K sliced off below
    n_m = (m + tm - 1) // tm
    n_row_pad = (-n_pts) % 8
    pts8 = jnp.pad(points.astype(jnp.float32),
                   ((0, n_row_pad), (0, COORD_PAD - 3)),
                   constant_values=0.0)
    qp = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, COORD_PAD - 3)))

    kernel = functools.partial(
        _knn_anchored_kernel, k=k, r2=float(r2), skip_test=bool(skip_test),
        level=int(level), n_m=n_m, n_pts=n_pts, m=m, tm=tm, ws=tuple(ws),
        dims=tuple(dims), cap=int(cap))
    n_flat = dense_flat.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, n_m),
        in_specs=[
            pl.BlockSpec((tq, COORD_PAD), lambda i, j, a, l: (i, 0)),
            # coordinate table and dense grid: full blocks with constant
            # index maps — resident across the whole candidate stream
            pl.BlockSpec((n_pts + n_row_pad, COORD_PAD),
                         lambda i, j, a, l: (0, 0)),
            pl.BlockSpec((n_flat,), lambda i, j, a, l: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((tq, kp), lambda i, j, a, l: (i, 0)),
            pl.BlockSpec((tq, kp), lambda i, j, a, l: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((tq, kp), jnp.float32),
            pltpu.VMEM((tq, kp), jnp.int32),
        ],
    )
    out_d2, out_idx = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles * tq, kp), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles * tq, kp), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(anchors.astype(jnp.int32), levels.astype(jnp.int32), qp, pts8,
      dense_flat)
    return out_d2[:, :k], out_idx[:, :k]
