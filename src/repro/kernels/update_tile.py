"""Pallas TPU kernel: fused re-binning + motion statistics for dynamic
scenes (DESIGN.md section 7).

One pass over the moved points produces everything the incremental grid
update needs: the clipped integer cell assignment (consumed by the dense
scatter in ``core/grid.py`` AND by query scheduling on the self-query fast
path), the out-of-bounds count (points whose true cell left the frozen
grid), and the max squared displacement vs the plan-anchor positions (the
temporal-coherence staleness statistic). The jnp path materializes three
separate intermediates for these; here each [TN, 8] position tile is read
from VMEM once and reduced in-register.

Grid: (N / TN,). Coordinates are padded 3 -> 8 sublanes like the other
kernels in this package (zero columns change no statistic: they are masked
out of the bounds test and contribute 0 to displacement). Each tile writes
its two partial statistics broadcast across one ``(1, 128)`` lane row of a
``[n_tiles, 1, 128]`` output: a block whose last two dimensions equal the
array's, which Mosaic accepts (a ``(1, 1)`` block of ``[n_tiles, 1]`` is
refused for its sublane dimension).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

DEFAULT_TN = 256
COORD_PAD = 8
LANE = 128


@functools.partial(jax.jit,
                   static_argnames=("spec", "mask_parked", "tn",
                                    "interpret"))
def bin_disp_tile(
    points: jax.Array,
    anchor_points: jax.Array,
    spec,                     # core.types.GridSpec (hashable/static)
    *,
    origin: jax.Array | None = None,
    mask_parked: bool = False,
    tn: int = DEFAULT_TN,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused binning + stats of ``points`` [N, 3] against ``anchor_points``.

    Returns ``(ccoord [N, 3] int32 clipped, oob int32, max_disp2 f32)`` —
    bit-identical to the jnp path in ``core.grid._bin_and_stats``.
    ``origin`` overrides the static spec origin (sharded slabs);
    ``mask_parked`` excludes rows parked at the slab-padding sentinel from
    both statistics (tested in-register against ``types.PARK_THRESHOLD``,
    so the mask costs no extra pass).
    """
    from ..core.types import PARK_THRESHOLD
    n = points.shape[0]
    npad = (-n) % tn
    # rows: edge-replicate (real coordinates, masked out of the reductions
    # by row index); columns: zero-pad 3 -> 8 sublanes
    pp = jnp.pad(points.astype(jnp.float32), ((0, npad), (0, 0)),
                 mode="edge")
    ap = jnp.pad(anchor_points.astype(jnp.float32), ((0, npad), (0, 0)),
                 mode="edge")
    pp = jnp.pad(pp, ((0, 0), (0, COORD_PAD - 3)))
    ap = jnp.pad(ap, ((0, 0), (0, COORD_PAD - 3)))
    n_tiles = pp.shape[0] // tn

    if origin is None:
        origin = jnp.asarray(tuple(spec.origin) + (0.0,) * (COORD_PAD - 3),
                             jnp.float32)[None, :]
    else:
        origin = jnp.concatenate(
            [origin.astype(jnp.float32).reshape(3),
             jnp.zeros((COORD_PAD - 3,), jnp.float32)])[None, :]
    hi = jnp.asarray(tuple(d - 1 for d in spec.dims)
                     + (0,) * (COORD_PAD - 3), jnp.int32)[None, :]
    inv_cell = 1.0 / spec.cell_size

    def kernel(p_ref, a_ref, o_ref, h_ref, cc_ref, oob_ref, d2_ref):
        i = pl.program_id(0)
        p = p_ref[...]                                      # [TN, 8]
        a = a_ref[...]
        o = o_ref[...]                                      # [1, 8]
        h = h_ref[...]                                      # [1, 8]
        axis = jax.lax.broadcasted_iota(jnp.int32, (tn, COORD_PAD), 1)
        real_col = axis < 3
        row = i * tn + jax.lax.broadcasted_iota(jnp.int32, (tn, 1), 0)
        real_row = row < n                                  # [TN, 1]
        if mask_parked:
            parked = jnp.any(
                (jnp.abs(p) >= jnp.float32(PARK_THRESHOLD)) & real_col,
                axis=1, keepdims=True)                      # [TN, 1]
            real_row = real_row & jnp.logical_not(parked)

        c = jnp.floor((p - o) * inv_cell).astype(jnp.int32)
        escaped = ((c < 0) | (c > h)) & real_col
        oob_row = jnp.any(escaped, axis=1, keepdims=True)   # [TN, 1]
        oob = jnp.sum((oob_row & real_row).astype(jnp.int32))
        oob_ref[...] = jnp.full(oob_ref.shape, oob, jnp.int32)

        d = p - a                                           # pad cols: 0
        d2 = jnp.sum(d * d, axis=1, keepdims=True)          # [TN, 1]
        d2max = jnp.max(jnp.where(real_row, d2, 0.0))
        d2_ref[...] = jnp.full(d2_ref.shape, d2max, jnp.float32)

        cc_ref[...] = jnp.clip(c, 0, h)

    cc, oob_part, d2_part = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tn, COORD_PAD), lambda i: (i, 0)),
            pl.BlockSpec((tn, COORD_PAD), lambda i: (i, 0)),
            pl.BlockSpec((1, COORD_PAD), lambda i: (0, 0)),
            pl.BlockSpec((1, COORD_PAD), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tn, COORD_PAD), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, LANE), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, LANE), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((pp.shape[0], COORD_PAD), jnp.int32),
            jax.ShapeDtypeStruct((n_tiles, 1, LANE), jnp.int32),
            jax.ShapeDtypeStruct((n_tiles, 1, LANE), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(pp, ap, origin, hi)
    return (cc[:n, :3], jnp.sum(oob_part[:, 0, 0]),
            jnp.max(d2_part[:, 0, 0]))
