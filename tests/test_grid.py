import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.grid import (box_count, build_cell_grid, choose_grid_spec,
                             parked_mask, update_cell_grid,
                             update_cell_grid_traced)
from repro.core.types import PARK_SENTINEL


def _points(rng, n):
    return rng.random((n, 3)).astype(np.float32)


def test_build_no_overflow_with_planned_capacity(rng):
    pts = _points(rng, 2000)
    spec = choose_grid_spec(pts, radius=0.1)
    grid = build_cell_grid(jnp.asarray(pts), spec)
    assert int(grid.overflow) == 0
    assert int(grid.counts.sum()) == 2000


def test_every_point_in_its_cell(rng):
    pts = _points(rng, 500)
    spec = choose_grid_spec(pts, radius=0.15)
    grid = build_cell_grid(jnp.asarray(pts), spec)
    dense = np.asarray(grid.dense)
    ccoord = np.asarray(spec.cell_of(jnp.asarray(pts)))
    for idx in range(0, 500, 37):
        cx, cy, cz = ccoord[idx]
        assert idx in dense[cx, cy, cz], (idx, ccoord[idx])


def _table_grid(rng, builder):
    """(grid, points) from one of the builders that write CellGrid.coords;
    the update builders step twice, so the second update re-bins a grid
    that an update wrote."""
    pts = _points(rng, 600)
    spec = choose_grid_spec(pts, radius=0.1, capacity_slack=2.0)
    if builder == "build":
        return build_cell_grid(jnp.asarray(pts), spec), pts
    if builder == "parked_valid":
        pts[::7] = np.float32(PARK_SENTINEL)
        dev = jnp.asarray(pts)
        grid = build_cell_grid(dev, spec,
                               valid=jnp.logical_not(parked_mask(dev)))
        assert not np.isin(np.arange(0, 600, 7), np.asarray(grid.dense)).any()
        return grid, pts
    grid = build_cell_grid(jnp.asarray(pts), spec)
    for _ in range(2):
        moved = np.clip(pts + rng.normal(0, 0.01, pts.shape), 0, 1
                        ).astype(np.float32)
        if builder == "update_traced":
            grid, _stats, _cc = jax.jit(update_cell_grid_traced)(
                grid, jnp.asarray(moved), jnp.asarray(pts))
        else:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")      # CPU ignores donation
                grid, _stats, _cc = update_cell_grid(
                    grid, jnp.asarray(moved), jnp.asarray(pts),
                    donate=builder == "update_donated")
        pts = moved
    return grid, pts


@pytest.mark.parametrize("builder", ["build", "parked_valid", "update",
                                     "update_traced", "update_donated"])
def test_coordinate_table_holds_each_slots_point(rng, builder):
    """CellGrid.coords holds, in every slot, the coordinates of the point
    that ``dense`` names there, and 0.0 in every empty slot, whichever
    builder wrote it: the fresh build, a build that drops parked rows by
    ``valid``, and the incremental update (jitted, traced, donated)."""
    grid, pts = _table_grid(rng, builder)
    dx, dy, dz = grid.spec.dims
    cap = grid.spec.capacity
    assert grid.coords.shape == (dx, dy, dz * 3 * cap)
    assert grid.coords.dtype == jnp.float32
    dense = np.asarray(grid.dense).reshape(-1)
    # per cell: the x of its slots, then their y, then their z
    table = np.moveaxis(np.asarray(grid.coords).reshape(-1, 3, cap), 1,
                        2).reshape(-1, 3)
    full = dense >= 0
    assert full.any()
    np.testing.assert_array_equal(table[full], pts[dense[full]])
    assert (table[~full] == 0.0).all()


@given(st.integers(10, 400), st.integers(0, 2**31 - 1))
@settings(deadline=None, max_examples=15)
def test_sat_box_count_matches_brute(n, seed):
    rng = np.random.default_rng(seed)
    pts = _points(rng, n)
    spec = choose_grid_spec(pts, radius=0.2)
    grid = build_cell_grid(jnp.asarray(pts), spec)
    ccoord = np.asarray(spec.cell_of(jnp.asarray(pts)))
    lo = jnp.asarray([[1, 1, 1]], jnp.int32)
    hi = jnp.asarray([[3, 2, 4]], jnp.int32)
    got = int(box_count(grid.sat, lo, hi)[0])
    want = int(np.sum(np.all((ccoord >= [1, 1, 1]) & (ccoord <= [3, 2, 4]),
                             axis=1)))
    assert got == want


def test_capacity_overflow_reported(rng):
    pts = np.zeros((50, 3), np.float32)  # all in one cell
    spec = choose_grid_spec(pts, radius=0.1, capacity=8)
    grid = build_cell_grid(jnp.asarray(pts), spec)
    assert int(grid.overflow) == 42
    assert int(grid.counts.max()) == 8


def _assert_spec_sane(spec, radius):
    assert spec.cell_size > 0 and np.isfinite(spec.cell_size)
    assert all(isinstance(d, int) and 0 < d < 64 for d in spec.dims)
    assert all(np.isfinite(o) for o in spec.origin)
    # the full-radius window must fit: extent was clamped to >= radius
    assert all(d * spec.cell_size >= radius for d in spec.dims)


def test_degenerate_extent_identical_points(rng):
    """Regression: a zero-extent bbox (all points identical) must not
    produce zero-size cells, NaN/degenerate dims, or wrong results —
    the extent clamps to ``radius`` per axis."""
    from repro.core import neighbor_search
    from repro.kernels.ref import brute_force_search

    pts = np.full((40, 3), 0.25, np.float32)
    spec = choose_grid_spec(pts, radius=0.05)
    _assert_spec_sane(spec, 0.05)
    res = neighbor_search(pts, pts[:7], 0.05, 8, mode="knn")
    _oi, _od, oc = brute_force_search(jnp.asarray(pts),
                                      jnp.asarray(pts[:7]), 0.05, 8)
    np.testing.assert_array_equal(np.asarray(oc), np.asarray(res.counts))
    np.testing.assert_allclose(np.asarray(res.distances2), 0.0, atol=1e-6)


def test_degenerate_extent_coplanar_points(rng):
    """Regression: one zero-extent axis (coplanar set) — dims stay finite
    and small on the flat axis and the search stays oracle-exact."""
    from repro.core import neighbor_search
    from repro.kernels.ref import brute_force_search

    pts = rng.random((300, 3)).astype(np.float32)
    pts[:, 2] = 0.4                              # flat in z
    r, k = 0.08, 8
    spec = choose_grid_spec(pts, radius=r)
    _assert_spec_sane(spec, r)
    qs = pts[::5]
    res = neighbor_search(pts, qs, r, k, mode="knn", knn_window="exact")
    _oi, od, oc = brute_force_search(jnp.asarray(pts), jnp.asarray(qs),
                                     r, k)
    d_ref = np.where(np.isinf(np.asarray(od)), -1.0, np.asarray(od))
    d_got = np.where(np.isinf(np.asarray(res.distances2)), -1.0,
                     np.asarray(res.distances2))
    np.testing.assert_allclose(d_got, d_ref, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(oc), np.asarray(res.counts))
