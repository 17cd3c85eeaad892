"""End-to-end search correctness vs the brute-force oracle, across the
paper's optimization ablation matrix (Fig. 13) and point distributions."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (NeighborSearch, SearchOpts, SearchParams,
                        neighbor_search)
from repro.core.grid import build_cell_grid, choose_grid_spec
from repro.core.search import _select, _tile_d2, window_tile_search
from repro.data.pointclouds import clustered_cloud, kitti_like_cloud, \
    uniform_cloud
from repro.kernels.ref import brute_force_search


def _check_knn_exact(pts, qs, r, k, opts):
    oi, od, oc = brute_force_search(jnp.asarray(pts), jnp.asarray(qs), r, k)
    res = neighbor_search(pts, qs, r, k, mode="knn", opts=opts,
                          knn_window="exact")
    d_ref = np.where(np.isinf(np.asarray(od)), -1.0, np.asarray(od))
    d_got = np.where(np.isinf(np.asarray(res.distances2)), -1.0,
                     np.asarray(res.distances2))
    np.testing.assert_allclose(d_got, d_ref, atol=1e-5)
    assert np.array_equal(np.asarray(oc), np.asarray(res.counts))


@pytest.mark.parametrize("schedule,partition,bundle", list(
    itertools.product([False, True], repeat=3)))
def test_knn_ablation_matrix(rng, schedule, partition, bundle):
    pts = rng.random((1500, 3)).astype(np.float32)
    qs = rng.random((400, 3)).astype(np.float32)
    opts = SearchOpts(schedule=schedule, partition=partition, bundle=bundle)
    _check_knn_exact(pts, qs, 0.12, 8, opts)


@pytest.mark.parametrize("maker", [uniform_cloud, kitti_like_cloud,
                                   clustered_cloud])
def test_knn_distributions(maker):
    pts = maker(3000, seed=1)
    qs = maker(500, seed=2)
    _check_knn_exact(pts, qs, 0.1, 8, SearchOpts())


def test_range_counts_and_radius(rng):
    pts = rng.random((2500, 3)).astype(np.float32)
    qs = rng.random((600, 3)).astype(np.float32)
    r, k = 0.09, 16
    oi, od, oc = brute_force_search(jnp.asarray(pts), jnp.asarray(qs), r, k)
    res = neighbor_search(pts, qs, r, k, mode="range")
    ri = np.asarray(res.indices)
    rd = np.asarray(res.distances2)
    assert np.array_equal(np.asarray(oc), np.asarray(res.counts))
    valid = ri >= 0
    assert (rd[valid] <= r * r + 1e-6).all()
    # returned indices are actual points at the reported distances
    d_check = np.sum((qs[:, None, :] - pts[np.clip(ri, 0, None)]) ** 2, -1)
    np.testing.assert_allclose(np.where(valid, d_check, 0),
                               np.where(valid, rd, 0), atol=1e-5)


def test_knn_heuristic_recall_uniform(rng):
    """Paper's heuristic window (section 5.1) is approximate by design;
    on locally-uniform data it should be near-exact."""
    pts = rng.random((4000, 3)).astype(np.float32)
    qs = rng.random((500, 3)).astype(np.float32)
    r, k = 0.1, 8
    oi, od, oc = brute_force_search(jnp.asarray(pts), jnp.asarray(qs), r, k)
    res = neighbor_search(pts, qs, r, k, mode="knn", knn_window="heuristic")
    ref_sets = [set(row[row >= 0].tolist()) for row in np.asarray(oi)]
    got_sets = [set(row[row >= 0].tolist()) for row in
                np.asarray(res.indices)]
    hits = sum(len(a & b) for a, b in zip(ref_sets, got_sets))
    total = max(sum(len(a) for a in ref_sets), 1)
    assert hits / total > 0.95, hits / total


@given(st.integers(20, 300), st.integers(1, 16),
       st.floats(0.03, 0.4), st.integers(0, 2**31 - 1))
@settings(deadline=None, max_examples=20)
def test_knn_exact_property(n, k, r, seed):
    rng = np.random.default_rng(seed)
    pts = rng.random((n, 3)).astype(np.float32)
    qs = rng.random((max(n // 3, 5), 3)).astype(np.float32)
    _check_knn_exact(pts, qs, r, k, SearchOpts())


def test_pallas_path_matches_jnp_path(rng):
    pts = rng.random((2000, 3)).astype(np.float32)
    qs = rng.random((500, 3)).astype(np.float32)
    params = SearchParams(radius=0.1, k=8, mode="knn", knn_window="exact")
    res_j = NeighborSearch(pts, params, SearchOpts()).query(qs)
    res_p = NeighborSearch(pts, params,
                           SearchOpts(use_pallas=True,
                                      query_tile=128)).query(qs)
    np.testing.assert_allclose(
        np.where(np.isinf(np.asarray(res_j.distances2)), -1,
                 np.asarray(res_j.distances2)),
        np.where(np.isinf(np.asarray(res_p.distances2)), -1,
                 np.asarray(res_p.distances2)), atol=1e-5)
    assert np.array_equal(np.asarray(res_j.counts), np.asarray(res_p.counts))


def test_query_equals_point_is_own_neighbor(rng):
    pts = rng.random((500, 3)).astype(np.float32)
    res = neighbor_search(pts, pts[:50], 0.1, 1, mode="knn")
    np.testing.assert_array_equal(np.asarray(res.indices)[:, 0],
                                  np.arange(50))
    # expanded-form distance: |q|^2+|p|^2-2qp is ~eps, not exactly 0
    np.testing.assert_allclose(np.asarray(res.distances2)[:, 0], 0.0,
                               atol=1e-6)


def test_report_breakdown_populated(rng):
    pts = rng.random((1000, 3)).astype(np.float32)
    qs = rng.random((200, 3)).astype(np.float32)
    ns = NeighborSearch(pts, SearchParams(radius=0.1, k=4))
    ns.query(qs)
    assert ns.report.num_partitions >= 1
    assert len(ns.report.bundles) >= 1
    assert ns.report.t_search > 0


def _row_gather_tile(grid, points, qt, spec, w, radius, k, skip_test):
    """The search tile with its coordinates read the other way: the same
    window of candidate ids, then one row gather ``points[cand]`` per
    candidate, into the same ``_tile_d2`` and ``_select``."""
    ws = tuple(min(2 * w + 1, d) for d in spec.dims)
    dims = jnp.asarray(spec.dims, jnp.int32)
    start = jnp.clip(spec.cell_of(qt) - w, 0,
                     dims - jnp.asarray(ws, jnp.int32))
    cand = jax.vmap(lambda st: jax.lax.dynamic_slice(
        grid.dense, (st[0], st[1], st[2], 0),
        (*ws, spec.capacity)).reshape(-1))(start)
    rows = points[jnp.clip(cand, 0, points.shape[0] - 1)]      # [T, M, 3]
    d2 = _tile_d2(qt, jnp.swapaxes(rows, 1, 2))
    return _select(d2, cand, k, jnp.float32(radius) ** 2, skip_test)


def _tile_scene(rng, scene):
    """(points, queries, radius, k, w): a uniform knn scene searched over
    its full-radius window, or a clustered range scene over a narrower
    window (where the sphere-test skip is what a megacell would allow)."""
    if scene == "knn":
        pts = rng.random((1537, 3)).astype(np.float32)
        return pts, rng.random((256, 3)).astype(np.float32), 0.12, 8, 4
    pts = clustered_cloud(1537, seed=int(rng.integers(1 << 30)))
    qs = pts[rng.choice(len(pts), 256, replace=False)]
    return pts, qs, 0.05, 16, 2


@pytest.mark.parametrize("skip_test", [False, True])
@pytest.mark.parametrize("scene", ["knn", "range"])
def test_window_tile_search_matches_row_gather(rng, scene, skip_test):
    """The tile that reads its candidates' coordinates from the grid's
    coordinate table returns what the per-candidate row gather of the
    points returns: counts exact, d2 within 1e-6, ids equal up to ties
    at the K-th place."""
    pts, qs, r, k, w = _tile_scene(rng, scene)
    spec = choose_grid_spec(pts, r)
    grid = build_cell_grid(jnp.asarray(pts), spec)
    args = (grid, jnp.asarray(pts), jnp.asarray(qs))
    d2a, ia, ca = jax.jit(lambda g, p, q: window_tile_search(
        g, p, q, spec, w, r, k, skip_test))(*args)
    d2b, ib, cb = jax.jit(lambda g, p, q: _row_gather_tile(
        g, p, q, spec, w, r, k, skip_test))(*args)
    ca, cb = np.asarray(ca), np.asarray(cb)
    np.testing.assert_array_equal(ca, cb)
    assert ca.max() > 0
    da = np.where(np.isinf(d2a), -1.0, np.asarray(d2a))
    db = np.where(np.isinf(d2b), -1.0, np.asarray(d2b))
    np.testing.assert_allclose(da, db, rtol=0, atol=1e-6)
    ia, ib = np.asarray(ia), np.asarray(ib)
    for row in np.flatnonzero((np.sort(ia, 1) != np.sort(ib, 1)).any(1)):
        kth = da[row, ca[row] - 1]
        assert (np.abs(da[row][~np.isin(ia[row], ib[row])] - kth)
                <= 1e-6).all(), row
        assert (np.abs(db[row][~np.isin(ib[row], ia[row])] - kth)
                <= 1e-6).all(), row


def test_window_tile_search_reads_no_point_rows(rng):
    """The tile's jaxpr holds no gather from the points: candidates'
    coordinates come as window slices of the coordinate table."""
    pts, qs, r, k, w = _tile_scene(rng, "knn")
    spec = choose_grid_spec(pts, r)
    grid = build_cell_grid(jnp.asarray(pts), spec)
    closed = jax.make_jaxpr(lambda g, p, q: window_tile_search(
        g, p, q, spec, w, r, k, False))(grid, jnp.asarray(pts),
                                        jnp.asarray(qs))
    points_var = closed.jaxpr.invars[len(jax.tree.leaves(grid))]
    assert points_var.aval.shape == pts.shape

    def gathers(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "gather":
                yield eqn.invars[0]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from gathers(sub)

    operands = list(gathers(closed.jaxpr))
    assert operands                     # the window slices are gathers
    # by identity at the top level, by shape inside nested jaxprs
    assert not any(v is points_var or v.aval.shape == pts.shape
                   for v in operands)
