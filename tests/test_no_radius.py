"""Exact kNN with no radius (``SearchParams(radius=math.inf)``) through
``repro.api``, one cloud and batches of clouds (DESIGN.md sections 2 and
8): against the brute-force reference ``kernels/ref.brute_force_search``
at ``radius=inf``, bitwise against per-cloud calls, and clouds kept
apart."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api as api
from repro.core import SearchParams
from repro.kernels.ref import brute_force_search

INF = math.inf


def surface_cloud(rng, n):
    """Points on a sphere and a thin plate, centred and scaled into the
    unit sphere: a small surface-sampled cloud."""
    m = n // 2
    v = rng.normal(size=(m, 3))
    sphere = 0.4 * v / np.linalg.norm(v, axis=1, keepdims=True)
    plate = np.column_stack([rng.uniform(-0.8, 0.8, n - m),
                             rng.uniform(-0.5, 0.5, n - m),
                             rng.choice([-0.01, 0.01], n - m)])
    pc = np.concatenate([sphere + [0.3, 0.0, 0.2], plate])
    pc = pc - pc.mean(axis=0)
    return (pc / np.linalg.norm(pc, axis=1).max()).astype(np.float32)


def volume_clouds(rng, b, n):
    return rng.random((b, n, 3)).astype(np.float32)


def reference(points, queries, k):
    idx, d2, cnt = brute_force_search(jnp.asarray(points),
                                      jnp.asarray(queries), INF, k)
    return np.asarray(idx), np.asarray(d2), np.asarray(cnt)


def assert_matches_reference(res, points, queries, k):
    """Counts and squared distances exactly the reference's, and each
    returned id a distinct point of this cloud at its returned distance:
    a valid k-nearest set, whichever of two points tied at one distance
    it holds."""
    ridx, rd2, rcnt = reference(points, queries, k)
    idx, d2 = np.asarray(res.indices), np.asarray(res.distances2)
    np.testing.assert_array_equal(np.asarray(res.counts), rcnt)
    np.testing.assert_array_equal(d2, rd2)
    ok = idx >= 0
    np.testing.assert_array_equal(ok, ridx >= 0)
    p, q = np.asarray(points), np.asarray(queries)
    own = np.sum((q[:, None] - p[np.clip(idx, 0, None)]) ** 2, -1)
    np.testing.assert_allclose(own[ok], d2[ok], atol=1e-5)
    for row, n in zip(idx, rcnt):
        assert len(np.unique(row[:n])) == n


def cloud(res, c):
    return jax.tree.map(lambda a: a[c], res)


# a jit wrapper of its own: jit caches are shared by every wrapper of one
# function, and tests elsewhere count api.query's compiles
_query = jax.jit(lambda index, q: api.query(index, q))


def search(points, k, queries=None):
    index = api.build_index(points, SearchParams(radius=INF, k=k))
    return _query(index, index.points if queries is None else queries)


def test_range_mode_with_no_radius_is_refused():
    with pytest.raises(ValueError, match="finite radius"):
        SearchParams(radius=INF, k=4, mode="range")
    assert not SearchParams(radius=INF, k=4).bounded
    assert SearchParams(radius=0.1, k=4, mode="range").bounded


@pytest.mark.parametrize("kind", ["session", "eager"])
def test_grid_searchers_refuse_no_radius(rng, kind):
    from repro.core import NeighborSearch, SimulationSession
    make = SimulationSession if kind == "session" else NeighborSearch
    with pytest.raises(ValueError, match="finite radius"):
        make(volume_clouds(rng, 1, 100)[0], SearchParams(radius=INF, k=4))


@pytest.mark.parametrize("arg", ["spec", "origin"])
def test_no_radius_index_takes_no_grid_arguments(rng, arg):
    from repro.core import choose_grid_spec
    pts = volume_clouds(rng, 1, 100)[0]
    kw = {"spec": choose_grid_spec(pts, 0.1)} if arg == "spec" \
        else {"origin": (0.0, 0.0, 0.0)}
    with pytest.raises(ValueError, match="no grid spec"):
        api.build_index(pts, SearchParams(radius=INF, k=4), **kw)


def test_no_radius_index_is_not_updated(rng):
    pts = volume_clouds(rng, 1, 100)[0]
    index = api.build_index(pts, SearchParams(radius=INF, k=4))
    with pytest.raises(ValueError, match="build it anew"):
        api.update_index(index, pts + 0.01)


def test_surface_cloud_matches_reference(rng):
    pts = surface_cloud(rng, 1024)
    res = search(pts, 20)
    assert_matches_reference(res, pts, pts, 20)
    # every point is its own nearest
    np.testing.assert_array_equal(np.asarray(res.indices)[:, 0],
                                  np.arange(1024))


@pytest.mark.parametrize("kind", ["surface", "volume"])
def test_batch_equals_per_cloud_calls_bitwise(rng, kind):
    if kind == "surface":
        clouds, k = np.stack([surface_cloud(rng, 512) for _ in range(4)]), 20
    else:
        clouds, k = volume_clouds(rng, 4, 700), 5
    # each cloud's last points query it: not a multiple of the tile
    queries = clouds[:, -300:]
    res = search(clouds, k, queries)
    assert np.asarray(res.indices).shape == (4, 300, k)
    for c in range(4):
        one = search(clouds[c], k, queries[c])
        for a, b in zip(jax.tree.leaves(res), jax.tree.leaves(one)):
            np.testing.assert_array_equal(np.asarray(a)[c], np.asarray(b))
        assert_matches_reference(one, clouds[c], queries[c], k)


def test_vmap_over_clouds_equals_the_batch_index(rng):
    clouds = volume_clouds(rng, 3, 300)
    params = SearchParams(radius=INF, k=5)
    per = jax.vmap(lambda x: api.query(api.build_index(x, params), x))
    for a, b in zip(jax.tree.leaves(per(clouds)),
                    jax.tree.leaves(search(clouds, 5))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_batch_never_returns_another_clouds_point(rng):
    # cloud 1 is cloud 0 moved by 1e-3: every query of either cloud has
    # the other cloud's twin of each neighbour nearer than its own next
    # neighbour; a search that crossed clouds would return (and count) it
    base = volume_clouds(rng, 1, 1500)[0]
    clouds = np.stack([base, base + np.float32(1e-3)])
    res = search(clouds, 3)
    for c in range(2):
        assert_matches_reference(cloud(res, c), clouds[c], clouds[c], 3)


def test_the_batch_program_has_no_branch_over_windows(rng):
    # every tile runs the same search of its own cloud: no lax.switch
    # (which vmap would turn into running every branch for every tile)
    index = api.build_index(volume_clouds(rng, 3, 200),
                            SearchParams(radius=INF, k=4))
    jaxpr = jax.make_jaxpr(api.query)(index, index.points)
    assert not [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "cond"]


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_far_outlier_over_a_wide_extent(rng):
    # one point a thousand cloud widths away: a grid at the cloud's
    # density would span ~10^9 cells; the whole-cloud search reads N
    pts = volume_clouds(rng, 1, 2000)[0]
    pts[-1] = 1000.0
    res = search(pts, 8)
    assert_matches_reference(res, pts, pts, 8)
    # itself, then the cube's nearest corner points
    np.testing.assert_allclose(np.asarray(res.distances2)[-1, :2],
                               [0.0, np.sum((pts[-1] - pts[:-1]) ** 2,
                                            1).min()], rtol=1e-5)


@pytest.mark.parametrize("batch", [False, True])
def test_k_above_the_clouds_points(rng, batch):
    n, k = 40, 64
    clouds = volume_clouds(rng, 3, n)
    pts = clouds if batch else clouds[0]
    res = search(pts, k)
    idx, d2 = np.asarray(res.indices), np.asarray(res.distances2)
    cnt = np.asarray(res.counts)
    assert np.all(cnt == n)
    assert np.all(idx[..., n:] == -1) and np.all(np.isinf(d2[..., n:]))
    assert np.all(np.sort(idx[..., :n], axis=-1) == np.arange(n))
    for c in range(3 if batch else 1):
        one = cloud(res, c) if batch else res
        assert_matches_reference(one, clouds[c], clouds[c], k)


def test_duplicate_points(rng):
    base = volume_clouds(rng, 1, 1500)[0]
    pts = np.concatenate([base, base[:700], base[:300]])   # 3 copies of some
    res = search(pts, 6)
    assert_matches_reference(res, pts, pts, 6)
    d2 = np.asarray(res.distances2)
    assert np.all(d2[:300, :3] == 0.0)     # a point and its two copies


def test_queries_other_than_the_points(rng):
    pts = volume_clouds(rng, 2, 2000)
    q = volume_clouds(rng, 2, 300) * 1.2 - 0.1     # some outside the cloud
    res = search(pts, 5, queries=q)
    for c in range(2):
        assert_matches_reference(cloud(res, c), pts[c], q[c], 5)


def test_queries_must_match_the_batch(rng):
    index = api.build_index(volume_clouds(rng, 2, 100),
                            SearchParams(radius=INF, k=4))
    with pytest.raises(ValueError, match="do not match"):
        api.query(index, volume_clouds(rng, 3, 10))
