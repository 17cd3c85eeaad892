"""Functional pytree-first API contract tests (DESIGN.md section 8):
parity with the eager host-planned path, composition under jit and vmap
(bitwise vs per-scene), zero mid-trace host syncs, the traced margin/
staleness contract, grad-safety, the one-shot index cache, the fused
path's refusal on a TPU backend, and the public-API snapshot."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api as api
from repro.core import (NeighborSearch, SearchOpts, SearchParams,
                        neighbor_search)
from repro.kernels.ref import brute_force_search


def _d2(res):
    d = np.asarray(res.distances2)
    return np.where(np.isinf(d), -1.0, d)


def _assert_indices_valid(res, pts, qs, radius):
    ri = np.asarray(res.indices)
    valid = ri >= 0
    rd = np.asarray(res.distances2)
    assert (rd[valid] <= radius * radius + 1e-6).all()
    recompute = np.sum(
        (np.asarray(qs)[:, None] - np.asarray(pts)[np.clip(ri, 0, None)])
        ** 2, -1)
    np.testing.assert_allclose(recompute[valid], rd[valid], atol=1e-5)


def _assert_same_up_to_ties(res_a, res_b, atol=1e-6):
    """Two search paths that expand |q-p|^2 differently (the jnp einsum
    and the fused kernel's MXU form) agree to f32 rounding, not bitwise:
    counts exact, d2 within ``atol``, and neighbor ids equal except where
    two candidates tie within ``atol`` at the K-th place."""
    ca, cb = np.asarray(res_a.counts), np.asarray(res_b.counts)
    np.testing.assert_array_equal(ca, cb)
    da, db = _d2(res_a), _d2(res_b)
    np.testing.assert_allclose(da, db, rtol=0, atol=atol)
    ia, ib = np.asarray(res_a.indices), np.asarray(res_b.indices)
    for row in np.flatnonzero((np.sort(ia, 1) != np.sort(ib, 1)).any(1)):
        kth = da[row, ca[row] - 1]
        only_a = ~np.isin(ia[row], ib[row])
        only_b = ~np.isin(ib[row], ia[row])
        assert (np.abs(da[row][only_a] - kth) <= atol).all(), row
        assert (np.abs(db[row][only_b] - kth) <= atol).all(), row


def _scene(rng, n=1500, nq=397):
    return (rng.random((n, 3)).astype(np.float32),
            rng.random((nq, 3)).astype(np.float32))


PARAMS = SearchParams(radius=0.11, k=8, knn_window="exact")


def test_query_matches_eager_neighborsearch(rng):
    """Acceptance: the traced path must match the eager host-planned
    executor exactly — knn distances bitwise (both paths run the identical
    per-tile ops; bundling may widen eager windows but the exact-window
    guarantee makes the k-nearest set identical) and counts bitwise."""
    pts, qs = _scene(rng)
    res_e = NeighborSearch(pts, PARAMS, SearchOpts()).query(qs)
    res_f = api.query(api.build_index(pts, PARAMS, SearchOpts()), qs)
    np.testing.assert_array_equal(_d2(res_e), _d2(res_f))
    np.testing.assert_array_equal(np.asarray(res_e.counts),
                                  np.asarray(res_f.counts))
    _assert_indices_valid(res_f, pts, qs, PARAMS.radius)
    # and against the brute-force oracle
    _oi, od, oc = brute_force_search(jnp.asarray(pts), jnp.asarray(qs),
                                     PARAMS.radius, PARAMS.k)
    np.testing.assert_allclose(
        _d2(res_f), np.where(np.isinf(np.asarray(od)), -1.0,
                             np.asarray(od)), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(oc), np.asarray(res_f.counts))


def test_query_under_jit_zero_host_syncs(rng):
    """jax.jit(api.query) must trace end-to-end — any mid-trace host sync
    (np.asarray / device_get on a tracer) raises TracerArrayConversionError
    — produce bitwise-identical results, and compile exactly once."""
    pts, qs = _scene(rng)
    index = api.build_index(pts, PARAMS, SearchOpts())
    jitted = jax.jit(api.query)
    res_j = jitted(index, qs)
    res_f = api.query(index, qs)
    np.testing.assert_array_equal(np.asarray(res_j.indices),
                                  np.asarray(res_f.indices))
    np.testing.assert_array_equal(_d2(res_j), _d2(res_f))
    np.testing.assert_array_equal(np.asarray(res_j.counts),
                                  np.asarray(res_f.counts))
    jitted(index, qs)
    assert jitted._cache_size() == 1


def test_vmap_over_stacked_scenes_bitwise(rng):
    """Acceptance: vmap over 4 stacked independent same-spec scenes matches
    the per-scene results bitwise — multi-scene batching is just vmap."""
    params = SearchParams(radius=0.1, k=8, knn_window="exact")
    scenes = [rng.random((1200, 3)).astype(np.float32) for _ in range(4)]
    qss = [rng.random((256, 3)).astype(np.float32) for _ in range(4)]
    index0 = api.build_index(scenes[0], params, SearchOpts())
    spec = index0.spec
    idxs = [index0] + [api.build_index(s, params, SearchOpts(), spec=spec)
                       for s in scenes[1:]]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *idxs)
    qstack = jnp.stack([jnp.asarray(q) for q in qss])
    bat = jax.jit(jax.vmap(api.query))(stacked, qstack)
    for b in range(4):
        one = api.query(idxs[b], qss[b])
        np.testing.assert_array_equal(np.asarray(bat.indices[b]),
                                      np.asarray(one.indices))
        np.testing.assert_array_equal(np.asarray(bat.distances2[b]),
                                      np.asarray(one.distances2))
        np.testing.assert_array_equal(np.asarray(bat.counts[b]),
                                      np.asarray(one.counts))


def test_build_index_traceable_with_explicit_spec(rng):
    """build_index is pure given a spec (composes under jit); without one
    it needs concrete points and must say so under a trace."""
    pts, qs = _scene(rng, n=800, nq=128)
    spec = api.build_index(pts, PARAMS).spec
    res = jax.jit(
        lambda p, q: api.query(api.build_index(p, PARAMS, spec=spec), q)
    )(pts, qs)
    ref = api.query(api.build_index(pts, PARAMS, spec=spec), qs)
    np.testing.assert_array_equal(np.asarray(res.indices),
                                  np.asarray(ref.indices))
    with pytest.raises(TypeError, match="choose_grid_spec"):
        jax.jit(lambda p: api.build_index(p, PARAMS))(pts)


def test_update_index_matches_fresh_build(rng):
    pts, qs = _scene(rng)
    index = api.build_index(pts, PARAMS, SearchOpts())
    moved = np.clip(pts + rng.normal(0, 0.004, pts.shape), 0.0,
                    1.0).astype(np.float32)
    index2, stats = api.update_index(index, moved)
    assert int(stats.overflow) >= 0 and int(stats.oob) == 0
    np.testing.assert_allclose(
        float(stats.max_disp2),
        np.max(np.sum((moved - pts) ** 2, axis=-1)), rtol=1e-6)
    fresh = api.build_index(moved, PARAMS, SearchOpts(), spec=index.spec)
    res_u = api.query(index2, qs)
    res_f = api.query(fresh, qs)
    np.testing.assert_array_equal(np.asarray(res_u.indices),
                                  np.asarray(res_f.indices))
    np.testing.assert_array_equal(_d2(res_u), _d2(res_f))


def test_margin_plan_stays_exact_under_drift(rng):
    """The traced staleness contract: a plan captured with margin=2 stays
    exact (knn distances/counts vs a fresh plan at the NEW positions) while
    every point drifts less than half a cell — the session's lax.cond
    replay branch is sound."""
    pts, _ = _scene(rng, n=1200)
    index = api.build_index(pts, PARAMS, SearchOpts())
    plan = api.plan_query(index, pts, margin=2)
    cell = index.spec.cell_size
    # bounded drift: per-axis uniform keeps every |delta| < 0.4 * cell
    delta = rng.uniform(-0.4 * cell / np.sqrt(3), 0.4 * cell / np.sqrt(3),
                        pts.shape).astype(np.float32)
    moved = np.clip(pts + delta, 0.0, 1.0).astype(np.float32)
    index2, _stats = api.update_index(index, moved)
    replayed = api.execute_plan(index2, moved, plan)
    fresh = api.query(
        api.build_index(moved, PARAMS, SearchOpts(), spec=index.spec), moved)
    np.testing.assert_array_equal(_d2(replayed), _d2(fresh))
    np.testing.assert_array_equal(np.asarray(replayed.counts),
                                  np.asarray(fresh.counts))


def test_explicit_w_ladder_stays_exact(rng):
    """SearchOpts.w_ladder coarsens the traced switch ladder; queries round
    up to the nearest ladder window, so results stay exact."""
    pts, qs = _scene(rng)
    res_ref = api.query(api.build_index(pts, PARAMS, SearchOpts()), qs)
    res_lad = api.query(
        api.build_index(pts, PARAMS, SearchOpts(w_ladder=(2,))), qs)
    np.testing.assert_array_equal(_d2(res_ref), _d2(res_lad))
    np.testing.assert_array_equal(np.asarray(res_ref.counts),
                                  np.asarray(res_lad.counts))


def test_w_ladder_with_partitioning_disabled_stays_exact(rng):
    """Regression: with partitioning inactive there are no per-query
    levels, so an explicit (smaller-than-full) ladder must not shadow the
    full-radius window — every query still searches w_full."""
    pts, qs = _scene(rng)
    res_ref = api.query(
        api.build_index(pts, PARAMS, SearchOpts(partition=False)), qs)
    res_lad = api.query(
        api.build_index(pts, PARAMS,
                        SearchOpts(partition=False, w_ladder=(1,))), qs)
    np.testing.assert_array_equal(_d2(res_ref), _d2(res_lad))
    np.testing.assert_array_equal(np.asarray(res_ref.indices),
                                  np.asarray(res_lad.indices))
    np.testing.assert_array_equal(np.asarray(res_ref.counts),
                                  np.asarray(res_lad.counts))


# ---------------------------------------------------------------------------
# single-program Pallas pipeline (level-segmented launches, DESIGN.md s3)
# ---------------------------------------------------------------------------

PALLAS_OPTS = SearchOpts(use_pallas=True, query_tile=128)


def test_pallas_traced_bitwise_parity_under_jit(rng):
    """Acceptance: jax.jit(api.query) with SearchOpts(use_pallas=True)
    compiles the level-segmented fused path end-to-end and matches the jnp
    traced path: counts exactly, distances to f32 rounding, neighbor ids
    up to ties."""
    pts, qs = _scene(rng)
    res_j = api.query(api.build_index(pts, PARAMS, SearchOpts()), qs)
    index_p = api.build_index(pts, PARAMS, PALLAS_OPTS)
    jitted = jax.jit(api.query)
    res_p = jitted(index_p, qs)
    _assert_same_up_to_ties(res_j, res_p)
    _assert_indices_valid(res_p, pts, qs, PARAMS.radius)
    # one compiled program, reused on the second call (the jit cache is
    # shared across jax.jit(api.query) wrappers, so assert no growth)
    cache = jitted._cache_size()
    jitted(index_p, qs)
    assert jitted._cache_size() == cache


def test_pallas_traced_vmap_bitwise(rng):
    """Acceptance: vmap over stacked same-spec scenes through the fused
    path matches the per-scene results bitwise — the level-segmented
    launches (one masked kernel launch per ladder level) batch where the
    per-tile lax.switch would have executed every branch."""
    params = SearchParams(radius=0.1, k=8, knn_window="exact")
    scenes = [rng.random((1200, 3)).astype(np.float32) for _ in range(3)]
    qss = [rng.random((256, 3)).astype(np.float32) for _ in range(3)]
    index0 = api.build_index(scenes[0], params, PALLAS_OPTS)
    idxs = [index0] + [api.build_index(s, params, PALLAS_OPTS,
                                       spec=index0.spec)
                       for s in scenes[1:]]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *idxs)
    qstack = jnp.stack([jnp.asarray(q) for q in qss])
    bat = jax.jit(jax.vmap(api.query))(stacked, qstack)
    for b in range(3):
        one = api.query(idxs[b], qss[b])
        np.testing.assert_array_equal(np.asarray(bat.distances2[b]),
                                      np.asarray(one.distances2))
        np.testing.assert_array_equal(np.asarray(bat.counts[b]),
                                      np.asarray(one.counts))


def test_pallas_traced_range_mode_counts(rng):
    """The skip-sphere-test entries of the segmented ladder are exact:
    range-mode counts match the oracle and every returned index is within
    the radius (the megacell that held >= K in-sphere points stays inside
    the escalated shared window, bounding the streamed top-K)."""
    pts, qs = _scene(rng)
    params = SearchParams(radius=0.1, k=8, mode="range")
    res = jax.jit(api.query)(api.build_index(pts, params, PALLAS_OPTS), qs)
    _oi, _od, oc = brute_force_search(jnp.asarray(pts), jnp.asarray(qs),
                                      params.radius, params.k)
    np.testing.assert_array_equal(np.asarray(oc), np.asarray(res.counts))
    _assert_indices_valid(res, pts, qs, params.radius)


def test_segment_launches_safety_valve(rng, monkeypatch):
    """REPRO_SEGMENT_LAUNCHES=0 (read per call, not at import) falls the
    fused traced path back to the jnp lax.switch dispatch — the results
    agree either way (counts exactly, distances to f32 rounding)."""
    pts, qs = _scene(rng, n=900, nq=200)
    index = api.build_index(pts, PARAMS, PALLAS_OPTS)
    res_seg = api.query(index, qs)
    monkeypatch.setenv("REPRO_SEGMENT_LAUNCHES", "0")
    res_jnp = api.query(index, qs)
    jaxpr = str(jax.make_jaxpr(api.query)(index, qs))
    assert "pallas_call" not in jaxpr          # valve really took the exit
    _assert_same_up_to_ties(res_seg, res_jnp)


def test_pallas_anchors_on_device_zero_host_syncs(rng):
    """Anchors-on-device: a jitted execute_plan over the fused path must
    trace end-to-end (any mid-trace host sync — np.asarray / device_get on
    a tracer, as the old host-metadata anchor computation did — raises
    TracerArrayConversionError) and compile exactly once (trace-counting
    pattern from tests/test_executor.py)."""
    pts, qs = _scene(rng, n=900, nq=200)
    index = api.build_index(pts, PARAMS, PALLAS_OPTS)

    jitted = jax.jit(api.execute_plan)
    plan = api.plan_query(index, qs)
    res = jitted(index, qs, plan)
    ref = api.execute_plan(index, qs, plan)
    np.testing.assert_array_equal(_d2(res), _d2(ref))
    cache = jitted._cache_size()
    jitted(index, qs, plan)
    assert jitted._cache_size() == cache
    # and the fused kernel really is on the traced path: the jaxpr contains
    # one pallas launch per segment-ladder level (the masked launches),
    # not a lax.switch over window branches
    from repro.kernels.ops import segment_levels
    jaxpr = str(jax.make_jaxpr(api.execute_plan)(index, qs, plan))
    n_levels = len(segment_levels(plan.ladder, index.spec.dims))
    assert jaxpr.count("pallas_call") == n_levels


@pytest.mark.parametrize("surface", ["build_index", "NeighborSearch",
                                     "SimulationSession"])
def test_fused_path_refused_on_tpu_backend(rng, monkeypatch, surface):
    """On a TPU backend the fused kNN kernel's in-kernel gathers do not
    compile, so every surface refuses use_pallas=True up front, naming
    the gather — never interpreting it, never taking the jnp path."""
    from repro.core import SimulationSession
    pts, _ = _scene(rng, n=300, nq=8)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    build = {"build_index": api.build_index,
             "NeighborSearch": NeighborSearch,
             "SimulationSession": SimulationSession}[surface]
    with pytest.raises(NotImplementedError, match="gather"):
        build(pts, PARAMS, PALLAS_OPTS)
    # the default jnp path is what runs there, and it is not refused
    assert api.build_index(pts, PARAMS).points.shape == pts.shape


def test_grad_safety(rng):
    """Distances are differentiable w.r.t. the query positions through the
    whole traced pipeline (schedule sort, switch dispatch, top-k, scatter)."""
    pts, qs = _scene(rng, n=600, nq=128)
    index = api.build_index(pts, PARAMS, SearchOpts())

    def loss(q):
        res = api.query(index, q)
        return jnp.sum(jnp.where(jnp.isinf(res.distances2), 0.0,
                                 res.distances2))

    g = jax.grad(loss)(jnp.asarray(qs))
    assert g.shape == qs.shape
    assert bool(jnp.all(jnp.isfinite(g)))
    assert float(jnp.abs(g).sum()) > 0.0


def test_one_shot_cache_reuses_searcher(rng):
    """Satellite contract: repeated one-shot neighbor_search over the same
    point set must reuse ONE cached searcher (plan/compile caches warm)
    instead of rebuilding per call."""
    api.searcher_cache_clear()
    pts, qs = _scene(rng, n=900, nq=200)
    ns1 = api.cached_searcher(pts, PARAMS)
    ns2 = api.cached_searcher(pts, PARAMS)
    assert ns1 is ns2
    assert api.searcher_cache_stats()["entries"] == 1
    res1 = neighbor_search(pts, qs, PARAMS.radius, PARAMS.k)
    res2 = neighbor_search(pts, qs, PARAMS.radius, PARAMS.k)
    # one-shot calls with the same (points, params, opts) hit the same entry
    assert api.searcher_cache_stats()["entries"] == 1
    np.testing.assert_array_equal(np.asarray(res1.indices),
                                  np.asarray(res2.indices))
    other = rng.random((900, 3)).astype(np.float32)
    assert api.cached_searcher(other, PARAMS) is not ns1
    assert api.searcher_cache_stats()["entries"] == 2
    api.searcher_cache_clear()


# ---------------------------------------------------------------------------
# public-API snapshot
# ---------------------------------------------------------------------------

# Frozen export list of repro.api. If this assertion fails you changed the
# public surface: update the snapshot AND add a CHANGES.md note in the same
# commit.
API_SNAPSHOT = (
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
)


def test_public_api_snapshot():
    assert tuple(sorted(api.__all__)) == API_SNAPSHOT, (
        "repro.api exports changed — update API_SNAPSHOT in tests/test_api.py"
        " and record the change in CHANGES.md")
    for name in API_SNAPSHOT:
        assert callable(getattr(api, name)) or hasattr(api, name)
