"""QueryExecutor contract tests (DESIGN.md section 3): oracle equivalence
of the batched/async path, the one-sync contract, and zero-recompilation
steady state."""
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import NeighborSearch, SearchOpts, SearchParams
from repro.kernels.ref import brute_force_search


def _result_tuple(res):
    d2 = np.asarray(res.distances2)
    return (np.asarray(res.indices), np.where(np.isinf(d2), -1.0, d2),
            np.asarray(res.counts))


@pytest.mark.parametrize("mode", ["knn", "range"])
@pytest.mark.parametrize("schedule,partition", list(
    itertools.product([False, True], repeat=2)))
def test_executor_identical_to_host_loop(rng, mode, schedule, partition):
    """The executor is a pure re-orchestration: same launches, same math —
    results must be bit-identical to the legacy per-bundle host loop,
    including padded-bucket edge rows (397 is never a bucket multiple)."""
    pts = rng.random((1800, 3)).astype(np.float32)
    qs = rng.random((397, 3)).astype(np.float32)
    params = SearchParams(radius=0.11, k=8, mode=mode, knn_window="exact")
    kw = dict(schedule=schedule, partition=partition)
    res_old = NeighborSearch(pts, params,
                             SearchOpts(executor=False, **kw)).query(qs)
    res_new = NeighborSearch(pts, params,
                             SearchOpts(executor=True, **kw)).query(qs)
    for a, b in zip(_result_tuple(res_old), _result_tuple(res_new)):
        np.testing.assert_array_equal(a, b)


def test_executor_matches_ref_oracle(rng):
    """End-to-end against kernels/ref: distances^2 and counts exact, every
    returned index verified by distance recomputation (tie-safe)."""
    pts = rng.random((2200, 3)).astype(np.float32)
    qs = rng.random((500, 3)).astype(np.float32)
    r, k = 0.1, 8
    res = NeighborSearch(pts, SearchParams(radius=r, k=k, knn_window="exact"),
                         SearchOpts()).query(qs)
    oi, od, oc = brute_force_search(jnp.asarray(pts), jnp.asarray(qs), r, k)
    d_ref = np.where(np.isinf(np.asarray(od)), -1.0, np.asarray(od))
    d_got = np.where(np.isinf(np.asarray(res.distances2)), -1.0,
                     np.asarray(res.distances2))
    np.testing.assert_allclose(d_got, d_ref, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(oc), np.asarray(res.counts))
    ri = np.asarray(res.indices)
    valid = ri >= 0
    recompute = np.sum((qs[:, None] - pts[np.clip(ri, 0, None)]) ** 2, -1)
    np.testing.assert_allclose(recompute[valid],
                               np.asarray(res.distances2)[valid], atol=1e-5)


def test_executor_pallas_path_matches(rng):
    pts = rng.random((1500, 3)).astype(np.float32)
    qs = rng.random((300, 3)).astype(np.float32)
    params = SearchParams(radius=0.1, k=8, knn_window="exact")
    res_j = NeighborSearch(pts, params, SearchOpts()).query(qs)
    ns_p = NeighborSearch(pts, params,
                          SearchOpts(use_pallas=True, query_tile=128))
    res_p = ns_p.query(qs)
    np.testing.assert_allclose(
        np.where(np.isinf(np.asarray(res_j.distances2)), -1,
                 np.asarray(res_j.distances2)),
        np.where(np.isinf(np.asarray(res_p.distances2)), -1,
                 np.asarray(res_p.distances2)), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(res_j.counts),
                                  np.asarray(res_p.counts))
    # the pallas plan fetch carries the query cells in the same transfer
    assert ns_p.executor.stats()["last"]["host_syncs"] == 1


def test_one_sync_contract(rng):
    """Exactly one blocking result sync per query(); partitioning adds at
    most one small plan-metadata fetch (the host launch orchestration)."""
    pts = rng.random((2000, 3)).astype(np.float32)
    qs = rng.random((400, 3)).astype(np.float32)
    ns = NeighborSearch(pts, SearchParams(radius=0.09, k=8), SearchOpts())
    ns.query(qs)
    last = ns.executor.stats()["last"]
    assert last["host_syncs"] == 1
    assert last["plan_fetches"] <= 1
    assert ns.report.host_syncs == 1
    # without partitioning there is no data-dependent plan: zero fetches
    ns2 = NeighborSearch(pts, SearchParams(radius=0.09, k=8),
                         SearchOpts(partition=False))
    ns2.query(qs)
    last2 = ns2.executor.stats()["last"]
    assert last2["host_syncs"] == 1
    assert last2["plan_fetches"] == 0


def test_execute_async_overlap_matches_execute(rng):
    """Dispatch-then-stage carryover: two batches dispatched before either
    syncs return results identical to the blocking path, each paying its
    own single host sync at wait()."""
    pts = rng.random((1800, 3)).astype(np.float32)
    qa = rng.random((384, 3)).astype(np.float32)
    qb = rng.random((384, 3)).astype(np.float32)
    ns = NeighborSearch(pts, SearchParams(radius=0.09, k=8), SearchOpts())
    ref_a, ref_b = ns.query(qa), ns.query(qb)

    pa = ns.executor.execute_async(qa)      # both in flight before any sync
    pb = ns.executor.execute_async(qb)
    got_b = pb.wait()                       # out-of-order sync is fine
    got_a = pa.wait()
    for got, ref in ((got_a, ref_a), (got_b, ref_b)):
        np.testing.assert_array_equal(np.asarray(got.indices),
                                      np.asarray(ref.indices))
        np.testing.assert_array_equal(np.asarray(got.counts),
                                      np.asarray(ref.counts))
    last = ns.executor.stats()["last"]
    assert last["host_syncs"] == 1          # per-batch, not accumulated
    assert last["plan_cache_hit"] and last["launcher_cache_hit"]
    assert pa.wait() is got_a               # idempotent
    assert pa.done() and pb.done()


def test_signature_batching_folds_bundles(rng):
    """Bundles sharing (w_search, skip_test) must fold into one launch:
    launches <= bundles always, and == unique signatures."""
    pts = np.concatenate([
        rng.random((3000, 3)) * 0.25,                    # dense cluster
        rng.random((300, 3)) * 0.75 + 0.25,              # sparse remainder
    ]).astype(np.float32)
    qs = pts[rng.integers(0, len(pts), 500)]
    ns = NeighborSearch(pts, SearchParams(radius=0.08, k=16, mode="range"),
                        SearchOpts(bundle=False))   # 1 bundle per partition
    ns.query(qs)
    sigs = {(b.w_search, b.skip_test) for b in ns.report.bundles}
    assert ns.report.launches == len(sigs)
    assert ns.report.launches <= len(ns.report.bundles)


def test_second_query_zero_recompiles(rng):
    """Steady state (SPH stepping): a repeat same-shape query must hit the
    plan cache and compile nothing."""
    from repro.core.search import window_search

    pts = rng.random((2000, 3)).astype(np.float32)
    qs = rng.random((384, 3)).astype(np.float32)
    ns = NeighborSearch(pts, SearchParams(radius=0.1, k=8), SearchOpts())
    ns.executor.warmup(qs)
    jit_before = window_search._cache_size()
    ns.query(qs)
    st = ns.executor.stats()
    assert st["last"]["compilations"] == 0
    assert st["last"]["plan_cache_hit"]
    assert window_search._cache_size() == jit_before
    # same-shape but different values: plan may differ, compiles must not
    # (padded-N bucketing bounds the signature set)
    qs2 = rng.random((384, 3)).astype(np.float32)
    jit_before = window_search._cache_size()
    ns.query(qs2)
    assert window_search._cache_size() == jit_before


def test_drifting_queries_reuse_compiled_schedule(rng):
    """The SPH regime: query values drift step to step, partition counts
    shift within the same padded buckets — the compiled launch schedule
    must be reused (launcher cache keyed by buckets, not exact counts)."""
    pts = rng.random((2000, 3)).astype(np.float32)
    qs = rng.random((384, 3)).astype(np.float32)
    ns = NeighborSearch(pts, SearchParams(radius=0.1, k=8), SearchOpts())
    ns.executor.warmup(qs)
    for _ in range(3):
        qs = np.clip(qs + rng.normal(0, 0.002, qs.shape).astype(np.float32),
                     0, 1)
        ns.query(qs)
        st = ns.executor.stats()
        assert st["last"]["compilations"] == 0
        assert st["launcher_cache_entries"] == 1


def test_warmup_stats_surface(rng):
    pts = rng.random((1000, 3)).astype(np.float32)
    qs = rng.random((200, 3)).astype(np.float32)
    ns = NeighborSearch(pts, SearchParams(radius=0.1, k=4), SearchOpts())
    st = ns.executor.warmup(qs)
    assert st["queries"] == 1
    assert st["launches"] >= 1
    assert st["signatures"] >= 1
    assert "jit_cache_sizes" in st
    assert ns.report.t_search > 0


def test_capture_plan_replay_matches_direct_query(rng):
    """capture_plan/execute(reuse=...) is public eager surface (the session
    now replays plans on device, core/api.py, but eager steppers can still
    capture once and replay): a replayed margin-inflated plan must match a
    direct query exactly in knn mode, with zero host planning on replay."""
    pts = rng.random((1500, 3)).astype(np.float32)
    qs = rng.random((384, 3)).astype(np.float32)
    params = SearchParams(radius=0.1, k=8, knn_window="exact")
    ns = NeighborSearch(pts, params, SearchOpts())
    handle = ns.executor.capture_plan(qs, margin=1)
    res_r = ns.executor.execute(qs, reuse=handle)
    res_d = NeighborSearch(pts, params, SearchOpts()).query(qs)
    for a, b in zip(_result_tuple(res_r), _result_tuple(res_d)):
        d2a, d2b = np.asarray(a), np.asarray(b)
        if d2a.dtype == np.float32 or d2a.dtype == np.float64:
            np.testing.assert_array_equal(d2a, d2b)
    np.testing.assert_array_equal(np.asarray(res_r.counts),
                                  np.asarray(res_d.counts))
    last = ns.executor.stats()["last"]
    assert last["plan_reused"] and last["plan_fetches"] == 0


def test_cache_hit_miss_accounting(rng):
    """The unified-registry counters tell the full plan/compile cache
    story: misses on first sight, hits on repeats, a fresh shape is a new
    miss, and invalidate() starts the count again from cold."""
    pts = rng.random((1500, 3)).astype(np.float32)
    qs = rng.random((384, 3)).astype(np.float32)
    ns = NeighborSearch(pts, SearchParams(radius=0.1, k=8), SearchOpts())
    ex = ns.executor

    ns.query(qs)                       # cold: both caches miss
    st = ex.stats()
    assert st["plan_cache_misses"] == 1 and st["plan_cache_hits"] == 0
    assert st["launcher_cache_misses"] == 1
    assert st["launcher_cache_hits"] == 0

    ns.query(qs)                       # repeat: both caches hit
    st = ex.stats()
    assert st["plan_cache_hits"] == 1 and st["plan_cache_misses"] == 1
    assert st["launcher_cache_hits"] == 1
    assert st["launcher_cache_misses"] == 1
    assert st["last"]["plan_cache_hit"]
    assert st["last"]["launcher_cache_hit"]

    qs2 = rng.random((512, 3)).astype(np.float32)
    ns.query(qs2)                      # new shape: new plan, new launcher
    st = ex.stats()
    assert st["plan_cache_misses"] == 2
    assert st["launcher_cache_misses"] == 2
    assert not st["last"]["plan_cache_hit"]

    ex.invalidate()                    # respec analogue: cold again
    st = ex.stats()
    assert st["invalidations"] == 1
    assert st["plan_cache_entries"] == 0
    assert st["launcher_cache_entries"] == 0
    ns.query(qs)
    st = ex.stats()
    assert st["plan_cache_misses"] == 3
    assert not st["last"]["plan_cache_hit"]


def test_warmup_yields_zero_compile_misses(rng):
    """warmup() populates both caches: the next same-shape query must see
    zero compile (launcher) misses and a plan-cache hit."""
    pts = rng.random((1200, 3)).astype(np.float32)
    qs = rng.random((256, 3)).astype(np.float32)
    ns = NeighborSearch(pts, SearchParams(radius=0.1, k=8), SearchOpts())
    ns.executor.warmup(qs)
    before = ns.executor.stats()["launcher_cache_misses"]
    ns.query(qs)
    st = ns.executor.stats()
    assert st["launcher_cache_misses"] == before
    assert st["last"]["compilations"] == 0
    assert st["last"]["plan_cache_hit"]


def test_launcher_program_carries_the_search_scopes(rng):
    """The executor's one launch program runs under the scope of
    ``api.execute_plan``, with the per-tile search's stage scopes inside,
    so the device-trace metrics read it by the same names; the plan
    fetch is a ``sync`` of its own inside ``plan``."""
    import re

    from repro import obs
    pts = rng.random((1200, 3)).astype(np.float32)
    qs = jnp.asarray(rng.random((300, 3)).astype(np.float32))
    params = SearchParams(radius=0.11, k=8, knn_window="exact")
    ns = NeighborSearch(pts, params)
    obs.configure(mode="log")
    try:
        h = ns.executor.capture_plan(qs)
        paths = {s["path"] for s in obs.recent_spans()}
    finally:
        obs.configure()
    assert "plan/sync" in paths
    launcher = ns.executor._get_launcher(h.groups, h.nq)
    k = params.k
    text = launcher.lower(
        ns.grid, ns.points, qs[h.perm], h.perm, h.sels_dev,
        jnp.full((h.nq, k), -1, jnp.int32),
        jnp.full((h.nq, k), jnp.inf, jnp.float32),
        jnp.zeros((h.nq,), jnp.int32)).compile().as_text()
    op_names = re.findall(r'op_name="([^"]+)"', text)
    for stage in ("window_gather", "row_gather", "distance", "select"):
        assert any(re.search(rf"repro\.execute_plan/.*repro\.search\.{stage}",
                             n) for n in op_names), stage
