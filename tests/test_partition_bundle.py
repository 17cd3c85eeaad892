"""Megacell partitioning (section 5.1) + bundling theorem (appendix C)."""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.bundle import (Bundle, CostModel, exhaustive_best,
                               plan_bundles, total_cost)
from repro.core.grid import build_cell_grid, choose_grid_spec
from repro.core.partition import (Partition, compute_megacells,
                                  megacell_statics, plan_partitions)
from repro.core.types import SearchParams


def test_megacell_count_satisfies_k(rng):
    pts = rng.random((3000, 3)).astype(np.float32)
    qs = rng.random((300, 3)).astype(np.float32)
    params = SearchParams(radius=0.25, k=8)
    spec = choose_grid_spec(pts, radius=0.05, cell_size=0.05)
    grid = build_cell_grid(jnp.asarray(pts), spec)
    st_ = megacell_statics(spec.cell_size, params, w_max=6)
    assert st_.has_megacells
    w_search, skip, rho = compute_megacells(grid, jnp.asarray(qs), st_,
                                            params)
    assert (np.asarray(w_search) >= 0).all()
    assert (np.asarray(w_search) <= st_.w_full).all()
    assert (np.asarray(rho) > 0).all()


def test_partition_grouping_is_a_permutation(rng):
    w = jnp.asarray(rng.integers(0, 4, 100), jnp.int32)
    skip = jnp.asarray(rng.integers(0, 2, 100).astype(bool))
    rho = jnp.ones((100,), jnp.float32)
    plan = plan_partitions(w, skip, rho, w_full=5)
    assert sorted(plan.perm.tolist()) == list(range(100))
    assert sum(p.count for p in plan.partitions) == 100
    # members of each partition share (w, skip)
    for p in plan.partitions:
        sel = plan.perm[p.start: p.start + p.count]
        assert (np.asarray(w)[sel] == p.w_search).all()
        assert (np.asarray(skip)[sel] == p.skip_test).all()


def _mk_parts(ns, ws):
    """Partitions with the paper's inverse N<->S correlation: sort so the
    largest query count gets the smallest window."""
    ns = sorted(ns, reverse=True)
    ws = sorted(set(ws))[: len(ns)]
    while len(ws) < len(ns):
        ws.append(ws[-1] + 1)
    parts, start = [], 0
    k = 8
    out = []
    for n, w in zip(ns, ws):
        rho = k / ((2 * w + 1) * 0.1) ** 3
        out.append(Partition(w_search=w, skip_test=False, count=n, rho=rho,
                             start=start))
        start += n
    return out


def _assert_planned_is_optimal(parts):
    model = CostModel()
    kw = dict(n_points=50_000, cell_size=0.1, mode="knn", k=8, w_sph=10)
    planned = plan_bundles(parts, model, **kw)
    best, best_cost = exhaustive_best(parts, model, **kw)
    got_cost = total_cost(planned, parts, model,
                          n_points=50_000, cell_size=0.1, mode="knn", k=8)
    assert got_cost <= best_cost * (1 + 1e-9), (got_cost, best_cost)


@given(st.lists(st.integers(1, 10000), min_size=1, max_size=6, unique=True),
       st.lists(st.integers(1, 8), min_size=1, max_size=6))
@settings(deadline=None, max_examples=40)
def test_bundling_matches_exhaustive_under_inverse_correlation(ns, ws):
    """Appendix C theorem: the linear-scan suffix-merge strategy achieves
    the exhaustive optimum when N and S are inversely correlated. Query
    counts are distinct: with four equal counts (no correlation at all)
    the optimum pairs the partitions into two merged bundles, a shape the
    one-merged-bundle strategy cannot take."""
    _assert_planned_is_optimal(_mk_parts(ns, ws))


def test_bundling_equal_counts_merge_widest_first():
    """Equal query counts: the widest windows are merged first (three
    single-query partitions at windows 7, 8, 9 — merging 8 and 9 is the
    optimum; merging 7 and 8 is not)."""
    _assert_planned_is_optimal(_mk_parts([1, 1, 1], [7]))


def test_bundling_disabled_is_listing3(rng):
    parts = _mk_parts([100, 50, 10], [1, 2, 3])
    model = CostModel()
    kw = dict(n_points=1000, cell_size=0.1, mode="knn", k=8, w_sph=10)
    bundles = plan_bundles(parts, model, enable=False, **kw)
    assert len(bundles) == 3
    assert all(len(b.members) == 1 for b in bundles)


def test_bundle_skip_test_conservative():
    """A merged bundle may only skip the sphere test if every member could
    AND the merged window stays sphere-inscribed."""
    parts = [
        Partition(w_search=1, skip_test=True, count=10, rho=1.0, start=0),
        Partition(w_search=4, skip_test=True, count=5, rho=1.0, start=10),
    ]
    model = CostModel(k_knn=1e12)  # force maximal merging
    bundles = plan_bundles(parts, model, n_points=100, cell_size=0.1,
                           mode="range", k=8, w_sph=2)
    merged = [b for b in bundles if len(b.members) == 2]
    for b in merged:
        assert not b.skip_test  # w=4 > w_sph=2 -> must keep the test


def test_range_cost_model_prefers_fewer_builds_when_search_cheap():
    parts = _mk_parts([1000, 900, 800], [1, 2, 3])
    model = CostModel(k_range_skip=1e-9, k_range_test=1e-9)
    bundles = plan_bundles(parts, model, n_points=10_000, cell_size=0.1,
                           mode="range", k=8, w_sph=10)
    assert len(bundles) == 1  # build cost dominates -> single bundle
