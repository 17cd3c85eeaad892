"""SimulationSession contract tests (DESIGN.md sections 7-8): per-step
exactness against a fresh-search oracle on moving points (including across
respecs), the device-resident staleness steady state (zero host
replanning, zero per-step stats fetches, zero retraces), and the update
kernel itself."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SearchOpts, SearchParams, SessionOpts,
                        SimulationSession, update_cell_grid)
from repro.kernels.ref import brute_force_search


def _assert_oracle_exact(res, pts, qs, radius, k, mode="knn"):
    """Counts exact and every returned index verified by distance
    recomputation; in knn mode the distance multiset is exact too (range
    mode returns *any* bounded-K in-radius subset per the paper's
    interface, so only counts/validity are contractual — mirroring
    test_search.test_range_counts_and_radius)."""
    _oi, od, oc = brute_force_search(jnp.asarray(pts), jnp.asarray(qs),
                                     radius, k)
    np.testing.assert_array_equal(np.asarray(oc), np.asarray(res.counts))
    if mode == "knn":
        d_ref = np.where(np.isinf(np.asarray(od)), -1.0, np.asarray(od))
        d_got = np.where(np.isinf(np.asarray(res.distances2)), -1.0,
                         np.asarray(res.distances2))
        np.testing.assert_allclose(d_got, d_ref, atol=1e-5)
    ri = np.asarray(res.indices)
    valid = ri >= 0
    rd = np.asarray(res.distances2)
    assert (rd[valid] <= radius * radius + 1e-6).all()
    recompute = np.sum(
        (np.asarray(qs)[:, None] - np.asarray(pts)[np.clip(ri, 0, None)])
        ** 2, -1)
    np.testing.assert_allclose(recompute[valid], rd[valid], atol=1e-5)


def _drift(rng, pts, sigma):
    return np.clip(pts + rng.normal(0, sigma, pts.shape), 0.0,
                   1.0).astype(np.float32)


@pytest.mark.parametrize("mode", ["knn", "range"])
def test_session_exact_on_moving_sequence(rng, mode):
    """Randomized moving-point sequence: every step — fast replays and
    replans alike — must match the brute-force oracle on the *current*
    positions."""
    pts = rng.random((1400, 3)).astype(np.float32)
    params = SearchParams(radius=0.1, k=8, mode=mode, knn_window="exact")
    sess = SimulationSession(pts, params)
    saw_fast = saw_replan = False
    for _ in range(7):
        res = sess.step(pts)
        _assert_oracle_exact(res, pts, pts, 0.1, 8, mode)
        saw_fast |= sess.report.fast
        saw_replan |= sess.report.replanned
        pts = _drift(rng, pts, 0.002)
    assert saw_fast and saw_replan      # both regimes actually exercised
    assert sess.stats()["respecs"] == 0


def test_session_external_queries_exact(rng):
    """Queries independent of the points, both moving."""
    pts = rng.random((1200, 3)).astype(np.float32)
    qs = rng.random((300, 3)).astype(np.float32)
    params = SearchParams(radius=0.12, k=8, knn_window="exact")
    sess = SimulationSession(pts, params)
    for _ in range(5):
        res = sess.step(pts, qs)
        _assert_oracle_exact(res, pts, qs, 0.12, 8)
        pts = _drift(rng, pts, 0.002)
        qs = _drift(rng, qs, 0.002)


def test_session_steady_state_zero_host_replanning(rng):
    """THE acceptance property: below-threshold steps perform no host-side
    work at all — the staleness decision is a device `lax.cond` (plan
    replayed on device), the per-step stats fetch is gone (stats_fetches
    stays 0), and the fused step program is not retraced."""
    pts = rng.random((1500, 3)).astype(np.float32)
    sess = SimulationSession(pts, SearchParams(radius=0.1, k=8))
    sess.step(pts)                              # capture + compile (force)
    pts = _drift(rng, pts, 0.0004)
    sess.step(pts)                              # compiles the replay variant
    cache = sess.stats()["step_cache_size"]
    for _ in range(4):
        pts = _drift(rng, pts, 0.0004)          # well below threshold
        sess.step(pts)
        assert sess.report.fast
        assert not sess.report.replanned and not sess.report.respecced
        # no retrace: the lax.cond replay re-enters the same compiled step
        assert sess.stats()["step_cache_size"] == cache
    st = sess.stats()
    assert st["fast_steps"] == 5 and st["replans"] == 1
    # the per-step stats fetch is gone from the fast path entirely
    assert st["stats_fetches"] == 0


def test_session_replans_when_displacement_exceeds_threshold(rng):
    pts = rng.random((1000, 3)).astype(np.float32)
    sess = SimulationSession(pts, SearchParams(radius=0.1, k=8))
    sess.step(pts)
    cell = sess.spec.cell_size
    # move one point a full cell: the max-displacement statistic must
    # trip the staleness threshold even though the mean drift is ~zero
    pts2 = pts.copy()
    pts2[17] += np.float32([cell, 0, 0])
    sess.step(pts2)
    assert sess.report.replanned and not sess.report.respecced
    assert sess.stats()["replans"] == 2


def test_session_respec_on_escape_and_overflow(rng):
    """Out-of-bounds and capacity-overflow both trigger the respec
    fallback, and results stay oracle-exact across it."""
    pts = rng.random((900, 3)).astype(np.float32) * 0.5
    params = SearchParams(radius=0.08, k=8, knn_window="exact")
    sess = SimulationSession(pts, params)
    sess.step(pts)
    old_spec = sess.spec

    far = (pts + np.float32([2.0, 0.0, 0.0])).astype(np.float32)
    res = sess.step(far)
    assert sess.report.respecced and sess.report.oob > 0
    assert sess.spec is not old_spec
    _assert_oracle_exact(res, far, far, 0.08, 8)

    # keep stepping after the respec: session still works and goes fast
    nxt = _drift(rng, far - np.float32([2.0, 0, 0]), 0.0) \
        + np.float32([2.0, 0, 0])
    res = sess.step((nxt + 0.0005).astype(np.float32))
    assert sess.report.fast

    # capacity overflow: pile a third of the cloud into one cell
    sess2 = SimulationSession(pts, params,
                              sopts=SessionOpts(capacity_slack=1.0))
    sess2.step(pts)
    squeezed = pts.copy()
    squeezed[:300] = pts[0]
    res = sess2.step(squeezed)
    assert sess2.report.respecced and sess2.report.overflow > 0
    _assert_oracle_exact(res, squeezed, squeezed, 0.08, 8)
    assert sess2.stats()["respecs"] == 1


def test_respec_hysteresis_logarithmic(rng):
    """Respec hysteresis (ROADMAP): each respec plans geometrically more
    headroom, so an adversarial workload that keeps outrunning the frozen
    spec — here a constant-velocity escape from the domain — triggers
    O(log frames) respecs, not one per frame, while every step stays
    oracle-exact."""
    pts = rng.random((400, 3)).astype(np.float32)
    params = SearchParams(radius=0.1, k=4, knn_window="exact")
    # max_dim bounds the dense grid as the escaping domain stretches (CPU
    # test budget); the hysteresis behavior under test is unaffected
    sess = SimulationSession(pts, params, sopts=SessionOpts(max_dim=48))
    steps = 24
    vel = np.float32([3.0 * 0.1, 0.0, 0.0])   # 3 radii per frame: the
    # initial 1-radius margin is outrun immediately and every frame after
    respec_frames = []
    for f in range(steps):
        cur = (pts + f * vel).astype(np.float32)
        res = sess.step(cur)
        if sess.report.respecced:
            respec_frames.append(f)
        # counts stay oracle-exact; the distance check needs a coordinate-
        # scaled tolerance because the expanded |q|^2+|p|^2-2qp form loses
        # f32 bits as the escaping cloud drifts far from the origin
        _oi, od, oc = brute_force_search(jnp.asarray(cur), jnp.asarray(cur),
                                         0.1, 4)
        np.testing.assert_array_equal(np.asarray(oc),
                                      np.asarray(res.counts))
        d_ref = np.where(np.isinf(np.asarray(od)), -1.0, np.asarray(od))
        d_got = np.where(np.isinf(np.asarray(res.distances2)), -1.0,
                         np.asarray(res.distances2))
        np.testing.assert_allclose(d_got, d_ref, atol=1e-5)
    respecs = sess.stats()["respecs"]
    # geometric margin growth: each respec buys ~2x more frames than the
    # last, so ceil(log2(total drift / initial margin)) + O(1) respecs
    assert respecs <= int(math.ceil(math.log2(steps * 3))) + 2, respecs
    assert respecs < steps / 2
    # and the bought headroom is real: the gaps between respecs grow
    gaps = np.diff([0] + respec_frames)
    assert respecs >= 2 and (gaps[-1] >= gaps[0])

    # growth disabled reverts to the old behavior: the same adversary
    # respecs on (nearly) every frame
    sess0 = SimulationSession(pts, params,
                              sopts=SessionOpts(respec_growth=1.0,
                                                max_dim=48))
    for f in range(10):
        sess0.step((pts + f * vel).astype(np.float32))
    assert sess0.stats()["respecs"] >= 8


def test_session_respec_disabled_raises(rng):
    pts = rng.random((400, 3)).astype(np.float32)
    sess = SimulationSession(pts, SearchParams(radius=0.1, k=4),
                             sopts=SessionOpts(auto_respec=False))
    sess.step(pts)
    with pytest.raises(RuntimeError, match="frozen grid"):
        sess.step(pts + np.float32([3.0, 0, 0]))


def test_session_retrace_contract_across_replans_and_respec(rng):
    """Replan and replay are the SAME compiled program (the two branches of
    the device `lax.cond`): an above-threshold step must not retrace, and
    only a respec — which changes the frozen spec the program specializes
    on — may compile new step variants. The session stays exact throughout."""
    pts = rng.random((1300, 3)).astype(np.float32)
    params = SearchParams(radius=0.1, k=8, knn_window="exact")
    sess = SimulationSession(pts, params)
    sess.step(pts)
    pts = _drift(rng, pts, 0.0003)
    sess.step(pts)                      # fast step (replay variant compiled)
    cache = sess.stats()["step_cache_size"]
    assert sess.report.fast
    # a replan with unchanged shapes re-enters the same compiled step: the
    # cond simply takes the other branch
    big = sess.spec.cell_size
    pts2 = pts.copy()
    pts2[3] += np.float32([big, 0, 0])
    res = sess.step(pts2)
    assert sess.report.replanned
    assert sess.stats()["step_cache_size"] == cache
    _assert_oracle_exact(res, pts2, pts2, 0.1, 8)
    # respec: new frozen spec -> the old spec's step variants are released
    # and replaced by the new specialization, exact results throughout
    pts3 = (pts2 + np.float32([4.0, 0, 0])).astype(np.float32)
    res = sess.step(pts3)
    assert sess.report.respecced
    assert sess.stats()["respecs"] == 1
    assert sess.stats()["step_cache_size"] == 1     # old variants dropped
    _assert_oracle_exact(res, pts3, pts3, 0.1, 8)
    # and the session re-enters the fast path on the new spec
    pts4 = _drift(rng, pts3 - np.float32([4.0, 0, 0]), 0.0002) \
        + np.float32([4.0, 0, 0])
    sess.step(pts4.astype(np.float32))
    assert sess.report.fast


def test_session_self_query_shares_device_buffer(rng):
    """step(points) and step(points, queries=points) are the same fast
    path, and results equal the explicit two-array call."""
    pts = rng.random((800, 3)).astype(np.float32)
    params = SearchParams(radius=0.1, k=8, knn_window="exact")
    s1 = SimulationSession(pts, params)
    s2 = SimulationSession(pts, params)
    r1 = s1.step(pts)
    r2 = s2.step(pts, qs_other := pts.copy())   # distinct array: full path
    np.testing.assert_array_equal(np.asarray(r1.counts),
                                  np.asarray(r2.counts))
    d1 = np.where(np.isinf(np.asarray(r1.distances2)), -1.0,
                  np.asarray(r1.distances2))
    d2 = np.where(np.isinf(np.asarray(r2.distances2)), -1.0,
                  np.asarray(r2.distances2))
    np.testing.assert_allclose(d1, d2, atol=1e-6)
    assert qs_other is not pts


def test_session_switching_query_sets_replans(rng):
    """Swapping between self-query and external queries must replan: the
    cached plan is anchored at the other set's positions (the displacement
    statistic does not track the swap), and results must stay exact."""
    pts = rng.random((700, 3)).astype(np.float32)
    qs = rng.random((700, 3)).astype(np.float32)   # same Nq as the points
    params = SearchParams(radius=0.11, k=8, knn_window="exact")
    sess = SimulationSession(pts, params)
    sess.step(pts)
    res = sess.step(pts, qs)
    assert sess.report.replanned
    _assert_oracle_exact(res, pts, qs, 0.11, 8)
    res = sess.step(pts)
    assert sess.report.replanned
    _assert_oracle_exact(res, pts, pts, 0.11, 8)


def test_session_pallas_path(rng):
    """The session composes with the fused-kernel search path (update
    kernel + knn tile kernel, both interpret-mode on CPU)."""
    pts = rng.random((600, 3)).astype(np.float32)
    params = SearchParams(radius=0.12, k=8, knn_window="exact")
    sess = SimulationSession(pts, params,
                             SearchOpts(use_pallas=True, query_tile=128))
    for _ in range(3):
        res = sess.step(pts)
        _assert_oracle_exact(res, pts, pts, 0.12, 8)
        pts = _drift(rng, pts, 0.0005)
    assert sess.stats()["fast_steps"] >= 1


def test_session_grid_donation_alias_safety(rng):
    """Grid-only donation (SessionOpts.donate_grid): the step donates the
    dense-grid leaves — always session-owned — while caller-aliased
    points/anchor buffers stay untouched. Forced ON here (the CPU backend
    ignores donation with a warning, but the donation *plumbing* — the
    grid split out as its own argument, no duplicate-donation, no donated
    caller buffer — is exercised identically), across replays, replans,
    and a respec."""
    import warnings
    pts = rng.random((800, 3)).astype(np.float32)
    params = SearchParams(radius=0.1, k=8, knn_window="exact")
    sess = SimulationSession(pts, params,
                             sopts=SessionOpts(donate_grid=True))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")          # CPU donation warning
        caller_buf = jnp.asarray(pts)
        res = sess.step(caller_buf)              # force/capture step
        _assert_oracle_exact(res, pts, pts, 0.1, 8)
        # the caller's device buffer must NOT have been donated away
        np.testing.assert_array_equal(np.asarray(caller_buf), pts)
        pts2 = _drift(rng, pts, 0.0003)
        res = sess.step(pts2)                    # replay step
        _assert_oracle_exact(res, pts2, pts2, 0.1, 8)
        assert sess.report.fast
        big = pts2.copy()
        big[5] += np.float32([sess.spec.cell_size, 0, 0])
        res = sess.step(big)                     # replan step
        _assert_oracle_exact(res, big, big, 0.1, 8)
        far = (big + np.float32([4.0, 0, 0])).astype(np.float32)
        res = sess.step(far)                     # respec step
        assert sess.report.respecced
        _assert_oracle_exact(res, far, far, 0.1, 8)

    # default (auto) on CPU disables donation: no warning path at all
    sess2 = SimulationSession(pts, params)
    res = sess2.step(pts)
    _assert_oracle_exact(res, pts, pts, 0.1, 8)


def _assert_table_matches(grid, pts):
    """CellGrid.coords holds each slot's point, 0.0 where dense is -1."""
    cap = grid.spec.capacity
    dense = np.asarray(grid.dense).reshape(-1)
    table = np.moveaxis(np.asarray(grid.coords).reshape(-1, 3, cap), 1,
                        2).reshape(-1, 3)
    full = dense >= 0
    np.testing.assert_array_equal(table[full], np.asarray(pts)[dense[full]])
    assert (table[~full] == 0.0).all()


@pytest.mark.parametrize("donate_grid", [True, False])
def test_session_coordinate_table_tracks_positions(rng, donate_grid):
    """The session's grid carries the coordinate table of the current
    positions after every kind of step — replay, replan, respec — with
    the grid donated into each step or not."""
    import warnings
    pts = rng.random((800, 3)).astype(np.float32)
    params = SearchParams(radius=0.1, k=8, knn_window="exact")
    sess = SimulationSession(pts, params,
                             sopts=SessionOpts(donate_grid=donate_grid))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")          # CPU donation warning
        sess.step(pts)
        _assert_table_matches(sess.index.grid, pts)
        pts2 = _drift(rng, pts, 0.0003)
        sess.step(pts2)
        assert sess.report.fast
        _assert_table_matches(sess.index.grid, pts2)
        big = pts2.copy()
        big[5] += np.float32([sess.spec.cell_size, 0, 0])
        sess.step(big)
        assert sess.report.replanned
        _assert_table_matches(sess.index.grid, big)
        far = (big + np.float32([4.0, 0, 0])).astype(np.float32)
        res = sess.step(far)
        assert sess.report.respecced
        _assert_table_matches(sess.index.grid, far)
        _assert_oracle_exact(res, far, far, 0.1, 8)


def test_update_cell_grid_matches_fresh_build(rng):
    """The incremental update must produce the bit-identical structure a
    fresh build over the moved points would."""
    from repro.core import build_cell_grid, choose_grid_spec
    pts = rng.random((1000, 3)).astype(np.float32)
    spec = choose_grid_spec(pts, 0.1, capacity_slack=2.0)
    grid = build_cell_grid(jnp.asarray(pts), spec)
    moved = _drift(rng, pts, 0.01)
    g2, stats, ccoord = update_cell_grid(grid, jnp.asarray(moved),
                                         jnp.asarray(pts))
    fresh = build_cell_grid(jnp.asarray(moved), spec)
    np.testing.assert_array_equal(np.asarray(g2.dense),
                                  np.asarray(fresh.dense))
    np.testing.assert_array_equal(np.asarray(g2.sat),
                                  np.asarray(fresh.sat))
    np.testing.assert_array_equal(np.asarray(g2.coords),
                                  np.asarray(fresh.coords))
    np.testing.assert_array_equal(np.asarray(ccoord),
                                  np.asarray(spec.cell_of(
                                      jnp.asarray(moved))))
    assert int(stats.oob) == 0
    d2 = np.max(np.sum((moved - pts) ** 2, axis=-1))
    np.testing.assert_allclose(float(stats.max_disp2), d2, rtol=1e-6)


def test_update_kernel_matches_jnp_path(rng):
    """kernels/update_tile vs the jnp binning+stats: bit-identical cells,
    counters, and displacement statistic (incl. out-of-bounds points)."""
    from repro.core.grid import _bin_and_stats, choose_grid_spec
    from repro.kernels.update_tile import bin_disp_tile
    pts = rng.random((777, 3)).astype(np.float32)
    spec = choose_grid_spec(pts, 0.1)
    anchor = _drift(rng, pts, 0.01)
    moved = pts.copy()
    moved[7] = [9.0, 9.0, 9.0]
    moved[123] = [-4.0, 0.5, 0.5]
    cj, oj, dj = _bin_and_stats(spec, jnp.asarray(moved),
                                jnp.asarray(anchor))
    cp, op, dp = bin_disp_tile(jnp.asarray(moved), jnp.asarray(anchor),
                               spec, interpret=True)
    np.testing.assert_array_equal(np.asarray(cj), np.asarray(cp))
    assert int(oj) == int(op) == 2
    np.testing.assert_allclose(float(dj), float(dp), rtol=1e-6)
