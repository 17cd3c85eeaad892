"""Smoke run of the neighbor-search main path on a TPU.

    python chip_smoke.py                # one chip: every phase but sharding
    python chip_smoke.py --four-chips   # only the sharded session, 4 chips

One process drives the chip through the entry points a user calls, at
deployment sizes, and checks every phase against an independent float64
brute force on the host (2,048 sampled queries per phase):

* ``lidar``   a 120,000-point LiDAR-like frame, self-query kNN (k=16,
              r=0.01), through ``jax.jit(repro.api.query)`` and through the
              eager ``NeighborSearch`` executor;
* ``session`` ``SimulationSession`` over 262,144 drifting particles (k=16,
              r=0.03, ~30 neighbors in range) for 5 steps, checked on the
              last frame;
* ``serve``   ``NeighborService`` with the LiDAR frame and a 60,000-point
              clustered scene resident, 64 requests of 256-2,048 queries,
              every future resolved to a result and checked;
* ``fused``   ``SearchOpts(use_pallas=True)`` is refused on the TPU (the
              fused kNN kernel's in-kernel gathers do not compile), and the
              Mosaic-compiled grid-update kernel matches its jnp form.

``--four-chips`` runs ``ShardedSession`` (4 slabs) against a single-device
``SimulationSession`` on 2^20 particles (k=16, r=0.019, ~30 neighbors in
range) for 5 steps instead, with counts equal on every step, and prints
each device's memory in use. ``--particles N`` resizes that phase, with
the radius scaled to keep ~30 neighbors in range.

Earlier lines give the device and each phase's compile and run wall times
(smoke wall times, not benchmark numbers). The last line is one JSON
object, printed only when every phase passed. Without a TPU the script
exits nonzero before doing any work.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SAMPLE = 2048         # oracle-checked queries per phase
TOL = 1e-6            # |d2| agreement, and the f32 ambiguity band


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class Oracle:
    """Float64 brute-force kNN-within-radius on the host for a set of
    queries, independent of the library: for each query the in-range
    candidates (ids and exact squared distances, ascending)."""

    def __init__(self, points, queries, radius: float, k: int,
                 chunk: int = 64):
        p = np.asarray(points, np.float64)
        q = np.asarray(queries, np.float64)
        self.r2, self.k = float(radius) ** 2, int(k)
        self.q, self.p = q, p
        pn = np.einsum("ij,ij->i", p, p)
        self.ids, self.d2 = [], []
        for s in range(0, len(q), chunk):
            qc = q[s:s + chunk]
            # expanded form only prefilters (loosely); distances that
            # decide anything are recomputed in difference form below
            dd = (np.einsum("ij,ij->i", qc, qc)[:, None] + pn[None, :]
                  - 2.0 * (qc @ p.T))
            rows, cols = np.nonzero(dd <= self.r2 + 1e-5)
            for i, ids in enumerate(np.split(
                    cols, np.searchsorted(rows, np.arange(1, len(qc))))):
                d = np.sum((qc[i] - p[ids]) ** 2, axis=1)
                keep = d <= self.r2 + TOL
                order = np.argsort(d[keep], kind="stable")
                self.ids.append(ids[keep][order])
                self.d2.append(d[keep][order])

    def check(self, idx, d2, cnt) -> tuple[int, float, list]:
        """Compare search results (rows aligned with the oracle's queries).
        Returns (mismatched queries, max |d2 - oracle d2|, examples).

        Counts and neighbor sets must match exactly except where f32
        rounding makes the answer ambiguous: a point within ``TOL`` of r^2,
        or candidates tied within ``TOL`` at the K-th place."""
        idx, d2, cnt = (np.asarray(a) for a in (idx, d2, cnt))
        bad, examples, max_err = 0, [], 0.0
        for i in range(len(self.ids)):
            why = self._check_row(i, idx[i], d2[i], int(cnt[i]))
            if isinstance(why, str):
                bad += 1
                if len(examples) < 5:
                    examples.append(f"query {i}: {why}")
            else:
                max_err = max(max_err, why)
        return bad, max_err, examples

    def _check_row(self, i, idx, d2, cnt):
        ids, od2 = self.ids[i], self.d2[i]
        got = idx[idx >= 0]
        if len(got) != cnt or len(np.unique(got)) != len(got):
            return f"count {cnt} vs {len(got)} ids {got.tolist()}"
        n_strict = int(np.sum(od2 < self.r2 - TOL))
        lo, hi = min(self.k, n_strict), min(self.k, len(ids))
        if not lo <= cnt <= hi:
            return f"count {cnt} outside oracle [{lo}, {hi}]"
        if cnt == 0:
            return 0.0
        dg = np.sum((self.q[i] - self.p[got]) ** 2, axis=1)
        if np.any(dg > self.r2 + TOL):
            return f"returned a point beyond r: d2 {dg.max()}"
        err = float(np.max(np.abs(d2[idx >= 0] - dg)))
        if err > TOL:
            return f"d2 off by {err}"
        kth = od2[cnt - 1]
        must = ids[od2 < kth - TOL]
        if not np.all(np.isin(must, got)):
            return f"missed nearer ids {np.setdiff1d(must, got).tolist()}"
        if np.any(dg > kth + TOL):
            return f"returned id beyond the K-th distance {kth}"
        return err


def check(phase: str, what: str, oracle: Oracle, idx, d2, cnt) -> None:
    bad, err, examples = oracle.check(idx, d2, cnt)
    n = len(oracle.ids)
    if bad:
        raise AssertionError(f"{what}: {bad}/{n} queries disagree with the "
                             f"float64 oracle: {examples}")
    log(phase, f"{what}: {n} queries match the float64 oracle "
               f"(max |d2 err| {err:.3e})")


def timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def drift_frames(pos: np.ndarray, steps: int, sigma: float, seed: int):
    """A smooth trajectory: a per-particle velocity that random-walks."""
    rng = np.random.default_rng(seed + 1)
    vel = rng.normal(0, sigma, pos.shape).astype(np.float32)
    frames = [pos]
    for _ in range(steps - 1):
        vel = (0.9 * vel + rng.normal(0, 0.3 * sigma, pos.shape)
               ).astype(np.float32)
        pos = np.clip(pos + vel, 0.0, 1.0).astype(np.float32)
        frames.append(pos)
    return frames


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def phase_lidar(seed: int, lidar) -> None:
    import jax
    import repro.api as api
    from repro.core import NeighborSearch
    params = api.SearchParams(radius=0.01, k=16, knn_window="exact")
    sample = np.random.default_rng(seed).choice(len(lidar), SAMPLE,
                                                replace=False)
    oracle = Oracle(lidar, lidar[sample], params.radius, params.k)

    index, t = timed(lambda: api.build_index(lidar, params))
    log("lidar", f"build_index {len(lidar)} points: {t:.3f}s "
                 f"(grid {index.spec.dims} x cap {index.spec.capacity})")
    q = index.points
    t0 = time.perf_counter()
    query = jax.jit(api.query).lower(index, q).compile()
    log("lidar", f"compile jit(api.query): {time.perf_counter() - t0:.3f}s")
    res, t_first = timed(lambda: query(index, q))
    _, t_run = timed(lambda: query(index, q))
    log("lidar", f"jit(api.query) self-kNN: first {t_first:.3f}s, "
                 f"warm {t_run:.3f}s")
    check("lidar", "jit(api.query)", oracle, res.indices[sample],
          res.distances2[sample], res.counts[sample])

    ns = NeighborSearch(lidar, params)
    res_e, t_first = timed(lambda: ns.query(lidar))
    _, t_run = timed(lambda: ns.query(lidar))
    last = ns.executor.stats()["last"]
    log("lidar", f"NeighborSearch executor: first (incl. compile) "
                 f"{t_first:.3f}s, warm {t_run:.3f}s, launches "
                 f"{last['launches']}, host syncs {last['host_syncs']}")
    check("lidar", "NeighborSearch.query", oracle, res_e.indices[sample],
          res_e.distances2[sample], res_e.counts[sample])


def phase_session(seed: int, n: int = 262_144, steps: int = 5) -> None:
    from repro.core import SearchParams, SimulationSession
    from repro.data.pointclouds import uniform_cloud
    params = SearchParams(radius=0.03, k=16, knn_window="exact")
    frames = drift_frames(uniform_cloud(n, seed), steps, 3e-4, seed)
    sess = SimulationSession(frames[0], params)
    log("session", f"{n} particles, grid {sess.spec.dims} x cap "
                   f"{sess.spec.capacity}")
    for s, frame in enumerate(frames):
        res, t = timed(lambda: sess.step(frame))
        rep = sess.report
        log("session", f"step {s}: {t:.3f}s (replanned={rep.replanned} "
                       f"respecced={rep.respecced} max_disp="
                       f"{rep.max_disp:.2e})")
    st = sess.stats()
    log("session", f"steps {st['steps']}, fast {st['fast_steps']}, replans "
                   f"{st['replans']}, respecs {st['respecs']}, step "
                   f"programs compiled {st['step_cache_size']}")
    last = frames[-1]
    sample = np.random.default_rng(seed + 2).choice(n, SAMPLE, replace=False)
    oracle = Oracle(last, last[sample], params.radius, params.k)
    mean_in_range = np.mean([len(ids) for ids in oracle.ids])
    log("session", f"oracle: {mean_in_range:.1f} points in range per query")
    check("session", "SimulationSession.step (last frame)", oracle,
          res.indices[sample], res.distances2[sample], res.counts[sample])


def phase_serve(seed: int, lidar, n_nbody: int = 60_000) -> None:
    from repro.core import SearchParams
    from repro.data.pointclouds import clustered_cloud
    from repro.serve import NeighborService, ServeOpts
    scenes = {
        "lidar": (lidar, SearchParams(radius=0.01, k=16,
                                      knn_window="exact")),
        "nbody": (clustered_cloud(n_nbody, seed),
                  SearchParams(radius=0.02, k=16, knn_window="exact")),
    }
    svc = NeighborService(ServeOpts(max_batch=2048, max_pending=1 << 20))
    t0 = time.perf_counter()
    for sid, (pts, params) in scenes.items():
        svc.register_scene(sid, pts)
        for nq in (1024, 2048):
            svc.registry.get(sid).variant(params).warm(nq)
    log("serve", f"register + compile 2 scenes x buckets (1024, 2048): "
                 f"{time.perf_counter() - t0:.3f}s")

    rng = np.random.default_rng(seed + 3)
    requests = []
    for _ in range(64):
        sid = ("lidar", "nbody")[int(rng.integers(2))]
        pts, params = scenes[sid]
        nq = int(rng.integers(256, 2049))
        q = (pts[rng.choice(len(pts), nq)]
             + rng.normal(0, params.radius / 4, (nq, 3))).astype(np.float32)
        requests.append((sid, q, svc.submit(sid, q, params, now=0.0)))
    rows = sum(len(q) for _, q, _ in requests)
    t0 = time.perf_counter()
    reports = svc.drain(now=0.0)
    log("serve", f"drained {len(requests)} requests ({rows} queries) in "
                 f"{len(reports)} batches: {time.perf_counter() - t0:.3f}s")

    per_request = SAMPLE // len(requests)
    bad = []
    for r, (sid, q, fut) in enumerate(requests):
        res = fut.result(timeout=600.0)        # raises the request's error
        if fut.quality is not None and fut.quality.reduced_ladder:
            bad.append(f"request {r} degraded")
            continue
        pts, params = scenes[sid]
        sample = rng.choice(len(q), per_request, replace=False)
        oracle = Oracle(pts, q[sample], params.radius, params.k)
        n_bad, _err, ex = oracle.check(np.asarray(res.indices)[sample],
                                       np.asarray(res.distances2)[sample],
                                       np.asarray(res.counts)[sample])
        if n_bad:
            bad.append(f"request {r} ({sid}): {n_bad} queries: {ex}")
    if bad:
        raise AssertionError(f"serve: {len(bad)} requests wrong: {bad[:5]}")
    log("serve", f"all {len(requests)} futures resolved to results; "
                 f"{per_request * len(requests)} sampled queries match the "
                 f"float64 oracle")


def phase_fused(seed: int, n: int = 262_144) -> None:
    import jax
    import jax.numpy as jnp
    import repro.api as api
    from repro.core.grid import _bin_and_stats
    from repro.data.pointclouds import uniform_cloud
    from repro.kernels.update_tile import bin_disp_tile
    pts = uniform_cloud(n, seed)
    params = api.SearchParams(radius=0.03, k=16, knn_window="exact")
    try:
        api.build_index(pts, params, api.SearchOpts(use_pallas=True))
    except NotImplementedError as e:
        if "gather" not in str(e):
            raise AssertionError(f"refusal does not name the gather: {e}")
        log("fused", "use_pallas=True refused on the TPU backend, naming "
                     "the in-kernel gather")
    else:
        raise AssertionError("use_pallas=True was not refused on the TPU")

    spec = api.build_index(pts, params).spec
    moved = jnp.asarray(drift_frames(pts, 2, 1e-3, seed)[1])
    anchor = jnp.asarray(pts)
    kernel = jax.jit(lambda p, a: bin_disp_tile(p, a, spec))
    t0 = time.perf_counter()
    compiled = kernel.lower(moved, anchor).compile()
    t_compile = time.perf_counter() - t0
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("bin_disp_tile did not compile to a Mosaic "
                             "kernel")
    (cc, oob, d2), t = timed(lambda: compiled(moved, anchor))
    cj, oj, dj = jax.jit(lambda p, a: _bin_and_stats(spec, p, a))(moved,
                                                                 anchor)
    cc, cj = np.asarray(cc), np.asarray(cj)
    # the kernel scales by 1/cell, the jnp path divides by cell: a point
    # within f32 rounding of a cell face may land on either side of it
    # (each form rounds a few times, relative to the cell coordinate)
    t_cell = ((np.asarray(moved, np.float64) - np.asarray(spec.origin))
              / spec.cell_size)
    band = 4 * np.finfo(np.float32).eps * max(spec.dims)
    on_face = np.abs(t_cell - np.round(t_cell)) < band
    differ = cc != cj
    if not (np.all(on_face[differ]) and np.all(np.abs(cc - cj) <= 1)):
        raise AssertionError(
            f"bin_disp_tile bins {int(np.sum(differ & ~on_face))} "
            f"coordinates away from any cell face differently from the jnp "
            f"path")
    if int(oob) != int(oj) or not np.isclose(float(d2), float(dj),
                                              rtol=TOL, atol=0):
        raise AssertionError(f"bin_disp_tile statistics (oob {int(oob)}, "
                             f"max d2 {float(d2)!r}) disagree with the jnp "
                             f"path ({int(oj)}, {float(dj)!r})")
    log("fused", f"bin_disp_tile (Mosaic) on {len(pts)} points: compile "
                 f"{t_compile:.3f}s, run {t:.3f}s, matches the jnp path "
                 f"({int(np.sum(np.any(differ, axis=1)))} points binned "
                 f"across a cell face they lie within {band:.2e} cells of)")


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------

def phase_sharded(seed: int, n: int = 1 << 20, steps: int = 5) -> None:
    import jax
    from repro.core import SearchParams, ShardedSession, SimulationSession
    from repro.data.pointclouds import uniform_cloud
    radius = 0.019 * ((1 << 20) / n) ** (1 / 3)     # ~30 neighbors in range
    params = SearchParams(radius=radius, k=16, knn_window="exact")
    frames = drift_frames(uniform_cloud(n, seed), steps, 3e-4, seed)
    sharded = ShardedSession(frames[0], params, n_slabs=4)
    single = SimulationSession(frames[0], params)
    log("sharded", f"{n} particles (r={radius:.4f}) over "
                   f"{len(jax.devices())} devices; slab grid "
                   f"{sharded.spec.dims} x cap {sharded.spec.capacity}")
    for s, frame in enumerate(frames):
        rs, t_sh = timed(lambda: sharded.step(frame))
        rr, t_si = timed(lambda: single.step(frame))
        equal = np.array_equal(np.asarray(rs.counts), np.asarray(rr.counts))
        log("sharded", f"step {s}: sharded {t_sh:.3f}s, single {t_si:.3f}s,"
                       f" counts equal: {equal}")
        if not equal:
            raise AssertionError(f"step {s}: sharded counts differ from the "
                                 f"single-device session")
    st = sharded.stats()
    log("sharded", f"fast {st['fast_steps']}, replans {st['replans']}, "
                   f"reroutes {st['reroutes']}, migrated {st['migrated']}")
    # device 0 also holds the single-device session; the slabs must have
    # put state on every other device too
    for d in jax.devices():
        in_use = (d.memory_stats() or {}).get("bytes_in_use")
        log("sharded", f"device {d.id}: bytes_in_use {in_use}")
        if in_use == 0:
            raise AssertionError(f"device {d.id} holds nothing: the slabs "
                                 f"are not spread over the devices")
    last = frames[-1]
    sample = np.random.default_rng(seed + 2).choice(n, SAMPLE, replace=False)
    oracle = Oracle(last, last[sample], params.radius, params.k)
    check("sharded", "ShardedSession.step (last frame)", oracle,
          np.asarray(rs.indices)[sample], np.asarray(rs.distances2)[sample],
          np.asarray(rs.counts)[sample])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-session phase on 4 chips")
    ap.add_argument("--particles", type=int, default=1 << 20,
                    help="particle count of the --four-chips phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform!r} devices; the smoke runs "
             f"only on the chip")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        fail(f"--four-chips needs 4 TPU devices, found {len(devices)}")
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the repro package is missing under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import configure_compile_cache
    print(f"chip_smoke: {dev.platform} {dev.device_kind} x {len(devices)}, "
          f"jax {jax.__version__}, compile cache "
          f"{configure_compile_cache()}", flush=True)

    from repro.data.pointclouds import kitti_like_cloud
    if args.four_chips:
        phases = [("sharded",
                   lambda: phase_sharded(args.seed, args.particles))]
    else:
        lidar = kitti_like_cloud(120_000, args.seed)
        phases = [("lidar", lambda: phase_lidar(args.seed, lidar)),
                  ("session", lambda: phase_session(args.seed)),
                  ("serve", lambda: phase_serve(args.seed, lidar)),
                  ("fused", lambda: phase_fused(args.seed))]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception:      # report every phase, then fail the run
            traceback.print_exc()
            failed.append(name)
            log(name, f"FAILED after {time.perf_counter() - t0:.3f}s")
            continue
        peak = dev.memory_stats().get("peak_bytes_in_use")
        log(name, f"passed in {time.perf_counter() - t0:.3f}s (device 0 "
                  f"peak bytes in use so far: {peak})")
    if failed:
        fail(f"phases failed: {', '.join(failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
