"""Executor throughput: legacy per-bundle host loop vs device-resident
QueryExecutor vs the single-program traced Pallas pipeline on Fig. 11-style
workloads.

Measures steady-state end-to-end ``query()`` latency (plan/compile caches
warm — the SPH-stepping regime) plus the dispatch/sync counts that explain
it, asserts the paths return oracle-identical results, and writes the rows
to ``BENCH_executor.json`` at the repo root so the perf trajectory
accumulates across PRs. The ``pallas_traced`` column times
``jax.jit(api.query)`` with ``SearchOpts(use_pallas=True)`` — the
level-segmented fused-kernel schedule as ONE compiled program (DESIGN.md
section 3); it runs only off-TPU, where the kernels run in interpret
mode, so that column measures orchestration structure, not kernel speed.

``REPRO_BENCH_SMOKE=1`` shrinks the workloads for CI (scripts/ci.sh).
"""
from __future__ import annotations

import os

import jax
import numpy as np

import repro.api as api
from repro.core import NeighborSearch, SearchOpts, SearchParams
from repro.data.pointclouds import dataset_by_name

from .common import emit, write_bench

SMOKE = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_executor.json")


def _paired_timeit(fn_a, fn_b, repeats: int = 5):
    """Interleaved best-of timing: alternating A/B runs so machine noise
    (shared CPU) hits both paths equally instead of biasing whichever ran
    in the quieter window."""
    import time

    import jax

    ts_a, ts_b = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_a())
        ts_a.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fn_b())
        ts_b.append(time.perf_counter() - t0)
    return min(ts_a), min(ts_b)


def _assert_identical(a, b):
    assert np.array_equal(np.asarray(a.indices), np.asarray(b.indices))
    assert np.array_equal(np.asarray(a.counts), np.asarray(b.counts))
    da = np.where(np.isinf(np.asarray(a.distances2)), -1.0,
                  np.asarray(a.distances2))
    db = np.where(np.isinf(np.asarray(b.distances2)), -1.0,
                  np.asarray(b.distances2))
    assert np.array_equal(da, db)


def run(k=8):
    if SMOKE:
        # distinct name so the noisier 3-repeat smoke row never clobbers
        # the committed full-run row under the merge-accumulate write
        cases = [("kitti-stream-512-smoke", "kitti", 8_000, 512, 0.04, 128)]
    else:
        # batch cases: Fig. 11 regimes (kernel-bound; the executor must not
        # regress). stream cases: small repeated batches, the serving/SPH
        # steady state where host orchestration is a visible fraction and
        # the one-sync compiled schedule pays off.
        cases = [
            ("kitti-40k", "kitti", 40_000, 5_000, 0.02, 256),
            ("scan-30k", "scan", 30_000, 5_000, 0.03, 256),
            ("nbody-30k", "nbody", 30_000, 5_000, 0.03, 256),
            ("kitti-stream-512", "kitti", 8_000, 512, 0.04, 128),
            ("nbody-stream-512", "nbody", 8_000, 512, 0.04, 128),
        ]
    results = {}
    for name, kind, n, nq, r, tile in cases:
        pts = dataset_by_name(kind, n, seed=1)
        qs = dataset_by_name(kind, nq, seed=2)
        params = SearchParams(radius=r, k=k)

        ns_old = NeighborSearch(pts, params,
                                SearchOpts(executor=False, query_tile=tile))
        res_old = ns_old.query(qs)                       # warm jit caches
        ns_new = NeighborSearch(pts, params, SearchOpts(query_tile=tile))
        ns_new.executor.warmup(qs)
        res_new = ns_new.query(qs)
        _assert_identical(res_old, res_new)
        t_old, t_new = _paired_timeit(lambda: ns_old.query(qs),
                                      lambda: ns_new.query(qs),
                                      repeats=3 if SMOKE else 7)
        st = ns_new.executor.stats()

        # single-program traced Pallas pipeline: jit(api.query), the whole
        # schedule->anchor->gather->knn as one compiled program. It exists
        # only off-TPU (build_index refuses it on a TPU backend), where
        # interpret mode emulates the kernels in Python, so the column is
        # only affordable on the stream-sized cases.
        t_tr = None
        if jax.default_backend() != "tpu" and nq <= 1024:
            index_p = api.build_index(pts, params,
                                      SearchOpts(use_pallas=True,
                                                 query_tile=tile))
            traced = jax.jit(api.query)
            qs_dev = np.asarray(qs, np.float32)
            res_tr = traced(index_p, qs_dev)             # warm compile
            # counts are exact across the fused and jnp paths; distances
            # agree to f32 rounding (the two expand |q-p|^2 differently)
            assert np.array_equal(np.asarray(res_tr.counts),
                                  np.asarray(res_new.counts))
            d_tr = np.where(np.isinf(np.asarray(res_tr.distances2)), -1.0,
                            np.asarray(res_tr.distances2))
            d_ex = np.where(np.isinf(np.asarray(res_new.distances2)), -1.0,
                            np.asarray(res_new.distances2))
            np.testing.assert_allclose(d_tr, d_ex, rtol=0, atol=1e-6)
            _, t_tr = _paired_timeit(lambda: ns_new.query(qs),
                                     lambda: traced(index_p, qs_dev),
                                     repeats=3 if SMOKE else 7)

        row = {
            "old_us": t_old * 1e6,
            "new_us": t_new * 1e6,
            "pallas_traced_us": None if t_tr is None else t_tr * 1e6,
            "speedup": t_old / t_new,
            "bundles": len(ns_new.report.bundles),
            "launches_old": ns_old.report.launches,
            "launches_new": ns_new.report.launches,
            "host_syncs_old": ns_old.report.host_syncs,
            "host_syncs_new": ns_new.report.host_syncs,
            "steady_state_compilations": st["last"]["compilations"],
            "plan_cache_hit": st["last"]["plan_cache_hit"],
        }
        results[name] = row
        emit(f"figtp/{name}/host_loop", t_old / nq,
             f"launches={row['launches_old']};"
             f"host_syncs={row['host_syncs_old']}")
        emit(f"figtp/{name}/executor", t_new / nq,
             f"launches={row['launches_new']};host_syncs=1;"
             f"speedup={row['speedup']:.2f}x")
        if t_tr is not None:
            emit(f"figtp/{name}/pallas-traced", t_tr / nq,
                 "one compiled program;interpret-mode kernels")

    return write_bench(OUT_PATH, results)
