"""Device-resident bundle executor (DESIGN.md section 3).

The legacy orchestrator (``NeighborSearch._query_host_loop``) ran a Python
loop over bundles with a blocking ``jax.device_get`` + numpy scatter per
bundle — giving back on the host most of what scheduling/partitioning won
on the device, exactly the naive-mapping overhead the paper warns about.
The executor keeps the whole execution phase device-resident:

  * **signature batching** — bundles sharing a static launch signature
    ``(w_search, skip_test, padded-N bucket)`` are folded into one padded
    launch with concatenated segment metadata, so B bundles become
    ~|unique signatures| dispatches instead of B;
  * **async dispatch + on-device scatter** — the whole launch schedule
    (per group: gather -> padded search -> scatter through the composed
    schedule∘partition permutation with ``.at[].set``) runs as ONE jitted
    program with donated output buffers, on BOTH the jnp and the Pallas
    path (the fused kernel's tile-window anchors are computed on device —
    ``kernels/ops.window_search_segmented`` — so no launch needs host
    metadata). No per-bundle ``device_get``, no numpy scatter;
  * **one-sync contract** — exactly ONE blocking host sync materializes
    the results (``jax.block_until_ready`` over the three output arrays).
    The only other host transfer is the *plan fetch*: one fused
    ``device_get`` of the per-query partition metadata (w_search / skip /
    rho) that data-dependent partitioning requires, mirroring the paper's
    host-side launch orchestration. Both are counted in ``stats()``;
  * **plan + compile caching** — host partition/bundle plans are cached
    by value fingerprint and compiled searchers are cached per launch
    signature (the jit cache does the compiling; the executor tracks
    first-seen signatures and jit cache sizes so ``stats()`` can prove a
    steady-state query recompiles nothing).
"""
from __future__ import annotations

import collections
import hashlib
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..reliability import faults
from .bundle import bundle_query_sel
from .partition import (PartitionPlan, compute_megacells,
                        inflate_plan_inputs, plan_partitions, trivial_plan)
from .schedule import schedule_cells
from .types import Array, SearchResult

_PLAN_CACHE_MAX = 32
_LAUNCHER_CACHE_MAX = 32


def _fingerprint(*arrays: np.ndarray) -> bytes:
    h = hashlib.sha1()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.digest()


class LaunchGroup:
    """One padded device launch covering every bundle of one signature."""

    __slots__ = ("w_search", "skip_test", "sel", "pad_n", "n_bundles")

    def __init__(self, w_search: int, skip_test: bool, sel: np.ndarray,
                 pad_n: int, n_bundles: int):
        self.w_search = w_search
        self.skip_test = skip_test
        self.sel = sel              # scheduled-order query positions
        self.pad_n = pad_n
        self.n_bundles = n_bundles


class PlanHandle:
    """A captured schedule∘partition∘bundle plan, replayable across frames.

    Produced by ``QueryExecutor.capture_plan`` and replayed with
    ``execute(queries, reuse=handle)``: the handle owns the Morton schedule
    permutation (device), the partition plan and launch groups, and the
    edge-padded per-group selection vectors (device, uploaded once).
    Replaying performs ZERO host-side planning: no schedule, no plan fetch,
    no partition/bundle recompute, no padding work. The dynamic-scene
    session (``core/dynamic.py``) holds one handle per plan anchor and
    replays it while the max-displacement statistic stays below threshold;
    ``margin`` records the window inflation baked into the plan (the
    staleness contract, ``partition.inflate_plan_inputs``).
    """

    __slots__ = ("perm", "plan", "bundles", "groups", "sels_dev",
                 "nq", "margin")

    def __init__(self, perm, plan, bundles, groups, sels_dev, nq, margin):
        self.perm = perm
        self.plan = plan
        self.bundles = bundles
        self.groups = groups
        self.sels_dev = sels_dev
        self.nq = nq
        self.margin = margin


class PendingResult:
    """A dispatched-but-unsynced query (``QueryExecutor.execute_async``).

    The launch schedule is already in flight on the device; ``wait()``
    performs the one-sync-contract blocking materialization (idempotent —
    repeated calls return the same ``SearchResult``). Letting the caller
    defer the sync is what enables multi-batch pipelining: stage and
    dispatch batch N+1 on the host while batch N executes, then wait on
    N — the serving drain loop's dispatch-then-stage contract
    (``repro.serve``, DESIGN.md section 10).
    """

    __slots__ = ("_executor", "_arrays", "_last", "_sp_query", "_t_launch",
                 "_result")

    def __init__(self, executor, arrays, last, sp_query, t_launch):
        self._executor = executor
        self._arrays = arrays
        self._last = last
        self._sp_query = sp_query
        self._t_launch = t_launch
        self._result: SearchResult | None = None

    def done(self) -> bool:
        return self._result is not None

    def wait(self) -> SearchResult:
        if self._result is None:
            self._result = self._executor._finalize(
                self._arrays, self._last, self._sp_query, self._t_launch)
        return self._result


class QueryExecutor:
    """Executes a ``NeighborSearch``'s bundle plan device-resident.

    Owned by the search object (``ns.executor``); reusable across queries —
    steady-state repeated queries hit the plan cache and compile nothing.
    Surface: ``execute()`` (called by ``NeighborSearch.query``),
    ``capture_plan()``/``execute(reuse=...)`` (the dynamic-scene session),
    ``invalidate()`` (respec), ``warmup()``, ``stats()``.
    """

    def __init__(self, ns):
        self.ns = ns
        self._plan_cache: collections.OrderedDict = collections.OrderedDict()
        self._launcher_cache: collections.OrderedDict = \
            collections.OrderedDict()
        self._signatures: set = set()
        # the totals live in the unified registry (repro.obs): counters for
        # the caching/sync contract, histograms for latency percentiles
        self._metrics = obs.metric_set("executor")
        self._last: dict = {}

    # -- planning -----------------------------------------------------------

    def _plan(self, queries_s: Array, margin: int = 0):
        """Fetch partition metadata (ONE fused device_get), then plan and
        group on host — or reuse a cached plan for this fingerprint.

        ``margin`` inflates every per-query window by that many cells
        (clamped to w_full) before partitioning — the staleness allowance a
        capture-for-reuse plan carries (``partition.inflate_plan_inputs``).
        """
        ns = self.ns
        nq = queries_s.shape[0]
        partitioned = ns.opts.partition and ns.statics.has_megacells

        if partitioned:
            w_dev, s_dev, r_dev = compute_megacells(
                ns.grid, queries_s, ns.statics, ns.params)
            # a blocking transfer: its own span, so host planning is not
            # blamed for the wait
            with obs.span("sync"):
                w_np, s_np, r_np = (np.asarray(a) for a in jax.device_get(
                    (w_dev, s_dev, r_dev)))
            self._last["plan_fetches"] += 1
            if margin:
                w_np, s_np = inflate_plan_inputs(
                    w_np, s_np, margin=margin, w_full=ns.statics.w_full,
                    w_sph=ns.statics.w_sph)
            key = (nq, margin, _fingerprint(w_np, s_np, r_np))
        else:
            key = (nq, margin, b"nopart")

        hit = self._plan_cache.get(key)
        if hit is not None:
            self._plan_cache.move_to_end(key)
            self._last["plan_cache_hit"] = True
            plan, bundles, groups = hit
            return plan, bundles, groups

        plan = (plan_partitions(w_np, s_np, r_np, ns.statics.w_full)
                if partitioned else trivial_plan(nq, ns.statics.w_full))
        bundles = ns._bundle(plan)
        groups = self._build_groups(plan, bundles)
        self._plan_cache[key] = (plan, bundles, groups)
        if len(self._plan_cache) > _PLAN_CACHE_MAX:
            self._plan_cache.popitem(last=False)
        return plan, bundles, groups

    def _prepare_launch(self, groups):
        """Edge-pad each group's selection to its bucket (device)."""
        return tuple(jnp.asarray(
            np.pad(g.sel, (0, g.pad_n - g.sel.shape[0]), mode="edge"),
            jnp.int32) for g in groups)

    def capture_plan(self, queries, *, qcells_dev: Array | None = None,
                     margin: int = 0) -> PlanHandle:
        """Schedule + partition + bundle ``queries`` once and freeze the
        result into a replayable :class:`PlanHandle`.

        ``qcells_dev`` optionally supplies the queries' device cell
        coordinates (the self-query fast path reuses the grid update's
        binning); ``margin`` bakes the staleness allowance into every
        window so the handle stays exact while displacements remain under
        the session threshold.
        """
        ns = self.ns
        self._last = collections.Counter()    # scratch for _plan's counters
        queries = jnp.asarray(queries, jnp.float32)
        nq = queries.shape[0]
        with obs.span("plan", capture=True, nq=nq, margin=margin) as sp:
            if not ns.opts.schedule:
                perm = jnp.arange(nq, dtype=jnp.int32)
            elif qcells_dev is not None:
                perm, _ = schedule_cells(qcells_dev)
            else:
                perm, _ = ns._schedule(queries)
            queries_s = queries[perm]
            plan, bundles, groups = self._plan(queries_s, margin=margin)
            sels_dev = self._prepare_launch(groups)
        self._metrics.count("plan_fetches", self._last["plan_fetches"])
        self._metrics.count("plan_captures")
        self._metrics.observe("plan_s", sp.duration)
        return PlanHandle(perm=perm, plan=plan, bundles=bundles,
                          groups=groups, sels_dev=sels_dev, nq=nq,
                          margin=margin)

    def _build_groups(self, plan: PartitionPlan,
                      bundles) -> list[LaunchGroup]:
        """Fold bundles sharing (w_search, skip_test) into one launch."""
        from .search import _pad_bucket

        by_sig: dict = {}
        order: list = []
        for b in bundles:
            sig = b.signature
            if sig not in by_sig:
                by_sig[sig] = []
                order.append(sig)
            by_sig[sig].append(bundle_query_sel(plan, b))
        groups = []
        for sig in order:
            sels = by_sig[sig]
            sel = (sels[0] if len(sels) == 1
                   else np.concatenate(sels)).astype(np.int64)
            groups.append(LaunchGroup(
                w_search=sig[0], skip_test=sig[1], sel=sel,
                pad_n=_pad_bucket(sel.shape[0], self.ns.opts.query_tile),
                n_bundles=len(sels)))
        return groups

    # -- compiled launch schedules ------------------------------------------

    def _get_launcher(self, groups, nq: int):
        """One jitted program running the WHOLE launch schedule: per group
        gather -> padded window search -> on-device scatter through the
        composed schedule∘partition permutation. Cached by the plan's
        *padded-bucket* shape ``(w, skip, pad_n)`` per group, NOT by exact
        query counts or plan values: the selection vector is edge-padded to
        the bucket on the host, so steady-state queries whose partition
        counts drift within the same buckets (SPH stepping) reuse the
        compiled schedule unchanged.

        Covers the Pallas path too: ``window_search_pallas`` is pure
        traced JAX (tile-window anchors computed on device via the
        level-segmented launches of ``kernels/ops``), so the fused kernels
        compile INTO the launch schedule. The three output buffers are
        donated — the caller hands in fresh init arrays and XLA scatters
        into them in place instead of materializing copies.
        """
        ns = self.ns
        metas = tuple((g.w_search, g.skip_test, g.pad_n) for g in groups)
        key = (metas, nq, ns.params.k, ns.opts.query_tile,
               ns.opts.use_pallas)
        launcher = self._launcher_cache.get(key)
        if launcher is not None:
            self._launcher_cache.move_to_end(key)
            self._last["launcher_cache_hit"] = True
            return launcher
        faults.maybe_fail("compile")
        self._last["compilations"] += 1
        searcher = ns._searcher()
        spec, radius, k, tile = (ns.spec, ns.params.radius, ns.params.k,
                                 ns.opts.query_tile)
        for g in groups:
            self._signatures.add((g.w_search, g.skip_test, g.pad_n, tile,
                                  k, ns.opts.use_pallas))

        @partial(jax.jit, donate_argnums=(5, 6, 7))
        def launcher(grid, points, queries_s, perm, sels,
                     out_idx, out_d2, out_cnt):
            # the scope of api.execute_plan: the search metrics read the
            # executor's launches by the same name
            with jax.named_scope("repro.execute_plan"):
                for (w, skip, _pad_n), sel in zip(metas, sels):
                    # sel arrives edge-padded to the bucket: padded slots
                    # repeat the group's last real query, so their searched
                    # rows are identical to that query's row and the
                    # duplicate scatter writes below are idempotent
                    qb = queries_s[sel]
                    idx, d2, cnt = searcher(grid, points, qb, spec, w,
                                            radius, k, skip, tile)
                    orig = perm[sel]
                    out_idx = out_idx.at[orig].set(idx)
                    out_d2 = out_d2.at[orig].set(d2)
                    out_cnt = out_cnt.at[orig].set(cnt)
                return out_idx, out_d2, out_cnt

        self._launcher_cache[key] = launcher
        if len(self._launcher_cache) > _LAUNCHER_CACHE_MAX:
            self._launcher_cache.popitem(last=False)
        return launcher

    # -- execution ----------------------------------------------------------

    def execute(self, queries, *,
                reuse: PlanHandle | None = None) -> SearchResult:
        """Run one query. With ``reuse`` the given captured plan is replayed
        verbatim — no schedule, no plan fetch, no partition/bundle work, no
        padding: pure device dispatch through the cached compiled launch
        schedule (the dynamic-scene steady state)."""
        return self.execute_async(queries, reuse=reuse).wait()

    def execute_async(self, queries, *,
                      reuse: PlanHandle | None = None) -> "PendingResult":
        """Plan and dispatch one query WITHOUT the blocking result sync.

        Returns a :class:`PendingResult` whose ``wait()`` performs the
        one-sync materialization. Splitting dispatch from sync lets a
        streaming caller (the serving drain loop, an SPH stepper over many
        independent batches) stage batch N+1 on the host while batch N
        still executes on device — the pipelining the one-sync contract
        otherwise serializes away. Overlap-safe: every per-call counter
        rides the pending record, not executor scratch state.
        """
        ns = self.ns
        # compiles: programs JAX compiled while planning and dispatching
        last = dict(host_syncs=0, plan_fetches=0, launches=0,
                    dispatches=0, compilations=0, compiles=0, bundles=0,
                    plan_cache_hit=False, plan_reused=False,
                    launcher_cache_hit=False)
        compiles0 = obs.thread_compiles()
        self._last = last
        queries = jnp.asarray(queries, jnp.float32)
        nq = queries.shape[0]
        k = ns.params.k

        # the top-level query span stays open until the pending result's
        # wait() — plan/launch/sync all nest under it, preserving the
        # section-9 span taxonomy across the dispatch/sync split
        sp_query = obs.span("query", nq=nq)
        sp_query.__enter__()
        try:
            # fault-injection seam (reliability.faults): a scheduled
            # launch fault fails the dispatch before any device work
            faults.maybe_fail("launch")
            pending = self._dispatch_pending(queries, nq, k, reuse, last,
                                             sp_query)
            last["compiles"] = obs.thread_compiles() - compiles0
            return pending
        except BaseException:
            sp_query.__exit__(None, None, None)
            raise

    def _dispatch_pending(self, queries, nq, k, reuse, last, sp_query):
        ns = self.ns
        with obs.span("plan", reused=reuse is not None) as sp_plan:
            if reuse is not None:
                if reuse.nq != nq:
                    raise ValueError(f"reused plan was captured for nq="
                                     f"{reuse.nq}, got {nq} queries")
                perm = reuse.perm
                queries_s = queries[perm]
                plan, bundles, groups = (reuse.plan, reuse.bundles,
                                         reuse.groups)
                sels_dev = reuse.sels_dev
                last["plan_reused"] = True
            else:
                perm, _inv = ns._schedule(queries)
                queries_s = queries[perm]
                plan, bundles, groups = self._plan(queries_s)
                sels_dev = self._prepare_launch(groups)
        ns.report.t_opt = sp_plan.duration
        ns.report.num_partitions = plan.num_partitions
        ns.report.bundles = bundles
        last["bundles"] = len(bundles)
        last["launches"] = len(groups)

        t0 = time.perf_counter()
        with obs.span("launch", groups=len(groups)):
            launcher = self._get_launcher(groups, nq)
            # selections are edge-padded to their buckets so the
            # launcher only ever sees bucketed shapes (zero retraces on
            # count drift); the freshly-initialized output buffers are
            # donated into the program
            out_idx, out_d2, out_cnt = launcher(
                ns.grid, ns.points, queries_s, perm, sels_dev,
                jnp.full((nq, k), -1, jnp.int32),
                jnp.full((nq, k), jnp.inf, jnp.float32),
                jnp.zeros((nq,), jnp.int32))
        last["dispatches"] = 1
        return PendingResult(self, (out_idx, out_d2, out_cnt), last,
                             sp_query, t0)

    def _finalize(self, arrays, last, sp_query, t_launch) -> SearchResult:
        """The pending result's one blocking sync + metric/report flush."""
        ns = self.ns
        out_idx, out_d2, out_cnt = arrays
        faults.maybe_delay()          # injected straggler: sync is late
        with obs.span("sync"):
            jax.block_until_ready(arrays)
        sp_query.__exit__(None, None, None)
        last["host_syncs"] += 1
        ns.report.t_search = time.perf_counter() - t_launch
        ns.report.launches = last["launches"]
        ns.report.host_syncs = last["host_syncs"]
        ns.report.plan_fetches = last["plan_fetches"]
        self._last = last

        m = self._metrics
        m.count("queries")
        for key in ("launches", "dispatches", "bundles", "host_syncs",
                    "plan_fetches", "compilations"):
            m.count(key, last[key])
        m.count("plan_cache_hits", int(last["plan_cache_hit"]))
        m.count("plan_cache_misses",
                int(not (last["plan_cache_hit"] or last["plan_reused"])))
        m.count("plan_reuses", int(last["plan_reused"]))
        m.count("launcher_cache_hits", int(last["launcher_cache_hit"]))
        m.count("launcher_cache_misses", last["compilations"])
        m.observe("query_s", sp_query.duration)
        m.observe("plan_s", ns.report.t_opt)
        m.gauge("plan_cache_entries", len(self._plan_cache))
        m.gauge("launcher_cache_entries", len(self._launcher_cache))

        return SearchResult(indices=out_idx, distances2=out_d2,
                            counts=out_cnt)

    def invalidate(self) -> None:
        """Drop every cached plan, compiled launch schedule, and signature.

        A respec (``core/dynamic.py``) changes the grid spec that cached
        launchers close over and that every plan was computed against —
        replaying any of them would search the wrong geometry, so the
        caches are cleared wholesale and outstanding ``PlanHandle``s must
        be discarded by their owner."""
        self._plan_cache.clear()
        self._launcher_cache.clear()
        self._signatures.clear()
        self._metrics.count("invalidations")

    # -- surface ------------------------------------------------------------

    def warmup(self, queries) -> dict:
        """Run one query to populate the plan and compile caches (SPH-style
        steppers call this once before the timed loop). Returns stats()."""
        self.execute(queries)
        return self.stats()

    def stats(self) -> dict:
        """Counters for the caching/sync contract.

        ``last`` holds the most recent query's breakdown; ``compilations``
        counts first-seen launch signatures (the jit cache compiles once per
        signature); ``jit_cache_sizes`` exposes the actual jit caches so
        tests can assert a steady-state query compiled nothing.
        """
        from .search import window_search
        sizes = {"window_search": window_search._cache_size()}
        if self.ns.opts.use_pallas:
            from ..kernels.knn_tile import knn_tile, knn_tile_anchored
            sizes["knn_tile"] = knn_tile._cache_size()
            sizes["knn_tile_anchored"] = knn_tile_anchored._cache_size()
        return {
            **self._metrics.counters(),
            "last": dict(self._last),
            "signatures": len(self._signatures),
            "plan_cache_entries": len(self._plan_cache),
            "launcher_cache_entries": len(self._launcher_cache),
            "jit_cache_sizes": sizes,
        }
