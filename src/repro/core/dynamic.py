"""Dynamic-scene subsystem: persistent sessions over moving points
(DESIGN.md sections 7-8).

RTNN's target applications — SPH fluids, MD, point-cloud registration — are
*frame-stepped*: points move a little each step. The static pipeline pays
its whole cost again every frame (host `choose_grid_spec` sync, full grid
rebuild, cold plan/compile caches); the paper's Fig. 15 makes build time a
first-class cost for exactly this reason, and follow-on work (RT-kNNS
Unbound; dynamic fixed-radius RT search) centers keeping the index resident
across rounds. :class:`SimulationSession` is that steady-state path, now a
thin shim over the functional core (``core/api.py``):

* **frozen spec** — the `GridSpec` is planned ONCE (with domain margin and
  capacity slack so points can drift), so every step's shapes are static
  and the one compiled step program stays valid across the whole run;
* **one fused step program** — ``step()`` dispatches a single jitted
  program: ``update_index`` (incremental re-bin + on-device counters and
  the max-displacement statistic) followed by the staleness branch and the
  search. No host work between update and search;
* **device-resident staleness** — the replan-vs-replay decision is
  ``lax.cond(max_disp2 > threshold^2, replan, replay)`` ON DEVICE: the
  replan branch recomputes the (level, Morton) :class:`~.api.QueryPlan`
  (with the ``reuse_margin_cells`` inflation baked in, the staleness
  contract of ``partition.inflate_plan_inputs``) and re-anchors; the
  replay branch returns the captured plan unchanged. The per-step stats
  fetch of the previous design is gone — the ONLY per-step host transfer
  is one packed flags scalar that rides the result materialization
  (it doubles as the respec guard);
* **self-query fast path** — ``step(points)`` (the SPH/MD case) never
  uploads a second array; points and queries are the same device buffer
  through the whole fused program;
* **respec fallback** — a nonzero overflow / out-of-bounds counter (bit 1
  of the flags scalar) means the frozen grid can no longer represent the
  scene exactly; the session falls back to the (rare) host-side
  respec-and-rebuild — fresh spec, fresh ``NeighborIndex``, forced replan
  — and re-executes the step so results stay exact across the respec.
  Respecs carry hysteresis: each one plans with geometrically growing
  capacity/margin headroom (``SessionOpts.respec_growth``), so adversarial
  workloads that keep exhausting the spec pay O(log frames) respecs.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import api
from .grid import choose_grid_spec
from .types import (Array, GridSpec, SearchOpts, SearchParams, SearchResult)


@dataclasses.dataclass(frozen=True)
class SessionOpts:
    """Static knobs of a :class:`SimulationSession`.

    ``displacement_frac``  staleness threshold as a fraction of cell size:
                           the cached plan is replayed while the max
                           displacement since its capture stays below
                           ``displacement_frac * cell_size``. Must be
                           <= 0.5 for the ``reuse_margin_cells`` default to
                           keep reused plans exact (a half-cell drift moves
                           any point's cell by at most one).
    ``reuse_margin_cells`` window inflation baked into captured plans (see
                           ``partition.inflate_plan_inputs``): 2 cells
                           absorb candidate drift + the query's own cell
                           shift at the default threshold.
    ``capacity_slack``     cell-capacity headroom of the frozen spec (the
                           static path plans exactly at the observed max
                           occupancy; moving points need room to pile up).
                           Search cost scales with capacity — the default
                           absorbs the typical +1 occupancy drift without
                           inflating the candidate gather much; denser
                           pile-ups fall back to a respec.
    ``domain_margin_radii`` bounding-box padding of the frozen spec, in
                           search radii per side (= 4 cells of drift room
                           at the default cell size) so points can drift
                           without leaving the grid; escapes respec.
    ``auto_respec``        respec-and-rebuild when overflow/out-of-bounds
                           is detected (False: raise instead — for tests
                           and workloads that must never pay a respec).
    ``respec_growth``      respec hysteresis: every respec multiplies the
                           new spec's capacity slack AND domain margin by
                           ``respec_growth ** respecs_so_far``, so the
                           headroom grows geometrically. An adversarial
                           workload that keeps outrunning the frozen spec
                           (a constant-velocity escapee, a cell that
                           points keep piling into) then triggers O(log
                           frames) respecs instead of one per frame —
                           each respec buys exponentially more frames.
                           Set to 1.0 to disable (fixed headroom).
    ``respec_boost_max``   cap on the accumulated hysteresis multiplier:
                           capacity scales the dense grid's memory, so
                           unbounded geometric growth would trade a cheap
                           respec for an allocation failure on a
                           long-lived adversarial session. Past the cap
                           the respec cadence degrades gracefully from
                           O(log frames) back to O(frames / cap).
    ``donate_grid``        alias-safe grid-only donation of the fused step:
                           the dense-grid leaves of the index (always
                           session-owned — built fresh by build/update,
                           never aliasing caller arrays) are donated to the
                           step program so XLA updates the dense array in
                           place, while the points/anchor leaves — which CAN
                           alias caller-owned device buffers — are left
                           alone. None = auto (on everywhere except the CPU
                           backend, which ignores donation and would warn).
                           After a step the PREVIOUS index's grid buffers
                           are consumed: callers holding ``sess.index``
                           across steps on non-CPU backends should re-read
                           the property.
    """

    displacement_frac: float = 0.45
    reuse_margin_cells: int = 2
    capacity_slack: float = 1.5
    domain_margin_radii: float = 1.0
    max_dim: int = 256
    auto_respec: bool = True
    respec_growth: float = 2.0
    respec_boost_max: float = 64.0
    donate_grid: bool | None = None


@dataclasses.dataclass
class StepReport:
    """Per-step breakdown (the session analogue of ``SearchReport``).

    The staleness statistic lives on device but rides the packed telemetry
    vector (obs/device.py), so ``max_disp`` / ``overflow`` / ``oob`` are
    populated every step at no extra sync; ``t_update``/``t_plan`` are 0.0
    because update, plan, and search are one fused program timed as
    ``t_search``.
    """

    t_update: float = 0.0      # merged into t_search (fused step program)
    t_plan: float = 0.0        # merged into t_search (fused step program)
    t_search: float = 0.0      # fused step dispatch + telemetry/result sync
    fast: bool = False         # replayed the captured plan (device decision)
    replanned: bool = False
    respecced: bool = False
    max_disp: float = 0.0      # from the packed telemetry vector
    overflow: int = 0
    oob: int = 0
    compiles: int = 0          # programs JAX compiled during the step


def validate_session_opts(sopts: SessionOpts) -> None:
    """The staleness-contract invariant shared by every session surface
    (`SimulationSession`, `core/shards.ShardedSession`): each of the query
    and its candidates may shift ceil(frac) cells before a replan, so the
    baked-in window margin must cover both or plan reuse silently loses
    exactness."""
    if sopts.displacement_frac <= 0.0:
        raise ValueError("displacement_frac must be > 0")
    need = 2 * math.ceil(sopts.displacement_frac)
    if sopts.reuse_margin_cells < need:
        raise ValueError(
            f"reuse_margin_cells={sopts.reuse_margin_cells} cannot keep "
            f"reused plans exact at displacement_frac="
            f"{sopts.displacement_frac} (needs >= {need})")


def session_grid_spec(points: np.ndarray, radius: float,
                      sopts: SessionOpts = SessionOpts(),
                      boost: float = 1.0) -> GridSpec:
    """Host-side planning of a session's *frozen* grid: the static policy
    of ``choose_grid_spec`` plus drift headroom (domain margin, capacity
    slack) so the spec survives many frames of motion.

    ``boost`` scales both headroom knobs — the respec-hysteresis factor
    (``respec_growth ** respecs``) the session passes on each respec so
    repeated exhaustion buys geometrically growing headroom."""
    return choose_grid_spec(
        np.asarray(points, np.float32), radius,
        max_dim=sopts.max_dim,
        capacity_slack=sopts.capacity_slack * boost,
        domain_margin=sopts.domain_margin_radii * float(radius) * boost,
    )


# ---------------------------------------------------------------------------
# the fused step program
# ---------------------------------------------------------------------------

# flags bitmask in slot 0 of the packed telemetry vector returned by the
# fused step (ONE packed int32 vector is the only per-step host transfer;
# fetching it doubles as the result sync — obs/device.py lays out the
# remaining slots: overflow, oob, displacement bits, migration, halo, and
# the per-ladder-level occupancy histogram)
_FLAG_REPLANNED = 1     # staleness cond took the replan branch
_FLAG_EXHAUSTED = 2     # overflow/oob: frozen spec can no longer bin exactly


def _step_impl(grid, index_rest: api.NeighborIndex, plan, pts: Array,
               q: Array, anchor_q: Array, *, thr2: float, margin: int,
               force: bool, self_query: bool):
    """update_index -> lax.cond(stale, replan, replay) -> execute_plan.

    Everything device-resident: the staleness statistic (max displacement
    vs the plan anchor, plus query drift in external-query mode) is
    compared against the threshold on device, and both the fresh and the
    replayed :class:`~.api.QueryPlan` flow into the same compiled search.
    ``force`` (static) is the plan-capture variant: first step, shape or
    query-set changes, and the post-respec re-execution.

    The index arrives SPLIT: ``grid`` (argument 0) carries the dense-grid
    leaves so they can be donated on their own — they are session-owned by
    construction, unlike ``index_rest``'s points/anchor leaves, which can
    alias caller buffers (and, after a replan, each other) and must never
    be donated.
    """
    index = dataclasses.replace(index_rest, grid=grid)
    index2, stats = api.update_index(index, pts)
    bad = (stats.overflow > 0) | (stats.oob > 0)
    disp2 = stats.max_disp2
    if not self_query:
        disp2 = jnp.maximum(
            disp2, jnp.max(jnp.sum((q - anchor_q) ** 2, axis=-1)))

    if force:
        stale = jnp.bool_(True)
        plan2 = api.plan_query(index2, q, margin=margin)
        anchor2, anchor_q2 = pts, q
    else:
        stale = disp2 > jnp.float32(thr2)

        def replan(_):
            return api.plan_query(index2, q, margin=margin), pts, q

        def replay(_):
            return plan, index2.anchor_points, anchor_q

        plan2, anchor2, anchor_q2 = jax.lax.cond(stale, replan, replay, None)

    index3 = index2.with_anchor(anchor2)
    res = api.execute_plan(index3, q, plan2)
    flags = (stale.astype(jnp.int32) * _FLAG_REPLANNED
             + bad.astype(jnp.int32) * _FLAG_EXHAUSTED)
    # widen the flags scalar into the packed telemetry vector: still ONE
    # per-step transfer (obs/device.py), computed unconditionally so the
    # step jaxpr is identical with host-side telemetry on or off
    telem = obs.pack_step_telemetry(
        flags, overflow=stats.overflow, oob=stats.oob, max_disp2=disp2,
        occupancy=obs.level_occupancy(plan2.tile_levels,
                                      len(plan2.ladder)))
    return index3, plan2, anchor_q2, res, telem, stats


# NOTE: the step donates ONLY the grid argument (argument 0, the dense-grid
# leaves split out of the index). The points/anchor_points leaves can alias
# caller-owned arrays (build_index keeps the caller's device buffer), and
# after a replan both leaves can be the SAME buffer — donating them would
# invalidate caller arrays off-CPU and trip duplicate-donation. The grid
# leaves, by contrast, are always freshly built by build_cell_grid /
# update_cell_grid and owned by the session, so their donation is
# alias-safe (SessionOpts.donate_grid; auto-disabled on the CPU backend,
# which ignores donation).
_STEP_STATICS = ("thr2", "margin", "force", "self_query")


class SimulationSession:
    """Persistent neighbor search over a frame-stepped scene.

    >>> sess = SimulationSession(points, SearchParams(radius=0.1, k=8))
    >>> for _ in range(steps):
    ...     res = sess.step(points)          # self-query (SPH/MD)
    ...     points = integrate(points, res)

    ``step(points, queries)`` searches external queries instead; both forms
    return a ``SearchResult`` in query order, exact w.r.t. the *current*
    positions (oracle-identical to a fresh ``NeighborSearch``), including
    across respecs. ``stats()`` exposes the lifecycle counters the tests
    assert on (steps / fast_steps / replans / respecs / stats_fetches —
    the latter stays 0 on every non-respec step).
    """

    def __init__(
        self,
        points,
        params: SearchParams,
        opts: SearchOpts = SearchOpts(),
        sopts: SessionOpts = SessionOpts(),
        spec: GridSpec | None = None,
    ):
        validate_session_opts(sopts)
        if not params.bounded:
            raise ValueError(
                "a SimulationSession needs a finite radius: its staleness "
                "contract counts drift in cells of a radius; exact kNN "
                "with no radius is served by repro.api")
        self.sopts = sopts
        pts = jnp.asarray(points, jnp.float32)
        spec = spec or session_grid_spec(
            np.asarray(jax.device_get(pts)), params.radius, sopts)
        self._index = api.build_index(pts, params, opts, spec=spec)
        self._plan: api.QueryPlan | None = None
        self._anchor_queries: Array | None = None
        donate = sopts.donate_grid
        if donate is None:
            donate = jax.default_backend() != "cpu"
        # per-session jit so a respec can release the step variants
        # compiled against the old spec (and session teardown frees them
        # all) instead of pinning them in a module-global cache forever
        self._step_fn = jax.jit(_step_impl, static_argnames=_STEP_STATICS,
                                donate_argnums=(0,) if donate else ())
        # lifecycle counters + step-latency histogram in the unified
        # registry (repro.obs)
        self._metrics = obs.metric_set("session")
        self.report = StepReport()

    # -- surface ------------------------------------------------------------

    @property
    def spec(self) -> GridSpec:
        return self._index.spec

    @property
    def params(self) -> SearchParams:
        return self._index.params

    @property
    def index(self) -> api.NeighborIndex:
        """The session-managed functional index (``core/api.py``)."""
        return self._index

    def stats(self) -> dict:
        counters = dict(steps=0, fast_steps=0, replans=0, respecs=0,
                        stats_fetches=0, host_syncs=0, compiles=0)
        counters.update(self._metrics.counters())
        return {
            **counters,
            "last": dataclasses.asdict(self.report),
            "step_cache_size": int(self._step_fn._cache_size()),
        }

    # -- lifecycle ----------------------------------------------------------

    def _dispatch(self, index, pts, q, anchor_q, force, self_query):
        thr2 = float((self.sopts.displacement_frac *
                      index.spec.cell_size) ** 2)
        # grid split out as its own (donatable) argument; the rest of the
        # index rides with grid=None (an empty pytree slot)
        return self._step_fn(
            index.grid, dataclasses.replace(index, grid=None),
            None if force else self._plan, pts, q, anchor_q,
            thr2=thr2, margin=int(self.sopts.reuse_margin_cells),
            force=bool(force), self_query=bool(self_query))

    def _dispatch_synced(self, index, pts, q, anchor_q, force, self_query):
        """Launch the fused step, then fetch the packed telemetry vector —
        still the session's ONE blocking transfer per step. A compile of
        the step program shows as a ``compile`` span nested under the
        launch (``obs/compiles.py``)."""
        with obs.span("launch", forced=bool(force)):
            out = self._dispatch(index, pts, q, anchor_q, force, self_query)
        with obs.span("sync"):
            telem = obs.unpack_step_telemetry(
                np.asarray(jax.device_get(out[4])))
        self._metrics.count("host_syncs")
        return out, telem

    def step(self, points, queries=None) -> SearchResult:
        """Advance the session to ``points`` and search.

        ``queries=None`` (or ``queries is points``) is the self-query fast
        path: every particle queries its own neighborhood over the shared
        device buffer. Results are in query order, exact for the current
        positions. One fused device program per step; one packed flags
        scalar is the only host transfer (it materializes the results).
        """
        rep = StepReport()
        m = self._metrics
        compiles0 = obs.thread_compiles()
        with obs.span("step") as sp_step:
            pts = jnp.asarray(points, jnp.float32)
            self_query = queries is None or queries is points
            q = pts if self_query else jnp.asarray(queries, jnp.float32)

            with obs.span("plan"):
                index = self._index
                if pts.shape != index.points.shape:
                    # particle count changed under the frozen spec: re-seat
                    # the leaves; the displacement statistic restarts here
                    index = dataclasses.replace(index, points=pts,
                                                anchor_points=pts)
                    self._plan = None

                anchor_q = self._anchor_queries
                # switching between self-query and external queries always
                # replans: the captured plan is anchored at the other set's
                # positions, which the displacement statistic does not track
                force = (self._plan is None
                         or self._plan.nq != q.shape[0]
                         or self_query != (anchor_q is None))
                if self_query:
                    anchor_q = q
                elif anchor_q is None or anchor_q.shape != q.shape:
                    anchor_q = q
                    force = True

            out, tel = self._dispatch_synced(index, pts, q, anchor_q,
                                             force, self_query)
            index3, plan2, anchor_q2, res, _telem, _stats = out
            fl = tel["flags"]

            if fl & _FLAG_EXHAUSTED:
                # rare path: the packed telemetry already carries the
                # counters (no extra stats fetch — stats_fetches stays 0
                # even here); respec-and-rebuild on the host and re-execute
                # so results stay exact
                rep.overflow, rep.oob = tel["overflow"], tel["oob"]
                rep.max_disp = math.sqrt(max(tel["max_disp2"], 0.0))
                if not self.sopts.auto_respec:
                    # keep the session consistent (updated grid, dropped
                    # plan) before raising
                    self._index = index3
                    self._plan = None
                    self._anchor_queries = None if self_query else anchor_q2
                    raise RuntimeError(
                        f"frozen grid exhausted (overflow={rep.overflow}, "
                        f"out_of_bounds={rep.oob}) and auto_respec is "
                        f"disabled")
                # respec hysteresis: each respec plans with geometrically
                # more capacity/margin headroom, so an adversarial pile-up
                # or escapee costs O(log frames) respecs, not one per frame
                respecs = m.count("respecs")
                boost = min(
                    float(self.sopts.respec_growth) ** int(respecs),
                    float(self.sopts.respec_boost_max))
                spec = session_grid_spec(
                    np.asarray(jax.device_get(pts)), index.params.radius,
                    self.sopts, boost=boost)
                index = api.build_index(pts, index.params, index.opts,
                                        spec=spec)
                # release every step variant compiled against the old spec
                # (the new-spec trace replaces them; the analogue of the
                # executor path's invalidate())
                self._step_fn.clear_cache()
                rep.respecced = True
                out, tel = self._dispatch_synced(index, pts, q, anchor_q,
                                                 True, self_query)
                index3, plan2, anchor_q2, res, _telem, _stats = out
                fl = tel["flags"]
                if fl & _FLAG_EXHAUSTED:        # pragma: no cover
                    raise RuntimeError(
                        f"respec failed to absorb the scene (overflow="
                        f"{tel['overflow']}, oob={tel['oob']})")

            self._index = index3
            self._plan = plan2
            self._anchor_queries = None if self_query else anchor_q2
            if not rep.respecced:
                # (the respec path keeps the PRE-respec counters: the
                # post-respec re-execution is clean by construction)
                rep.overflow, rep.oob = tel["overflow"], tel["oob"]
                rep.max_disp = math.sqrt(max(tel["max_disp2"], 0.0))
            if fl & _FLAG_REPLANNED:
                rep.replanned = True
                m.count("replans")
            else:
                rep.fast = True
                m.count("fast_steps")
            m.count("steps")
            m.count("overflow_points", tel["overflow"])
            m.count("oob_points", tel["oob"])
            for lvl, occ in enumerate(tel["occupancy"]):
                m.count(f"level_occ_{lvl}", occ)
            m.gauge("staleness_disp2", tel["max_disp2"])
            m.gauge("step_cache_size", int(self._step_fn._cache_size()))
            rep.compiles = obs.thread_compiles() - compiles0
            m.count("compiles", rep.compiles)
            sp_step.set(compiles=rep.compiles)
        rep.t_search = sp_step.duration
        m.observe("step_s", rep.t_search)
        self.report = rep
        return res
