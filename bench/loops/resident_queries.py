"""Registration calls against a resident scan: one client's eager calls in
a closed loop.

Set-up draws the scan from ``(seed, 0)`` and indexes it once in one
``repro.core.NeighborSearch`` with the default options, over the frozen
grid spec that ``fresh_frames`` runs too (``LoopBase.plan_spec``,
planned over the sensor's calibration frame, the same for every seed).
Each timed unit is one call, ``ns.query(q)``, the eager path: the
executor schedules the queries, fetches their partition metadata, plans
partitions and bundles on the host and launches one program per call.
The next call starts when this one's result is ready.

A call's queries are what a registration step asks of the resident scan:
the feature points of the next scan, moved by the pose that is being
refined. They are ``n`` points of a fresh scene drawn from ``(seed, 4,
call)`` at the configuration's density, turned about the scan's centre by
a yaw within ``yaw_deg`` and shifted in x and y by up to ``shift_radii``
search radii, both drawn from ``(seed, 5, call)``, and clipped to the
scan's bounding box. The sizes ``n`` are dealt from ``call_sizes`` like
cards: each deck of ``len(call_sizes)`` calls holds every size once, in
an order drawn from ``(seed, 6, deck)``, so every seed meets the same
sizes, and a warm-up of one deck compiles every program the window runs
(the executor compiles per query count). A producer thread makes the
calls ahead on the host (numpy only), so a timed call holds no query
generation; the executor's plan cache, keyed by the plan's values, misses
on every call.
"""
from __future__ import annotations

import math

import jax
import numpy as np

from bench.lib.loopbase import LoopBase, Record
from bench.lib.producer import Producer
from bench.lib.rng import rng_for

# executor counters whose change over the window ``notes`` reports, by
# the name it prints
COUNTERS = {"calls": "queries", "launches": "launches",
            "plan_fetches": "plan_fetches",
            "plan_cache_hits": "plan_cache_hits"}


def make_search(points, params, spec):
    """The system under test: the scan indexed once, default options."""
    from repro.core import NeighborSearch
    return NeighborSearch(points, params, spec=spec)


class Loop(LoopBase):
    unit_name = "call"
    _calls = None

    def setup(self):
        t = self.traffic
        self.scan = self.scene(0)
        self._lo, self._hi = self.scan.min(axis=0), self.scan.max(axis=0)
        self._yaw = math.radians(float(t["yaw_deg"]))
        self._shift = float(t["shift_radii"]) * self.params.radius
        self.spec = self.plan_spec()
        self._ns = make_search(jax.device_put(self.scan), self.params,
                               self.spec)
        self._overflow = self._ns.grid.overflow
        self._calls = Producer(self.queries, t["queue_depth"], "bench-calls")
        for _ in range(int(t["warm_units"])):
            self.unit()
        self.records.clear()
        self._calls.waits = 0
        self._stats0 = self._counts()

    def size(self, call: int) -> int:
        """The number of queries of call ``call``, dealt from its deck."""
        sizes = self.traffic["call_sizes"]
        deck, pos = divmod(call, len(sizes))
        order = rng_for(self.seed, 6, deck).permutation(len(sizes))
        return int(sizes[order[pos]])

    def queries(self, call: int) -> np.ndarray:
        """The queries of call ``call``."""
        q = self.scene(4, call, points=self.size(call))
        rng = rng_for(self.seed, 5, call)
        yaw = rng.uniform(-self._yaw, self._yaw)
        shift = rng.uniform(-self._shift, self._shift, 2)
        c = 0.5 * (self._lo[:2] + self._hi[:2])
        rot = np.array([[math.cos(yaw), -math.sin(yaw)],
                        [math.sin(yaw), math.cos(yaw)]])
        q[:, :2] = (q[:, :2] - c) @ rot.T + c + shift
        return np.clip(q, self._lo, self._hi)

    def unit(self):
        with jax.profiler.TraceAnnotation("bench.dequeue"):
            q = self._calls.take()
        with jax.profiler.TraceAnnotation("bench.call"):
            res = self._ns.query(q)
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready(res)
        # every call searches the one grid: the points it dropped count
        # against each
        self.records.append(Record(points=self.scan, result=res,
                                   overflow=self._overflow, queries=q))

    def _counts(self) -> dict:
        from repro import obs
        st = self._ns.executor.stats()
        return {**{k: int(st.get(c, 0)) for k, c in COUNTERS.items()},
                "compiles": obs.thread_compiles()}

    def sizes(self):
        """(points, queries, K) of one call; its queries are the mean over
        the window's calls."""
        nq = np.mean([len(r.queries) for r in self.records])
        return len(self.scan), float(nq), self.params.k

    def notes(self):
        now = self._counts()
        return {**{k: now[k] - self._stats0[k] for k in now},
                "producer_waits": self._calls.waits,
                "grid_capacity": self.spec.capacity,
                "grid_dims": list(self.spec.dims)}

    def close(self):
        if self._calls is not None:
            self._calls.close()
