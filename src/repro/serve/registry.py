"""Scene registry: LRU of device-resident scenes keyed by scene id
(DESIGN.md section 10).

A serving process holds many tenants' scenes but bounded device memory, so
residency is explicit: a :class:`SceneRegistry` keeps at most ``capacity``
scenes resident, each a :class:`SceneRecord` owning the uploaded points and
one :class:`SceneVariant` per search signature ``(SearchParams,
SearchOpts)`` — the unit the micro-batcher buckets requests by. A variant
owns a built ``NeighborSearch`` (functional ``NeighborIndex`` + the
host-planned ``QueryExecutor`` with its plan/compile caches) plus a
*private* jitted ``api.query`` wrapper, so evicting the scene releases
every compiled serve program along with the executor caches
(``executor.invalidate()``) instead of pinning them in a process-global
jit cache forever. Eviction fires registered callbacks so the service can
fail or re-route in-flight requests for the evicted tenant.

Live :class:`~repro.core.SimulationSession` scenes register too
(``add_session``): their variant serves queries against the session's
*current* index leaves — same aux, so stepping the session never retraces
the serve program.
"""
from __future__ import annotations

import collections
import threading

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core import api
from ..core.search import NeighborSearch, _pad_bucket
from ..core.types import SearchOpts, SearchParams


def _fresh_query_fn():
    """A jitted ``api.query`` with its OWN jit cache (a distinct closure
    per call), so dropping the variant releases its compiled programs."""

    def _serve_query(index, queries):
        return api.query(index, queries)

    return jax.jit(_serve_query)


class SceneVariant:
    """One scene under one search signature: the compiled serving unit.

    ``index`` is the functional pytree the drained launches run against;
    ``fn`` the variant-private jitted ``api.query``; ``searcher`` the eager
    host-planned surface over the same leaves (its executor caches are the
    per-scene cache handles the registry invalidates on evict).
    """

    __slots__ = ("params", "opts", "searcher", "session", "fn", "warmed",
                 "_quality")

    def __init__(self, params: SearchParams, opts: SearchOpts, *,
                 searcher: NeighborSearch | None = None, session=None):
        self.params = params
        self.opts = opts
        self.searcher = searcher
        self.session = session
        self.fn = _fresh_query_fn()
        self.warmed: set[int] = set()
        self._quality: tuple[int, int] | None = None

    @property
    def index(self) -> api.NeighborIndex:
        if self.session is not None:
            return self.session.index
        return self.searcher.index

    def pad_to_bucket(self, n: int) -> int:
        """Padded launch size for ``n`` concatenated queries (power-of-two
        multiple of the query tile — the executor's recompile-bounding
        bucket discipline)."""
        return _pad_bucket(n, self.opts.query_tile)

    def warm(self, nq: int) -> int:
        """Compile the serve program for the ``nq``-query bucket (one dummy
        launch); returns the padded bucket size. Idempotent per bucket."""
        pad_n = self.pad_to_bucket(nq)
        if pad_n not in self.warmed:
            dummy = jnp.zeros((pad_n, 3), jnp.float32)
            jax.block_until_ready(self.fn(self.index, dummy))
            self.warmed.add(pad_n)
        return pad_n

    def quality_counters(self) -> tuple[int, int]:
        """``(overflow, oob)`` device quality counters for responses served
        off this variant (DESIGN.md section 11). A static scene's grid is
        frozen after build, so its overflow scalar is fetched ONCE and
        cached — no extra per-drain host sync; a session-backed scene reads
        the host-side counters the session's packed telemetry already
        published for the current frame (no device fetch at all)."""
        if self.session is not None:
            rep = self.session.report
            return int(rep.overflow), int(rep.oob)
        if self._quality is None:
            self._quality = (
                int(jax.device_get(self.searcher.index.grid.overflow)), 0)
        return self._quality

    def compiled_programs(self) -> int:
        """Entries in the variant-private jit cache (tests assert re-warm
        after eviction/readmission through this)."""
        return int(self.fn._cache_size())

    def release(self) -> None:
        """Drop compiled state: executor plan/compile caches and the
        variant-private jitted programs."""
        if self.searcher is not None:
            self.searcher.executor.invalidate()
        self.fn = None
        self.warmed.clear()


class SceneRecord:
    """One resident scene: the uploaded points plus its signature variants."""

    __slots__ = ("scene_id", "points", "spec", "session", "_variants")

    def __init__(self, scene_id, points=None, *, spec=None, session=None):
        self.scene_id = scene_id
        self.session = session
        self.spec = spec
        if session is not None:
            self.points = None
        else:
            self.points = np.asarray(points, np.float32)
        self._variants: dict = {}

    def variant(self, params: SearchParams,
                opts: SearchOpts = SearchOpts()) -> SceneVariant:
        """Get-or-build the scene's variant for one search signature."""
        key = (params, opts)
        v = self._variants.get(key)
        if v is not None:
            return v
        if self.session is not None:
            if params != self.session.params:
                raise ValueError(
                    f"scene {self.scene_id!r} is session-backed with params "
                    f"{self.session.params}; cannot serve {params}")
            v = SceneVariant(params, opts, session=self.session)
        else:
            v = SceneVariant(params, opts, searcher=NeighborSearch(
                self.points, params, opts, spec=self.spec))
        self._variants[key] = v
        return v

    def variants(self):
        return list(self._variants.values())

    def release(self) -> None:
        for v in self._variants.values():
            v.release()
        self._variants.clear()


class SceneRegistry:
    """LRU of resident :class:`SceneRecord`\\ s, explicit capacity.

    ``get``/``resolve`` touch the entry (most-recently-used); ``add_*``
    past capacity evicts the least-recently-used scene — releasing its
    executor caches and compiled serve programs and firing every
    ``on_evict`` callback with ``(scene_id, record)``.
    """

    def __init__(self, capacity: int = 8):
        if capacity < 1:
            raise ValueError("registry capacity must be >= 1")
        self.capacity = int(capacity)
        self._records: collections.OrderedDict = collections.OrderedDict()
        self._callbacks: list = []
        self._lock = threading.RLock()
        self._metrics = obs.metric_set("serve_registry")

    # -- membership ---------------------------------------------------------

    def on_evict(self, callback) -> None:
        """Register ``callback(scene_id, record)`` to fire on eviction."""
        self._callbacks.append(callback)

    def add_scene(self, scene_id, points, *, spec=None) -> SceneRecord:
        """Admit (or replace) a static scene; evicts LRU past capacity."""
        return self._admit(SceneRecord(scene_id, points, spec=spec))

    def add_session(self, scene_id, session) -> SceneRecord:
        """Admit a live ``SimulationSession`` as a dynamic scene."""
        return self._admit(SceneRecord(scene_id, session=session))

    def _admit(self, rec: SceneRecord) -> SceneRecord:
        with self._lock:
            old = self._records.pop(rec.scene_id, None)
            if old is not None:
                old.release()
            self._records[rec.scene_id] = rec
            self._metrics.count("admissions")
            while len(self._records) > self.capacity:
                lru_id = next(iter(self._records))
                self._evict_locked(lru_id)
            self._metrics.gauge("resident_scenes", len(self._records))
        return rec

    def evict(self, scene_id) -> None:
        with self._lock:
            self._evict_locked(scene_id)
            self._metrics.gauge("resident_scenes", len(self._records))

    def _evict_locked(self, scene_id) -> None:
        rec = self._records.pop(scene_id)
        rec.release()
        self._metrics.count("evictions")
        for cb in self._callbacks:
            cb(scene_id, rec)

    def clear(self) -> None:
        with self._lock:
            for scene_id in list(self._records):
                self._evict_locked(scene_id)
            self._metrics.gauge("resident_scenes", 0)

    # -- lookup -------------------------------------------------------------

    def __contains__(self, scene_id) -> bool:
        with self._lock:
            return scene_id in self._records

    def __len__(self) -> int:
        return len(self._records)

    def get(self, scene_id) -> SceneRecord:
        """Fetch + LRU-touch; ``KeyError`` when not resident."""
        with self._lock:
            rec = self._records[scene_id]
            self._records.move_to_end(scene_id)
            return rec

    def resolve(self, scene_id, params: SearchParams,
                opts: SearchOpts = SearchOpts()) -> SceneVariant:
        """``get`` + get-or-build the signature variant (the drain path)."""
        return self.get(scene_id).variant(params, opts)

    def scene_ids(self) -> list:
        with self._lock:
            return list(self._records)

    def stats(self) -> dict:
        with self._lock:
            return {
                **self._metrics.counters(),
                "resident_scenes": len(self._records),
                "capacity": self.capacity,
                "variants": sum(len(r._variants)
                                for r in self._records.values()),
            }
