"""kNN graphs of point-cloud training batches, back to back: a training
input pipeline in a closed loop.

Every unit is a fresh batch of clouds drawn from ``(seed, batch index)``
(``bench/scenes/surface_samples.py``: ModelNet40-like shapes, augmented
as DGCNN trains). A producer thread makes batches ahead on the host into
a bounded queue (numpy only). The loop moves them to the device and
launches one jitted program a batch, compiled in set-up: one index over
the whole batch, then every point's K nearest points of its own cloud
with no radius (``repro.api.build_index`` over [B, N, 3],
``plan_query``, ``execute_plan``), each query against its whole cloud.

A unit is one batch's graph, ready on the host. It launches batches
until ``DEPTH`` are in flight and then waits for the oldest, as a
training step takes the graph that its input pipeline prefetched: the
device always holds the next batches, so a unit lasts one batch's device
time, and a late host turn shorter than the batches queued behind is
hidden. Set-up ends with nothing in flight, so the window's first unit
launches its own batch and the ones behind it: a window of units holds
the device work of as many batches (and the first operations of the
next), traced or not. One record per cloud: the comparison judges each
cloud's rows over that cloud's points.
"""
from __future__ import annotations

from collections import deque

import jax

from bench.lib.loopbase import LoopBase, Record
from bench.lib.producer import Producer

DEPTH = 3       # batches in flight when a unit starts to wait


def graph_program(params):
    """The timed path: index + self-query of one batch of clouds."""
    import repro.api as api

    def batch(points):
        index = api.build_index(points, params)
        plan = api.plan_query(index, index.points)
        return api.execute_plan(index, index.points, plan)

    return batch


class _Batch:
    """One batch in flight: its points and its result. The result's copy
    to the host is queued behind the program and taken when the batch's
    unit waits (``host``), so the device holds ``DEPTH`` batches'
    results, not the window's (a window holds hundreds of batches of
    5.4 MB)."""

    def __init__(self, points, result):
        self.points, self._device, self._host = points, result, None
        for leaf in jax.tree.leaves(self._device):
            leaf.copy_to_host_async()

    def done(self) -> bool:
        return self._host is not None or all(
            leaf.is_ready() for leaf in jax.tree.leaves(self._device))

    def host(self):
        if self._host is None:
            self._host, self._device = jax.device_get(self._device), None
        return self._host


class _CloudResult:
    """One cloud's rows of a batch result, as a search result."""

    def __init__(self, batch: _Batch, cloud: int):
        self._batch, self._cloud = batch, cloud

    def _rows(self, name):
        return getattr(self._batch.host(), name)[self._cloud]

    indices = property(lambda self: self._rows("indices"))
    distances2 = property(lambda self: self._rows("distances2"))
    counts = property(lambda self: self._rows("counts"))


class Loop(LoopBase):
    unit_name = "batch"
    _batches = None
    idle_launches = 0       # launches that found every batch done: the
                            # device had sat idle, waiting for the host

    def build_program(self):
        return jax.jit(graph_program(self.params))

    def setup(self):
        from repro import obs
        self._program = self.build_program()
        self._batches = Producer(self.scene, self.traffic["queue_depth"],
                                 "bench-batches")
        self._flight = deque()
        for _ in range(int(self.traffic["warm_units"])):
            self.unit()
        while self._flight:
            self._flight.popleft().host()
        self.records.clear()
        self._batches.waits = 0
        self.idle_launches = 0
        self._compiles0 = obs.thread_compiles()

    def _launch(self):
        with jax.profiler.TraceAnnotation("bench.dequeue"):
            pts = self._batches.take()
        with jax.profiler.TraceAnnotation("bench.transfer"):
            dev = jax.device_put(pts)
        with jax.profiler.TraceAnnotation("bench.program"):
            if self._flight and self._flight[-1].done():
                self.idle_launches += 1
            self._flight.append(_Batch(pts, self._program(dev)))

    def unit(self):
        with jax.profiler.TraceAnnotation("bench.batch"):
            while len(self._flight) < DEPTH:
                self._launch()
            batch = self._flight.popleft()
            with jax.profiler.TraceAnnotation("bench.wait"):
                batch.host()
            self.records.extend(
                Record(points=batch.points[c],
                       result=_CloudResult(batch, c))
                for c in range(len(batch.points)))

    def sizes(self):
        """(points, queries, K) of one batch: every point of every cloud
        queries its own cloud."""
        n = int(self._scene_args["clouds"]) * int(self._scene_args["points"])
        return n, n, self.params.k

    def notes(self):
        from repro import obs
        return {"compiles": obs.thread_compiles() - self._compiles0,
                "producer_waits": self._batches.waits,
                "idle_launches": self.idle_launches}

    def close(self):
        if self._batches is not None:
            self._batches.close()
