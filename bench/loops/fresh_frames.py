"""Fresh frames, back to back: a sensor stream in a closed loop.

Every frame is a new scene drawn from ``(seed, frame index)``. A producer
thread makes frames ahead on the host into a bounded queue (numpy only;
it never touches JAX). A timed frame takes one from the queue, moves it to
the device and runs one jitted program, compiled in set-up: a fresh grid
build, then a self-query of every point (``repro.api.build_index`` +
``repro.api.query``). Nothing is reused from one frame to the next.

The grid's spec is static in that program, so it is planned once, in
set-up, by the program's own policy for a grid that has to outlast many
frames (``repro.core.session_grid_spec``: capacity and domain headroom)
over the sensor's calibration frame. That frame is the same for every
seed, so every seed runs the same program and finds it in the compile
cache.
"""
from __future__ import annotations

import jax

from bench.lib.loopbase import LoopBase, Record
from bench.lib.producer import Producer


def frame_program(params, spec):
    """The timed path: build + self-query of one frame, and the grid's
    overflow count."""
    import repro.api as api

    def frame(points):
        index = api.build_index(points, params, spec=spec)
        return api.query(index, index.points), index.grid.overflow

    return frame


class Loop(LoopBase):
    unit_name = "frame"
    _frames = None

    def build_program(self):
        return jax.jit(frame_program(self.params, self.spec))

    def setup(self):
        self.spec = self.plan_spec()
        self._program = self.build_program()
        self._frames = Producer(self.scene, self.traffic["queue_depth"],
                                "bench-frames")
        for _ in range(int(self.traffic["warm_units"])):
            self._run_frame()
        self.records.clear()
        self._frames.waits = 0

    def _run_frame(self):
        with jax.profiler.TraceAnnotation("bench.dequeue"):
            pts = self._frames.take()
        with jax.profiler.TraceAnnotation("bench.transfer"):
            dev = jax.device_put(pts)
        with jax.profiler.TraceAnnotation("bench.program"):
            res, overflow = self._program(dev)
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready((res, overflow))
        self.records.append(Record(points=pts, result=res,
                                   overflow=overflow))

    def unit(self):
        with jax.profiler.TraceAnnotation("bench.frame"):
            self._run_frame()

    def notes(self):
        return {"producer_waits": self._frames.waits,
                "grid_capacity": self.spec.capacity,
                "grid_dims": list(self.spec.dims)}

    def close(self):
        if self._frames is not None:
            self._frames.close()
