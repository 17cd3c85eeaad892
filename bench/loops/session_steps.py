"""A particle simulation's stepping loop: one ``SimulationSession`` over
the configuration's scene, self-queried once per step.

The session is opened over the block at rest (the scene with the
configuration's ``rest_scene`` parameters: the bare lattice), the same for
every seed, and plans its own frozen grid from it. The particles start
at the seed's jittered positions. They stay on the device; between steps
a small jitted drift from the benchmark moves them, standing in for the
solver's integrator: each particle's velocity is damped, kicked by
Gaussian noise and pulled back toward its starting position (the
pressure that holds a fluid near its rest density), and a particle that
crosses a wall of the tank is reflected back inside. The noise comes
from ``(seed, step)``, so the same seed gives the same trajectory.

Where the session's grid fills up it re-plans and re-runs the step
itself (a respec), and its answers stay exact; the respecs are counted
on an earlier line of the run, not judged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.lib.loopbase import LoopBase, Record
from bench.lib.rng import key_for, rng_for


def make_session(points, params):
    """The system under test: a session that plans its own grid."""
    from repro.core import SimulationSession
    return SimulationSession(points, params)


def drift_program(sigma, damping, kick, tether, lo, hi):
    def drift(pos, vel, site, key):
        noise = jax.random.normal(key, pos.shape, jnp.float32)
        vel = damping * vel + (kick * sigma) * noise - tether * (pos - site)
        pos = pos + vel
        for wall, side in ((lo, -1.0), (hi, 1.0)):
            out = side * (pos - wall) > 0
            pos = jnp.where(out, 2.0 * wall - pos, pos)
            vel = jnp.where(out, -vel, vel)
        return pos, vel
    return drift


class Loop(LoopBase):
    unit_name = "step"

    def setup(self):
        d = self.traffic["drift"]
        rest = self.scene(0, **self.config["rest_scene"])
        site = self.scene(0)
        vel0 = rng_for(self.seed, 1).normal(0.0, d["sigma"], site.shape)
        self._site = jax.device_put(site)
        self._pos = self._site
        self._vel = jax.device_put(vel0.astype(site.dtype))
        self._key = jax.random.PRNGKey(key_for(self.seed, 2))
        self._drift = jax.jit(drift_program(
            float(d["sigma"]), float(d["damping"]), float(d["kick"]),
            float(d["tether"]), float(d["walls"][0]), float(d["walls"][1])))
        self._session = make_session(jax.device_put(rest), self.params)
        self._step = 0
        # the first step plans (the forced variant), the second compiles
        # the replay/replan step
        for _ in range(int(self.traffic["warm_units"])):
            self._run_step()
        self.records.clear()
        self._stats0 = self._session.stats()

    def _run_step(self):
        with jax.profiler.TraceAnnotation("bench.drift"):
            self._pos, self._vel = self._drift(
                self._pos, self._vel, self._site,
                jax.random.fold_in(self._key, self._step))
        self._step += 1
        res = self._session.step(self._pos)
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready(res)
        self.records.append(Record(points=self._pos, result=res))

    def unit(self):
        with jax.profiler.TraceAnnotation("bench.step"):
            self._run_step()

    def notes(self):
        st = self._session.stats()
        spec = self._session.spec
        return {**{k: int(st.get(k, 0) - self._stats0.get(k, 0))
                   for k in ("steps", "replans", "fast_steps", "respecs",
                             "overflow_points", "oob_points")},
                "grid_capacity": spec.capacity, "grid_dims": list(spec.dims)}
