"""End-to-end driver: a minimal SPH-style fluid step loop built on the
neighbor-search core — the application class (SPlisHSPlasH / cuNSearch)
the paper's range search serves.

Default path is the dynamic-scene subsystem (DESIGN.md section 7): ONE
persistent ``SimulationSession`` owns a frozen grid across the whole run,
each step re-bins the moved particles device-resident and replays the
cached schedule/partition plan while displacements stay small. Positions
never leave the device. ``--rebuild`` keeps the legacy path for A/B: a
fresh ``NeighborSearch`` per frame (host spec planning, full rebuild, cold
plan caches — what the session amortizes away).

Each step: (1) update structure over moved particles, (2) range search
around every particle (self-query), (3) density + pressure-force kernel
sums over the returned neighbor lists, (4) symplectic Euler integration.

  PYTHONPATH=src python examples/sph_fluid.py --particles 8000 --steps 5
  PYTHONPATH=src python examples/sph_fluid.py --rebuild   # legacy A/B
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (NeighborSearch, SearchOpts, SearchParams,
                        SimulationSession)
from repro.launch.cache import configure_compile_cache

H = 0.06            # smoothing radius
K_MAX = 32          # bounded neighbor count (the paper's K)
REST_DENSITY = 600.0
STIFFNESS = 200.0
DT = 4e-4
GRAVITY = jnp.asarray([0.0, 0.0, -9.8])


@jax.jit
def sph_forces(pos, vel, nbr_idx, nbr_d2):
    """Poly6 density + spiky pressure-gradient forces over the fixed-K
    neighbor lists returned by the search."""
    valid = nbr_idx >= 0
    safe = jnp.clip(nbr_idx, 0)
    d2 = jnp.where(valid, nbr_d2, H * H)
    w = jnp.maximum(H * H - d2, 0.0) ** 3                    # poly6 core
    density = jnp.sum(jnp.where(valid, w, 0.0), axis=1) * 315.0 / (
        64.0 * jnp.pi * H**9) + 1e-6
    pressure = STIFFNESS * jnp.maximum(density - REST_DENSITY, 0.0)

    d = jnp.sqrt(jnp.maximum(d2, 1e-12))
    dirs = (pos[:, None, :] - pos[safe]) / d[..., None]
    spiky = (H - d) ** 2 * 45.0 / (jnp.pi * H**6)
    p_i = pressure[:, None]
    p_j = pressure[safe]
    rho_j = density[safe]
    f = dirs * (spiky * (p_i + p_j) / (2.0 * rho_j))[..., None]
    f = jnp.sum(jnp.where(valid[..., None], f, 0.0), axis=1)
    return f / density[:, None] + GRAVITY, density


@jax.jit
def integrate(pos, vel, acc):
    """Symplectic Euler + reflective box walls, all on device."""
    vel = vel + DT * acc
    pos = pos + DT * vel
    pos = jnp.clip(pos, 0.0, 1.0)
    vel = jnp.where((pos <= 0.0) | (pos >= 1.0), -0.5 * vel, vel)
    return pos, vel


def step_rebuild(pos, vel):
    """Legacy per-frame teardown/rebuild (pre-session behavior)."""
    ns = NeighborSearch(np.asarray(pos),
                        SearchParams(radius=H, k=K_MAX, mode="range"),
                        SearchOpts())
    t0 = time.perf_counter()
    res = ns.query(np.asarray(pos))
    t_search = time.perf_counter() - t0
    t0 = time.perf_counter()
    acc, density = sph_forces(jnp.asarray(pos), vel, res.indices,
                              res.distances2)
    pos, vel = integrate(jnp.asarray(pos), vel, acc)
    jax.block_until_ready(pos)
    t_phys = time.perf_counter() - t0
    split = dict(update=0.0, plan=ns.report.t_opt, search=t_search,
                 physics=t_phys)
    info = (f"partitions={ns.report.num_partitions} "
            f"launches={ns.report.launches} syncs={ns.report.host_syncs}")
    return pos, vel, float(density.mean()), split, info


def step_session(sess, pos, vel):
    """Session path: incremental update + cached-plan replay, self-query."""
    res = sess.step(pos)
    r = sess.report
    t0 = time.perf_counter()
    acc, density = sph_forces(pos, vel, res.indices, res.distances2)
    pos, vel = integrate(pos, vel, acc)
    jax.block_until_ready(pos)
    t_phys = time.perf_counter() - t0
    split = dict(update=r.t_update, plan=r.t_plan, search=r.t_search,
                 physics=t_phys)
    info = (f"fast={int(r.fast)} replan={int(r.replanned)} "
            f"respec={int(r.respecced)} disp={r.max_disp:.4f}")
    return pos, vel, float(density.mean()), split, info


def main():
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--particles", type=int, default=8000)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--rebuild", action="store_true",
                    help="legacy rebuild-per-frame path (A/B baseline)")
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    pos = jnp.asarray(rng.random((args.particles, 3), np.float32) *
                      [0.4, 0.4, 0.8])          # dam-break column
    vel = jnp.zeros_like(pos)
    sess = None
    if not args.rebuild:
        sess = SimulationSession(
            pos, SearchParams(radius=H, k=K_MAX, mode="range"),
            SearchOpts())
    for s in range(args.steps):
        t0 = time.perf_counter()
        if args.rebuild:
            pos, vel, rho, split, info = step_rebuild(pos, vel)
        else:
            pos, vel, rho, split, info = step_session(sess, pos, vel)
        dt = time.perf_counter() - t0
        print(f"step {s}: mean_density={rho:9.1f} wall={dt:.2f}s "
              f"(update={split['update']:.3f} plan={split['plan']:.3f} "
              f"search={split['search']:.3f} "
              f"physics={split['physics']:.3f}) {info}")
    if sess is not None:
        st = sess.stats()
        print(f"session: {st['steps']} steps, {st.get('fast_steps', 0)} "
              f"fast, {st.get('replans', 0)} replans, "
              f"{st.get('respecs', 0)} respecs")
    assert np.isfinite(np.asarray(pos)).all()
    print("ok")


if __name__ == "__main__":
    main()
