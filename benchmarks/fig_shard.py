"""Sharded-scene benchmark (DESIGN.md section 6): slab-resident
``ShardedSession`` vs the single-device ``SimulationSession`` on the
identical drifting trajectory.

Two regimes:

* ``shard-1slab`` — a 1-slab mesh on the real device: measures the pure
  overhead of the sharded machinery (traced routing, halo/migration
  plumbing with no neighbors, per-slab plan state) against the plain
  session. This is the parity row: speedup ~1 means scale-out costs
  nothing when you don't scale.
* ``shard-{S}slab-hostdev`` — a subprocess under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``: S slabs on 8
  forced host devices vs the single-device session in the same process.
  The child runs with ``JAX_PLATFORMS=cpu``: the parent already holds
  any accelerator (a chip belongs to one process at a time).
  On one physical CPU the forced devices time-slice, so this measures the
  *scaling structure* (per-slab work shrinks with S, communication is
  O(surface)) rather than real speedup — the ratio is the tracked
  statistic, machine speed cancels.

Every timed frame is asserted count-exact between the two paths. Rows
merge-accumulate into ``BENCH_shard.json`` (committed baseline is the CI
regression gate — scripts/check_bench.py). ``REPRO_BENCH_SMOKE=1``
shrinks the workload for CI.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

SMOKE = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_shard.json")

_WORKER = r"""
import json, sys, time
import numpy as np
from repro.core import SearchParams, ShardedSession, SimulationSession

n, steps, n_slabs, radius, k = json.loads(sys.argv[1])
rng = np.random.default_rng(17)
pos = rng.random((n, 3)).astype(np.float32)
vel = rng.normal(0, 0.03 * radius / 4.0, (n, 3)).astype(np.float32)
frames = [pos]
for _ in range(steps - 1):
    vel = 0.9 * vel + rng.normal(0, 0.3 * 0.03 * radius / 4.0,
                                 (n, 3)).astype(np.float32)
    pos = np.clip(pos + vel, 0.0, 1.0).astype(np.float32)
    frames.append(pos)
params = SearchParams(radius=radius, k=k, knn_window="exact")

sharded = ShardedSession(frames[0], params, n_slabs=n_slabs)
single = SimulationSession(frames[0], params)
rs = sharded.step(frames[0])            # warm compile + plan (both paths)
rr = single.step(frames[0])
ts_sh, ts_si = [], []
for f in frames[1:]:
    t0 = time.perf_counter(); rs = sharded.step(f)
    ts_sh.append(time.perf_counter() - t0)
    t0 = time.perf_counter(); rr = single.step(f)
    ts_si.append(time.perf_counter() - t0)
    assert np.array_equal(np.asarray(rs.counts), np.asarray(rr.counts))
st = sharded.stats()
print("RESULT", json.dumps({
    "single_us_per_step": float(np.median(ts_si)) * 1e6,
    "sharded_us_per_step": float(np.median(ts_sh)) * 1e6,
    "speedup": float(np.median(ts_si)) / float(np.median(ts_sh)),
    "n_slabs": n_slabs, "points": n, "steps": steps,
    "fast_steps": st["fast_steps"], "replans": st["replans"],
    "migrated": st["migrated"], "host_routings": st["host_routings"],
}))
"""


def _run_case(n, steps, n_slabs, radius, k, devices):
    env = {**os.environ,
           "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..",
                                      "src"),
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "JAX_PLATFORMS": "cpu"}
    args = json.dumps([n, steps, n_slabs, radius, k])
    r = subprocess.run([sys.executable, "-c", _WORKER, args], env=env,
                       capture_output=True, text=True, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"fig_shard worker failed:\n{r.stderr}")
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def _parity_case(n, steps, radius, k):
    """In-process 1-slab parity (single real device)."""
    from repro.core import (SearchParams, ShardedSession,
                            SimulationSession)
    rng = np.random.default_rng(17)
    pos = rng.random((n, 3)).astype(np.float32)
    sigma = 0.03 * radius / 4.0
    vel = rng.normal(0, sigma, (n, 3)).astype(np.float32)
    frames = [pos]
    for _ in range(steps - 1):
        vel = 0.9 * vel + rng.normal(0, 0.3 * sigma,
                                     (n, 3)).astype(np.float32)
        pos = np.clip(pos + vel, 0.0, 1.0).astype(np.float32)
        frames.append(pos)
    params = SearchParams(radius=radius, k=k, knn_window="exact")
    sharded = ShardedSession(frames[0], params, n_slabs=1)
    single = SimulationSession(frames[0], params)
    sharded.step(frames[0])
    single.step(frames[0])
    ts_sh, ts_si = [], []
    for f in frames[1:]:
        t0 = time.perf_counter()
        rs = sharded.step(f)
        ts_sh.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        rr = single.step(f)
        ts_si.append(time.perf_counter() - t0)
        assert np.array_equal(np.asarray(rs.counts),
                              np.asarray(rr.counts))
    st = sharded.stats()
    return {
        "single_us_per_step": float(np.median(ts_si)) * 1e6,
        "sharded_us_per_step": float(np.median(ts_sh)) * 1e6,
        "speedup": float(np.median(ts_si)) / float(np.median(ts_sh)),
        "n_slabs": 1, "points": n, "steps": steps,
        "fast_steps": st["fast_steps"], "replans": st["replans"],
        "host_routings": st["host_routings"],
    }


def run():
    from .common import emit, write_bench
    if SMOKE:
        n, steps, slabs = 2_000, 9, 4
    else:
        n, steps, slabs = 8_000, 12, 4
    radius, k = 0.05, 8
    results = {}

    row = _parity_case(n, steps, radius, k)
    name = "shard-1slab"
    results[name] = row
    emit(f"figshard/{name}/single", row["single_us_per_step"] / 1e6 / n,
         "plain session")
    emit(f"figshard/{name}/sharded", row["sharded_us_per_step"] / 1e6 / n,
         f"parity={row['speedup']:.2f}x;routing={row['host_routings']}")

    row = _run_case(n, steps, slabs, radius, k, devices=8)
    name = f"shard-{slabs}slab-hostdev"
    results[name] = row
    emit(f"figshard/{name}/single", row["single_us_per_step"] / 1e6 / n,
         "single device")
    emit(f"figshard/{name}/sharded", row["sharded_us_per_step"] / 1e6 / n,
         f"speedup={row['speedup']:.2f}x;migrated={row['migrated']};"
         f"routing={row['host_routings']}")

    return write_bench(OUT_PATH, results)
