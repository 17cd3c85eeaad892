"""Multi-device tests (subprocess: jax device count is locked at first
init, so each test spawns a fresh interpreter with 8 host devices). The
children are CPU-only by design: JAX_PLATFORMS=cpu keeps them off any
accelerator, which belongs to one process at a time."""
import os
import subprocess
import sys

import pytest

ENV = {**os.environ, "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}


def _run(code: str):
    r = subprocess.run([sys.executable, "-c", code], env=ENV,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_distributed_search_exact():
    out = _run("""
import numpy as np, jax, jax.numpy as jnp
from repro.core.distributed import distributed_neighbor_search
from repro.core.types import SearchParams
from repro.kernels.ref import brute_force_search
from jax.sharding import AxisType
rng = np.random.default_rng(3)
pts = rng.random((4000, 3)).astype(np.float32)
qs = rng.random((900, 3)).astype(np.float32)
r, K = 0.07, 8
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
res = distributed_neighbor_search(mesh, pts, qs, SearchParams(radius=r, k=K))
oi, od, oc = brute_force_search(jnp.asarray(pts), jnp.asarray(qs), r, K)
assert np.array_equal(np.asarray(oi), np.asarray(res.indices))
assert np.array_equal(np.asarray(oc), np.asarray(res.counts))
print("EXACT-MATCH")
""")
    assert "EXACT-MATCH" in out


def test_sharded_train_step_matches_single_device():
    """The pjit'd train step on a 4x2 mesh must produce the same loss as the
    unsharded step (same math, different partitioning)."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import smoke_config
from repro.data.pipeline import make_batch
from repro.models.config import get_config
from repro.models.model import init_params
from repro.sharding.rules import (param_pspecs, opt_pspecs, make_shard_fn,
                                  named_sharding_tree)
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.train_step import make_train_step
from repro.launch.mesh import make_test_mesh

cfg = smoke_config(get_config("grok-1-314b"))   # MoE path under sharding
key = jax.random.PRNGKey(0)
params = init_params(cfg, key)
opt_cfg = OptConfig(lr=1e-3, warmup_steps=1)
opt = init_opt_state(params, opt_cfg)
batch = make_batch(cfg, 8, 16, key)
batch = jax.tree.map(lambda a: a[None], batch)

ref_step = jax.jit(make_train_step(cfg, opt_cfg))
_, _, m_ref = ref_step(params, opt, batch)

mesh = make_test_mesh((4, 2), ("data", "model"))
shard = make_shard_fn(mesh)
p_sh = named_sharding_tree(param_pspecs(params, mesh), mesh)
o_sh = named_sharding_tree(opt_pspecs(jax.eval_shape(lambda p: init_opt_state(p, opt_cfg), params), mesh), mesh)
with mesh:
    sh_step = jax.jit(make_train_step(cfg, opt_cfg, shard=shard),
                      in_shardings=(p_sh, o_sh, None),
                      out_shardings=(p_sh, o_sh, None))
    p2, o2, m_sh = sh_step(params, opt, batch)
a, b = float(m_ref["loss"]), float(m_sh["loss"])
assert abs(a - b) < 1e-3, (a, b)
print("LOSS-MATCH", a, b)
""")
    assert "LOSS-MATCH" in out


def test_production_mesh_shapes():
    out = _run("""
import numpy as np
import jax
from repro.launch.mesh import make_production_mesh
# only 8 devices here: expect the helpful error for the 256-chip mesh
try:
    make_production_mesh()
    print("UNEXPECTED-OK")
except RuntimeError as e:
    assert "xla_force_host_platform_device_count" in str(e)
    print("GUARDED")
""")
    assert "GUARDED" in out


def test_remesh_elastic():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_test_mesh
from repro.train.fault_tolerance import remesh
x = {"w": jnp.arange(32, dtype=jnp.float32).reshape(8, 4)}
mesh_a = make_test_mesh((8,), ("data",))
mesh_b = make_test_mesh((4,), ("data",))
specs = {"w": P("data", None)}
xa = remesh(x, mesh_a, specs)
xb = remesh(xa, mesh_b, specs)
assert np.array_equal(np.asarray(xb["w"]), np.arange(32).reshape(8, 4))
print("REMESH-OK")
""")
    assert "REMESH-OK" in out


def test_sharded_session_oracle_and_zero_host_routing():
    """THE sharded acceptance property (DESIGN.md section 6): a 4-slab
    ShardedSession stepping a drifting trajectory is oracle-equal to the
    single-device search on every frame — including frames where particles
    migrate across slab faces — and performs ZERO host-side routing after
    construction (the host_routings counter stays at 1)."""
    out = _run("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import SearchParams, ShardedSession
from repro.kernels.ref import brute_force_search
rng = np.random.default_rng(2)
n = 1200
pts = rng.random((n, 3)).astype(np.float32)
params = SearchParams(radius=0.1, k=8, knn_window="exact")
sess = ShardedSession(pts, params, n_slabs=4)
vel = rng.normal(0, 0.004, (n, 3)).astype(np.float32)
for f in range(6):
    rs = sess.step(pts)
    oi, od, oc = brute_force_search(jnp.asarray(pts), jnp.asarray(pts),
                                    0.1, 8)
    np.testing.assert_array_equal(np.asarray(oc), np.asarray(rs.counts))
    ds = np.where(np.isinf(np.asarray(rs.distances2)), -1,
                  np.asarray(rs.distances2))
    dr = np.where(np.isinf(np.asarray(od)), -1, np.asarray(od))
    np.testing.assert_allclose(ds, dr, atol=1e-5)
    np.testing.assert_array_equal(np.sort(np.asarray(rs.indices), 1),
                                  np.sort(np.asarray(oi), 1))
    pts = np.clip(pts + vel, 0.0, 1.0).astype(np.float32)  # coherent drift
st = sess.stats()
assert st["migrated"] > 0, st          # faces were actually crossed
assert st["host_routings"] == 1, st    # construction only — zero per-step
assert st["steps"] == 6 and st["reroutes"] == 0, st
print("SHARDED-ORACLE-OK", st["migrated"])
""")
    assert "SHARDED-ORACLE-OK" in out


def test_sharded_session_steady_state_replays():
    """Below-threshold drift on a multi-slab mesh replays every slab's
    captured plan on device: fast steps with zero host routing. Drift is
    y/z-only so slab/halo membership is frame-stable — any x-crossing
    (migration, halo entry/exit) changes a row's occupant and correctly
    forces that slab to replan."""
    out = _run("""
import numpy as np
from repro.core import SearchParams, ShardedSession
rng = np.random.default_rng(5)
pts = rng.random((900, 3)).astype(np.float32)
sess = ShardedSession(pts, SearchParams(radius=0.1, k=8,
                                        knn_window="exact"), n_slabs=4)
sess.step(pts)
drift = np.zeros_like(pts)
for _ in range(4):
    drift[:, 1:] = rng.normal(0, 0.0002, (900, 2))
    pts = np.clip(pts + drift, 0.0, 1.0).astype(np.float32)
    sess.step(pts)
st = sess.stats()
assert st["fast_steps"] >= 3, st
assert st["host_routings"] == 1, st
assert st["migrated"] == 0, st
print("SHARDED-STEADY-OK")
""")
    assert "SHARDED-STEADY-OK" in out


def test_sharded_migration_into_nearly_full_slab():
    """Regression: an arrival from the RIGHT neighbor sits in the second
    half of the migration buffer; the free-row merge must rank ARRIVALS
    against the free-row count, not buffer positions — otherwise a slab
    with fewer free rows than migrate_cap spuriously flags exhaustion and
    forces a host re-route although rows are free."""
    out = _run("""
import numpy as np
from repro.core import SearchParams, ShardedSession
from repro.core.shards import ShardOpts
from repro.kernels.ref import brute_force_search
import jax.numpy as jnp
rng = np.random.default_rng(11)
# slab 1 fuller than slab 0 so point_cap (slack 1.0) leaves slab 0 only
# a few free rows — fewer than migrate_cap
pts = rng.random((200, 3)).astype(np.float32)
pts[:96, 0] = pts[:96, 0] * 0.5          # slab 0: 96 rows
pts[96:, 0] = 0.5 + pts[96:, 0] * 0.5    # slab 1: 104 rows
shopts = ShardOpts(point_slack=1.0, domain_margin_radii=2.0)
params = SearchParams(radius=0.05, k=4, knn_window="exact")
sess = ShardedSession(pts, params, n_slabs=2, shopts=shopts)
assert sess.layout.point_cap == 104
sess.step(pts)
# walk one slab-1 point leftwards across the face: it must merge into
# one of slab 0's free rows without tripping the exhausted fallback
pts2 = pts.copy()
pts2[100, 0] = 0.49
res = sess.step(pts2)
st = sess.stats()
assert st["migrated"] >= 1, st
assert st["reroutes"] == 0 and st["host_routings"] == 1, st
oi, od, oc = brute_force_search(jnp.asarray(pts2), jnp.asarray(pts2),
                                0.05, 4)
np.testing.assert_array_equal(np.asarray(oc), np.asarray(res.counts))
np.testing.assert_array_equal(np.sort(np.asarray(res.indices), 1),
                              np.sort(np.asarray(oi), 1))
print("MIGRATE-MERGE-OK")
""")
    assert "MIGRATE-MERGE-OK" in out


def test_distributed_routing_edge_cases():
    """Satellite: empty slabs, all-points-in-one-slab skew, and queries
    landing exactly on slab faces must all round-trip in original query
    order with correct global ids."""
    out = _run("""
import numpy as np, jax, jax.numpy as jnp
from repro.core.distributed import distributed_neighbor_search
from repro.core.types import SearchParams
from repro.kernels.ref import brute_force_search
from jax.sharding import AxisType

def check(pts, qs, r=0.08, K=8):
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    res = distributed_neighbor_search(mesh, pts, qs,
                                      SearchParams(radius=r, k=K))
    oi, od, oc = brute_force_search(jnp.asarray(pts), jnp.asarray(qs),
                                    r, K)
    np.testing.assert_array_equal(np.asarray(oc), np.asarray(res.counts))
    np.testing.assert_array_equal(np.sort(np.asarray(res.indices), 1),
                                  np.sort(np.asarray(oi), 1))
    dg = np.where(np.isinf(np.asarray(res.distances2)), -1,
                  np.asarray(res.distances2))
    dr = np.where(np.isinf(np.asarray(od)), -1, np.asarray(od))
    np.testing.assert_allclose(dg, dr, atol=1e-5)

rng = np.random.default_rng(7)

# 1. empty middle slabs: bimodal x — slabs 1..2 own (almost) nothing
pts = rng.random((1500, 3)).astype(np.float32)
pts[:, 0] = np.where(rng.random(1500) < 0.5, pts[:, 0] * 0.1,
                     0.9 + pts[:, 0] * 0.1)
qs = rng.random((300, 3)).astype(np.float32)   # queries everywhere,
check(pts, qs)                                  # incl. the empty slabs
print("EDGE-EMPTY-OK")

# 2. all-points-in-one-slab skew: one outlier stretches the domain so
# ~all points land in slab 0
pts = rng.random((1000, 3)).astype(np.float32)
pts[:, 0] *= 0.05
pts[0, 0] = 1.0
qs = rng.random((200, 3)).astype(np.float32)
check(pts, qs)
print("EDGE-SKEW-OK")

# 3. queries exactly on slab faces (and points near them): the face
# position must route to exactly one slab and find cross-face neighbors
# through the halo
pts = rng.random((2000, 3)).astype(np.float32)
qs = rng.random((256, 3)).astype(np.float32)
lo = pts[:, 0].min()
width = (pts[:, 0].max() - lo) / 4.0
for i, s in enumerate([1, 2, 3] * 40):          # exact face x-coords
    qs[i, 0] = np.float32(lo + s * width)
check(pts, qs)
print("EDGE-FACE-OK")
""")
    assert "EDGE-EMPTY-OK" in out
    assert "EDGE-SKEW-OK" in out
    assert "EDGE-FACE-OK" in out


def test_api_query_composes_with_shard_map():
    """The functional core's acceptance composition: stacked same-spec
    scenes sharded over a device mesh axis, a vmapped api.query per shard —
    per-scene results must match the single-device call bitwise."""
    out = _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
import repro.api as api
from repro.core import SearchOpts, SearchParams, choose_grid_spec
rng = np.random.default_rng(5)
B = 8
params = SearchParams(radius=0.1, k=8, knn_window="exact")
scenes = [rng.random((900, 3)).astype(np.float32) for _ in range(B)]
qss = [rng.random((128, 3)).astype(np.float32) for _ in range(B)]
spec = choose_grid_spec(np.concatenate(scenes), params.radius)
idxs = [api.build_index(s, params, SearchOpts(), spec=spec) for s in scenes]
stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *idxs)
qstack = jnp.stack([jnp.asarray(q) for q in qss])
mesh = jax.make_mesh((B,), ("pod",), axis_types=(AxisType.Auto,))
fn = jax.shard_map(lambda idx, qs: jax.vmap(api.query)(idx, qs),
                   mesh=mesh, in_specs=(P("pod"), P("pod")),
                   out_specs=P("pod"), check_vma=False)
res = jax.jit(fn)(stacked, qstack)
for b in range(B):
    one = api.query(idxs[b], qss[b])
    assert np.array_equal(np.asarray(res.indices[b]), np.asarray(one.indices))
    assert np.array_equal(np.asarray(res.distances2[b]),
                          np.asarray(one.distances2))
    assert np.array_equal(np.asarray(res.counts[b]), np.asarray(one.counts))
print("SHARD-MATCH")
""")
    assert "SHARD-MATCH" in out
