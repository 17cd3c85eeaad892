"""Unified telemetry layer tests (DESIGN.md section 9).

Three contracts:

1. registry/tracing semantics — counters/gauges/histograms aggregate and
   render; spans nest, ring-buffer, and stream to JSONL;
2. the acceptance surface — with REPRO_TRACE on, one SimulationSession
   step and one ShardedSession step emit JSONL spans covering the
   plan/compile/launch/sync stages plus p50/p99 metrics, and
   ``repro.obs.summary()`` renders the unified registry;
3. the parity guarantee — the device programs and host-sync counts are
   bitwise-identical with telemetry on vs off for ``api.query``,
   ``SimulationSession.step`` and ``ShardedSession.step`` (device-side
   telemetry is computed unconditionally; only host recording is gated).
"""
import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import (SearchOpts, SearchParams, ShardedSession,
                        SimulationSession)
from repro.core import api, dynamic

PARAMS = SearchParams(radius=0.12, k=8, knn_window="exact")


@pytest.fixture(autouse=True)
def clean_obs():
    """Every test starts with an empty registry/ring and ends with the
    trace mode restored to whatever the environment knob says (so a
    REPRO_TRACE=1 CI run keeps its mode across this module)."""
    obs.reset()
    yield
    obs.configure()     # re-read REPRO_TRACE / REPRO_TRACE_PATH
    obs.reset()


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ---------------------------------------------------------------- registry


def test_registry_kinds_and_counters_surface():
    ms = obs.metric_set("unit")
    ms.count("steps")
    ms.count("steps", 2)
    ms.gauge("cache_entries", 7)
    for v in [0.001, 0.002, 0.003]:
        ms.observe("step_s", v)
    assert ms.counters() == {"steps": 3}       # counters only, int totals
    assert ms.counter_value("steps") == 3.0
    snap = ms.snapshot()
    assert snap["steps"]["kind"] == "counter"
    assert snap["cache_entries"]["kind"] == "gauge"
    assert snap["cache_entries"]["value"] == 7
    hist = snap["step_s"]
    assert hist["kind"] == "histogram" and hist["count"] == 3
    for key in ("p50", "p95", "p99"):
        assert key in hist


def test_histogram_percentiles_from_reservoir():
    h = obs.Histogram()
    for v in range(1, 101):
        h.observe(float(v))
    pct = h.percentiles()
    assert pct["p50"] == pytest.approx(50.5, abs=1.0)
    assert pct["p95"] == pytest.approx(95.0, abs=1.5)
    assert pct["p99"] == pytest.approx(99.0, abs=1.5)
    assert h.count == 100 and h.vmin == 1.0 and h.vmax == 100.0


def test_registry_aggregates_same_component_instances():
    """Two instances of one component (e.g. two sessions) fold into one
    aggregate row — counter totals sum."""
    a, b = obs.metric_set("session"), obs.metric_set("session")
    a.count("steps", 2)
    b.count("steps", 3)
    agg = obs.REGISTRY.aggregate()
    assert agg["session"]["steps"]["value"] == 5


def test_summary_renders_unified_table():
    ms = obs.metric_set("executor")
    ms.count("queries", 4)
    ms.observe("query_s", 0.002)
    text = obs.summary()
    assert "repro.obs summary" in text
    assert "executor" in text and "queries" in text
    # histogram rows display seconds-suffixed metrics in microseconds
    assert "query_us" in text and "p99" in text


def test_metrics_dict_schema():
    ms = obs.metric_set("exec")
    ms.count("launches", 2)
    payload = obs.metrics_dict()
    assert payload["schema"] == "repro.obs/v1"
    rows = {(r["component"], r["name"]): r for r in payload["metrics"]}
    assert rows[("exec", "launches")]["value"] == 2


# ----------------------------------------------------------------- tracing


def test_trace_knob_parsing():
    from repro.obs import tracing
    assert tracing._parse_knob(None) == ("off", None)
    assert tracing._parse_knob("0") == ("off", None)
    assert tracing._parse_knob("1") == ("log", None)
    assert tracing._parse_knob("2") == ("jsonl", None)
    assert tracing._parse_knob("jsonl") == ("jsonl", None)
    assert tracing._parse_knob("/tmp/t.jsonl") == ("jsonl", "/tmp/t.jsonl")


def test_spans_nest_and_record_paths():
    obs.configure(mode="log")
    with obs.span("step", slabs=2):
        with obs.span("plan"):
            pass
        with obs.span("launch"):
            obs.record_span("compile", 0.5)
    paths = [s["path"] for s in obs.recent_spans()]
    assert paths == ["step/plan", "step/launch/compile", "step/launch",
                     "step"]
    top = obs.recent_spans()[-1]
    assert top["attrs"] == {"slabs": 2}
    assert top["dur_s"] >= 0.0


def test_spans_dropped_when_off():
    obs.configure(mode="off")
    with obs.span("query") as sp:
        pass
    assert sp.duration >= 0.0        # timing still available to the caller
    assert obs.recent_spans() == []


def test_jsonl_streaming_and_export(tmp_path):
    out = str(tmp_path / "trace.jsonl")
    obs.configure(mode="jsonl", path=out)
    with obs.span("query", nq=64):
        pass
    ms = obs.metric_set("exec")
    ms.observe("query_s", 0.004)
    recs = _read_jsonl(out)
    assert [r["name"] for r in recs if r["type"] == "span"] == ["query"]
    # export appends the aggregated metric rows to the same stream
    obs.export_jsonl(out)
    metrics = [r for r in _read_jsonl(out) if r["type"] == "metric"]
    row = next(r for r in metrics
               if r["component"] == "exec" and r["name"] == "query_s")
    assert row["kind"] == "histogram" and "p50" in row and "p99" in row


# ------------------------------------------------- acceptance: sessions emit


def _jitter(rng, pts, scale=0.004):
    return np.clip(pts + rng.normal(0, scale, pts.shape).astype(np.float32),
                   0, 1).astype(np.float32)


def test_session_step_emits_jsonl_telemetry(rng, tmp_path):
    """One SimulationSession.step with REPRO_TRACE on emits JSONL spans
    covering plan, compile, launch, and sync, plus histogram metrics with
    p50/p99 — and the device counters ride the ONE packed host sync."""
    out = str(tmp_path / "session.jsonl")
    obs.configure(mode="jsonl", path=out)
    pts = rng.random((500, 3)).astype(np.float32)
    sess = SimulationSession(pts, PARAMS)
    sess.step(pts)                                  # cold: compiles
    sess.step(_jitter(rng, pts))                    # steady state
    obs.export_jsonl(out)

    recs = _read_jsonl(out)
    paths = {r["path"] for r in recs if r["type"] == "span"}
    assert {"step", "step/plan", "step/launch", "step/launch/compile",
            "step/sync"} <= paths
    rows = {(r["component"], r["name"]): r for r in recs
            if r["type"] == "metric"}
    hist = rows[("session", "step_s")]
    assert hist["count"] == 2 and "p50" in hist and "p99" in hist
    # device counters arrived via the packed vector: one sync per step,
    # zero separate stats fetches, occupancy histogram populated
    st = sess.stats()
    assert st["host_syncs"] == 2 and st["stats_fetches"] == 0
    assert any(k == ("session", n) for k, n in
               ((key, key[1]) for key in rows) if n.startswith("level_occ_"))
    assert "session" in obs.summary()


def test_sharded_session_step_emits_jsonl_telemetry(rng, tmp_path):
    """Same acceptance surface for the sharded step program (n_slabs=1
    runs the full shard_map path in-process on one device)."""
    out = str(tmp_path / "shard.jsonl")
    obs.configure(mode="jsonl", path=out)
    pts = rng.random((600, 3)).astype(np.float32)
    sess = ShardedSession(pts, PARAMS, n_slabs=1)
    sess.step(pts)
    sess.step(_jitter(rng, pts))
    obs.export_jsonl(out)

    recs = _read_jsonl(out)
    paths = {r["path"] for r in recs if r["type"] == "span"}
    assert {"step", "step/plan", "step/launch", "step/launch/compile",
            "step/sync"} <= paths
    rows = {(r["component"], r["name"]): r for r in recs
            if r["type"] == "metric"}
    hist = rows[("sharded_session", "step_s")]
    assert hist["count"] == 2 and "p50" in hist and "p99" in hist
    assert ("sharded_session", "halo_rows") in rows
    st = sess.stats()
    assert st["host_syncs"] == 2
    assert "sharded_session" in obs.summary()


# ----------------------------------------------- parity: telemetry on vs off


def test_query_jaxpr_identical_on_off(rng):
    """api.query traces to the same program whether host telemetry is
    recording or not (launch count included — the jaxpr is compared as a
    whole)."""
    pts = rng.random((800, 3)).astype(np.float32)
    qs = rng.random((128, 3)).astype(np.float32)
    index = api.build_index(pts, PARAMS, SearchOpts())
    obs.configure(mode="off")
    jaxpr_off = str(jax.make_jaxpr(api.query)(index, jnp.asarray(qs)))
    obs.configure(mode="log")
    jaxpr_on = str(jax.make_jaxpr(api.query)(index, jnp.asarray(qs)))
    assert jaxpr_off == jaxpr_on


def test_session_step_jaxpr_identical_on_off(rng):
    """The fused session step program is a constant function of the trace
    mode: telemetry packing is unconditional, recording is host-side."""
    pts = rng.random((400, 3)).astype(np.float32)
    sess = SimulationSession(pts, PARAMS)
    sess.step(pts)                                  # materialize the plan
    thr2 = float((sess.sopts.displacement_frac *
                  sess.index.spec.cell_size) ** 2)
    fn = functools.partial(
        dynamic._step_impl, thr2=thr2,
        margin=int(sess.sopts.reuse_margin_cells), force=False,
        self_query=True)
    args = (sess.index.grid, dataclasses.replace(sess.index, grid=None),
            sess._plan, sess.index.points, sess.index.points,
            sess.index.points)
    obs.configure(mode="off")
    jaxpr_off = str(jax.make_jaxpr(fn)(*args))
    obs.configure(mode="log")
    jaxpr_on = str(jax.make_jaxpr(fn)(*args))
    assert jaxpr_off == jaxpr_on


def test_sharded_step_jaxpr_identical_on_off(rng):
    pts = rng.random((500, 3)).astype(np.float32)
    sess = ShardedSession(pts, PARAMS, n_slabs=1)
    args = (sess._pts, sess._ids, sess._index, sess._plan,
            sess._mig_total, jnp.asarray(pts))
    prog = sess._step_fn.__wrapped__
    obs.configure(mode="off")
    jaxpr_off = str(jax.make_jaxpr(prog)(*args))
    obs.configure(mode="log")
    jaxpr_on = str(jax.make_jaxpr(prog)(*args))
    assert jaxpr_off == jaxpr_on


def test_session_results_and_syncs_identical_on_off(rng):
    """Stepping two sessions through the same trajectory, one with
    telemetry recording and one without, produces bitwise-identical
    results and identical host-sync counts."""
    pts0 = rng.random((400, 3)).astype(np.float32)
    traj = [pts0]
    for _ in range(2):
        traj.append(_jitter(rng, traj[-1]))

    def run(mode):
        obs.reset()
        obs.configure(mode=mode)
        sess = SimulationSession(pts0, PARAMS)
        outs = [sess.step(p) for p in traj]
        return outs, sess.stats()

    outs_off, st_off = run("off")
    outs_on, st_on = run("log")
    for a, b in zip(outs_off, outs_on):
        np.testing.assert_array_equal(np.asarray(a.indices),
                                      np.asarray(b.indices))
        np.testing.assert_array_equal(np.asarray(a.counts),
                                      np.asarray(b.counts))
        np.testing.assert_array_equal(np.asarray(a.distances2),
                                      np.asarray(b.distances2))
    assert st_off["host_syncs"] == st_on["host_syncs"] == len(traj)
    assert st_off["stats_fetches"] == st_on["stats_fetches"] == 0
    assert st_off["step_cache_size"] == st_on["step_cache_size"]


def test_executor_syncs_identical_on_off(rng):
    """api-level query through the executor: the one-sync contract is
    unchanged by telemetry recording."""
    from repro.core import NeighborSearch

    pts = rng.random((900, 3)).astype(np.float32)
    qs = rng.random((160, 3)).astype(np.float32)

    def run(mode):
        obs.reset()
        obs.configure(mode=mode)
        ns = NeighborSearch(pts, PARAMS, SearchOpts())
        res = ns.query(qs)
        return res, ns.executor.stats()["last"]["host_syncs"]

    res_off, syncs_off = run("off")
    res_on, syncs_on = run("log")
    assert syncs_off == syncs_on == 1
    np.testing.assert_array_equal(np.asarray(res_off.indices),
                                  np.asarray(res_on.indices))


# ------------------------------------------- compiles, from JAX's own events


def _compile_params():
    # a signature no other test of this module compiles, so the programs
    # below are not already in JAX's in-process caches
    return SearchParams(radius=0.13, k=7, knn_window="exact")


def test_session_step_counts_its_compiles(rng):
    """The first step and a respec step compile their step program; a
    steady replayed step compiles nothing. Each step's count is on its
    report, on its ``step`` span and summed in ``stats()``."""
    obs.configure(mode="log")
    pts = rng.random((613, 3)).astype(np.float32) * 0.5
    sess = SimulationSession(pts, _compile_params())
    sess.step(pts)                                  # capture: compiles
    first = sess.report.compiles
    assert first >= 1
    sess.step(_jitter(rng, pts, 0.0005))            # replay variant
    sess.step(_jitter(rng, pts, 0.0005))            # steady
    assert sess.report.fast and sess.report.compiles == 0
    steps = [s for s in obs.recent_spans() if s["path"] == "step"]
    assert steps[0]["attrs"]["compiles"] == first
    assert steps[-1]["attrs"]["compiles"] == 0
    far = (pts + np.float32([2.0, 0.0, 0.0])).astype(np.float32)
    sess.step(far)                                  # respec: a new spec
    assert sess.report.respecced and sess.report.compiles >= 1
    st = sess.stats()
    assert st["last"]["compiles"] == sess.report.compiles
    assert st["compiles"] == sum(s["attrs"]["compiles"] for s in
                                 obs.recent_spans() if s["path"] == "step")
    # the compiles come from JAX's event: each is a compile span nested
    # in the step, and is counted in the registry's compile component
    paths = [s["path"] for s in obs.recent_spans() if s["name"] == "compile"]
    assert len([p for p in paths if p.startswith("step/")]) == st["compiles"]
    agg = obs.REGISTRY.aggregate()["compile"]
    assert agg["compiles"]["value"] >= st["compiles"]
    assert agg["compile_s"]["count"] == agg["compiles"]["value"]


def test_sharded_session_step_counts_its_compiles(rng):
    pts = rng.random((617, 3)).astype(np.float32)
    sess = ShardedSession(pts, _compile_params(), n_slabs=1)
    sess.step(pts)
    assert sess.last_compiles >= 1
    sess.step(_jitter(rng, pts, 0.0005))
    sess.step(_jitter(rng, pts, 0.0005))
    assert sess.last_compiles == 0
    st = sess.stats()
    assert st["last_compiles"] == 0 and st["compiles"] >= 1


def test_executor_query_counts_its_compiles(rng):
    from repro.core import NeighborSearch
    pts = rng.random((619, 3)).astype(np.float32)
    qs = rng.random((131, 3)).astype(np.float32)
    ns = NeighborSearch(pts, _compile_params())
    ns.query(qs)
    assert ns.executor.stats()["last"]["compiles"] >= 1
    ns.query(qs)
    assert ns.executor.stats()["last"]["compiles"] == 0


def test_compile_counters_follow_jax_events():
    """The listeners are registered with ``jax.monitoring`` itself: an
    event recorded there is counted, and survives an ``obs.reset()``."""
    from jax import monitoring
    from repro.obs import compiles
    obs.reset()
    monitoring.record_event(compiles.CACHE_HIT_EVENT)
    monitoring.record_event_duration_secs(compiles.BACKEND_COMPILE_EVENT,
                                          0.25, fun_name="jit(f)")
    monitoring.record_event_duration_secs("/jax/some/other_duration", 1.0)
    agg = obs.REGISTRY.aggregate()["compile"]
    assert agg["compile_cache_hits"]["value"] == 1
    assert agg["compiles"]["value"] == 1
    assert agg["compile_s"]["sum"] == 0.25
    # the process-wide set outlives the eviction of older instance sets
    from repro.obs import registry
    for _ in range(registry._LIVE_SETS_MAX + 8):
        obs.metric_set("executor")
    monitoring.record_event_duration_secs(compiles.BACKEND_COMPILE_EVENT,
                                          0.5, fun_name="jit(g)")
    assert obs.REGISTRY.aggregate()["compile"]["compiles"]["value"] == 2


def test_span_start_is_on_the_profiler_clock(tmp_path):
    """A span's recorded start and the profiler's TraceMe event of the
    same span agree once the profile's start time is subtracted."""
    import glob

    from jax.profiler import ProfileData
    obs.configure(mode="log")
    jax.profiler.start_trace(str(tmp_path))
    with obs.span("probe.clock"):
        pass
    jax.profiler.stop_trace()
    rec = next(s for s in obs.recent_spans() if s["name"] == "probe.clock")
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path[0])
    base = event = None
    for plane in pd.planes:
        for name, value in plane.stats:
            if name == "profile_start_time":
                base = value
        for line in plane.lines:
            for e in line.events:
                if e.name == "probe.clock":
                    event = e
    assert base is not None and event is not None
    assert abs(rec["t0_s"] * 1e9 - base - event.start_ns) < 1e6
