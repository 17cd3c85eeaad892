"""The trace reductions and the roofline work counts, on the CPU."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import roofline  # noqa: E402
from bench.lib import trace as T  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def synthetic():
    """Device 0: ops at [1,3], [2,4], [5,6] and [9,12] (clipped to the
    window at 10). Window [0.5, 10]. The profiler's self times: the build
    scope 3 s, the search scope 2 s."""
    ops = [["fusion.1", 1.0, 3.0, 0], ["fusion.2", 2.0, 4.0, 0],
           ["gather.3", 5.0, 6.0, 0], ["gather.3", 9.0, 12.0, 0]]
    spans = [["bench.window", 0.5, 10.0], ["bench.frame", 0.5, 7.0],
             ["bench.dequeue", 0.5, 0.9], ["bench.wait", 4.0, 7.0],
             ["bench.frame", 7.0, 10.0], ["bench.transfer", 7.0, 8.5]]
    hlo = [["fusion.1", "jit(f)/repro.build_index/sort", 2.0],
           ["fusion.2", "jit(f)/repro.build_index/scatter", 1.0],
           ["gather.3", "jit(f)/repro.execute_plan/while", 2.0],
           ["copy.4", "", 0.5]]
    return T.Trace(window=(0.5, 10.0), device_ops=ops, host_spans=spans,
                   hlo_ops=hlo)


def test_busy_idle_and_scopes():
    tr = synthetic()
    assert tr.window_s() == pytest.approx(9.5)
    assert tr.busy_s() == pytest.approx(3.0 + 1.0 + 1.0)
    assert tr.idle_share() == pytest.approx(1 - 5.0 / 9.5)
    assert tr.scope_s(["repro.build_index"]) == pytest.approx(3.0)
    assert tr.scope_s(["repro.execute_plan"]) == pytest.approx(2.0)
    assert tr.scope_s(["/sort", "/while"]) == pytest.approx(4.0)
    assert tr.scope_s(["repro.no_such_scope"]) is None


def test_top_ops_and_idle_gaps():
    tr = synthetic()
    top = tr.top_ops(10)
    assert [k for k, _ in top[:3]] == [
        "fusion.1 jit(f)/repro.build_index/sort",
        "gather.3 jit(f)/repro.execute_plan/while",
        "fusion.2 jit(f)/repro.build_index/scatter"]
    assert top[-1] == ["copy.4", pytest.approx(0.5)]
    assert len(tr.top_ops(2)) == 2
    # gaps of the busy union [1,4], [5,6], [9,10] in the window [0.5,10],
    # each named by the innermost span open at its middle
    gaps = dict(tr.idle_gaps(10))
    assert gaps == {"bench.dequeue": pytest.approx(0.5),
                    "bench.wait": pytest.approx(1.0),
                    "bench.transfer": pytest.approx(3.0)}


def test_compact_round_trip(tmp_path):
    tr = synthetic()
    T.save(tr, str(tmp_path / "t.json.gz"))
    back = T.load(str(tmp_path / "t.json.gz"))
    assert back.busy_s() == tr.busy_s()
    assert back.idle_gaps(10) == tr.idle_gaps(10)


def test_reads_a_profiler_trace(tmp_path):
    """A real profile written on this backend: the window span is found
    and the host spans inside it kept (no TPU plane, so no device ops)."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sort(x) * 2)
    x = jnp.arange(1000.0)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.frame"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.read_dir(tmp_path)
    assert tr.window_s() > 0
    assert "bench.frame" in {s[0] for s in tr.host_spans}
    assert tr.devices() == [] and tr.busy_s() == 0.0
    assert tr.hlo_ops == [] and tr.scope_s(["jit"]) is None


@pytest.mark.parametrize("config,least", [
    # 12 B per point, 12 B per query, (16 x 8 + 4) B of results per query
    ("kitti_hdl64_frame", 120_000 * 12 * 2 + 120_000 * 132),
    # (64 x 8 + 4) B of results per particle
    ("sph_lattice_262k", 262_144 * 12 * 2 + 262_144 * 516),
])
def test_least_bytes_of_each_cell(config, least):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    s = cfg["scene"]
    n = s.get("points") or s["lattice"][0] * s["lattice"][1] * \
        s["lattice"][2]
    assert roofline.least_bytes(n, n, cfg["search"]["k"]) == least


def test_least_time_is_bound_by_bytes_on_v5e():
    # 18.72 MB over 819 GB/s against 8 flops x 1.6M neighbours over
    # 197 TFLOP/s
    t = roofline.least_seconds(120_000, 120_000, 16, 1.6e6, "TPU v5 lite")
    assert t == pytest.approx(18_720_000 / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


RECORDED = ROOT / "bench" / "tests" / "data" / "lidar_frames_head.json.gz"


def _union_by_grid(intervals, lo, hi, step=1e-7):
    """An independent busy length: mark a fine grid of instants."""
    import numpy as np
    t = np.arange(lo, hi, step)
    busy = np.zeros(len(t), bool)
    for a, b in intervals:
        busy[(t >= a) & (t < b)] = True
    return busy.sum() * step


def test_recorded_chip_trace():
    """25 ms of a traced `lidar.frames` window on a TPU v5e around the
    boundary of two frames: busy time and idle gaps agree with an
    independent count, and the gap between the frames is named by the
    host span that was open."""
    tr = T.load(str(RECORDED))
    lo, hi = tr.window
    assert tr.devices() == [0] and len(tr.device_ops) > 20
    clipped = [(max(o[1], lo), min(o[2], hi)) for o in tr.device_ops
               if o[2] > lo and o[1] < hi]
    assert tr.busy_s() == pytest.approx(_union_by_grid(clipped, lo, hi),
                                        abs=2e-6)
    assert 0.0 < tr.idle_share() < 1.0
    gaps = tr.idle_gaps(100)
    assert sum(s for _, s in gaps) == pytest.approx(
        tr.window_s() - tr.busy_s(), abs=1e-9)
    assert {name for name, _ in gaps} <= {s[0] for s in tr.host_spans}


def _run_info(trace, notes=None):
    from bench.lib.harness import RunInfo
    return RunInfo(setup_s=12.5, window_s=9.5, units=2,
                   sizes=(120_000, 120_000, 16), neighbours=1.6e6,
                   device_kind="TPU v5 lite", trace=trace,
                   notes=notes or {})


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_reads_the_trace(metric):
    """Each metric file's reducer reads a number from a trace whose
    operations carry the program's scopes and whose host spans and
    window counts are the program's, and nothing (None, never 0) where
    the scopes it names ran nothing."""
    from bench.lib import names
    read = names.metric_reader(metric)
    tr = synthetic()
    tr.hlo_ops += [["fusion.9", "jit(f)/repro.update_index/scatter", 0.25],
                   ["fusion.8", "jit(f)/repro.plan_query/sort", 0.125]]
    tr.host_spans += [["query", 1.0, 6.0], ["plan", 1.0, 2.0],
                      ["sync", 1.5, 1.75], ["launch", 2.0, 2.5],
                      ["sync", 5.0, 6.0]]
    value = read(_run_info(tr, notes={"compiles": 1}))
    assert value is not None and value > 0
    bare = synthetic()
    bare.hlo_ops = [[o[0], "", o[2]] for o in bare.hlo_ops]
    spec = names.load_json("metrics", metric)
    if spec.get("scopes"):
        assert read(_run_info(bare)) is None
    assert read(_run_info(None)) is None


def test_scope_metrics_per_unit():
    from bench.lib import names
    run = _run_info(synthetic())
    assert names.metric_reader("grid_device_ms.frame")(run) == \
        pytest.approx(1e3 * 3.0 / 2)
    assert names.metric_reader("search_device_ms.frame")(run) == \
        pytest.approx(1e3 * 2.0 / 2)
    least = roofline.least_seconds(120_000, 120_000, 16, 1.6e6,
                                   "TPU v5 lite")
    assert names.metric_reader("search_roofline.frame")(run) == \
        pytest.approx(100 * least / 1.0)
    assert names.metric_reader("device_idle_share.frame")(run) == \
        pytest.approx(100 * (1 - 5.0 / 9.5))
