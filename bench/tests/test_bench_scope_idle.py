"""Idle time inside the program, put down to scopes
(``bench/lib/timeline.py``, ``bench/reducers/scope_idle.py``), and the
pinned readings of the trace reductions that came before it."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import names  # noqa: E402
from bench.lib import timeline as TL  # noqa: E402
from bench.lib import trace as T  # noqa: E402
from bench.lib.harness import RunInfo  # noqa: E402

RECORDED = ROOT / "bench" / "tests" / "data" / "lidar_frames_head.json.gz"
STEP = "jit(_step_impl)/repro.execute_plan/while/body/closed_call"
ROWS = f"{STEP}/repro.launch.level0_w2/repro.search.row_gather/gather"
TOPK = f"{STEP}/repro.launch.level1_w3/repro.search.select/top_k"


def two_programs():
    """Program "7" (the drift) and program "9" (the session step) both
    name an operation ``fusion.3``; ``copy-done.4`` has no framework op
    name. Gaps, in the window [0, 16]: 1 s after the drift, 0.5 s
    between the step's ops, 0.25 s before and 0.25 s after the unnamed
    copy, and 2 s between the step and the next drift."""
    ops = [["fusion.3", 0.0, 2.0, 0, "7"],       # drift
           ["fusion.3", 3.0, 5.0, 0, "9"],       # step: row gather
           ["fusion.5", 5.5, 6.0, 0, "9"],       # step: select
           ["copy-done.4", 6.25, 7.0, 0, "9"],   # unnamed
           ["fusion.3", 7.25, 8.0, 0, "9"],      # step: row gather
           ["fusion.3", 10.0, 11.0, 0, "7"],     # next drift
           ["fusion.3", 3.0, 9.0, 1, "9"]]       # another device
    names_ = [["7", "fusion.3", "jit(drift)/add"],
              ["9", "fusion.3", ROWS],
              ["9", "fusion.5", TOPK],
              ["9", "copy-done.4", ""]]
    return TL.Timeline(window=(0.0, 16.0), ops=ops, names=names_)


def test_gaps_sum_exactly_and_names_resolve_by_program():
    tl = two_programs()
    assert tl.framework_names() == ["jit(drift)/add", ROWS, TOPK, "",
                                    ROWS, "jit(drift)/add", ROWS]
    seconds, gaps = tl.scope_gaps(["repro.execute_plan"])
    # 0.5 + 0.25 + 0.25 inside the step; the 1 s and 2 s on either side
    # border the drift, whose fusion.3 is not the step's
    assert seconds == 1.0
    assert sum(g[2] for g in gaps) == seconds
    rows = "repro.execute_plan/repro.launch.level0_w2/repro.search.row_gather"
    top = "repro.execute_plan/repro.launch.level1_w3/repro.search.select"
    assert gaps == [
        [rows, top, 0.5, ["fusion.3", "fusion.5"]],
        # the unnamed copy is passed over, and still reported
        [top, rows, 0.25, ["fusion.5", "copy-done.4", "fusion.3"]],
        [top, rows, 0.25, ["fusion.5", "copy-done.4", "fusion.3"]]]
    seconds, gaps = tl.scope_gaps(["jit(drift)", "repro.execute_plan"])
    assert seconds == 4.0 and len(gaps) == 5


def test_without_programs_a_shared_name_goes_unnamed():
    tl = two_programs()
    tl.ops = [o[:4] + [None] for o in tl.ops]
    tl.names = [[None] + n[1:] for n in tl.names]
    fws = tl.framework_names()
    assert fws[2] == TOPK                    # fusion.5: one program
    assert fws[0] == fws[1] == ""            # fusion.3: two
    # the select is the one named op left: no gap has one on both sides
    assert tl.scope_gaps(["repro.execute_plan"]) == (0.0, [])


def test_no_gap_reads_zero_and_no_scope_reads_none():
    tl = TL.Timeline(window=(0.0, 4.0),
                     ops=[["fusion.3", 0.0, 1.0, 0, "9"],
                          ["fusion.5", 1.0, 3.0, 0, "9"]],
                     names=[["9", "fusion.3", ROWS], ["9", "fusion.5", TOPK]])
    assert tl.scope_gaps(["repro.execute_plan"]) == (0.0, [])
    assert tl.scope_gaps(["repro.build_index"]) == (None, [])
    assert TL.Timeline((0.0, 1.0), [], []).scope_gaps(["x"]) == (None, [])


def test_names_and_labels():
    assert TL.hlo_name("%fusion.21 = s32[120000]{0} fusion(%a)") == \
        "fusion.21"
    assert TL.hlo_name("copy-done.4") == "copy-done.4"
    assert TL.scope_label(ROWS) == (
        "repro.execute_plan/repro.launch.level0_w2/repro.search.row_gather")
    assert TL.scope_label("jit(drift)/add") == "jit(drift)/add"
    assert TL.program_key(12) == TL.program_key("12") == \
        TL.program_key(12.0) == "12"
    assert TL.program_key(None) is None and TL.program_key("") is None


class _Event:
    def __init__(self, name, start_ns, duration_ns, stats=()):
        self.name, self.start_ns, self.duration_ns = name, start_ns, \
            duration_ns
        self.stats = list(stats)


class _Line:
    def __init__(self, events):
        self.events = events


def test_programs_from_the_modules_line():
    """An op takes its own ``program_id`` stat where it has one, else the
    program whose run holds its start."""
    modules = _Line([_Event("jit_drift(7)", 0, 100),
                     _Event("jit__step_impl(9)", 200, 300,
                            [("program_id", 9)])])
    ops = _Line([_Event("%fusion.3 = f32[8]", 10, 5),
                 _Event("fusion.3", 250, 5),
                 _Event("fusion.3", 150, 5),              # between runs
                 _Event("fusion.3", 260, 5, [("program_id", 11)])])
    assert TL._programs(ops, modules) == ["7", "9", None, "11"]
    assert TL._programs(ops, None) == [None, None, None, "11"]
    # a TPU module's 64-bit id as its run's name gives it, and as
    # hlo_stats gave it for the same program (a JSON double), on a v5e
    big = _Line([_Event("jit_frame(4132172928810767120)", 0, 100)])
    assert TL._programs(_Line([_Event("fusion.3", 5, 1)]), big) == \
        [TL.program_key(4132172928810767360.0)] == ["4132172928810767360"]


def _run(trace, units=2):
    return RunInfo(setup_s=12.5, window_s=9.5, units=units,
                   sizes=(120_000, 120_000, 16), neighbours=1.6e6,
                   device_kind="TPU v5 lite", trace=trace)


def test_reducer_reads_ms_per_unit_and_names_the_gaps(capsys):
    """Without its profile on disk the reducer reads the compact trace:
    device 0 runs a row gather, a select, then a row gather again."""
    tr = T.Trace(window=(0.0, 10.0),
                 device_ops=[["%fusion.3 = f32[8]", 1.0, 2.0, 0],
                             ["%fusion.5 = f32[8]", 2.5, 3.0, 0],
                             ["%fusion.3 = f32[8]", 4.0, 5.0, 0]],
                 host_spans=[["bench.window", 0.0, 10.0]],
                 hlo_ops=[["fusion.3", ROWS, 2.0], ["fusion.5", TOPK, 0.5]])
    read = names.metric_reader("search_idle_ms.step")
    assert read(_run(tr)) == pytest.approx(1e3 * 1.5 / 2)
    err = capsys.readouterr().err
    assert "idle in repro.execute_plan: 250 ms/unit in 1 gaps between " \
           "repro.execute_plan/repro.launch.level0_w2/" \
           "repro.search.row_gather and " in err
    assert "(most: fusion.5 | fusion.3)" in err
    tr.hlo_ops = [[o[0], "", o[2]] for o in tr.hlo_ops]
    assert read(_run(tr)) is None
    assert read(_run(None)) is None


def test_recorded_chip_trace_gaps_by_scope():
    """The recorded 25 ms of a `lidar.frames` window on a TPU v5e: the
    frame's last search operations, the wait for the next frame, then
    that frame's grid build. Named by scope, the gaps inside each scope
    add up to that scope's span less its busy time, and the wait between
    the frames counts only for a metric that holds both scopes."""
    tr = T.load(str(RECORDED))
    tl = TL.from_trace(tr)
    search = [TL.hlo_name(o[0]) for o in tr.device_ops[:8]]
    build = [TL.hlo_name(o[0]) for o in tr.device_ops[8:]]
    tl.names = ([[None, n, f"jit(frame)/repro.execute_plan/{n}"]
                 for n in search]
                + [[None, n, f"jit(frame)/repro.build_index/{n}"]
                   for n in build])

    def inside(ops):
        return (ops[-1][2] - ops[0][1]
                - T.union_length([(o[1], o[2]) for o in ops]))

    s_search, g_search = tl.scope_gaps(["repro.execute_plan"])
    s_build, g_build = tl.scope_gaps(["repro.build_index"])
    assert s_search == pytest.approx(inside(tr.device_ops[:8]), abs=1e-12)
    assert s_build == pytest.approx(inside(tr.device_ops[8:]), abs=1e-12)
    assert 0 < s_search < 1e-5 and 0 < s_build < 1e-5
    s_both, g_both = tl.scope_gaps(["repro.execute_plan",
                                    "repro.build_index"])
    wait = tr.device_ops[8][1] - tr.device_ops[7][2]
    assert s_both == pytest.approx(s_search + s_build + wait, abs=1e-12)
    assert ["repro.execute_plan", "repro.build_index"] == \
        max(g_both, key=lambda g: g[2])[:2]


HANDOVER = ROOT / "bench" / "tests" / "data" / "sph_drift_handover.json.gz"


def _reference_gaps(tl, patterns):
    """The same reduction by brute force: merge the first device's
    intervals one by one, and for each gap scan every operation for the
    nearest named one on each side."""
    lo, hi = tl.window
    fws = tl.framework_names()
    dev = min(o[3] for o in tl.ops)
    ops = sorted((max(o[1], lo), min(o[2], hi), fw)
                 for o, fw in zip(tl.ops, fws)
                 if o[3] == dev and o[2] > lo and o[1] < hi)
    busy = []
    for a, b, _fw in ops:
        if busy and a <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], b)
        else:
            busy.append([a, b])
    total = 0.0
    for (_s, a), (b, _e) in zip(busy, busy[1:]):
        before = [o for o in ops if o[2] and o[1] <= a]
        after = [o for o in ops if o[2] and o[0] >= b]
        if before and after:
            fb = max(before, key=lambda o: o[1])[2]
            fa = min(after, key=lambda o: o[0])[2]
            if any(p in fb for p in patterns) and \
                    any(p in fa for p in patterns):
                total += b - a
    return total


def test_recorded_handover_between_programs():
    """64 operations of a `sph.drift` window recorded on a TPU v5e, where
    the random-key program hands over to the drift and the drift to the
    session step: three programs, each operation found by
    (program, HLO name), and the gaps by scope as a brute-force count
    reads them. The 1.85 ms launch gap between the drift and the step
    counts only for a metric that holds both."""
    import gzip
    d = json.load(gzip.open(HANDOVER, "rt"))
    tl = TL.Timeline(window=tuple(d["window"]), ops=d["ops"],
                     names=d["names"])
    keys = {(p, h) for p, h, _fw in tl.names}
    assert all((o[4], o[0]) in keys for o in tl.ops)
    jit_of = {}
    for o, fw in zip(tl.ops, tl.framework_names()):
        if fw.startswith("jit("):
            jit_of.setdefault(o[4], set()).add(fw.split("/")[0])
    assert sorted(sorted(v) for v in jit_of.values()) == [
        ["jit(_step_impl)"], ["jit(_threefry_fold_in)"], ["jit(drift)"]]
    for patterns in (["repro.update_index"], ["jit(drift)"],
                     ["jit(drift)", "repro.update_index"], ["jit("]):
        seconds, gaps = tl.scope_gaps(patterns)
        assert seconds == pytest.approx(_reference_gaps(tl, patterns),
                                        abs=1e-15)
        assert sum(g[2] for g in gaps) == pytest.approx(seconds, abs=1e-15)
    both, gaps = tl.scope_gaps(["jit(drift)", "repro.update_index"])
    launch = max(gaps, key=lambda g: g[2])
    assert launch[0].startswith("jit(drift)")
    assert launch[1] == "repro.update_index"
    assert launch[2] == pytest.approx(0.047723708 - 0.045874909, abs=1e-9)
    assert launch[3] == ["subtract_select_fusion", "copy.204", "fusion.131"]
    assert tl.scope_gaps(["repro.update_index"])[0] < 1e-6 < both


def test_reads_a_profile_written_here(tmp_path):
    """A real profile on this backend: the window is found, no TPU plane
    means no device operations, and a compact trace of the same window
    takes the profile's timeline, one of another window does not."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sort(x) * 2)
    x = jnp.arange(1000.0)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.read_dir(tmp_path)
    tl = TL.read_run(tr, trace_dir=tmp_path)
    assert tl.window == pytest.approx(tr.window) and tl.ops == []
    other = T.Trace(window=(0.0, 1.0), device_ops=[["fusion.1", 0.1, 0.2, 0]],
                    host_spans=[], hlo_ops=[])
    assert TL.read_run(other, trace_dir=tmp_path).ops == [
        ["fusion.1", 0.1, 0.2, 0, None]]


# -- the reductions that came before, pinned -------------------------------

def _synthetic():
    sys.path.insert(0, str(Path(__file__).parent))
    from test_bench_trace import synthetic
    return synthetic()


def test_existing_reductions_read_as_before_on_the_recorded_trace():
    tr = T.load(str(RECORDED))
    assert tr.window_s() == 0.025000000000000355
    assert tr.busy_s() == 0.006003975000000494
    assert tr.idle_share() == 0.7598409999999837
    assert tr.idle_gaps(10) == [["bench.wait", 0.01899602499999986]]
    assert tr.top_ops(10) == []
    for scope in ("repro.build_index", "repro.execute_plan",
                  "repro.plan_query", "repro.update_index"):
        assert tr.scope_s([scope]) is None


def test_existing_reductions_read_as_before_on_the_synthetic_trace():
    tr = _synthetic()
    assert (tr.window_s(), tr.busy_s(), tr.idle_share()) == \
        (9.5, 5.0, 0.4736842105263158)
    assert tr.idle_gaps(10) == [["bench.transfer", 3.0], ["bench.wait", 1.0],
                                ["bench.dequeue", 0.5]]
    assert tr.top_ops(10) == [
        ["fusion.1 jit(f)/repro.build_index/sort", 2.0],
        ["gather.3 jit(f)/repro.execute_plan/while", 2.0],
        ["fusion.2 jit(f)/repro.build_index/scatter", 1.0],
        ["copy.4", 0.5]]
    assert tr.scope_s(["repro.build_index"]) == 3.0
    assert tr.scope_s(["repro.execute_plan"]) == 2.0
    run = _run(tr)
    before = {"grid_device_ms.frame": 1500.0,
              "search_device_ms.frame": 1000.0,
              "search_device_ms.step": 1000.0,
              "device_idle_share.frame": 100 * (1 - 5.0 / 9.5),
              "device_idle_share.step": 100 * (1 - 5.0 / 9.5),
              "grid_device_ms.step": None, "plan_device_ms.frame": None}
    for metric, value in before.items():
        assert names.metric_reader(metric)(run) == value, metric


def test_new_metrics_are_entries_of_the_benchmark():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for unit, cell, moves in (("frame", "lidar.frames", "frame_s"),
                              ("step", "sph.drift", "step_s")):
        for stem, scope in (("row_gather_device_ms",
                             "repro.search.row_gather"),
                            ("window_gather_device_ms",
                             "repro.search.window_gather"),
                            ("search_idle_ms", "repro.execute_plan")):
            m = per_layer[f"{stem}.{unit}"]
            assert (m["layer"], m["moves"], m["workloads"]) == \
                ("search kernel", moves, [cell])
            assert names.load_json("metrics", f"{stem}.{unit}")["scopes"] \
                == [scope]
