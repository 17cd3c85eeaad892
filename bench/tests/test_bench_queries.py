"""The comparison over records whose queries are not their points, and
the reducers of per-call metrics, on the CPU.

A record without queries is judged exactly as before queries existed:
the same rows drawn from the seed, the same numbers. A record with
queries is judged over its own queries, so an answer that is right for
the scan point a query was drawn from, and wrong for the query itself,
is caught."""
import json
import sys
import threading
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import harness, names  # noqa: E402
from bench.lib import trace as T  # noqa: E402
from bench.lib.loopbase import Record  # noqa: E402
from bench.lib.producer import Producer  # noqa: E402
from bench.lib.rng import rng_for  # noqa: E402

SEED = 2**33 + 5
R, K = 0.05, 16
CONFIG = {"limits": {"d2_err_max": 2e-6}, "sample_rows": 64,
          "search": {"radius": R, "k": K, "mode": "knn"}}


def scan(stream):
    gen = names.load_module("scenes", "kitti_like_cloud").generate
    return gen(rng_for(2**40 + 11, stream), points=2000, z_range=0.04)


def exact_f32(points, queries):
    """Bounded-K nearest in-range points, float32 difference form, as a
    search result."""
    d2 = np.sum((queries[:, None, :] - points[None, :, :]) ** 2, axis=-1,
                dtype=np.float32)
    d2 = np.where(d2 <= np.float32(R) ** 2, d2, np.inf)
    order = np.argsort(d2, axis=1, kind="stable")[:, :K]
    dk = np.take_along_axis(d2, order, axis=1)
    ok = np.isfinite(dk)
    return types.SimpleNamespace(indices=np.where(ok, order, -1),
                                 distances2=dk, counts=ok.sum(axis=1))


def jittered(points, n, stream):
    rng = rng_for(SEED, 4, stream)
    rows = rng.choice(len(points), n, replace=False)
    q = points[rows] + rng.normal(0.0, R / 4, (n, 3)).astype(np.float32)
    return np.clip(q, points.min(axis=0), points.max(axis=0)), rows


def judge(records):
    """compare's checks and failed units, and the rows it drew."""
    drawn = []

    def answers(rec, rows):
        drawn.append(rows)
        return harness.program_rows(rec, rows)

    loop = types.SimpleNamespace(records=records)
    checks, failed = harness.compare(loop, CONFIG, SEED, answers)
    return {k: c["value"] for k, c in checks.items()}, failed, drawn


def test_self_query_records_are_judged_as_before():
    # rows and numbers as compare gave them before records held queries
    recs = [Record(points=p, result=exact_f32(p, p))
            for p in (scan(0), scan(1))]
    checks, failed, drawn = judge(recs)
    assert [r[:6].tolist() for r in drawn] == [
        [66, 150, 204, 321, 337, 401], [51, 137, 145, 176, 216, 285]]
    assert [int(r.sum()) for r in drawn] == [35315, 31272]
    assert checks == {"wrong_rows": 0,
                      "d2_err_max": 2.3962773085250966e-10}
    assert failed == 0


def test_jittered_queries_are_judged_over_their_own_rows():
    pts = scan(0)
    q, _ = jittered(pts, 300, 0)
    rec = Record(points=pts, result=exact_f32(pts, q), queries=q)
    checks, failed, drawn = judge([rec])
    assert checks["wrong_rows"] == 0 and failed == 0
    assert checks["d2_err_max"] < CONFIG["limits"]["d2_err_max"]
    # the rows are drawn over the 300 queries, not the 2,000 points
    assert len(drawn[0]) == 64 and drawn[0].max() < 300


def test_wrong_answer_for_a_jittered_query_is_caught():
    pts = scan(0)
    q, src = jittered(pts, 300, 0)
    # the answers of the scan points the queries were drawn from: right
    # for a self-query, wrong for the jittered query
    rec = Record(points=pts, result=exact_f32(pts, pts[src]), queries=q)
    checks, failed, _ = judge([rec])
    assert checks["wrong_rows"] > 0 and failed == 1
    # one row's nearest neighbour swapped for another point
    res = exact_f32(pts, q)
    row = int(judge([Record(points=pts, result=res, queries=q)])[2][0][0])
    res.indices[row, 0] = (res.indices[row, 0] + 1) % len(pts)
    checks, failed, _ = judge([Record(points=pts, result=res, queries=q)])
    assert checks["wrong_rows"] == 1 and failed == 1


def test_answers_to_other_queries_are_all_wrong():
    # a call that returns an earlier call's answer, of another length
    pts = scan(0)
    q, _ = jittered(pts, 300, 0)
    rec = Record(points=pts, result=exact_f32(pts, q[:200]), queries=q)
    checks, failed, _ = judge([rec])
    assert checks["wrong_rows"] == 64 and failed == 1


# -- the calls of the resident-scan traffic ----------------------------------

def scan_queries_loop(seed):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.resolve_cell(bench, "lidar.scan_queries", rehearse=True)
    loop = names.load_module("loops", cell.traffic["loop"]).Loop(
        cell.config, cell.traffic, seed)
    return loop, cell.traffic


@pytest.mark.parametrize("seed", [SEED, 7])
def test_every_deck_deals_each_size_once(seed):
    loop, traffic = scan_queries_loop(seed)
    sizes = traffic["call_sizes"]
    dealt = [loop.size(c) for c in range(5 * len(sizes))]
    for d in range(5):
        deck = dealt[d * len(sizes):(d + 1) * len(sizes)]
        assert sorted(deck) == sorted(sizes)
    assert dealt == [loop.size(c) for c in range(len(dealt))]
    # the warm-up is at least one deck, so it meets every size
    assert int(traffic["warm_units"]) >= len(sizes)


def test_producer_hands_over_in_order_and_stops():
    made = []

    def make(i):
        made.append(i)
        if i == 6:
            raise ValueError("no call 6")
        return i

    p = Producer(make, 2, "test-producer")
    assert [p.take() for _ in range(6)] == list(range(6))
    with pytest.raises(ValueError, match="no call 6"):
        p.take()
    p.close()
    assert made == list(range(7))
    q = Producer(lambda i: i, 2, "test-producer")
    assert q.take() == 0
    q.close()
    assert not any(t.name == "test-producer"
                   for t in threading.enumerate())


# -- reducers of the per-call metrics ---------------------------------------

def run_info(trace=None, **kw):
    fields = dict(setup_s=12.5, window_s=2.0, units=4,
                  sizes=(120_000, 4096, 16), neighbours=6.5e4,
                  device_kind="TPU v5 lite", trace=trace,
                  unit_s=[0.5, 0.25, 0.75, 0.5], notes={})
    return harness.RunInfo(**{**fields, **kw})


def reader(kind, **metric):
    return lambda run: names.load_module("reducers", kind).read(run, metric)


def test_per_second():
    assert reader("per_second")(run_info()) == pytest.approx(4 * 4096 / 2.0)


def test_unit_percentile_is_one_units_time(capsys):
    p95 = reader("unit_percentile", percentile=95)
    assert p95(run_info()) == pytest.approx(750.0)
    assert "beyond it" in capsys.readouterr().err
    times = [i / 1000 for i in range(1, 201)]     # 1 .. 200 ms
    assert p95(run_info(unit_s=times)) == pytest.approx(190.0)
    assert reader("unit_percentile", percentile=50)(
        run_info(unit_s=times)) == pytest.approx(100.0)
    assert capsys.readouterr().err == ""
    assert p95(run_info(unit_s=[])) is None


def test_span_ms_reads_the_union_inside_the_window():
    spans = [["bench.window", 1.0, 9.0],
             ["plan", 0.5, 1.5],                  # clipped to 1.0 .. 1.5
             ["sync", 0.6, 0.8],                  # outside the window
             ["plan", 3.0, 4.0], ["sync", 3.25, 3.5],
             ["plan", 3.5, 4.5],                  # overlaps: union 3 .. 4.5
             ["sync", 5.0, 5.5],                  # not inside a plan
             ["launch", 6.0, 7.0]]
    tr = T.Trace(window=(1.0, 9.0), device_ops=[], host_spans=spans)
    run = run_info(trace=tr)
    assert reader("span_ms", spans=["plan"])(run) == pytest.approx(
        1e3 * (0.5 + 1.5) / 4)
    assert reader("span_ms", spans=["sync"], inside=["plan"])(run) == \
        pytest.approx(1e3 * 0.25 / 4)
    assert reader("span_ms", spans=["sync"])(run) == pytest.approx(
        1e3 * 0.75 / 4)
    assert reader("span_ms", spans=["compile"])(run) is None
    assert reader("span_ms", spans=["plan"])(run_info()) is None


def test_note_per_unit():
    count = reader("note_per_unit", note="compiles")
    assert count(run_info(notes={"compiles": 2})) == pytest.approx(0.5)
    assert count(run_info(notes={"compiles": 0})) == 0.0
    assert count(run_info()) is None
