"""The harness on the CPU at each cell's rehearsal size.

Every cell of BENCHMARK.json resolves its configuration, traffic, loop,
scene and metric files by name; a rehearsal run prints the contract's
last line; a traced run leaves out, and never zeroes, a metric whose
scopes match nothing; and a run whose timed path is broken underneath
comes out not correct."""
import contextlib
import io
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as bench_run  # noqa: E402
from bench.lib import names  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def run(workload, *extra, seed=2**33 + 5, seconds=0.5, trace=0):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                             "--seconds", str(seconds), "--trace",
                             str(trace), *extra])
    return rc, out.getvalue(), err.getvalue()


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_by_name(workload):
    cell = names.resolve(BENCH, workload)
    names.load_module("loops", cell.traffic["loop"]).Loop
    names.load_module("scenes", cell.config["scene"]["kind"]).generate
    names.load_module("scenes", cell.config["rehearsal"]["scene"]["kind"])
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(names.metric_reader(m["name"]))


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_prints_the_result_line(workload):
    rc, out, err = run(workload, "--rehearse")
    assert rc == 0, err
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    cell = names.resolve(BENCH, workload)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    for name, m in line["metrics"].items():
        assert m["value"] > 0, name
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    # the numbers compared close standard error, each beside its limit
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert [t.split(":")[0] for t in tail] == [
        f"check {k}" for k in line["checks"]]


@pytest.mark.parametrize("workload", CELLS)
def test_traced_rehearsal_leaves_out_what_it_cannot_read(workload):
    # the CPU has no TPU device plane: every device metric finds nothing
    # and is left out, never printed as 0; the program's host spans and
    # counters are there to read
    rc, out, err = run(workload, "--rehearse", trace=1)
    assert rc == 0, err
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True
    cell = names.resolve(BENCH, workload)
    assert set(line["metrics"]) == {m["name"] for m in cell.per_layer
                                    if m["source"] != "device_trace"}
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_accelerator_no_result():
    rc, out, _ = run(CELLS[0])
    assert rc != 0 and out.strip() == ""


def test_bench_files_alone_do_not_run(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    subprocess.run(["cp", "-r", str(ROOT / "bench"), str(tmp_path)],
                   check=True)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=300,
                       env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout.strip() == ""


# -- faults planted under the timed path --------------------------------

def _stale(first):
    """Every call after the first returns the first call's answer."""
    memo = []

    def call(*a):
        if not memo:
            memo.append(first(*a))
        return memo[0]
    return call


def _half_missing(res):
    n = res.counts.shape[0] // 2
    return type(res)(indices=res.indices.at[n:].set(-1),
                     distances2=res.distances2.at[n:].set(np.inf),
                     counts=res.counts.at[n:].set(0))


def _altered(res):
    idx = res.indices
    return type(res)(indices=idx.at[::8, 0].set((idx[::8, 0] + 1)
                                                % idx.shape[0]),
                     distances2=res.distances2, counts=res.counts)


class _Wrapped:
    """``inner``, with the results of its method ``method`` wrapped by
    ``fault``."""

    def __init__(self, inner, method, fault):
        self._inner = inner
        setattr(self, method, lambda *a: fault(getattr(inner, method)(*a)))

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _wrap_system(mod, factory, method, fault):
    """Make ``mod.<factory>`` build the system under test with ``fault``
    planted under its ``method``."""
    make = getattr(mod, factory)

    def broken(*a):
        inner = make(*a)
        if fault == "stale":
            return _Wrapped(inner, method, _stale(lambda r: r))
        return _Wrapped(inner, method, _half_missing if fault == "half"
                        else _altered)
    setattr(mod, factory, broken)


def _plant(monkeypatch, fault):
    real = names.load_module

    def load(kind, name):
        mod = real(kind, name)
        if kind != "loops":
            return mod
        if name == "fresh_frames":
            build = mod.Loop.build_program

            def broken(self):
                prog = build(self)
                if fault == "stale":
                    return _stale(prog)
                wrap = _half_missing if fault == "half" else _altered
                return lambda pts: (lambda r, o: (wrap(r), o))(*prog(pts))
            mod.Loop.build_program = broken
        elif name == "session_steps":
            _wrap_system(mod, "make_session", "step", fault)
        elif name == "resident_queries":
            _wrap_system(mod, "make_search", "query", fault)
        else:
            raise AssertionError(f"no fault planter for loop {name}")
        return mod
    monkeypatch.setattr(names, "load_module", load)


@pytest.mark.parametrize("fault", ["stale", "half", "altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(monkeypatch, workload, fault):
    _plant(monkeypatch, fault)
    rc, out, err = run(workload, "--rehearse")
    assert rc == 0, err
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False, line["checks"]
    assert line["failed"] >= 1


@pytest.mark.parametrize("workload", CELLS)
def test_readings_separate_program_and_control(workload):
    from bench import readings
    out = io.StringIO()
    with contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(io.StringIO()):
        rc = readings.main(["--workload", workload, "--units", "2",
                            "--seeds", "11", "12", "--control-seeds", "13",
                            "--rehearse"])
    assert rc == 0
    summary = json.loads(out.getvalue().strip().splitlines()[-1])
    limit = names.resolve(BENCH, workload).config["limits"]["d2_err_max"]
    assert summary["program_wrong_rows"] == 0
    assert summary["lower_d2_err_max"] < limit
    assert summary["upper_d2_err_max"] > 3 * limit
