"""One run of one cell: set-up, the timed (or traced) window, the
comparison with the reference, and the result line."""
from __future__ import annotations

import dataclasses
import json
import math
import shutil
import sys
import time

import numpy as np

from bench.lib import names
from bench.lib.oracle import Oracle
from bench.lib.rng import rng_for


def resolve_cell(bench: dict, workload: str, rehearse: bool):
    """The cell by name; ``rehearse`` swaps in the configuration's
    rehearsal sizes."""
    cell = names.resolve(bench, workload)
    if rehearse:
        cell.config = {**cell.config, **cell.config["rehearsal"]}
    return cell


@dataclasses.dataclass
class RunInfo:
    """What the reducers read (``bench/reducers/<kind>.py``)."""

    setup_s: float
    window_s: float          # host clock, timed or traced window
    units: int               # whole frames/steps in that window
    sizes: tuple             # (points, queries, K) of one unit, queries
                             # the mean over the window's units
    neighbours: float        # mean returned neighbours per unit
    device_kind: str
    trace: object = None     # bench.lib.trace.Trace of a traced run
    unit_s: list = dataclasses.field(default_factory=list)
    # host seconds of each unit of the window, in order
    notes: dict = dataclasses.field(default_factory=dict)
    # the loop's counts of the window (``LoopBase.notes``)


def _timed_unit(loop, unit_s: list) -> None:
    t0 = time.perf_counter()
    loop.unit()
    unit_s.append(time.perf_counter() - t0)


def _timed_window(loop, seconds: float):
    unit_s = []
    t0 = time.perf_counter()
    while True:
        _timed_unit(loop, unit_s)
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return elapsed, unit_s


def _traced_window(loop, units: int, trace_dir):
    import jax

    from bench.lib import trace as trace_mod
    shutil.rmtree(trace_dir, ignore_errors=True)
    # device operations and TraceMe spans only: the Python tracer would
    # put an event on every call of the host loop
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = True     # the scopes of the operations
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    unit_s = []
    try:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(units):
                _timed_unit(loop, unit_s)
        elapsed = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    return elapsed, unit_s, trace_mod.read_dir(trace_dir)


def program_rows(rec, rows):
    """The timed path's answers for ``rows`` of one unit."""
    res = rec.result
    return [np.asarray(a)[rows] for a in (res.indices, res.distances2,
                                          res.counts)]


def queries_of(rec):
    """The queries that a unit's result rows answer, on the host: the
    record's own, or its points where the unit self-queried."""
    return np.asarray(rec.points if rec.queries is None else rec.queries)


def compare(loop, config: dict, seed: int, answers=program_rows):
    """The numbers compared, each beside its limit, and the units that
    failed. A sample of rows of every timed unit, drawn from the seed over
    that unit's queries, is judged by the float64 oracle over that unit's
    own points. ``answers`` gives the rows judged (the control puts the
    reference in the program's place there)."""
    limit = float(config["limits"]["d2_err_max"])
    recs = loop.records
    per_unit = max(1, math.ceil(int(config["sample_rows"]) / len(recs)))
    wrong, err_max, overflow, failed, examples = 0, 0.0, 0, 0, []
    s = config["search"]
    for u, rec in enumerate(recs):
        pts, qs = np.asarray(rec.points), queries_of(rec)
        rows = np.sort(rng_for(seed, 3, u).choice(
            len(qs), min(per_unit, len(qs)), replace=False))
        ovf = 0 if rec.overflow is None else int(np.asarray(rec.overflow))
        n_answers = int(rec.result.counts.shape[0])
        if n_answers != len(qs):
            # answers to other queries than these: every row is wrong
            bad = [f"unit {u}: {n_answers} answers for {len(qs)} "
                   f"queries"] * len(rows)
            err = 0.0
        else:
            oracle = Oracle(pts, qs[rows], s["radius"], s["k"], s["mode"],
                            band=limit)
            verdicts, err = oracle.check(*answers(rec, rows))
            bad = [f"unit {u} row {int(rows[i])}: {why}"
                   for i, why in enumerate(verdicts) if why is not None]
        wrong += len(bad)
        err_max = max(err_max, err)
        overflow += ovf
        failed += int(bool(bad) or ovf > 0 or err > limit)
        examples += bad[:3]
    checks = {
        "wrong_rows": {"value": wrong, "limit": 0},
        "d2_err_max": {"value": err_max, "limit": limit},
    }
    if any(rec.overflow is not None for rec in recs):
        # points the grid dropped: answers near them are wrong, whether
        # or not the sample holds one
        checks["grid_overflow"] = {"value": overflow, "limit": 0}
    for e in examples[:5]:
        print(f"bench: wrong {e}", file=sys.stderr)
    return checks, failed


def run_cell(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
             trace_dir, devices):
    """Run the cell once; returns (result line object, checks)."""
    import jax
    loop = names.load_module("loops", cell.traffic["loop"]).Loop(
        cell.config, cell.traffic, seed)
    tr = None
    try:
        loop.setup()
        setup_s = time.perf_counter() - t_start
        if trace:
            window_s, unit_s, tr = _traced_window(
                loop, int(cell.traffic["trace_units"]), trace_dir)
        else:
            window_s, unit_s = _timed_window(loop, seconds)
    finally:
        loop.close()
    units = len(unit_s)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    notes = loop.notes()
    print(f"bench: {cell.name} seed {seed}: set-up {setup_s:.3f} s, "
          f"{units} {loop.unit_name}s in {window_s:.3f} s; "
          f"{json.dumps(notes)}", flush=True)

    neighbours = float(np.mean([int(jax.numpy.sum(r.result.counts))
                                for r in loop.records]))
    sizes = loop.sizes()
    info = RunInfo(setup_s=setup_s, window_s=window_s, units=units,
                   sizes=sizes, neighbours=neighbours,
                   device_kind=devices[0].device_kind, trace=tr,
                   unit_s=unit_s, notes=notes)
    checks, failed = compare(loop, cell.config, seed)
    loop.records.clear()

    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        value = names.metric_reader(m["name"])(info)
        if value is None:
            print(f"bench: metric {m['name']} read nothing; left out of "
                  f"the line", file=sys.stderr)
        else:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": units, "failed": failed, "metrics": metrics,
              "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    result["checks"] = checks
    return result, checks
