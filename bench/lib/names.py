"""Finding what a cell uses by name.

Every piece that belongs to one configuration, traffic mix, metric, loop
or scene kind is a file of its own, found by the name that
``BENCHMARK.json``, a configuration or a metric file gives it:

* ``bench/configs/<config>.json``  (the entry's ``file``)
* ``bench/traffic/<traffic>.json``  a traffic mix: parameters only
* ``bench/loops/<loop>.py``          the loop a mix names, ``Loop``
* ``bench/scenes/<kind>.py``         a scene generator, ``generate``
* ``bench/metrics/<metric>.json``    a metric: its reducer and what the
                                     reducer reads (scope patterns)
* ``bench/reducers/<kind>.py``       a reducer, ``read(run, metric)``

So a later change adds a cell or a metric by adding files and entries,
and edits none. A file that is missing is an error; a reducer whose
trace scopes match nothing returns None, and the harness leaves that
metric out of the result line and says so on standard error.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(kind: str, name: str) -> dict:
    """Read ``bench/<kind>/<name>.json``."""
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def metric_reader(name: str):
    """``read(run)`` of metric ``name``: its file's reducer, given the
    file."""
    metric = load_json("metrics", name)
    reducer = load_module("reducers", metric["reducer"])
    return lambda run: reducer.read(run, metric)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, resolved."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # metric entries that this cell reports
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, workload: str) -> Cell:
    """The cell ``workload`` of ``bench`` (the parsed BENCHMARK.json)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=load_json("traffic", w["traffic"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])
