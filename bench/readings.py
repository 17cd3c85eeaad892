"""Readings that the limits of ``correct`` are set from (not a run of the
benchmark; the benchmark's own runs never run the control).

    python3 bench/readings.py --workload <cell> --units <n> \\
        --seeds 1 2 ... --control-seeds 101 102 103

For each ``--seeds`` seed it runs the cell's timed path for ``--units``
units after its set-up and prints the numbers compared (the lower
readings). For each ``--control-seeds`` seed it drives the same traffic
and puts the control in the program's place: the plain reference's brute
force with its cross term one step down in precision, at ``high`` (three
bf16 passes), judged by the same comparison (the upper readings).
One JSON object per seed, then a summary line. Runs on the chip only,
like the benchmark; ``--rehearse`` as in ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--units", type=int, default=2)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench.lib import harness, names
    from bench.lib.control import brute_force
    if not args.rehearse:
        if jax.devices()[0].platform != "tpu":
            print("readings: no accelerator", file=sys.stderr)
            return 2
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.resolve_cell(bench, args.workload, args.rehearse)
    s = cell.config["search"]

    def control_rows(rec, rows):
        import numpy as np
        return brute_force(np.asarray(rec.points),
                           harness.queries_of(rec)[rows], s["radius"],
                           s["k"], "high")

    out = {"program": [], "control": []}
    for kind, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        for seed in seeds:
            loop = names.load_module("loops", cell.traffic["loop"]).Loop(
                cell.config, cell.traffic, seed)
            try:
                loop.setup()
                for _ in range(args.units):
                    loop.unit()
            finally:
                loop.close()
            notes = loop.notes()
            answers = (harness.program_rows if kind == "program"
                       else control_rows)
            checks, failed = harness.compare(loop, cell.config, seed,
                                             answers)
            row = {"kind": kind, "seed": seed, "units": args.units,
                   "failed": failed, "notes": notes,
                   **{k: c["value"] for k, c in checks.items()}}
            out[kind].append(row)
            print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "control": "high"}
    if out["program"]:
        summary["lower_d2_err_max"] = max(r["d2_err_max"]
                                          for r in out["program"])
        summary["program_wrong_rows"] = sum(r["wrong_rows"]
                                            for r in out["program"])
    if out["control"]:
        summary["upper_d2_err_max"] = min(r["d2_err_max"]
                                          for r in out["control"])
        summary["control_wrong_rows_min"] = min(r["wrong_rows"]
                                                for r in out["control"])
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
