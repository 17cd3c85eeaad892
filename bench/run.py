"""The benchmark: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; everything it
uses is found by name (``bench/lib/names.py``). A run builds its scene from
``--seed``, warms up the cell's own programs (set-up), then times whole
units (frames or steps) back to back until ``--seconds`` have passed. With
``--trace 1`` it instead traces ``trace_units`` units under the profiler
and reports the per-layer metrics read from that trace. Either way it
then compares a sample of the window's results, drawn from the seed, with
the float64 reference, and prints one JSON object as the last line of
standard output, and the numbers compared beside their limits as the last
lines of standard error.

It runs only on the accelerator: where JAX finds none, or fewer chips than
the cell asks for, it exits nonzero and prints no result. ``--rehearse``
runs the cell at its configuration's small rehearsal size on whatever
backend JAX has; it is for the benchmark's own tests and never measures.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="small rehearsal size on any backend (tests)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    bench_file = ROOT / "BENCHMARK.json"
    if not bench_file.is_file():
        return fail(f"{bench_file.name} is missing")
    from bench.lib import harness
    bench = json.loads(bench_file.read_text())
    try:
        cell = harness.resolve_cell(bench, args.workload, args.rehearse)
    except (KeyError, FileNotFoundError) as e:
        return fail(str(e))

    if not args.rehearse:
        # the compile cache sits at a fixed path inside the checkout, set
        # before JAX starts, and holds every program, so that a cell's
        # second run compiles none; the program's own entry points take
        # the directory given here
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    if not args.rehearse:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if not args.rehearse:
        if devices[0].platform != "tpu":
            return fail(f"no accelerator: JAX found {devices[0].platform!r} "
                        f"devices")
        if len(devices) < cell.chips:
            return fail(f"{args.workload} needs {cell.chips} chips, JAX "
                        f"found {len(devices)}")
    try:
        import repro  # noqa: F401
    except ImportError as e:
        return fail(f"the system under test is missing: {e}")

    result, checks = harness.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START, trace_dir=TRACE_DIR, devices=devices[:cell.chips])
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
