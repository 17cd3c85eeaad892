"""Benchmark driver: one module per paper figure/table (DESIGN.md section 5
index) + the dry-run roofline table. Prints ``name,us_per_call,derived``
CSV rows, then a per-figure summary table (name, old_us, new_us, speedup)
so the BENCH_* deltas are reviewable without opening the JSON.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig11      # one figure
"""
import sys
import time

# per-module mapping of row keys onto the summary's (old, new) columns —
# modules whose run() returns {case: {key: value}} rows with an A/B pair
_SUMMARY_COLS = {
    "figtp": ("old_us", "new_us"),
    "figbatch": ("sequential_us_per_frame", "vmapped_us_per_frame"),
    "figdyn": ("rebuild_us_per_step", "session_us_per_step"),
    "figshard": ("single_us_per_step", "sharded_us_per_step"),
    "figserve": ("sequential_us_per_req", "serve_us_per_req"),
}


def _summarize(key: str, results) -> list[tuple]:
    """Rows (name, old_us, new_us, speedup) for the summary table."""
    if not isinstance(results, dict):
        return []
    old_key, new_key = _SUMMARY_COLS.get(key, (None, None))
    rows = []
    for case, row in sorted(results.items()):
        if not isinstance(row, dict):
            continue
        if old_key in row and new_key in row:
            old_us, new_us = float(row[old_key]), float(row[new_key])
            rows.append((f"{key}/{case}", old_us, new_us,
                         old_us / new_us if new_us else float("nan")))
            if row.get("pallas_traced_us"):
                rows.append((f"{key}/{case}/pallas-traced", old_us,
                             float(row["pallas_traced_us"]),
                             old_us / float(row["pallas_traced_us"])))
    return rows


def _print_summary(rows: list[tuple]) -> None:
    if not rows:
        return
    name_w = max(len(r[0]) for r in rows) + 2
    print("\n# ---- summary (old vs new, best-of timings) ----")
    print(f"# {'name':<{name_w}}{'old_us':>12}{'new_us':>12}{'speedup':>9}")
    for name, old_us, new_us, speedup in rows:
        print(f"# {name:<{name_w}}{old_us:>12.1f}{new_us:>12.1f}"
              f"{speedup:>8.2f}x")


def main() -> None:
    from repro.launch.cache import configure_compile_cache
    configure_compile_cache()
    from . import (fig05_coherence, fig07_aabb_width, fig11_speedup,
                   fig12_breakdown, fig13_ablation, fig14_sensitivity,
                   fig15_build_time, fig16_partition_dist, fig_batch,
                   fig_dynamic, fig_serve, fig_shard, fig_throughput,
                   roofline)
    modules = {
        "fig05": fig05_coherence, "fig07": fig07_aabb_width,
        "fig11": fig11_speedup, "fig12": fig12_breakdown,
        "fig13": fig13_ablation, "fig14": fig14_sensitivity,
        "fig15": fig15_build_time, "fig16": fig16_partition_dist,
        "figbatch": fig_batch, "figdyn": fig_dynamic,
        "figserve": fig_serve, "figshard": fig_shard,
        "figtp": fig_throughput, "roofline": roofline,
    }
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    summary = []
    for key, mod in modules.items():
        if only and not key.startswith(only):
            continue
        t0 = time.time()
        results = mod.run()
        print(f"# {key} done in {time.time() - t0:.1f}s")
        summary.extend(_summarize(key, results))
    _print_summary(summary)
    # the unified telemetry registry (executor / session / serve counters
    # and latency percentiles collected while the figures ran) follows the
    # ratio table — metrics record regardless of REPRO_TRACE, so the table
    # prints unconditionally
    from repro import obs
    from repro.obs import slo
    print()
    print(obs.summary())
    # ... and the per-tenant SLO table whenever serve figures ran (the
    # board has tenants exactly when a NeighborService resolved traffic)
    if slo.BOARD.tenants():
        print()
        print(slo.summary())


if __name__ == '__main__':
    main()
