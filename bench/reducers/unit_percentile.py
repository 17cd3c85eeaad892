"""A percentile of the window's unit times, in ms: the metric's
``percentile`` by the nearest rank, so the reading is one unit's own
time. Where fewer than ten units lie beyond it, the tail is thin, and
standard error says so."""
import math
import sys


def read(run, metric):
    if not run.unit_s:
        return None
    p = float(metric["percentile"])
    times = sorted(run.unit_s)
    rank = max(1, math.ceil(p / 100.0 * len(times)))
    beyond = len(times) - rank
    if beyond < 10:
        print(f"bench: p{p:g} of {len(times)} units has {beyond} beyond it",
              file=sys.stderr)
    return 1e3 * times[rank - 1]
