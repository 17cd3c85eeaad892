"""Device idle milliseconds per unit inside the program, between
operations of the metric's ``scopes`` (``bench/lib/timeline.py``): 0.0
where those operations ran with no gap between them, None where none
ran. The five largest sums, keyed by the scopes before and after each
gap, go to standard error with the HLO operations that bracket them."""
import sys

from bench.lib import timeline


def read(run, metric):
    if run.trace is None:
        return None
    seconds, gaps = timeline.read_run(run.trace).scope_gaps(metric["scopes"])
    if seconds is None:
        return None
    by_pair: dict = {}
    for before, after, s, ops in gaps:
        entry = by_pair.setdefault((before, after), [0.0, 0, {}])
        entry[0] += s
        entry[1] += 1
        key = " | ".join(ops)
        entry[2][key] = entry[2].get(key, 0.0) + s
    top = sorted(by_pair.items(), key=lambda kv: -kv[1][0])[:5]
    for (before, after), (s, n, ops) in top:
        bracket = max(ops, key=ops.get)
        print(f"bench: idle in {'|'.join(metric['scopes'])}: "
              f"{1e3 * s / run.units:.4g} ms/unit in {n} gaps between "
              f"{before} and {after} (most: {bracket})", file=sys.stderr)
    return 1e3 * seconds / run.units
