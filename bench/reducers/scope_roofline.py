"""A kernel's share of its roofline, in percent: the least time any
search of the unit's points, queries and returned neighbours could take
(``bench/lib/roofline.py``) over the device time per unit in the
metric's ``scopes``."""
from bench.lib.roofline import least_seconds


def read(run, metric):
    if run.trace is None:
        return None
    busy = run.trace.scope_s(metric["scopes"])
    if not busy:
        return None
    n_points, n_queries, k = run.sizes
    least = least_seconds(n_points, n_queries, k, run.neighbours,
                          run.device_kind)
    return 100.0 * least / (busy / run.units)
