"""Queries answered per second: the queries of the units completed in
the timed window (their number times the mean of one unit) over the
window."""


def read(run, metric):
    return run.units * run.sizes[1] / run.window_s
