"""Seconds per unit: the timed window over the whole frames or steps
completed in it."""


def read(run, metric):
    return run.window_s / run.units
