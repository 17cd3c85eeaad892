"""Device milliseconds per unit in the operations whose scope holds one
of the metric's ``scopes``; None where none does."""


def read(run, metric):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(metric["scopes"])
    return None if seconds is None else 1e3 * seconds / run.units
