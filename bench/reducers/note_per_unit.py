"""A count of the window that the loop notes (``LoopBase.notes``), per
unit; None where the loop notes no such count."""


def read(run, metric):
    value = run.notes.get(metric["note"])
    return None if value is None else value / run.units
