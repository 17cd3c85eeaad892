"""Share of the traced window in which no operation ran on the device,
in percent."""


def read(run, metric):
    if run.trace is None or not run.trace.devices():
        return None
    return 100.0 * run.trace.idle_share()
