"""Set-up seconds: process start to the first timed unit, compile
included."""


def read(run, metric):
    return run.setup_s
