"""Set-up: process start to the first timed unit, compiles included."""


def read(run):
    return run.setup_s
