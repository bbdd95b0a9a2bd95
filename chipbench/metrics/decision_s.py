"""Seconds the window took per completed capacity decision."""


def read(run):
    return run.window_s / len(run.records) if run.records else None
