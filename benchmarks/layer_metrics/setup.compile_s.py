"""Seconds the XLA backend spent compiling before the window opened."""


def read(run):
    return run["compile_seconds_setup"]
