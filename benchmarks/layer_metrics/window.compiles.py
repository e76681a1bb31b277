"""Backend compiles that ended inside the measured window (expected: none)."""


def read(run):
    return float(run["compiles_in_window"])
