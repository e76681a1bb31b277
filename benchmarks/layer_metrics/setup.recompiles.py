"""Backend compiles in set-up of a function the process had compiled before (`compile/backend` spans with `seen` > 0): the donated-layout second compile of each donating program, and any other compile of a name seen before."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _setup import inside, setup_spans  # noqa: E402


def read(run):
    got = setup_spans(run)
    if got is None:
        return None
    return sum(1 for s in inside(got, "compile/backend") if s["args"].get("seen", 0) > 0)
