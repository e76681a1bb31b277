"""Host replay sampling and host-to-device transfer per gradient step."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import spans_in_window  # noqa: E402


def read(run):
    spent = spans_in_window(run, "replay/sample") + spans_in_window(run, "transfer/h2d_")
    if not spent:
        return None
    steps = run["window"].gradient_steps()
    return sum(spent) / steps if steps else None
