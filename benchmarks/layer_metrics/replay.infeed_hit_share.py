"""Share of the traced window's `infeed/take` spans whose batches were already
staged (`hit` in the span's args)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _scopes import program_spans  # noqa: E402


def read(run):
    takes = program_spans(run, "infeed/take")
    if not takes:
        return None
    return 100.0 * sum(bool(s["args"].get("hit")) for s in takes) / len(takes)
