"""Of the program's part of set-up (its `setup` root's start to set-up's end), the share under no span of the loop thread but the root and the `loop/iteration` spans, which only hold the others: the set-up analogue of `host.idle_unattributed_share`."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _setup import setup_spans, uncovered  # noqa: E402


def read(run):
    got = setup_spans(run)
    if got is None:
        return None
    program = got["end"] - got["root"]["start"]
    return 100.0 * sum(hi - lo for lo, hi in uncovered(got)) / program if program > 0 else None
