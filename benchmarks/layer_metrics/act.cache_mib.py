"""The player's cache by the program's own count, MiB: the gauges `player/cache_bytes/window` (the window layers' rings), `/full` (rows of the whole context) and `/state` (recurrent state) summed; a diagnostic of what the three kinds of state hold."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _counters import counter  # noqa: E402


def read(run):
    found = [counter(run, "player/cache_bytes/" + kind) for kind in ("window", "full", "state")]
    found = [value for value in found if value is not None]
    return sum(found) / 2**20 if found else None
