"""Seconds of set-up under the program's `compile/trace` or `compile/lower` spans (nested traces of inner jits count once): what every process pays again, whatever the persistent compile cache holds."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _setup import inside, seconds_under, setup_spans  # noqa: E402


def read(run):
    got = setup_spans(run)
    if got is None:
        return None
    return seconds_under(inside(got, "compile/trace", "compile/lower"), got["origin"], got["end"])
