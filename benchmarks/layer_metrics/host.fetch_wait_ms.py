"""Median wait of the action fetch (device -> host) in the window."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import median_or_none, spans_in_window  # noqa: E402


def read(run):
    return median_or_none(spans_in_window(run, "fetch/player_actions"))
