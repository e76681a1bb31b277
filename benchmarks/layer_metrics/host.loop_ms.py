"""Host work per iteration: the window's wall time less its action-fetch waits
and train enqueues, over its iterations (env step, buffer write, player dispatch)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import spans_in_window  # noqa: E402


def read(run):
    fetch = spans_in_window(run, "fetch/player_actions")
    if not fetch:
        return None
    window = run["window"]
    waits = sum(fetch) + sum(spans_in_window(run, "train/dispatch"))
    return (window.elapsed * 1e3 - waits) / len(window.iteration_ms())
