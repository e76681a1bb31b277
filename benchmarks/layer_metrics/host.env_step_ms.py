"""Median `interaction/env_step/*` span of the loop thread that started in the traced window."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _scopes import span_ms  # noqa: E402
from _spans import median_or_none  # noqa: E402


def read(run):
    return median_or_none(span_ms(run, "interaction/env_step/") or [])
