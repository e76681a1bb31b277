"""The train step's enqueue on the loop thread (`train/dispatch` spans of the traced window) per gradient step."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _scopes import per_gradient_step, span_ms  # noqa: E402


def read(run):
    return per_gradient_step(run, span_ms(run, "train/dispatch"))
