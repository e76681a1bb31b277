"""What the loop waited for its batches per gradient step: loop-thread time inside `infeed/take` and `transfer/h2d_sync` (not what the worker's `transfer/h2d_stage` hid)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _scopes import per_gradient_step, span_ms  # noqa: E402


def read(run):
    return per_gradient_step(run, span_ms(run, "infeed/take", "transfer/h2d_sync"))
