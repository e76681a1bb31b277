"""Device self time of the cross-attention layers (pre-norm, query and output projections, attention over the full layer's keys and values; scope `lm/cross_attn`, both directions) per execution of the train step."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _scopes import phase_ms  # noqa: E402


def read(run):
    return phase_ms(run, "lm/cross_attn")
