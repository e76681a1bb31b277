"""Device self time of pre-norm and differential attention over the whole context (scope `lm/full_attn`: the layer whose keys and values the cross layers read; both directions) per execution of the train step."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _scopes import phase_ms  # noqa: E402


def read(run):
    return phase_ms(run, "lm/full_attn")
