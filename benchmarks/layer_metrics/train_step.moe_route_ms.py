"""Device self time of the expert layers' pre-norm, router scores, top-k and the sort by expert (scope lm/moe_route) per execution of the train step."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _scopes import phase_ms  # noqa: E402


def read(run):
    return phase_ms(run, "lm/moe_route")
