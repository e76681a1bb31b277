"""Device self time of dispatch, the grouped products over the held experts and combine (scope lm/moe_experts) per execution of the train step."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _scopes import phase_ms  # noqa: E402


def read(run):
    return phase_ms(run, "lm/moe_experts")
