"""Device self time of the toy policy's loss (scope `toy/policy`, forward and backward) per execution of the step."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _scopes import phase_ms  # noqa: E402


def read(run):
    return phase_ms(run, "toy/policy")
