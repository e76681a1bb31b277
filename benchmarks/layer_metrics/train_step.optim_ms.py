"""Device self time of clipping, the three optimizer updates and the target EMA (scope `dv3/optim`) per execution of the train step."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _scopes import phase_ms  # noqa: E402


def read(run):
    return phase_ms(run, "dv3/optim")
