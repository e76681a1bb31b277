"""Device self time of the dynamics-learning scan (scope `dv3/rssm`), backward pass (`transpose(jvp(...))`), per execution of the train step."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _scopes import phase_ms  # noqa: E402


def read(run):
    return phase_ms(run, "dv3/rssm", "bwd")
