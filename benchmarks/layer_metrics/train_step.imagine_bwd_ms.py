"""Device self time of the imagination scan (scope `dv3/imagine`), backward pass, per execution of the train step: 0 where no gradient flows through the rollout (discrete actions)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _scopes import phase_ms  # noqa: E402


def read(run):
    return phase_ms(run, "dv3/imagine", "bwd")
