"""Device self time of the layers' SwiGLU (pre-norm and three products; scope `lm/dense_mlp`, both directions) per execution of the train step."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _scopes import phase_ms  # noqa: E402


def read(run):
    return phase_ms(run, "lm/dense_mlp")
