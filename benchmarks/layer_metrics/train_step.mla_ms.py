"""Device self time of pre-norm and latent attention (projections, RoPE, scores, output projection; scope lm/mla) per execution of the train step."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _scopes import phase_ms  # noqa: E402


def read(run):
    return phase_ms(run, "lm/mla")
