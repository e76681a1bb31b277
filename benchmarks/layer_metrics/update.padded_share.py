"""Share of the positions the gradient steps computed that are padding (left of a prompt, or past an episode's end): counters `ppo_lm/padded_tokens` over `ppo_lm/step_tokens`."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _counters import ratio  # noqa: E402


def read(run):
    return ratio(run, "ppo_lm/padded_tokens", "ppo_lm/step_tokens")
