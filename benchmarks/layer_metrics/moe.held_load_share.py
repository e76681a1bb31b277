"""Share of the router's (token, choice) slots that fell on an expert held here, over the run's gradient steps (counters `moe/held_slots` over `moe/routed_slots`); an even router gives held / n_routed_experts."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _counters import ratio  # noqa: E402


def read(run):
    return ratio(run, "moe/held_slots", "moe/routed_slots")
