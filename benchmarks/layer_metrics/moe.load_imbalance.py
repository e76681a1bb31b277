"""The fullest held expert's tokens over the mean held expert's, over the run's gradient steps: the sum of each step's largest
group (counter `moe/max_expert_tokens`, over layers and experts) over the sum of each step's mean group (`moe/held_slots` over
expert layers x experts held, from the configuration's file)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _counters import counter  # noqa: E402


def read(run):
    largest, held = counter(run, "moe/max_expert_tokens"), counter(run, "moe/held_slots")
    if largest is None or not held:
        return None
    model = run["cell"].config["model"]
    groups = (model["num_hidden_layers"] - model["first_k_dense_replace"]) * model["experts_held"][1]
    return largest * groups / held
