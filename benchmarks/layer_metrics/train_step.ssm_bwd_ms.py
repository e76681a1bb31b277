"""Device self time of the Mamba layers' mixers (scope `lm/ssm`), backward pass (`transpose(jvp(...))`: the layer's and the chunks' rematerialised forward counts here), per execution of the train step."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _scopes import phase_ms  # noqa: E402


def read(run):
    return phase_ms(run, "lm/ssm", "bwd")
