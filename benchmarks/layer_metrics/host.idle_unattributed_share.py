"""Of the device's idle time in the traced window, the share that no leaf span of the program's loop thread covers (spans shifted onto the device's clock)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _scopes import idle_unattributed_share  # noqa: E402


def read(run):
    return idle_unattributed_share(run)
