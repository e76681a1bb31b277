"""Seconds of the program's `setup/agent` span: building the agent and its weights, the optimizer's state, their placement on the mesh, a checkpoint's restore where there is one. In the benchmark the weights are the harness's (`build_agent` is the adapter's), so this holds their fill."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _setup import inside, setup_spans  # noqa: E402


def read(run):
    got = setup_spans(run)
    spans = inside(got, "setup/agent") if got else []
    return sum(s["end"] - s["start"] for s in spans) if spans else None
