"""The whole gradient step against the chip: operations the traced window's
gradient steps need (``step_flops(model)["total"]`` of the file the
configuration names, `benchmarks/flops/<flops>.py`, from its widths) over
traced seconds times the bf16 peak of the device kind."""
import importlib

import jax

from benchmarks.harness import device


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("gradient_steps") or not trace.get("window_s"):
        return None
    config = run["cell"].config
    flops = importlib.import_module("benchmarks.flops." + config["flops"])
    peak = device.peaks_for(jax.devices()[0].device_kind)["bf16_flops"] * trace["devices"]
    needed = flops.step_flops(config["model"])["total"] * trace["gradient_steps"]
    return 100.0 * needed / (trace["window_s"] * peak)
