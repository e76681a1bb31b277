"""The whole gradient step against the chip: operations the traced window's
gradient steps need (benchmarks/harness/flops.py, from the configuration's
widths) over traced seconds times the bf16 peak of the device kind."""
import jax

from benchmarks.harness import device, flops


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("gradient_steps") or not trace.get("window_s"):
        return None
    peak = device.peaks_for(jax.devices()[0].device_kind)["bf16_flops"] * trace["devices"]
    needed = flops.step_flops(run["cell"].config["model"])["total"] * trace["gradient_steps"]
    return 100.0 * needed / (trace["window_s"] * peak)
