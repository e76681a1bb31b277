#!/usr/bin/env python3
"""The benchmark's command: one process, one cell, one result line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs the cell's chips (exit 3 and no result line otherwise). The last line of
standard output is one JSON object: correct, attempted, failed, metrics,
device, with --trace 1 breakdown, and last `compared`: every number that
decided `correct` beside its limit (also the last lines of standard error).
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()  # set-up is counted from here: imports included

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(line: str) -> None:
    print(line, file=sys.stderr, flush=True)


def metrics_of(cell, run, trace: bool, root: str = ROOT) -> dict:
    """The cell's metrics of one run: end to end from the runner's readings,
    or per layer, each from its own reader; a reader that finds nothing to
    read returns None and its metric is left out."""
    from benchmarks.harness import manifest

    metrics = {}
    for metric in cell.per_layer() if trace else cell.end_to_end():
        if trace:
            value = manifest.load_reader(metric["name"], root)(run)
        else:
            value = run["readings"].get(metric["name"])
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return metrics


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmarks.harness import device, manifest, runner

    bench = manifest.load_manifest(ROOT)
    cell = manifest.Cell(bench, args.workload, ROOT)
    report = device.require_chips(cell.chips)
    # The program keeps JAX's persistent compile cache where
    # JAX_COMPILATION_CACHE_DIR says, else at <checkout>/.jax_cache.
    run_dir = os.path.join(ROOT, "benchmarks", ".runs", cell.name)
    run = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace), STARTED, run_dir, say)

    result = {
        "correct": bool(run["correct"]),
        "attempted": run["window"].env_steps(),
        "failed": 0,
        "metrics": metrics_of(cell, run, bool(args.trace)),
        "device": dict(report, memory_peak_bytes=int(run["readings"]["peak_hbm_gib"] * 2**30)),
    }
    if args.trace and run["trace"]:
        trace = run["trace"]
        result["device"]["busy_s"] = trace["busy_s"]
        result["device"]["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
    result["compared"] = run["compared"]
    for name, entry in run["compared"].items():
        say(f"compared {name}: {entry['value']:.6g} limit {entry['limit']}")
    say(f"correct: {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
