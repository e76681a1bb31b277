#!/usr/bin/env python3
"""The readings that the limits of `correct` are set from, for one cell and
seed, in one process on the chip. One path: a run of the cell as `run.py`
makes it, then the reference put in the program's place three times: as the
control (one precision below the configuration's), with half of every batch
left out (the mean taken over the rest), and with every step returning its
state unchanged; each through the harness's own comparison and `judge` with
the cell's limits.

    python3 benchmarks/calibrate.py --workload <name> --seed <n> [--seconds 3]

Prints a verdict line per side on standard error and one JSON object as its
last line: every number of every side, its verdict, and every leaf's norms.
The control and the faults have to come out as not correct. Not run by the
benchmark.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: The nearest precision below the one the configuration computes in.
BELOW = {"float32": "bf16", "bfloat16": "fp8"}


def sides(cell, run):
    """(name, what stands in the program's place) for the control and the faults."""
    from benchmarks.harness import compare

    config, reference = cell.config, run["reference"]
    captured, seed = run["record"].captured, run["record"].seed
    below = BELOW[config["model"]["compute_dtype"]]
    control = compare.reference_run(config, captured, seed, precision=below)
    control["acting"] = compare.acting_steps(config, reference["initial"], run["acted"], below)
    yield "control_" + below, control
    half = compare.load_adapter(config).half_of_the_batch(config["model"])
    fault = compare.reference_run(config, captured, seed, mutate=half)
    fault["acting"] = reference["acting"]  # the fault is in the train step: the player acts as it did
    yield "half_batch", fault
    fault = compare.reference_run(config, captured, seed, frozen=True)
    fault["acting"] = reference["acting"]
    yield "state_unchanged", fault


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=3.0)
    args = parser.parse_args(argv)

    from benchmarks.harness import compare, device, manifest, runner

    cell = manifest.Cell(manifest.load_manifest(ROOT), args.workload, ROOT)
    device.require_chips(cell.chips)
    say = lambda line: print(line, file=sys.stderr, flush=True)  # noqa: E731
    run_dir = os.path.join(ROOT, "benchmarks", ".runs", cell.name + ".calibrate")
    run = runner.run_cell(cell, args.seed, args.seconds, False, STARTED, run_dir, say)
    out = {"workload": cell.name, "seed": args.seed, "limits": cell.limits, "readings": run["readings"],
           "program": {"correct": run["correct"], "numbers": {k: v["value"] for k, v in run["compared"].items()},
                       "losses": run["program"]["losses"], "leaves": compare.leaf_table(run["program"], run["reference"])},
           "reference": {"losses": run["reference"]["losses"]}}
    steps_owed = run["compared"]["ratio_steps"]["value"]
    say("numbers program: " + json.dumps(out["program"]["numbers"]))
    adapter = compare.load_adapter(cell.config)
    for name, other in sides(cell, run):
        values = compare.numbers(adapter, other, run["reference"])
        values["ratio_steps"] = steps_owed
        correct, shown = compare.judge(values, cell.limits)
        out[name] = {"correct": correct, "numbers": values, "losses": other["losses"],
                     "leaves": compare.leaf_table(other, run["reference"])}
        over = [f"{k} {v['value']:.4g} > {v['limit']}" for k, v in shown.items() if v["limit"] is not None and not v["value"] <= v["limit"]]
        say(f"numbers {name}: " + json.dumps(values))
        say(f"verdict {name}: correct {correct}; over its limit: {', '.join(over) or 'nothing'}")
    say(f"verdict program: correct {run['correct']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
