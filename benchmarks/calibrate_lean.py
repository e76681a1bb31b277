#!/usr/bin/env python3
"""`calibrate.py` for a cell whose sides do not fit into the host's memory
beside the program's: the same run of the cell, the same sides in the same
order (`calibrate.sides`: the lower-precision control, half of every batch
left out, the state left unchanged), the same numbers and `judge` with the
cell's limits. What differs is what is kept: the program's side is let go
once its numbers are made, each side is let go before the next is built,
and no table of leaves is made. `--first N` stops after the first N sides
(a side costs a run of the reference with compiles of its own). `--moved-only`
makes of the control nothing but the adapter's `MOVED` (its first step on the
first minibatch and the same asked again on the altered one, as
`compare.reference_run` makes it: one step, no acting), which fits where the
whole control does not.

    python3 benchmarks/calibrate_lean.py --workload <name> --seed <n> [--seconds 5] [--first 1 | --moved-only]

Prints a verdict line per side on standard error and one JSON object as its
last line: every number of the program and of every side, and its verdict.
Not run by the benchmark.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def verdicts(cell, run, first: int = 3):
    """(name, numbers, correct, over) of the first ``first`` sides, each made,
    judged and let go before the next; ``run`` loses its program's side."""
    import jax

    from benchmarks import calibrate
    from benchmarks.harness import compare

    steps_owed = run["compared"]["ratio_steps"]["value"]
    # the sides read the captured steps' inputs and the reference's side, not the program's numbers
    run["program"] = None
    last = run["record"].captured[-1]
    last["params"] = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), last["params"])
    gc.collect()
    adapter = compare.load_adapter(cell.config)
    for name, other in itertools.islice(calibrate.sides(cell, run), first):
        values = compare.numbers(adapter, other, run["reference"])
        values["ratio_steps"] = steps_owed
        correct, shown = compare.judge(values, cell.limits)
        over = [f"{k} {v['value']:.4g} > {v['limit']}" for k, v in shown.items() if v["limit"] is not None and not v["value"] <= v["limit"]]
        other.clear()  # `sides` keeps its own name for what it yielded: empty it, so that the next side is built beside nothing
        gc.collect()
        yield name, values, correct, over


def moved_control(cell, run) -> float:
    """The control's `MOVED` alone, against the reference's of ``run``; ``run`` loses everything else."""
    import jax

    from benchmarks import calibrate
    from benchmarks.harness import compare

    adapter = compare.load_adapter(cell.config)
    batch, noise = adapter.reference_inputs(cell.config, run["record"].captured[0])
    other = adapter.flipped(batch, adapter.flipped_column(run["record"].seed, batch))
    step, moved, initial = run["record"].captured[0], run["reference"]["moved"], run["reference"]["initial"]
    run["reference"].clear()
    run.clear()
    gc.collect()
    control = compare.load_reference(cell.config, calibrate.BELOW[cell.config["model"]["compute_dtype"]])
    state = control.init(initial)
    with jax.default_matmul_precision("highest"):
        again = adapter.asked_again(control, state, other, noise, step)
        first = adapter.reference_step(control, state, batch, noise, step)[1]["grads"]
    return compare.leaf_differences({k: again.pop(k) - first.pop(k) for k in list(first)}, moved, "")[0]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--first", type=int, default=3, help="how many of calibrate.py's sides, in its order")
    parser.add_argument("--moved-only", action="store_true", help="of the control, the adapter's MOVED alone; no other side")
    args = parser.parse_args(argv)

    from benchmarks.harness import compare, device, manifest, runner

    cell = manifest.Cell(manifest.load_manifest(ROOT), args.workload, ROOT)
    device.require_chips(cell.chips)
    say = lambda line: print(line, file=sys.stderr, flush=True)  # noqa: E731
    run_dir = os.path.join(ROOT, "benchmarks", ".runs", cell.name + ".calibrate")
    run = runner.run_cell(cell, args.seed, args.seconds, False, STARTED, run_dir, say)
    out = {"workload": cell.name, "seed": args.seed, "limits": cell.limits, "readings": run["readings"],
           "program": {"correct": run["correct"], "numbers": {k: v["value"] for k, v in run["compared"].items()}}}
    say("numbers program: " + json.dumps(out["program"]["numbers"]))
    say(f"verdict program: correct {run['correct']}")
    if args.moved_only:
        name = compare.load_adapter(cell.config).MOVED
        value = moved_control(cell, run)
        out["control"] = {name: value}
        say(f"numbers control: {name} {value:.6g} (limit {cell.limits.get(name)})")
    for name, values, correct, over in () if args.moved_only else verdicts(cell, run, args.first):
        out[name] = {"correct": correct, "numbers": values}
        say(f"numbers {name}: " + json.dumps(values))
        say(f"verdict {name}: correct {correct}; over its limit: {', '.join(over) or 'nothing'}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
