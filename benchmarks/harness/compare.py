"""What decides `correct`: the program's first three gradient steps (taken
through the window's own call and feed, at the timed sizes) against the plain
reference on the same weights, batches and noise.

Whatever is particular to a program family is asked of the configuration's
adapter (`harness/adapters/<family>.py`): its parameter groups (``GROUPS``,
one optimizer each), how the benchmark's weights become the reference's
(``reference_initial``, ``to_reference``), what a step of the reference is
given (``reference_inputs``, ``reference_step``), and the two optional
numbers of its own (``asked_again`` / ``MOVED``, ``acting_reference`` /
``ACTING``). The measures are here.

Numbers compared (each has a limit of its own in the cell's file):

- ``loss.<name>``: the widest gap of that loss over the three steps, as a
  share of the reference's loss;
- ``grad.<group>``: the first gradient as the optimizer got it (Adam's
  first moment after step one over 1 - b1): by the worst leaf, the gap between
  the program's norm and the reference's, against the reference's norm of that
  leaf or of the median leaf, whichever is larger;
- ``change.<group>``: the same measure for the parameters' change over the
  three steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone under Adam);
- ``direction.<group>``: the first gradient element by element: by the
  median leaf, the norm of the difference between the program's leaf and the
  reference's, against the reference's norm of that leaf or of the median
  leaf, whichever is larger. A gap of norms is blind to an error that lies
  across the gradient (it enters at second order); this sees it at first;
- the adapter's ``MOVED`` (if it has ``asked_again``): the same measure for
  how a first gradient moves when one column of the first batch is altered
  (``flipped``; the compiled step asked again once the window has closed, the
  reference likewise). Where columns do not meet inside the model, a sound
  step moves as the reference does; one that leaves columns out moves by
  nothing or by too much, and reads 1;
- the adapter's ``ACTING`` (if it has ``acting_reference``): the acting steps
  made before the first gradient step, on the benchmark's own weights: what
  the program's player produced against the reference's equations on the
  same inputs, widest gap of one element as a share of the largest element
  (no sum over rows in it: a lower precision shows);
- ``ratio_steps``: gradient steps the window ran less those its policy steps
  owe by the recipe (the adapter's ``gradient_steps_owed``), in steps.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np


def load_adapter(config: Dict[str, Any]) -> Any:
    return importlib.import_module("benchmarks.harness.adapters." + config["adapter"])


def load_reference(config: Dict[str, Any], precision: str = "highest") -> Any:
    """The configuration's plain reference, computing in ``precision``."""
    return importlib.import_module("benchmarks.reference." + config["reference"]).Reference(config["model"], precision)


def _norm(x: Any) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def _leaves(reference: Dict[str, Any], prefix: str) -> List[str]:
    names = [k for k in reference if k.startswith(prefix)]
    if not names:
        raise SystemExit(f"benchmark: the reference has no leaf under {prefix!r}, a group of the adapter's GROUPS")
    return names


def leaf_gaps(program: Dict[str, Any], reference: Dict[str, Any], prefix: str, keep: Optional[set] = None) -> Tuple[float, str]:
    """Worst leaf's |‖program‖ - ‖reference‖| over max(‖reference‖, median ‖reference‖)."""
    names = _leaves(reference, prefix)
    ref_norms = {k: _norm(reference[k]) for k in names}
    median = float(np.median(list(ref_norms.values())))
    worst, where = 0.0, ""
    for k in names:
        if keep is not None and k not in keep:
            continue
        gap = abs(_norm(program[k]) - ref_norms[k]) / max(ref_norms[k], median, 1e-30)
        if gap > worst:
            worst, where = gap, k
    return worst, where


def leaf_differences(program: Dict[str, Any], reference: Dict[str, Any], prefix: str) -> Tuple[float, str]:
    """Median leaf's ‖program - reference‖ over max(‖reference‖, median ‖reference‖)."""
    names = _leaves(reference, prefix)
    ref_norms = {k: _norm(reference[k]) for k in names}
    median = float(np.median(list(ref_norms.values())))
    shares = sorted(
        (_norm(np.asarray(program[k], np.float32) - reference[k]) / max(ref_norms[k], median, 1e-30), k) for k in names
    )
    return shares[len(shares) // 2]


def acting_steps(config: Dict[str, Any], params: Dict[str, Any], acted: List[Dict[str, Any]], precision: str = "highest"):
    """What the reference makes of each acting step the program made before
    its first gradient step (the adapter's ``acting_reference``, on the
    benchmark's weights: ``params`` is the reference run's ``initial``);
    None for an adapter that has none."""
    import jax

    reference = getattr(load_adapter(config), "acting_reference", None)
    if reference is None:
        return None
    with jax.default_matmul_precision("highest"):
        return reference(load_reference(config, precision), params, acted)


def reference_run(
    config: Dict[str, Any], captured: List[Dict[str, Any]], seed: int, precision: str = "highest", mutate=None, frozen: bool = False
):
    """Three steps of the plain reference on what the program's three were
    given. For `calibrate.py`, which puts the reference in the program's place:
    a lower ``precision`` (the control), ``mutate`` applied to every batch and
    its noise, or ``frozen``: each step returns its state unchanged."""
    import jax

    from benchmarks.harness import weights as weights_mod

    adapter = load_adapter(config)
    ask = getattr(adapter, "asked_again", None)
    shapes = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), captured[-1]["params"])
    params = adapter.to_reference(adapter.reference_initial(weights_mod.make_weights(shapes, seed)))
    ref = load_reference(config, precision)
    state = ref.init(params)
    losses, first_grads, moved = [], None, None
    with jax.default_matmul_precision("highest"):
        for i, step in enumerate(captured):
            batch, noise = adapter.reference_inputs(config, step)
            other = adapter.flipped(batch, adapter.flipped_column(seed, batch)) if i == 0 and ask else None
            if mutate is not None:
                other = mutate(other, noise)[0] if other is not None else None
                batch, noise = mutate(batch, noise)
            again = ask(ref, state, other, noise, step) if other is not None else None
            after, out = adapter.reference_step(ref, state, batch, noise, step)
            state = state if frozen else after
            losses.append({k: float(v) for k, v in out["losses"].items()})
            if i == 0:
                first_grads = {k: np.asarray(v) for k, v in out["grads"].items()}
                if again is not None:
                    moved = {k: np.asarray(v) - first_grads[k] for k, v in again.items()}
    final = {k: np.asarray(v) for k, v in state["params"].items()}
    start = {k: np.asarray(v) for k, v in params.items()}
    return {"losses": losses, "first_grads": first_grads, "moved": moved, "params": final, "initial": start}


def numbers(
    adapter: Any, program: Dict[str, Any], reference: Dict[str, Any], where: Optional[Dict[str, str]] = None
) -> Dict[str, float]:
    """The numbers compared, named by ``adapter`` (``GROUPS``, ``MOVED``,
    ``ACTING``); ``where`` (if given) is filled with the worst leaf of each."""
    out: Dict[str, float] = {}
    where = {} if where is None else where
    if reference.get("acting") and program.get("acting"):
        out[adapter.ACTING] = max(
            float(np.max(np.abs(np.asarray(p, np.float32) - r)) / np.max(np.abs(r)))
            for p, r in zip(program["acting"], reference["acting"])
        )
    for name in program["losses"][0]:
        out[f"loss.{name}"] = max(
            abs(p[name] - r[name]) / max(abs(r[name]), 1e-30) for p, r in zip(program["losses"], reference["losses"])
        )
    initial = reference["initial"]
    if reference.get("moved") and program.get("moved"):  # every leaf of what was asked again
        out[adapter.MOVED], where[adapter.MOVED] = leaf_differences(program["moved"], reference["moved"], "")
    for opt, prefix in adapter.GROUPS.items():
        out[f"grad.{opt}"], where[f"grad.{opt}"] = leaf_gaps(program["first_grads"], reference["first_grads"], prefix)
        out[f"direction.{opt}"], where[f"direction.{opt}"] = leaf_differences(
            program["first_grads"], reference["first_grads"], prefix)
        grad_norms = {k: _norm(v) for k, v in reference["first_grads"].items() if k.startswith(prefix)}
        floor = 1e-3 * float(np.median(list(grad_norms.values())))
        moving = {k for k, n in grad_norms.items() if n >= floor}
        prog_change = {k: program["params"][k] - initial[k] for k in moving}
        ref_change = {k: reference["params"][k] - initial[k] for k in grad_norms}
        out[f"change.{opt}"], where[f"change.{opt}"] = leaf_gaps(prog_change, ref_change, prefix, keep=moving)
    return out


def leaf_table(program: Dict[str, Any], reference: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """Every leaf's norms (first gradient, how it moved, and the three-step
    change; the reference's, the program's, and of their difference where one
    is compared): what `calibrate.py` prints, so that any statistic of the
    leaves can be looked at without another run."""
    initial = reference["initial"]
    table = {}
    for k, ref_grad in reference["first_grads"].items():
        table[k] = {
            "ref_grad": _norm(ref_grad),
            "grad": _norm(program["first_grads"][k]),
            "grad_diff": _norm(np.asarray(program["first_grads"][k], np.float32) - ref_grad),
            "ref_change": _norm(reference["params"][k] - initial[k]),
            "change": _norm(program["params"][k] - initial[k]),
        }
        if reference.get("moved") and program.get("moved") and k in reference["moved"]:
            table[k]["ref_moved"] = _norm(reference["moved"][k])
            table[k]["moved_diff"] = _norm(np.asarray(program["moved"][k], np.float32) - reference["moved"][k])
    return table


def judge(values: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, Dict[str, Dict[str, Any]]]:
    """Each number beside its limit; a number with no limit in the cell's file
    is shown and not compared. A value that is not finite fails, and so does
    a limit whose number the run did not produce."""
    shown: Dict[str, Dict[str, Any]] = {}
    correct = all(name in values for name in limits)
    for name, value in values.items():
        limit = limits.get(name)
        ok = bool(np.isfinite(value)) and (limit is None or value <= limit)
        correct = correct and ok
        shown[name] = {"value": float(value), "limit": limit}
    return correct, shown
