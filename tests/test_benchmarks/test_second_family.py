"""A configuration is files and entries: a second program family, of another
shape on purpose (fixtures/toy_family: on-policy bursts, one parameter group,
no target network, nothing recurrent, scopes `toy/*`), goes through the
harness from new files only. The test writes over no file of the checkout and
patches no attribute of `compare`, `runner`, `manifest`, `_scopes` or `run`:
it puts the files where the harness looks (a temporary root for the data
files and the readers, `sys.modules` for the adapter, the reference and the
operation count) and adds the CPU to the table of peaks.
"""

import importlib.util
import json
import os
import re
import shutil
import sys
import time

import pytest

from conftest import ROOT
from benchmarks.harness import compare, device, manifest, runner, tracing

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "toy_family")
LAYER_METRICS = os.path.join(ROOT, "benchmarks", "layer_metrics")
READERS = ["train_step.device_ms", "train_step.mfu", "train_step.unscoped_share"]  # what every training cell reads
PEAK = 1e9  # operations per second of the stand-in "chip"


def load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """A root that holds what a `model_config` PR would add: the manifest's
    entries, the configuration's, the traffic's and the cell's files, one
    reader of its own beside the benchmark's."""
    root = tmp_path_factory.mktemp("toy_root")
    bench = json.loads(json.dumps(manifest.load_manifest(ROOT)))
    bench["configs"] = [{"name": "toy_pg", "source": "tests/test_benchmarks/fixtures/toy_family", "reduced": [],
                         "file": "benchmarks/configs/toy_pg.json", "why": "a second family, for the harness's own tests"}]
    bench["workloads"] = [{"name": "toy_pg.bursts", "config": "toy_pg", "traffic": "bursts", "chips": 1,
                           "why": "rollouts of 8 policy steps, then 2 gradient steps: gradient steps come in bursts"}]
    bench["per_layer"] = [m for m in bench["per_layer"] if m["name"] in READERS and "workloads" not in m]
    assert [m["name"] for m in bench["per_layer"]] == READERS  # read in every training cell: no list to extend
    bench["per_layer"].append({"name": "train_step.toy_policy_ms", "unit": "ms", "better": "lower", "source": "device_trace",
                               "layer": "train step", "moves": "env_steps_per_s", "workloads": ["toy_pg.bursts"]})
    for sub, src, dst in (("configs", "config.json", "toy_pg.json"), ("traffic", "traffic.json", "bursts.json"),
                          ("cells", "cell.json", "toy_pg.bursts.json"),
                          ("layer_metrics", "train_step.toy_policy_ms.py", "train_step.toy_policy_ms.py")):
        os.makedirs(root / "benchmarks" / sub, exist_ok=True)
        shutil.copy(os.path.join(TOY, src), root / "benchmarks" / sub / dst)
    for name in READERS + ["_scopes"]:
        shutil.copy(os.path.join(LAYER_METRICS, name + ".py"), root / "benchmarks" / "layer_metrics")
    with open(root / "BENCHMARK.json", "w") as fp:
        json.dump(bench, fp)
    return str(root)


@pytest.fixture(scope="module")
def toy_modules():
    """The family's code, found by the names the configuration's file gives."""
    names = {"benchmarks.harness.adapters.toy": "adapter.py", "benchmarks.reference.toy": "reference.py",
             "benchmarks.flops.toy": "flops.py"}
    assert not set(names) & set(sys.modules)
    sys.modules.update({name: load(name, os.path.join(TOY, path)) for name, path in names.items()})
    yield
    for name in names:
        del sys.modules[name]


@pytest.fixture(scope="module")
def toy_cell(toy_root, toy_modules):
    bench = manifest.load_manifest(toy_root)
    assert manifest.validate(bench, toy_root) == []
    return manifest.Cell(bench, "toy_pg.bursts", toy_root)


@pytest.fixture(scope="module")
def toy_run(toy_cell, tmp_path_factory):
    log = []
    run = runner.run_cell(toy_cell, 2**31 + 5, 0.5, False, time.perf_counter(), str(tmp_path_factory.mktemp("toy_run")), log.append)
    run["log"] = log
    return run


def test_the_window_closes_on_a_program_with_no_prefill_and_no_ratio(toy_run):
    window = toy_run["window"]
    assert window.phase == "closed" and window.elapsed >= 0.5
    assert window.train_steps[0] >= 3 and window.policy_steps[0] >= 16  # the adapter's warm_policy_steps
    assert set(toy_run["readings"]) == {"env_steps_per_s", "iter_p95_ms", "peak_hbm_gib", "setup_s"}


def test_correct_is_true_in_float32(toy_run, toy_cell):
    assert toy_run["correct"] is True
    compared = toy_run["compared"]
    assert set(toy_cell.limits) <= set(compared)
    # one group, no number of the first family's own
    assert sorted(compared) == ["change.policy", "direction.policy", "grad.policy", "loss.policy", "ratio_steps"]
    assert all(compared[k]["value"] <= 2e-4 for k in compared if k != "ratio_steps")


def test_ratio_steps_is_held_in_bursts(toy_run):
    window, mix = toy_run["window"], toy_run["cell"].traffic["overrides"]
    made = [b - a for a, b in zip(window.train_steps, window.train_steps[1:])]
    assert set(made) == {0, mix["update_epochs"]}  # nothing at most boundaries, a whole burst at the others
    bursts = [i for i, n in enumerate(made) if n]
    assert {b - a for a, b in zip(bursts, bursts[1:])} == {mix["rollout_steps"]}
    assert toy_run["compared"]["ratio_steps"]["value"] <= mix["update_epochs"] == toy_run["compared"]["ratio_steps"]["limit"]


def test_the_bfloat16_control_and_the_faults_are_not_correct(toy_run, toy_cell):
    """`calibrate.py`'s sides through the same adapter calls: the reference in
    the program's place one precision down, with half of the rollout left out,
    and with every step returning its state unchanged."""
    from benchmarks import calibrate

    adapter = compare.load_adapter(toy_cell.config)
    verdicts = {}
    for name, other in calibrate.sides(toy_cell, toy_run):
        values = dict(compare.numbers(adapter, other, toy_run["reference"]), ratio_steps=0.0)
        verdicts[name] = compare.judge(values, toy_cell.limits)
    assert sorted(verdicts) == ["control_bf16", "half_batch", "state_unchanged"]
    assert not any(correct for correct, _ in verdicts.values())
    assert verdicts["control_bf16"][1]["grad.policy"]["value"] > 10 * toy_run["compared"]["grad.policy"]["value"]
    assert verdicts["state_unchanged"][1]["change.policy"]["value"] == pytest.approx(1.0, abs=1e-6)


def test_a_group_without_leaves_says_so(toy_run, toy_cell):
    import types

    adapter = compare.load_adapter(toy_cell.config)
    other = types.SimpleNamespace(GROUPS={"policy": "pi/", "critic": "critic/"})
    with pytest.raises(SystemExit, match="no leaf under 'critic/'"):
        compare.numbers(other, adapter.program_numbers(toy_run["record"].captured), toy_run["reference"])


@pytest.fixture(scope="module")
def traced(toy_run, toy_root, tmp_path_factory):
    """The run as a traced run hands it to the readers, the device's part from a recorded trace."""
    made = load("toy_make_xplane", os.path.join(TOY, "make_xplane.py"))
    run_dir = tmp_path_factory.mktemp("toy_traced")
    where = run_dir / "xla_trace" / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(TOY, "toy.xplane.pb"), where / "host.xplane.pb")
    reduced = tracing.reduce_planes(tracing.load_planes(str(where / "host.xplane.pb")))
    return dict(toy_run, run_dir=str(run_dir), trace=dict(reduced, gradient_steps=made.STEPS)), made


def test_the_metric_loop_reads_the_second_family(traced, toy_cell, toy_root, monkeypatch):
    import jax

    from benchmarks import run as run_py

    run, made = traced
    monkeypatch.setitem(device.PEAKS, jax.devices()[0].device_kind, {"bf16_flops": PEAK})
    metrics = {k: v["value"] for k, v in run_py.metrics_of(toy_cell, run, True, toy_root).items()}
    assert sorted(metrics) == sorted(READERS + ["train_step.toy_policy_ms"])
    assert metrics["train_step.device_ms"] == pytest.approx(0.41)
    # the operation count is the configuration's own: benchmarks/flops/<config["flops"]>.py
    model = toy_cell.config["model"]
    count = 3 * 2 * model["rows"] * (model["obs"] * model["hidden"] + model["hidden"] * model["actions"])
    assert metrics["train_step.mfu"] == pytest.approx(100 * count * made.STEPS / (made.WINDOW_US * 1e-6 * PEAK))
    assert metrics["train_step.toy_policy_ms"] == pytest.approx(0.27)
    assert metrics["train_step.unscoped_share"] == pytest.approx(100 * 30 / 400)
    # end to end: the runner's readings under the manifest's names
    assert set(run_py.metrics_of(toy_cell, run, False, toy_root)) == {m["name"] for m in toy_cell.end_to_end()}


def test_a_phase_of_the_second_family_reads_through_the_shared_reduction(traced):
    sys.path.insert(0, LAYER_METRICS)
    import _scopes

    run, made = traced
    assert _scopes.phase_ms(run, "toy/policy", "fwd") == pytest.approx(0.15)
    assert _scopes.phase_ms(run, "toy/policy", "bwd") == pytest.approx(0.12)
    assert _scopes.phase_ms(run, "toy/optim") == pytest.approx(0.10)
    got = _scopes.scopes_of(run)
    assert {k: round(v * 1e6) for k, v in got["by_scope"].items()} == {k: made.STEPS * v for k, v in made.SELF_US.items()}
    # the same trace under the first family's list names no scope of that step: nothing, never a 0
    first = manifest.load_json(os.path.join(ROOT, "benchmarks", "configs", "dreamer_v3_XL.json"))["program"]
    other = dict(run, cell=type("Cell", (), {"config": {"program": dict(first, train_modules=["toy_step"])}}))
    assert _scopes.phase_ms(other, "toy/policy") is None and _scopes.unscoped_share(other) is None


FAMILY = re.compile(r"dv3|dreamer|wm/|world_model|target_critic|player_recurrent|replay_ratio|learning_starts", re.IGNORECASE)


def test_harness_names_no_family():
    """What names a family lives in its adapter, its reference, its operation
    count, its readers and its configuration's files; not in these."""
    bench = os.path.join(ROOT, "benchmarks")
    files = [os.path.join(bench, "harness", name) for name in sorted(os.listdir(os.path.join(bench, "harness"))) if name.endswith(".py")]
    files += [os.path.join(bench, name) for name in ("run.py", "calibrate.py")]
    files += [os.path.join(LAYER_METRICS, name) for name in ("_scopes.py", "_spans.py", "train_step.mfu.py")]
    assert len(files) >= 11
    found = []
    for path in files:
        with open(path) as fp:
            found += [f"{os.path.relpath(path, ROOT)}:{n}: {line.strip()}" for n, line in enumerate(fp, 1) if FAMILY.search(line)]
    assert found == []
