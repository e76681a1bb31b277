"""The harness end to end at micro widths on the CPU, the look for a chip
skipped: window edges, the preemption stop, nothing saved, the comparison
with the plain reference, and `correct` coming out false for a lower
precision and for each fault a training cell can have."""

import glob
import os

import numpy as np
import pytest

from conftest import MICRO_LIMITS, micro_cell

LOSSES_AND_NORMS = [k for k in MICRO_LIMITS if k != "ratio_steps"]


@pytest.fixture(scope="module")
def host_run(run_micro):
    return run_micro(micro_cell("dreamer_v3_XL", "crafter_host"), seed=2**31 + 11)


@pytest.fixture(scope="module")
def ring_run(run_micro):
    return run_micro(micro_cell("dreamer_v3_XL", "crafter_host", ring=True), seed=13, trace=True)


# ------------------------------------------------------------------ control flow
def test_window_edges(host_run):
    window = host_run["window"]
    assert window.phase == "closed"
    assert window.elapsed >= 1.0
    assert all(b > a for a, b in zip(window.edges, window.edges[1:]))
    # every iteration of the window is one policy step of the one env
    assert window.env_steps() == len(window.iteration_ms())
    assert sum(window.iteration_ms()) == pytest.approx(window.elapsed * 1e3)
    # set-up ended only after the first three gradient steps and the warm-up
    assert window.train_steps[0] >= 3 and window.policy_steps[0] >= 16 + 6


def test_readings(host_run):
    readings = host_run["readings"]
    assert set(readings) == {"env_steps_per_s", "iter_p95_ms", "peak_hbm_gib", "setup_s"}
    assert readings["env_steps_per_s"] == pytest.approx(host_run["window"].env_steps() / host_run["window"].elapsed)
    assert readings["setup_s"] > 0 and readings["iter_p95_ms"] > 0


def test_recipe_ratio_is_held(host_run):
    window = host_run["window"]
    assert abs(window.gradient_steps() - 0.5 * window.env_steps()) <= 1
    assert host_run["compared"]["ratio_steps"]["value"] <= 1


def test_preempted_and_nothing_saved(host_run):
    assert not glob.glob(os.path.join(host_run["run_dir"], "**", "*.ckpt"), recursive=True)
    assert not glob.glob(os.path.join(host_run["run_dir"], "**", "autoresume.json"), recursive=True)


def test_percentile_is_nearest_rank():
    from benchmarks.harness.runner import percentile

    values = list(range(1, 101))
    assert percentile(values, 95) == 95
    assert percentile([5.0], 95) == 5.0
    assert percentile([1, 2, 3, 4], 50) == 2


# ------------------------------------------------------------------ the comparison
@pytest.mark.parametrize("number", LOSSES_AND_NORMS)
def test_program_agrees_with_the_reference_in_float32(host_run, number):
    entry = host_run["compared"][number]
    assert entry["value"] <= entry["limit"] == MICRO_LIMITS[number]


def test_correct_is_true_in_float32(host_run, ring_run):
    assert host_run["correct"] is True
    assert ring_run["correct"] is True


def test_acting_steps_before_the_first_update_are_kept(host_run, ring_run):
    # replay ratio 0.5: the player acts twice on the benchmark's weights before the first gradient step; ratio 1: never
    assert len(host_run["acted"]) == 2 and len(ring_run["acted"]) == 0
    step = host_run["acted"][0]
    assert step["h"].shape == step["h_new"].shape and (step["h"] != step["h_new"]).any()
    assert "player.recurrent" not in ring_run["compared"]


def test_the_step_asked_again_repeats_itself(host_run):
    """`Record.sensitivity` makes the first step again from the seed: on the
    unchanged batch the compiled step has to give the first moment it gave
    the program (so the weights, the zeroed optimizer state and the key are
    the program's own), and the inverted column has to move it."""
    moved = host_run["program"]["moved"]
    assert set(moved) == {k for k in host_run["reference"]["first_grads"] if k.startswith("wm/")}
    assert all(np.isfinite(v).all() for v in moved.values())
    assert max(float(np.abs(v).max()) for v in moved.values()) > 0
    assert host_run["compared"]["moved.world_model"]["value"] <= MICRO_LIMITS["moved.world_model"]


def test_ring_steps_sample_inside_the_jit(ring_run):
    record = ring_run["record"]
    assert record.fused_calls == record.calls > 3
    assert all(step["fused"] for step in record.captured)


def test_traced_run_reads_what_it_can(ring_run):
    """On the CPU no operation runs on a device plane: the trace readers
    return nothing (never a 0), the span readers read the program's spans."""
    from conftest import ROOT
    from benchmarks.harness import manifest

    assert ring_run["spans"], "the traced run turns the program's telemetry on"
    assert manifest.load_reader("device.idle_share", ROOT)(ring_run) is None
    assert manifest.load_reader("train_step.mfu", ROOT)(ring_run) is None
    assert manifest.load_reader("host.fetch_wait_ms", ROOT)(ring_run) > 0
    assert manifest.load_reader("host.loop_ms", ROOT)(ring_run) > 0
    assert manifest.load_reader("replay.host_sample_ms", ROOT)(ring_run) is None


def test_a_bfloat16_program_fails_the_float32_comparison(run_micro):
    """The program's own lower-precision path, switched on, is the control:
    the same limits that float32 meets, a bf16-mixed run does not."""
    cell = micro_cell("dreamer_v3_XL", "crafter_host", precision="bf16-mixed")
    cell.config["model"]["compute_dtype"] = "bfloat16"
    run = run_micro(cell, seed=17)
    assert run["correct"] is False


@pytest.mark.parametrize("precision", ["bf16", "fp8"])
def test_a_lower_precision_reference_fails(host_run, precision):
    """The control: the reference in the program's place, one precision down."""
    from benchmarks.harness import compare

    cell = micro_cell("dreamer_v3_XL", "crafter_host")
    captured = host_run["record"].captured
    seed = host_run["record"].seed
    reference = dict(host_run["reference"])
    control = compare.reference_run(cell.config, captured, seed, precision=precision)
    control["acting"] = compare.acting_steps(cell.config, reference["initial"], host_run["acted"], precision)
    values = compare.numbers(compare.load_adapter(cell.config), control, reference)
    correct, _ = compare.judge(values, MICRO_LIMITS)
    assert correct is False
    assert max(values.values()) > 10 * max(host_run["compared"][k]["value"] for k in LOSSES_AND_NORMS)
    # the acting step has no sum over rows in it: there the lower precision shows on its own
    assert values["player.recurrent"] > 100 * host_run["compared"]["player.recurrent"]["value"]


# ------------------------------------------------------------------ faults
def unchanged_state(fn, state, opt_states, moments, data, key, tau):
    import jax
    import jax.numpy as jnp

    kept = jax.tree_util.tree_map(jnp.copy, state)
    out = fn(state, opt_states, moments, data, key, tau)
    return (kept,) + tuple(out[1:])


def half_batch(fn, state, opt_states, moments, data, key, tau):
    half = {k: v[:, : v.shape[1] // 2] for k, v in data.items()}
    return fn(state, opt_states, moments, half, key, tau)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(run_micro):
    run = run_micro(micro_cell("dreamer_v3_XL", "crafter_host"), seed=19, fault=unchanged_state)
    assert run["correct"] is False
    # no parameter moved: the change reads 1 by the comparison's measure
    assert run["compared"]["change.world_model"]["value"] == pytest.approx(1.0, abs=1e-3)


@pytest.mark.parametrize("seed,column", [(23, "left out"), (24, "kept")])
def test_half_of_the_batch_left_out_is_not_correct(run_micro, seed, column):
    """Whichever half the inverted column falls in: left out, the step does
    not move at all and reads 1; kept, it moves twice as far as the reference
    (1 again, but for the clipping of the gradient's norm)."""
    run = run_micro(micro_cell("dreamer_v3_XL", "crafter_host"), seed=seed, fault=half_batch)
    assert run["correct"] is False
    assert (seed % 4 >= 2) == (column == "left out")
    moved = run["compared"]["moved.world_model"]["value"]
    assert moved == pytest.approx(1.0, abs=1e-6) if column == "left out" else 0.8 < moved < 1.2


def test_calibrate_judges_the_control_and_the_faults(host_run):
    """`calibrate.py` puts both through the harness's comparison and `judge`
    with the cell's limits: neither may come out correct."""
    from benchmarks import calibrate
    from benchmarks.harness import compare

    cell = micro_cell("dreamer_v3_XL", "crafter_host")
    verdicts = {}
    for name, other in calibrate.sides(cell, host_run):
        values = compare.numbers(compare.load_adapter(cell.config), other, host_run["reference"])
        verdicts[name] = compare.judge(values, {k: v for k, v in cell.limits.items() if k != "ratio_steps"})
    assert set(verdicts) == {"control_bf16", "half_batch", "state_unchanged"}
    assert not any(correct for correct, _ in verdicts.values())
    assert verdicts["state_unchanged"][1]["change.world_model"]["value"] == pytest.approx(1.0, abs=1e-6)
    assert 0.8 < verdicts["half_batch"][1]["moved.world_model"]["value"] < 1.2


def test_a_limit_whose_number_is_missing_fails():
    from benchmarks.harness import compare

    assert compare.judge({"a": 0.1}, {"a": 0.2})[0] is True
    assert compare.judge({"a": 0.1}, {"a": 0.2, "b": 0.2})[0] is False
    assert compare.judge({"a": float("nan")}, {})[0] is False


def test_a_ring_that_fell_back_fails_the_run(run_micro, monkeypatch):
    from sheeprl_tpu.data import device_buffer

    monkeypatch.setattr(device_buffer.DeviceReplayRing, "_budget_bytes", lambda self: 1)
    with pytest.raises(SystemExit) as err:
        run_micro(micro_cell("dreamer_v3_XL", "crafter_host", ring=True), seed=29)
    assert "ring" in str(err.value)
