"""The token-policy family over its second backbone (`algo=ppo_lm` with a
`phi4flash` decoder, configuration `phi4_mini_flash_vp8`) through the harness
at micro widths on the CPU: the window closes on rollouts and bursts of
gradient steps, `correct` is true in float32 against limits near 1e-4, the
lower-precision control and both faults are not correct, the new readers read
what the program counts, and the configuration's files hold together. The chip
always runs the published widths."""

import copy
import json
import os
import time
import types

import numpy as np
import pytest

from conftest import ROOT
from benchmarks.harness import compare, manifest, runner

CELL = "phi4flash_vp8.long_doc_ppo"
BENCH = manifest.load_manifest(ROOT)
# the published ratios of widths kept small; layers 2-7 of 8 are one of every kind but one Mamba layer more (ssm, swa, ssm, full, gmu, cross)
MICRO = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2, intermediate_size=128, sliding_window=4,
             d_state=4, dt_rank=2, num_hidden_layers=8, layers_held=[2, 6], vocab_size=64)
GROUPS = ("ssm", "window_attn", "full_attn", "cross_attn", "gmu", "mlp", "embed_head", "value")
#: float32 readings at this size are 1e-5 or less; a bfloat16 reference reads 1e-3 or more
LIMITS = {name: 2e-4 for name in ["loss.policy", "loss.value", "loss.entropy", "moved.step", "player.logits"]
          + [f"{kind}.{group}" for kind in ("grad", "change", "direction") for group in GROUPS]}
LIMITS["ratio_steps"] = 2.0


def micro_cell(precision="32-true"):
    real = manifest.Cell(BENCH, CELL, ROOT)
    config, traffic = copy.deepcopy(real.config), copy.deepcopy(real.traffic)
    config["model"].update({k: v for k, v in MICRO.items() if k != "num_hidden_layers"}, num_hidden_layers=6, prompt_len=12,
                           rollout_steps=4, batch=2, compute_dtype="float32" if precision == "32-true" else "bfloat16")
    config["model"]["published"] = dict(config["model"]["published"], num_hidden_layers=8)
    config["program"]["overrides"].update({f"algo.model.{k}": v for k, v in MICRO.items()})
    config["program"]["overrides"]["fabric.precision"] = precision
    traffic["overrides"].update({"env.num_envs": 4, "algo.rollout_steps": 4, "algo.per_rank_num_batches": 2})
    traffic["env"].update(max_prompt_len=12, min_prompt_len=6, response_low=2, response_high=4, samples_per_prompt=2)
    return types.SimpleNamespace(name="micro_hybrid", chips=1, config=config, traffic=traffic, limits=dict(LIMITS))


@pytest.fixture(scope="module")
def run_hybrid(tmp_path_factory):
    from benchmarks.harness.adapters import ppo_lm_hybrid as adapter

    def run(cell, seed, fault=None, trace=False):
        real = adapter.Record

        def record(*args, **kwargs):
            rec = real(*args, **kwargs)
            rec.fault = fault
            return rec

        adapter.Record = record
        log = []
        try:
            out = runner.run_cell(cell, seed, 1.0, trace, time.perf_counter(), str(tmp_path_factory.mktemp("hybrid_run")), log.append)
        finally:
            adapter.Record = real
        out["log"] = log
        return out

    return run


@pytest.fixture(scope="module")
def sound(run_hybrid):
    return run_hybrid(micro_cell(), seed=2**31 + 33, trace=True)


# ------------------------------------------------------------------ control flow
def test_the_window_closes_on_rollouts_and_bursts(sound):
    window, mix = sound["window"], sound["cell"].traffic["overrides"]
    assert window.phase == "closed" and window.elapsed >= 1.0
    assert window.policy_steps[0] >= 16 + 4 and window.policy_steps[0] % 16 == 4 and window.train_steps[0] >= 3
    assert {b - a for a, b in zip(window.policy_steps, window.policy_steps[1:])} == {mix["env.num_envs"]}
    made = [b - a for a, b in zip(window.train_steps, window.train_steps[1:])]
    assert set(made) == {0, 2}  # nothing at a decode step, the whole update at a rollout's last step
    assert sound["compared"]["ratio_steps"]["value"] <= 2.0
    assert set(sound["readings"]) == {"env_steps_per_s", "iter_p95_ms", "peak_hbm_gib", "setup_s"}


# ------------------------------------------------------------------ the comparison
@pytest.mark.parametrize("number", [k for k in LIMITS if k != "ratio_steps"])
def test_program_agrees_with_the_reference_in_float32(sound, number):
    entry = sound["compared"][number]
    assert entry["value"] <= entry["limit"] == LIMITS[number]


def test_correct_is_true_and_no_route_is_compared(sound):
    assert sound["correct"] is True
    assert "loss.route_flips" not in sound["compared"]  # this family has no router
    assert sound["record"].captured[0]["routes"].shape == (0, 2 * 16, 1)


def test_the_first_rollout_went_through_ring_shared_cache_and_state(sound):
    """Prompts of 6-12 tokens against a window of 4: the ring had wrapped at
    prefill and wrapped again in the 3 decode steps; `player.logits` held every
    one of those logits to the reference's full forward pass."""
    acted = sound["acted"]
    assert len(acted) == 4 and all(step["tokens"].shape == (16,) and step["logits"].shape == (4, 64) for step in acted)
    assert acted[0]["tokens"][:12].tolist() == acted[1]["tokens"][:12].tolist()  # two samples of one prompt
    assert all(0 <= step["start"] <= 6 for step in acted)
    assert sound["compared"]["player.logits"]["value"] <= 2e-4


def test_the_step_asked_again_moves(sound):
    moved = sound["program"]["moved"]
    assert set(moved) == set(sound["reference"]["first_grads"])
    assert all(np.isfinite(v).all() for v in moved.values()) and max(float(np.abs(v).max()) for v in moved.values()) > 0


def test_calibrate_judges_the_control_and_the_faults(sound):
    from benchmarks import calibrate

    cell = micro_cell()
    adapter = compare.load_adapter(cell.config)
    verdicts = {}
    for name, other in calibrate.sides(cell, sound):
        values = dict(compare.numbers(adapter, other, sound["reference"]), ratio_steps=0.0)
        verdicts[name] = compare.judge(values, cell.limits)
    assert sorted(verdicts) == ["control_bf16", "half_batch", "state_unchanged"]
    assert not any(correct for correct, _ in verdicts.values())
    control = verdicts["control_bf16"][1]
    assert control["player.logits"]["value"] > 100 * sound["compared"]["player.logits"]["value"]
    assert control["grad.ssm"]["value"] > 20 * sound["compared"]["grad.ssm"]["value"]
    assert verdicts["state_unchanged"][1]["change.ssm"]["value"] == pytest.approx(1.0, abs=1e-6)
    assert verdicts["half_batch"][1]["loss.policy"]["value"] > 1e-2


# ------------------------------------------------------------------ readers and files
def test_the_new_readers_read_the_programs_counters(sound):
    read = lambda name: manifest.load_reader(name, ROOT)(sound)  # noqa: E731
    assert sound["spans"], "the traced run turns the program's telemetry on"
    counters = {r["name"]: r["value"] for r in sound["spans"] if r.get("type") == "counter"}
    # window 4 x 1 layer, 16 positions x 1 layer, (3 + 4) x 64 x 2 Mamba layers, 4 envs; float32
    sizes = {"window": 2 * 4 * 4 * 2 * 8 * 4, "full": 2 * 4 * 16 * 2 * 8 * 4, "state": 2 * 4 * (3 + 4) * 64 * 4}
    assert {k: counters["player/cache_bytes/" + k] for k in sizes} == sizes
    assert read("act.cache_mib") == pytest.approx(sum(sizes.values()) / 2**20)
    steps = counters["ppo_lm/step_tokens"] / 32
    assert counters["ssm/scan_chunks"] == 2 * steps and counters["moe/routed_slots"] == 0
    # on the CPU no operation runs on a device plane: the trace readers return nothing, never a 0
    for name in ("train_step.ssm_fwd_ms", "train_step.ssm_bwd_ms", "train_step.window_attn_ms", "train_step.full_attn_ms",
                 "train_step.cross_attn_ms", "train_step.gmu_ms", "train_step.dense_mlp_ms", "train_step.mfu"):
        assert read(name) is None, name
    for name in ("host.fetch_wait_ms", "host.loop_ms"):
        assert read(name) > 0, name
    spans = {r["name"] for r in sound["spans"] if r.get("type") == "span"}
    assert {"player/prefill", "train/dispatch", "loop/iteration", "fetch/player_actions", "interaction/env_step/slice0"} <= spans


def test_every_per_layer_metric_of_the_cell_has_an_entry():
    cell = manifest.Cell(BENCH, CELL, ROOT)
    names = [m["name"] for m in cell.per_layer()]
    listless = [m["name"] for m in BENCH["per_layer"] if "workloads" not in m]
    assert len(listless) == 11 and set(listless) <= set(names)
    new = [m["name"] for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(new) == sorted(["train_step.ssm_fwd_ms", "train_step.ssm_bwd_ms", "train_step.window_attn_ms", "train_step.full_attn_ms",
                                  "train_step.cross_attn_ms", "train_step.gmu_ms", "train_step.dense_mlp_ms", "act.cache_mib"])
    assert {m["layer"] for m in BENCH["per_layer"] if m["name"] in new} == {"train step", "player"}
    assert all(m["moves"] == "env_steps_per_s" for m in BENCH["per_layer"] if m["name"] in new)
    assert manifest.validate(BENCH, ROOT) == []


def test_step_scopes_are_the_programs_own():
    from sheeprl_tpu.telemetry import scopes

    cell = manifest.Cell(BENCH, CELL, ROOT)
    assert tuple(cell.config["program"]["step_scopes"]) == scopes.LM_HYBRID_STEP
    assert set(scopes.LM_HYBRID_STEP) & set(scopes.LM_STEP) == {scopes.LM_EMBED, scopes.LM_DENSE_MLP, scopes.LM_HEAD_LOSS, scopes.LM_OPTIM}
    assert scopes.LM_ACT_PREFILL not in scopes.LM_HYBRID_STEP and scopes.LM_ACT_DECODE not in scopes.LM_HYBRID_STEP


def test_the_configuration_is_the_catalogs_row_with_two_cuts():
    body = manifest.Cell(BENCH, CELL, ROOT).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide")
    with open(catalog) as fp:
        row = next(r for r in map(json.loads, fp) if r["name"] == "Phi-4-mini-flash-reasoning")
    assert body["source"].startswith(row["source_url"])
    differs = sorted(k for k, v in row["config"].items() if body.get(k, "missing") != v)
    assert differs == ["num_hidden_layers", "vocab_size"]
    assert sorted(body["reduced"]) == sorted(differs + ["agent_init"])
    model = body["model"]
    assert all(model[k] == row["config"][k] for k in row["config"] if k in model and k not in differs)
    assert model["published"] == {"num_hidden_layers": 32, "layers_held": None, "vocab_size": 200064}
    assert model["layers_held"] == [14, 6] and model["vocab_size"] * 8 == 200064 and model["expand"] * model["hidden_size"] == 5120
    assert {"d_state", "d_conv", "expand", "dt_rank", "conv_bias", "kinds_by_index", "memory", "differential_attention",
            "attention_biases", "window", "value_head", "kl_term", "prompts", "responses", "reward", "optimizer", "sampling"} <= set(body["assumed"])


def test_the_cells_limits_stand_between_their_readings():
    """The cell's file holds together: every limit beside the readings it was set from, with room above the largest
    sound reading, under the smaller control where one was read, and a `change.*` at most half of what a state left
    unchanged reads."""
    body = manifest.load_json(os.path.join(ROOT, "benchmarks", "cells", CELL + ".json"))
    limits, readings = body["limits"], body["readings"]
    assert limits == manifest.Cell(BENCH, CELL, ROOT).limits and set(limits) <= set(readings)
    for name, limit in limits.items():
        read = readings[name]
        assert read["limit"] == limit, name
        assert read["largest_sound"] == max(read["sound_runs"].values()), name
        if name != "ratio_steps":
            assert 1.5 * read["largest_sound"] <= limit, name
        if "control_fp8" in read:
            assert limit < min(read["control_fp8"].values()), name
        if name.startswith("change."):
            assert limit <= 0.5 * read["state_unchanged"], name
    shown = [name for name, read in readings.items() if isinstance(read, dict) and "sound_runs" in read and name not in limits]
    assert all("why_not_compared" in readings[name] for name in shown), shown


def test_adams_first_step_takes_a_lambda_vector_by_the_sign_of_one_scalar():
    """What `change.full_attn` and `change.cross_attn` read on one seed in thirteen each (0.163, 0.162): the four lambda
    vectors of a layer have the gradient of one scalar times a fixed vector, and Adam's first step is the gradient's
    sign. A first scalar near nothing, with the other sign on one side, moves the vector's three-step change by two
    steps of every element; the same scalar at the second step moves it by a hundredth of that."""
    from benchmarks.reference.phi4flash_ppo import _adam_leaf

    model = manifest.Cell(BENCH, CELL, ROOT).config["model"]
    lr, eps = float(model["optim"]["lr"]), float(model["optim"]["eps"])
    fixed = np.random.default_rng(0).normal(size=64).astype(np.float32) * 0.1  # `lk1 exp(lq1 . lk1)`: a lambda vector's draw

    def change(scalars):
        p = mu = nu = np.zeros(64, np.float32)
        for count, scalar in enumerate(scalars, 1):
            p, mu, nu = _adam_leaf(p, np.float32(scalar) * fixed, mu, nu, float(count), lr=lr, eps=eps)
        return float(np.linalg.norm(np.asarray(p, np.float64)))

    step = lr * 8.0  # one step of every one of 64 elements
    small = 1e-3
    assert abs(change([small, 1.0, 0.7]) - change([-small, 1.0, 0.7])) == pytest.approx(2 * step, rel=0.02)
    assert abs(change([1.0, small, 0.7]) - change([1.0, -small, 0.7])) < 0.02 * step
    # against the median leaf of an attention group (a bias or a norm's leaf of 2560: ~0.98e-3 read on the chip), two steps read 0.16
    assert 2 * step / 0.98e-3 == pytest.approx(0.163, abs=0.001)


def test_the_operation_count_is_the_issues_arithmetic():
    from benchmarks.flops.phi4flash_ppo import step_flops

    model = manifest.Cell(BENCH, CELL, ROOT).config["model"]
    flops = step_flops(model)
    assert flops["total"] == pytest.approx(sum(v for k, v in flops.items() if k != "total"))
    tokens = model["batch"] * (model["prompt_len"] + model["rollout_steps"])
    assert tokens == 8256 and 30e12 < flops["total"] < 35e12  # ~33 TFLOP a step: ISSUE 33's 44 with the rematerialised forward
    assert 0.68 < flops["dense_mlp"] / flops["total"] < 0.78  # three quarters of the step's operations
    assert flops["dense_mlp"] == 6 * 3 * 2 * tokens * 3 * 2560 * 10240
    # the window layer's attention is about an eighth of the full layer's at 4128 positions
    projections = 3 * 2 * tokens * (2560 * 5120 + 2560 * 2560)
    assert 0.2 < (flops["window_attn"] - projections) / (flops["full_attn"] - projections) < 0.27
    assert flops["scan_elementwise"] == 3 * 9 * 2 * tokens * 5120 * 16 and flops["scan_elementwise"] < 0.01 * flops["total"]


def test_the_parameters_are_the_issues_count():
    """697.1 M parameters at the cut, from the program's own shapes (traced, nothing allocated)."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.ppo_lm.agent import PPOLMAgent
    from sheeprl_tpu.models.hybrid_decoder import HybridConfig

    model = manifest.Cell(BENCH, CELL, ROOT).config["model"]
    cfg = HybridConfig.from_config(dict(model, num_hidden_layers=model["published"]["num_hidden_layers"]))
    agent = PPOLMAgent(cfg, model["prompt_len"], model["rollout_steps"], jnp.bfloat16, jnp.float32)
    shapes = jax.eval_shape(agent.init_params, jax.random.PRNGKey(0))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == 697_096_832
    assert agent.cache_bytes(8) == {"window": 2 * 8 * 512 * 20 * 64 * 2, "full": 2 * 8 * 4128 * 20 * 64 * 2,
                                    "state": 2 * 8 * (3 * 5120 * 2 + 16 * 5120 * 4)}
    assert agent.scan_chunks() == 2 * 65
