"""The second real family (`algo=ppo_lm` over a deepseek_v3 decoder,
configuration `kanana2_30b_a3b_ep8`) through the harness at micro widths on
the CPU: the window closes on rollouts and bursts of gradient steps, `correct`
is true in float32 against limits near 1e-4, the lower-precision control, a
bfloat16 program and every fault are not correct, and the new readers read
what the program counts. The chip always runs the published widths."""

import copy
import json
import os
import time
import types

import numpy as np
import pytest

from conftest import ROOT
from benchmarks.harness import compare, manifest, runner

CELL = "kanana2_ep8.long_prompt_ppo"
BENCH = manifest.load_manifest(ROOT)
MICRO = dict(hidden_size=32, num_attention_heads=2, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, kv_lora_rank=16,
             intermediate_size=64, moe_intermediate_size=16, n_routed_experts=16, experts_held=[4, 4], num_hidden_layers=3,
             vocab_size=64)
GROUPS = ("attention", "experts", "shared", "router", "dense", "embed_head", "value")
#: float32 readings at this size are 1e-5 or less; a bfloat16 reference and a bfloat16 program read 1e-3 or more
LIMITS = {name: 2e-4 for name in ["loss.policy", "loss.value", "loss.entropy", "moved.step", "player.logits"]
          + [f"{kind}.{group}" for kind in ("grad", "change", "direction") for group in GROUPS]}
LIMITS["ratio_steps"] = 2.0


def micro_cell(precision="32-true"):
    real = manifest.Cell(BENCH, CELL, ROOT)
    config, traffic = copy.deepcopy(real.config), copy.deepcopy(real.traffic)
    config["model"].update(MICRO, prompt_len=12, rollout_steps=4, batch=2,
                           compute_dtype="float32" if precision == "32-true" else "bfloat16")
    config["program"]["overrides"].update({f"algo.model.{k}": v for k, v in MICRO.items()})
    config["program"]["overrides"]["fabric.precision"] = precision
    traffic["overrides"].update({"env.num_envs": 4, "algo.rollout_steps": 4, "algo.per_rank_num_batches": 2})
    traffic["env"].update(max_prompt_len=12, min_prompt_len=4, response_low=2, response_high=4, samples_per_prompt=2)
    return types.SimpleNamespace(name="micro_lm", chips=1, config=config, traffic=traffic, limits=dict(LIMITS))


@pytest.fixture(scope="module")
def run_lm(tmp_path_factory):
    from benchmarks.harness.adapters import ppo_lm as adapter

    def run(cell, seed, fault=None, trace=False):
        real = adapter.Record

        def record(*args, **kwargs):
            rec = real(*args, **kwargs)
            rec.fault = fault
            return rec

        adapter.Record = record
        log = []
        try:
            out = runner.run_cell(cell, seed, 1.0, trace, time.perf_counter(), str(tmp_path_factory.mktemp("lm_run")), log.append)
        finally:
            adapter.Record = real
        out["log"] = log
        return out

    return run


@pytest.fixture(scope="module")
def sound(run_lm):
    return run_lm(micro_cell(), seed=2**31 + 11, trace=True)


# ------------------------------------------------------------------ control flow
def test_the_window_closes_on_rollouts_and_bursts(sound):
    window, mix = sound["window"], sound["cell"].traffic["overrides"]
    assert window.phase == "closed" and window.elapsed >= 1.0
    # set-up lasted through the first rollout (4 envs x 4 steps) and its update (2 gradient steps >= ... the third is the next rollout's),
    # and the window opened at a rollout's first policy step
    assert window.policy_steps[0] >= 16 + 4 and window.policy_steps[0] % 16 == 4 and window.train_steps[0] >= 3
    assert {b - a for a, b in zip(window.policy_steps, window.policy_steps[1:])} == {mix["env.num_envs"]}
    made = [b - a for a, b in zip(window.train_steps, window.train_steps[1:])]
    assert set(made) == {0, 2}  # nothing at a decode step, the whole update at a rollout's last step
    bursts = [i for i, n in enumerate(made) if n]
    assert {b - a for a, b in zip(bursts, bursts[1:])} == {mix["algo.rollout_steps"]}
    assert sound["compared"]["ratio_steps"]["value"] <= 2.0
    assert set(sound["readings"]) == {"env_steps_per_s", "iter_p95_ms", "peak_hbm_gib", "setup_s"}


# ------------------------------------------------------------------ the comparison
@pytest.mark.parametrize("number", [k for k in LIMITS if k != "ratio_steps"])
def test_program_agrees_with_the_reference_in_float32(sound, number):
    entry = sound["compared"][number]
    assert entry["value"] <= entry["limit"] == LIMITS[number]


def test_correct_is_true_and_the_routes_agree(sound):
    assert sound["correct"] is True
    assert sound["compared"]["loss.route_flips"] == {"value": 0.0, "limit": None}  # shown, not compared
    routes = sound["record"].captured[0]["routes"]
    assert routes.shape == (2, 2 * 16, 6) and routes.max() < 16


def test_the_first_rollout_is_kept_as_logits(sound):
    """Every env's whole sequence of the first rollout, and the logits each
    response token was drawn from, before any update."""
    acted = sound["acted"]
    assert len(acted) == 4 and all(step["tokens"].shape == (16,) and step["logits"].shape == (4, 64) for step in acted)
    assert acted[0]["tokens"][:12].tolist() == acted[1]["tokens"][:12].tolist()  # two samples of one prompt
    assert acted[0]["tokens"][:12].tolist() != acted[2]["tokens"][:12].tolist()
    assert all(0 <= step["start"] <= 8 and not step["tokens"][: step["start"]].any() for step in acted)


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
    assert control["grad.attention"]["value"] > 100 * sound["compared"]["grad.attention"]["value"]
    assert verdicts["state_unchanged"][1]["change.attention"]["value"] == pytest.approx(1.0, abs=1e-6)
    assert verdicts["half_batch"][1]["loss.policy"]["value"] > 1e-2


def test_the_lean_calibration_gives_calibrates_verdicts(run_lm):
    """`calibrate_lean.py` (for a cell whose sides outgrow the host beside the
    program's side) judges what `calibrate.py` judges, side by side, and keeps nothing."""
    from benchmarks import calibrate, calibrate_lean

    cell = micro_cell()
    adapter = compare.load_adapter(cell.config)
    run = run_lm(cell, seed=11)
    plain = {name: dict(compare.numbers(adapter, other, run["reference"])) for name, other in calibrate.sides(cell, run)}
    lean = list(calibrate_lean.verdicts(cell, run, first=2))
    assert [name for name, *_ in lean] == ["control_bf16", "half_batch"] and run["program"] is None
    for name, values, correct, over in lean:
        assert correct is False and over
        assert {k: values[k] for k in plain[name]} == pytest.approx(plain[name], rel=1e-6)
    # the control's moved.step alone (`--moved-only`) is the whole control's, made beside nothing
    assert calibrate_lean.moved_control(cell, run) == pytest.approx(plain["control_bf16"]["moved.step"], rel=1e-6) and not run


def test_a_bfloat16_program_fails_the_float32_comparison(run_lm):
    run = run_lm(micro_cell("bf16-mixed"), seed=17)
    assert run["correct"] is False
    assert run["compared"]["player.logits"]["value"] > 1e-3


def leaves_half_out(fn, params, opt_state, batch, clip_coef, ent_coef):
    return fn(params, opt_state, {k: v[: len(v) // 2] for k, v in batch.items()}, clip_coef, ent_coef)


def test_half_of_the_minibatch_left_out_is_not_correct(run_lm):
    """Seed 23 alters sequence 1 of 2: a step that reads only the first does not move at all, and `moved.step` reads 1."""
    run = run_lm(micro_cell(), seed=23, fault=leaves_half_out)
    assert run["correct"] is False
    assert run["compared"]["moved.step"]["value"] == pytest.approx(1.0, abs=1e-6)


def passes_through(seen):
    def fault(fn, params, opt_state, batch, clip_coef, ent_coef):
        seen.append(float(clip_coef))
        return fn(params, opt_state, batch, clip_coef, ent_coef)

    return fault


def test_the_step_asked_again_is_asked_with_the_clip_open(run_lm):
    """The window's steps get the recipe's clip; the two calls made once it has closed (`Record.sensitivity`) get
    `ASKED_CLIP`, as the reference's `asked_again` does: were one side's clip open alone, `moved.step` would not be 1e-5."""
    from benchmarks.harness.adapters import ppo_lm as adapter

    seen = []
    run = run_lm(micro_cell(), seed=29, fault=passes_through(seen))
    assert len(seen) >= 5 and seen[-2:] == [adapter.ASKED_CLIP] * 2 and seen[:-2] == pytest.approx([0.2] * (len(seen) - 2))
    assert run["correct"] is True and run["compared"]["moved.step"]["value"] <= LIMITS["moved.step"]


@pytest.mark.parametrize("clip, jumps", [(None, True), ("asked", False)])
def test_a_ratio_at_the_clips_edge_moves_the_gradient_only_under_the_recipes_clip(sound, clip, jumps):
    """Why the step is asked again with the clip open: a token whose ratio two precisions put on two sides of
    1 + clip_coef has its whole policy gradient in one answer and none in the other. Here one token of the first
    minibatch is given an old log-probability that puts its ratio a thousandth under and over the edge."""
    from benchmarks.harness.adapters import ppo_lm as adapter

    cell = sound["cell"]
    ref = compare.load_reference(cell.config)
    params = sound["reference"]["initial"]
    batch = adapter.reference_inputs(cell.config, sound["record"].captured[0])[0]
    P = cell.config["model"]["prompt_len"]
    logits = ref.logits(params, batch["tokens"][0], batch["start"][0])[0]
    logp = float(logits[batch["tokens"][0, P]] - np.log(np.sum(np.exp(logits))))

    def gradient(ratio):
        altered = {k: np.array(v) for k, v in batch.items()}
        altered["logprobs"][0, 0], altered["advantages"][0, 0], altered["mask"][0, 0] = logp - np.log(ratio), 1.0, 1.0
        return ref.first_gradient(ref.init(params), altered, clip_coef=adapter.ASKED_CLIP if clip else None)

    under, over = gradient(1.2 - 1e-3), gradient(1.2 + 1e-3)
    norm = lambda tree: float(np.sqrt(sum(np.sum(np.square(v, dtype=np.float64)) for v in tree.values())))  # noqa: E731
    moved = norm({k: over[k] - under[k] for k in under}) / norm(under)
    assert (moved > 0.05) if jumps else (moved < 0.005), moved


# ------------------------------------------------------------------ readers and files
def test_the_new_readers_read_the_programs_counters(sound):
    read = lambda name: manifest.load_reader(name, ROOT)(sound)  # noqa: E731
    assert sound["spans"], "the traced run turns the program's telemetry on"
    # even router over 16 experts of which 4 are held: about a quarter of the slots, exactly held / routed
    counters = {r["name"]: r["value"] for r in sound["spans"] if r.get("type") == "counter"}
    assert read("moe.held_load_share") == pytest.approx(100 * counters["moe/held_slots"] / counters["moe/routed_slots"])
    assert 10 < read("moe.held_load_share") < 45 and read("moe.load_imbalance") >= 1.0
    assert 0 < read("update.padded_share") < 100
    # on the CPU no operation runs on a device plane: the trace readers return nothing, never a 0
    for name in ("train_step.mla_ms", "train_step.moe_experts_ms", "act.decode_device_ms", "act.prefill_device_ms", "train_step.mfu"):
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
    assert len(new) == 11 and {"train step", "player", "expert layer"} == {m["layer"] for m in BENCH["per_layer"] if m["name"] in new}


def test_step_scopes_are_the_programs_own():
    from sheeprl_tpu.telemetry import scopes

    cell = manifest.Cell(BENCH, CELL, ROOT)
    assert tuple(cell.config["program"]["step_scopes"]) == scopes.LM_STEP
    assert scopes.LM_ACT_PREFILL not in scopes.LM_STEP and scopes.LM_ACT_DECODE not in scopes.LM_STEP


def test_the_configuration_is_the_catalogs_row_with_three_cuts():
    body = manifest.Cell(BENCH, CELL, ROOT).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide")
    with open(catalog) as fp:
        row = next(r for r in map(json.loads, fp) if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    assert body["source"].startswith(row["source_url"])
    differs = sorted(k for k, v in row["config"].items() if body.get(k, "missing") != v)
    assert differs == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert sorted(body["reduced"]) == sorted(differs + ["agent_init"])
    model = body["model"]
    assert all(model[k] == row["config"][k] for k in row["config"] if k in model and k not in differs + ["n_routed_experts"])
    assert model["n_routed_experts"] == 128 and model["experts_held"] == [0, 16]  # the router's width is the published one


def test_the_operation_count_is_the_issues_arithmetic():
    from benchmarks.flops.deepseek_v3_ppo import step_flops

    model = manifest.Cell(BENCH, CELL, ROOT).config["model"]
    flops = step_flops(model)
    assert flops["total"] == pytest.approx(sum(v for k, v in flops.items() if k != "total"))
    tokens = model["batch"] * (model["prompt_len"] + model["rollout_steps"])
    per_token = flops["total"] / 3 / tokens
    assert tokens == 8320 and 0.55e9 < per_token < 0.75e9  # ~0.6 GFLOP forward a token: ~16 TFLOP a step with its backward
    # attention's products are about a fifth of a layer's operations at 2080 positions
    scores = 3 * 2 * tokens * model["num_hidden_layers"] * 32 * 320 * 2080 / 2
    assert 0.12 < scores / flops["total"] < 0.3


def test_the_benchmarks_token_env_is_seeded():
    from benchmarks.envs.token_env import TokenBenchEnv

    make = lambda rank, seed=5: TokenBenchEnv(vocab_size=100, max_prompt_len=64, min_prompt_len=16, response_low=2, response_high=8,  # noqa: E731
                                              samples_per_prompt=4, run_seed=seed, rank=rank, response_len=3)
    a, b, c, other = make(0), make(3), make(4), make(0, seed=6)
    first = [env.reset()[0] for env in (a, b, c, other)]
    assert np.array_equal(first[0]["prompt"], first[1]["prompt"])  # envs 0..3 sample one prompt
    assert not np.array_equal(first[0]["prompt"], first[2]["prompt"]) and not np.array_equal(first[0]["prompt"], first[3]["prompt"])
    n = int(first[0]["prompt_len"][0])
    assert 16 <= n <= 64 and not first[0]["prompt"][: 64 - n].any() and first[0]["prompt"].max() < 100
    rewards, active = [], []
    for _ in range(8):
        obs, reward, terminated, truncated, _ = a.step(7)
        rewards.append(reward)
        active.append(int(obs["active"][0]))
        assert not terminated and not truncated and obs["token"][0] == 7
    length = active.index(0) + 1
    assert 2 <= length <= 8 and all(r == 0 for r in rewards[: length - 1] + rewards[length:]) and rewards[length - 1] in (-1.0, 0.0, 1.0)
    assert not np.array_equal(a.reset()[0]["prompt"], first[0]["prompt"])  # the next episode has a new prompt
    again = make(0)
    assert np.array_equal(again.reset()[0]["prompt"], first[0]["prompt"])
