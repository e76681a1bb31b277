"""PPO over tokens (`algo=ppo_lm`) through the CLI at the micro preset: dry
runs, the share overrides, checkpoint -> evaluate -> resume, a preemption in
the middle of a rollout, the counters in `telemetry tail`, and a short
learning run on the in-repo token task."""

import io
import os

import numpy as np
import pytest

from sheeprl_tpu.cli import evaluation, run


def overrides(**extra):
    args = [
        "exp=ppo_lm",
        "dry_run=True",
        "metric.log_level=0",
        "env.num_envs=4",
        "algo.rollout_steps=3",
        "algo.per_rank_num_batches=2",
        "algo.run_test=True",
        "checkpoint.every=0",
        "fabric.accelerator=cpu",
    ]
    args += [f"{k}={v}" for k, v in extra.items()]
    return args


@pytest.fixture(autouse=True)
def _chdir_tmp(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)


def find_checkpoints(root):
    return sorted(os.path.join(r, d) for r, dirs, _ in os.walk(root) for d in dirs if d.startswith("ckpt_") and d.endswith(".ckpt"))


@pytest.mark.parametrize("held", ["null", "[2,2]"])
def test_dry_run(held):
    """All experts held, and one rank's share of them (the router still scores all four)."""
    run(overrides(**{"algo.model.experts_held": held}))


def test_dry_run_bf16_mixed():
    run(overrides(**{"fabric.precision": "bf16-mixed"}))


def test_the_vocabulary_is_the_models():
    with pytest.raises(ValueError, match="vocab_size"):
        run(overrides(**{"env.wrapper.vocab_size": 12}))


def test_minibatches_divide_the_envs():
    with pytest.raises(ValueError, match="multiple of"):
        run(overrides(**{"algo.per_rank_num_batches": 3}))


def test_checkpoint_eval_resume_roundtrip(tmp_path):
    args = [a for a in overrides(**{"checkpoint.save_last": True}) if not a.startswith("checkpoint.every")]
    run(args)
    ckpts = find_checkpoints(tmp_path / "logs")
    assert ckpts, "no checkpoint written"
    evaluation([f"checkpoint_path={ckpts[-1]}", "fabric.accelerator=cpu"])
    run(overrides(**{"checkpoint.resume_from": ckpts[-1]}))


def test_a_preemption_is_honoured_within_one_policy_step(monkeypatch):
    """The loop's boundary is the policy step: a stop asked for in the middle
    of a rollout ends the run at the next boundary, before the update."""
    from sheeprl_tpu.algos.ppo_lm import ppo_lm
    from sheeprl_tpu.core import resilience

    seen, trained = [], []
    advance = resilience.PreemptionGuard.advance

    def stop_at_the_fourth(guard, policy_step):
        seen.append(int(policy_step))
        if len(seen) == 4:
            guard._preempted = True  # what the SIGTERM handler sets
        return advance(guard, policy_step)

    make = ppo_lm.make_train_step
    monkeypatch.setattr(resilience.PreemptionGuard, "advance", stop_at_the_fourth)
    monkeypatch.setattr(ppo_lm, "make_train_step", lambda *a, **k: (lambda *args: trained.append(1) or make(*a, **k)(*args)))
    run([a for a in overrides(**{"algo.rollout_steps": 8, "algo.total_steps": 4096, "algo.run_test": False}) if a != "dry_run=True"])
    assert seen == [4, 8, 12, 16] and not trained  # 4 envs a policy step; the rollout of 8 steps never finished


def test_gae_per_token():
    from sheeprl_tpu.algos.ppo_lm.utils import token_gae

    rewards = np.array([[0.0, 0.0], [0.0, 1.0], [-1.0, 0.0]], np.float32)  # env 0 ends at step 2, env 1 at step 1
    dones = np.array([[0, 0], [0, 1], [1, 0]], np.float32)
    values = np.array([[0.5, 0.2], [0.4, 0.1], [0.3, 0.0]], np.float32)
    returns, adv = token_gae(rewards, values, dones, 1.0, 1.0)
    # gamma = lambda = 1: the return of every token of an episode is the episode's reward
    assert np.allclose(returns[:, 0], -1.0) and np.allclose(returns[:2, 1], 1.0)
    assert np.allclose(adv, returns - values)
    # nothing leaks back over an episode's end: env 1's idle step 2 (reward 0, value 0) leaves step 1 alone
    _, adv95 = token_gae(rewards, values, dones, 1.0, 0.95)
    assert adv95[1, 1] == pytest.approx(1.0 - 0.1)
    assert adv95[0, 0] == pytest.approx((0.4 - 0.5) + 0.95 * ((0.3 - 0.4) + 0.95 * (-1.0 - 0.3)))


def test_the_token_env_keeps_to_its_interface():
    from sheeprl_tpu.envs.tokens import CopyLastTokenEnv

    env = CopyLastTokenEnv(vocab_size=8, max_prompt_len=6, min_prompt_len=2, response_len=2, seed=3)
    obs, _ = env.reset()
    n = int(obs["prompt_len"][0])
    assert env.observation_space.contains(obs) and 2 <= n <= 6
    assert not obs["prompt"][: 6 - n].any() and obs["token"][0] == obs["prompt"][-1] and obs["active"][0] == 1
    last = int(obs["prompt"][-1])
    obs, reward, terminated, truncated, _ = env.step(last)
    assert (reward, terminated, truncated, int(obs["active"][0]), int(obs["token"][0])) == (0.0, False, False, 1, last)
    obs, reward, terminated, truncated, _ = env.step((last + 1) % 8)
    assert (reward, terminated, truncated, int(obs["active"][0])) == (0.5, False, False, 0)  # one of two tokens right
    assert env.step(last)[1] == 0.0 and int(env.step(last)[0]["active"][0]) == 0  # idle until the loop resets it
    assert int(env.reset()[0]["active"][0]) == 1


def test_the_step_counters_reach_telemetry_tail(tmp_path):
    """The expert load and the token counts ride on the losses' fetch into the
    run's counters, and `telemetry tail` prints them with the cache gauge."""
    from sheeprl_tpu.telemetry.__main__ import tail

    run(overrides(**{"telemetry.enabled": True, "telemetry.flight.enabled": False, "algo.run_test": False}))
    out = io.StringIO()
    assert tail(str(tmp_path / "logs"), out=out) == 0
    text = out.getvalue()
    for name in ("moe/routed_slots", "moe/held_slots", "moe/overflow_chunks", "moe/max_expert_tokens", "ppo_lm/loss_tokens",
                 "ppo_lm/padded_tokens", "ppo_lm/step_tokens", "player/cache_tokens"):
        assert name in text, name
    values = {line.split()[0]: float(line.split()[1]) for line in text.splitlines() if line.startswith("  ") and len(line.split()) >= 2}
    # 2 epochs x 2 minibatches of 2 sequences x (8 + 3) positions, one expert layer, top 2 of 4, all held;
    # each of the 4 one-token answers is in the loss once an epoch; the prompts' padding is sent to no expert
    assert values["ppo_lm/step_tokens"] == 4 * 2 * 11
    assert values["ppo_lm/loss_tokens"] == 8 and 0 < values["ppo_lm/padded_tokens"] < values["ppo_lm/step_tokens"]
    # (padded_tokens also counts the 2 idle response positions of each of the 8 sequences, which are routed)
    in_context = values["ppo_lm/step_tokens"] - values["ppo_lm/padded_tokens"] + 8 * 2
    assert values["moe/routed_slots"] == values["moe/held_slots"] == in_context * 2
    assert values["moe/overflow_chunks"] == 0  # every expert held: one chunk is the whole layer, no chunk past it ever runs
    assert values["player/cache_tokens"] == values["ppo_lm/step_tokens"] / 2 - values["ppo_lm/padded_tokens"] / 2
    assert values["lm/attention_fused"] == 0  # off the TPU the step holds no attention kernel: the plain path
    assert values["ssm/scan_fused"] == 0  # this family has no state-space layer


def test_ppo_lm_learns_to_copy_the_last_token(monkeypatch):
    """A sign error in the token losses must fail the suite: 8192 policy steps
    on `answer with the prompt's last token` (vocabulary 16, chance 1/16)
    bring the greedy answer to the right token (~15 s on the CPU)."""
    import jax

    from sheeprl_tpu.algos.ppo_lm import ppo_lm

    got = {}
    monkeypatch.setattr(ppo_lm, "test", lambda agent, params, *a, **k: got.update(agent=agent, params=params) or 0.0)
    run(["exp=ppo_lm", "metric.log_level=0", "checkpoint.every=0", "checkpoint.save_last=False", "fabric.accelerator=cpu",
         "algo.total_steps=8192", "seed=5"])
    agent, params = got["agent"], got["params"]
    rng = np.random.default_rng(0)
    E, P, V = 64, agent.prompt_len, agent.model.vocab_size
    lengths = rng.integers(2, P + 1, E)
    prompts = np.zeros((E, P), np.int32)
    for e in range(E):
        prompts[e, P - lengths[e]:] = rng.integers(0, V, lengths[e])
    answer = jax.jit(lambda p, s, k: agent.prefill(p, s, prompts, lengths, np.ones(E, bool), k, greedy=True))
    (token, _, _), _, _ = answer(agent.acting_params(params), agent.init_state(E), jax.random.PRNGKey(0))
    accuracy = float(np.mean(np.asarray(token) == prompts[:, -1]))
    assert accuracy >= 0.9, f"ppo_lm stopped learning: greedy accuracy {accuracy:.2f} after 8192 policy steps"


# ------------------------------------------------------------------ the second backbone (`algo.model.model_type=phi4flash`)
def hybrid_overrides(**extra):
    """`exp=ppo_lm_phi4_mini_flash` with the cut's overrides at a toy size: the pipeline stage of layers 14-19 of 32."""
    small = {"algo.model.hidden_size": 32, "algo.model.num_attention_heads": 4, "algo.model.num_key_value_heads": 2,
             "algo.model.intermediate_size": 128, "algo.model.sliding_window": 4, "algo.model.d_state": 4, "algo.model.dt_rank": 2,
             "algo.model.vocab_size": 48, "algo.model.layers_held": "[14,6]", "env.wrapper.max_prompt_len": 10,
             "env.wrapper.min_prompt_len": 3, "fabric.precision": "32-true"}
    args = [a for a in overrides() if not a.startswith("exp=")]
    return ["exp=ppo_lm_phi4_mini_flash"] + args + [f"{k}={v}" for k, v in {**small, **extra}.items()]


def test_hybrid_checkpoint_eval_resume_roundtrip(tmp_path):
    args = [a for a in hybrid_overrides(**{"checkpoint.save_last": True}) if not a.startswith("checkpoint.every")]
    run(args)
    ckpts = find_checkpoints(tmp_path / "logs")
    assert ckpts, "no checkpoint written"
    evaluation([f"checkpoint_path={ckpts[-1]}", "fabric.accelerator=cpu"])
    run(hybrid_overrides(**{"checkpoint.resume_from": ckpts[-1], "algo.run_test": False}))


@pytest.mark.parametrize("override, error", [("algo.model.model_type=gpt2", "no backbone of ppo_lm"),
                                             ("algo.model.layers_held=[18,2]", "without layer 17")])
def test_hybrid_refuses_what_it_cannot_build(override, error):
    with pytest.raises(ValueError, match=error):
        run(hybrid_overrides() + [override])


def test_the_hybrid_counters_and_gauges_reach_telemetry_tail(tmp_path):
    """The scan's chunk count rides on the losses' fetch, the three kinds of player state are gauges on the rollout's."""
    from sheeprl_tpu.telemetry.__main__ import tail

    run(hybrid_overrides(**{"telemetry.enabled": True, "telemetry.flight.enabled": False, "algo.run_test": False}))
    out = io.StringIO()
    assert tail(str(tmp_path / "logs"), out=out) == 0
    text = out.getvalue()
    values = {line.split()[0]: float(line.split()[1]) for line in text.splitlines() if line.startswith("  ") and len(line.split()) >= 2}
    # 2 minibatches of 2 sequences x (10 + 3) positions; one chunk a sequence pass, two Mamba layers (14 and 16)
    assert values["ppo_lm/step_tokens"] == 2 * 2 * 13 and values["ssm/scan_chunks"] == 2 * 2
    assert values["moe/routed_slots"] == 0  # no expert layer in this backbone
    # 4 envs, float32: one ring of 4 rows, one shared cache of 13, two Mamba layers' (conv 3 + ssm 4) x 64
    assert values["player/cache_bytes/window"] == 2 * 4 * 4 * 2 * 8 * 4
    assert values["player/cache_bytes/full"] == 2 * 4 * 13 * 2 * 8 * 4
    assert values["player/cache_bytes/state"] == 2 * 4 * (3 + 4) * 64 * 4
    assert values["lm/attention_fused"] == 0  # off the TPU (and at heads of 8) every attention layer takes the plain path
    assert values["ssm/scan_fused"] == 0  # off the TPU both Mamba layers scan on the plain path


# ------------------------------------------------------------------ the latent-attention kernels' tile counters
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "plain"])
def test_the_tile_counters_ride_on_the_step_where_the_kernels_run(fused, monkeypatch):
    """At 2080 positions (five tiles of 512) a gradient step's kernels make
    forward, rematerialised forward and backward a layer: with the pairs they
    skip, every (layer, pass, row, head) counts the 15 pairs on or below the
    diagonal. The plain path skips nothing and counts nothing."""
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp
    import optax

    from sheeprl_tpu.algos.ppo_lm.agent import PPOLMAgent
    from sheeprl_tpu.algos.ppo_lm.ppo_lm import TILE_COUNTERS, make_train_step
    from sheeprl_tpu.models import pallas_mla_attention as kernel
    from sheeprl_tpu.models.transformer import TransformerConfig

    if fused:  # the rule asked about the shape alone, the kernels in the interpreter
        monkeypatch.setattr(kernel, "ineligible_reason", kernel.shape_ineligible_reason)
        monkeypatch.setattr(kernel, "mla_attention", lambda *a, run=kernel.mla_attention: run(*a, interpret=True))
    layers, heads, P, R = 2, 2, 2048, 32
    model = TransformerConfig(vocab_size=16, hidden_size=32, num_hidden_layers=layers, num_attention_heads=heads,
                              qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=16,
                              intermediate_size=32, moe_intermediate_size=16, n_routed_experts=4, n_shared_experts=1,
                              num_experts_per_tok=2)
    agent = PPOLMAgent(model, P, R, jnp.float32, jnp.float32)
    params = agent.init_params(jax.random.PRNGKey(0))
    tx = optax.sgd(1e-3)
    step = make_train_step(agent, tx, SimpleNamespace(algo=SimpleNamespace(vf_coef=0.5)))
    start = jnp.asarray([0, 1024], jnp.int32)  # the second row's first two tiles are padding
    rows = start.shape[0]
    tokens = jnp.where(jnp.arange(P + R)[None, :] >= start[:, None], 3, 0).astype(jnp.int32)
    zeros = jnp.zeros((rows, R), jnp.float32)
    batch = {"tokens": tokens, "start": start, "logprobs": zeros, "values": zeros, "advantages": zeros,
             "returns": zeros, "mask": jnp.ones((rows, R), jnp.float32)}
    text = str(jax.make_jaxpr(step)(params, tx.init(params), batch, 0.2, 0.0))
    calls = text.count("name=mla_attention_fwd") + text.count("name=mla_attention_bwd")
    assert calls == (3 * layers if fused else 0)
    metrics = step(params, tx.init(params), batch, 0.2, 0.0)[2]
    if not fused:
        assert not set(TILE_COUNTERS) & set(metrics)
        return
    visits, skipped = (float(metrics[name]) for name in TILE_COUNTERS)
    assert visits + skipped == layers * 3 * rows * heads * 15
    assert visits == layers * 3 * heads * (15 + 6) and skipped == layers * 3 * heads * 9
