"""Profile the DreamerV3-S train step on the real TPU chip.

Times the full jitted gradient step at the S-model benchmark shape
(batch 16 x sequence 64, 64x64 pixels), reports XLA's FLOPs estimate and the
resulting MFU, A/Bs the fused Pallas LN-GRU path against the unfused one,
and — with --phases — attributes the step time to its phases by timing each
stage as a standalone jitted fwd+bwd:

  encoder        embed_obs fwd+bwd (conv + mlp encoders)
  rssm_scan      the T-step dynamic-learning scan fwd+bwd (GRU + posterior)
  decoders       decode/reward/continue heads + losses fwd+bwd
  imagination    the H-step imagination rollout + actor loss fwd+bwd
  critic         critic loss fwd+bwd

Phase probes recompute the stage inputs outside the timed region, so the sum
of phases ~ the full step minus optimizer/apply overhead (XLA fuses more
aggressively inside the full step; treat phases as an attribution, not an
exact partition).

Usage: python scripts/profile_dreamer_v3.py [--trace-dir /tmp/dv3_trace]
       [--phases] [--iters N]
Writes a summary JSON to stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# v5e peak: ~197 TFLOP/s bf16, ~49 TFLOP/s fp32 (public spec)
PEAK_FLOPS = {"bf16": 197e12, "f32": 49e12}


def build(cfg_overrides):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import sheeprl_tpu

    sheeprl_tpu.register_all()
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import _make_optimizer, make_train_step
    from sheeprl_tpu.cli import check_configs
    from sheeprl_tpu.config.instantiate import instantiate
    from sheeprl_tpu.config.loader import compose
    import gymnasium as gym

    cfg = compose(
        "config",
        [
            "exp=dreamer_v3",
            "algo=dreamer_v3_S",
            "env=dummy",
            "env.num_envs=1",
            "env.capture_video=False",
            "env.screen_size=64",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            "algo.mlp_keys.decoder=[]",
            "algo.cnn_keys.decoder=[rgb]",
            "algo.run_test=False",
            "metric.log_level=0",
            "checkpoint.every=0",
        ]
        + cfg_overrides,
    )
    check_configs(cfg)
    runtime = instantiate(cfg.fabric)
    runtime.launch()
    runtime.seed_everything(cfg.seed)

    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8)})
    agent, agent_state = build_agent(runtime, (6,), False, cfg, obs_space)
    txs = {
        "world_model": _make_optimizer(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients),
        "actor": _make_optimizer(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients),
        "critic": _make_optimizer(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients),
    }
    opt_states = {k: txs[k].init(agent_state[k]) for k in ("world_model", "actor", "critic")}
    from sheeprl_tpu.utils.ops import init_moments

    train_fn = make_train_step(agent, txs, cfg, runtime.mesh)

    T, B = int(cfg.algo.per_rank_sequence_length), int(cfg.algo.per_rank_batch_size)
    key = jax.random.PRNGKey(0)
    data = {
        "rgb": jax.random.randint(key, (T, B, 64, 64, 3), 0, 255, jnp.int32).astype(jnp.uint8),
        "actions": jnp.zeros((T, B, 6), jnp.float32),
        "rewards": jnp.zeros((T, B, 1), jnp.float32),
        "terminated": jnp.zeros((T, B, 1), jnp.float32),
        "truncated": jnp.zeros((T, B, 1), jnp.float32),
        "is_first": jnp.zeros((T, B, 1), jnp.float32),
    }
    return cfg, agent, train_fn, agent_state, opt_states, init_moments(), data, (T, B)


def time_step(train_fn, agent_state, opt_states, moments, data, iters=100):
    """Donated-chain step timing through the telemetry StepTimer.

    The hand-rolled pattern this used to inline now lives in
    sheeprl_tpu/telemetry/step_timer.py: per-step dispatch walls accumulate
    async, and ONE flush bounds the chain — the flush's coalesced metric
    fetch is a host fetch of every step's loss, which drains the queue.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.telemetry import StepTimer

    key = jax.random.PRNGKey(1)
    tau = jnp.asarray(0.02, jnp.float32)
    # Warmup / compile. The step donates its inputs, so thread the state.
    # TWO warmup calls: the second call's inputs are donated outputs of the
    # first and can trigger one more compile (layout change) — keep it out
    # of the timed loop (the trap telemetry's recompile-after-warmup counter
    # now watches for in real runs).
    s, o, m, mt, key = train_fn(agent_state, opt_states, moments, data, key, tau)
    float(np.asarray(mt["Loss/world_model_loss"]))
    s, o, m, mt, key = train_fn(s, o, m, data, key, tau)
    float(np.asarray(mt["Loss/world_model_loss"]))
    st = StepTimer(name="profile")
    for _ in range(iters):
        with st.step():
            s, o, m, mt, key = train_fn(s, o, m, data, key, tau)
        st.pend(s["world_model"], mt["Loss/world_model_loss"])
    st.flush()  # ONE bound + ONE coalesced fetch ends the donated chain
    return st.seconds_per_step, (s, o, m)


# ---------------------------------------------------------------- phases
def build_phase_probes(cfg, agent, agent_state, data):
    """Standalone jitted fwd+bwd probes for each train-step stage."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.algos.dreamer_v3.agent import WorldModel, actor_forward
    from sheeprl_tpu.utils.distribution import (
        BernoulliSafeMode,
        Independent,
        MSEDistribution,
        TwoHotEncodingDistribution,
    )
    from sheeprl_tpu.utils.ops import compute_lambda_values

    wm_cfg = cfg.algo.world_model
    stochastic_size = int(wm_cfg.stochastic_size)
    discrete_size = int(wm_cfg.discrete_size)
    stoch_state_size = stochastic_size * discrete_size
    recurrent_state_size = int(wm_cfg.recurrent_model.recurrent_state_size)
    horizon = int(cfg.algo.horizon)
    spec = agent.actor_spec

    T, B = data["rewards"].shape[:2]
    wm_params = agent_state["world_model"]
    batch_obs = {"rgb": data["rgb"] / 255.0 - 0.5}
    key = jax.random.PRNGKey(2)
    dyn_keys = jax.random.split(key, T + 1)

    # Shared precomputed stage inputs (not timed).
    embedded = jax.jit(lambda p, o: agent.wm(p, o, method="embed_obs"))(wm_params, batch_obs)
    batch_actions = jnp.concatenate([jnp.zeros_like(data["actions"][:1]), data["actions"][:-1]], 0)
    is_first = data["is_first"].at[0].set(1.0)
    h0 = jnp.zeros((B, recurrent_state_size), embedded.dtype)
    z0 = jnp.zeros((B, stoch_state_size), embedded.dtype)

    def rssm_scan(p, embedded):
        def step(carry, x):
            h, z = carry
            action, emb, first, k = x
            h, post, prior, post_logits, prior_logits = agent.world_model.apply(
                p, z, h, action, emb, first, k, method=WorldModel.dynamic
            )
            return (h, post), (h, post, post_logits, prior_logits)

        (_, _), outs = jax.lax.scan(step, (h0, z0), (batch_actions, embedded, is_first, dyn_keys[:T]))
        return outs

    recurrent_states, posteriors, *_ = jax.jit(rssm_scan)(wm_params, embedded)
    latents = jnp.concatenate([posteriors, recurrent_states], -1)

    probes = {}

    # encoder fwd+bwd
    probes["encoder"] = jax.jit(
        jax.grad(lambda p, o: agent.wm(p, o, method="embed_obs").sum())
    ), (wm_params, batch_obs)

    # RSSM dynamic scan fwd+bwd (embedded given)
    def rssm_loss(p, emb):
        h, post, post_logits, prior_logits = rssm_scan(p, emb)
        return (h.sum() + post.sum() + post_logits.sum() + prior_logits.sum()).astype(jnp.float32)

    probes["rssm_scan"] = jax.jit(jax.grad(rssm_loss)), (wm_params, embedded)

    # decoder heads + reconstruction-style losses fwd+bwd (latents given)
    def dec_loss(p, lat):
        rec = agent.wm(p, lat, method="decode")
        po = MSEDistribution(rec["rgb"], dims=3)
        pr = TwoHotEncodingDistribution(agent.wm(p, lat, method="reward_logits"), dims=1)
        pc = Independent(BernoulliSafeMode(logits=agent.wm(p, lat, method="continue_logits")), 1)
        return (
            -po.log_prob(batch_obs["rgb"]).mean()
            - pr.log_prob(data["rewards"]).mean()
            - pc.log_prob(1 - data["terminated"]).mean()
        )

    probes["decoders"] = jax.jit(jax.grad(dec_loss)), (wm_params, latents)

    # imagination + actor loss fwd+bwd (world model frozen, as in the step)
    sg = jax.lax.stop_gradient
    imagined_prior0 = sg(posteriors).reshape(-1, stoch_state_size)
    recurrent0 = sg(recurrent_states).reshape(-1, recurrent_state_size)
    latent0 = jnp.concatenate([imagined_prior0, recurrent0], -1)
    k_img0, k_img, k_actor = jax.random.split(jax.random.PRNGKey(3), 3)

    def actor_sample(actor_params, latent, k):
        pre = agent.actor.apply(actor_params, sg(latent))
        actions, _ = actor_forward(pre, spec, k, greedy=False)
        return jnp.concatenate(actions, -1)

    def imagine_loss(actor_params):
        a0 = actor_sample(actor_params, latent0, k_img0)

        def img_step(carry, k):
            prior, h, actions = carry
            k_wm, k_act = jax.random.split(k)
            prior, h = agent.world_model.apply(
                wm_params, prior, h, actions, k_wm, method=WorldModel.imagination
            )
            latent = jnp.concatenate([prior, h], -1)
            next_actions = actor_sample(actor_params, latent, k_act)
            return (prior, h, next_actions), (latent, next_actions)

        _, (lats, acts) = jax.lax.scan(img_step, (imagined_prior0, recurrent0, a0), jax.random.split(k_img, horizon))
        traj = jnp.concatenate([latent0[None], lats], 0)
        imagined_actions = jnp.concatenate([a0[None], acts], 0)
        values = TwoHotEncodingDistribution(agent.critic_logits(agent_state["critic"], traj), dims=1).mean
        rewards = TwoHotEncodingDistribution(agent.wm(wm_params, traj, method="reward_logits"), dims=1).mean
        continues = Independent(
            BernoulliSafeMode(logits=agent.wm(wm_params, traj, method="continue_logits")), 1
        ).mode
        lambda_values = compute_lambda_values(rewards[1:], values[1:], continues[1:] * 0.997, 0.95)
        pre = agent.actor.apply(actor_params, sg(traj))
        _, policies = actor_forward(pre, spec, k_actor, greedy=False)
        logp = policies[0].log_prob(sg(imagined_actions))[..., None][:-1]
        return jnp.mean(logp * sg(lambda_values)) + lambda_values.mean()

    probes["imagination"] = jax.jit(jax.grad(imagine_loss)), (agent_state["actor"],)

    # critic fwd+bwd on the imagined trajectory shape ([horizon, T*B, L]:
    # the step's critic loss runs on traj[:-1])
    traj = jnp.zeros((horizon, T * B, stoch_state_size + recurrent_state_size), latents.dtype)
    lam = jnp.zeros((horizon, T * B, 1), jnp.float32)

    def critic_loss(critic_params):
        qv = TwoHotEncodingDistribution(agent.critic_logits(critic_params, traj), dims=1)
        return -(qv.log_prob(lam)).mean()

    probes["critic"] = jax.jit(jax.grad(critic_loss)), (agent_state["critic"],)
    return probes


def time_probe(grad_fn, args, iters=20):
    """On-chip phase time: run the probe `iters` times inside ONE jitted
    fori_loop (the carry is nudged by -1e-30 * grad each round, forcing a
    data dependency so the loop cannot be collapsed), so the per-call
    dispatch cost is paid once, not per iteration."""
    import jax
    import numpy as np

    params, rest = args[0], args[1:]

    @jax.jit
    def chained(p):
        def body(_, p):
            g = grad_fn(p, *rest)
            return jax.tree_util.tree_map(lambda a, b: a - 1e-30 * b, p, g)

        return jax.lax.fori_loop(0, iters, body, p)

    out = chained(params)  # compile + warm
    leaf = jax.tree_util.tree_leaves(out)[0]
    float(np.asarray(leaf.reshape(-1)[0]))
    t0 = time.perf_counter()
    out = chained(params)
    leaf = jax.tree_util.tree_leaves(out)[0]
    float(np.asarray(leaf.reshape(-1)[0]))
    return (time.perf_counter() - t0) / iters


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--trace-dir", default="/tmp/dv3_trace")
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--phases", action="store_true")
    parser.add_argument("--skip-ab", action="store_true", help="skip the fused/unfused A/B")
    args = parser.parse_args()

    import jax

    summary = {"backend": jax.default_backend(), "device": str(jax.devices()[0])}

    labels = (("fused", "1"),) if args.skip_ab else (("unfused", "0"), ("fused", "1"))
    results = {}
    for label, flag in labels:
        os.environ["SHEEPRL_TPU_FUSED_GRU"] = flag
        cfg, agent, train_fn, agent_state, opt_states, moments, data, (T, B) = build([])
        dt, carry = time_step(train_fn, agent_state, opt_states, moments, data, args.iters)
        results[label] = dt
        if label == "fused" or args.skip_ab:
            import jax.numpy as jnp

            key = jax.random.PRNGKey(1)
            tau = jnp.asarray(0.02, jnp.float32)
            lowered = train_fn.lower(*carry, data, key, tau)
            cost = lowered.compile().cost_analysis()
            if isinstance(cost, list):
                cost = cost[0]
            flops = float(cost.get("flops", 0.0)) if cost else 0.0
            summary["flops_per_step"] = flops
            summary["mfu_f32_peak"] = round(flops / dt / PEAK_FLOPS["f32"], 4) if flops else None
            summary["mfu_bf16_peak"] = round(flops / dt / PEAK_FLOPS["bf16"], 4) if flops else None
            if args.trace_dir:
                # One-step XLA trace window through the telemetry profiler
                # (the same machinery `telemetry.profiler.*` drives in runs).
                from sheeprl_tpu.telemetry import ProfilerWindow

                window = ProfilerWindow(trace_dir=args.trace_dir, start_step=0, stop_step=1)
                window.advance(0)
                s, o, m, _, _ = train_fn(*carry, data, key, tau)
                jax.block_until_ready(s["world_model"])
                window.close()
                summary["trace_dir"] = args.trace_dir

            if args.phases:
                # Rebuild fresh (non-donated) state for the probes.
                cfg, agent, _, agent_state, _, _, data, _ = build([])
                probes = build_phase_probes(cfg, agent, agent_state, data)
                phase_ms = {}
                for name, (fn, pargs) in probes.items():
                    phase_ms[name] = round(time_probe(fn, pargs, args.iters) * 1e3, 3)
                summary["phase_ms"] = phase_ms
                summary["phase_sum_ms"] = round(sum(phase_ms.values()), 3)

    for label in results:
        summary[f"train_step_ms_{label}"] = round(results[label] * 1e3, 3)
    if "unfused" in results and "fused" in results:
        summary["fused_speedup"] = round(results["unfused"] / results["fused"], 4)
    summary["batch"] = {"sequence_length": T, "batch_size": B}
    print(json.dumps(summary, indent=2))


if __name__ == "__main__":
    main()
