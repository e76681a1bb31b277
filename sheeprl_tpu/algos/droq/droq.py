"""DroQ training loop (reference: sheeprl/algos/droq/droq.py:31-436).

SAC's loop with the DroQ recipe (https://arxiv.org/abs/2110.02034): a high
replay ratio (20 gradient steps per env step by default), Dropout+LayerNorm
critics with live dropout in online AND target networks, target EMA after
every critic update, and the actor trained on the ensemble MEAN of the
Q-values over a separately sampled batch. One jitted, donated call runs the
G critic minibatches as a `lax.scan` followed by the single actor/alpha
update — the reference's python loop of G x num_critics backward passes
becomes one compiled program.
"""

from __future__ import annotations

import copy
import os
import warnings
from functools import partial
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.droq.agent import DROQAgent, build_agent
from sheeprl_tpu.algos.droq.utils import prepare_obs, test
from sheeprl_tpu.algos.sac.loss import entropy_loss, policy_loss
from sheeprl_tpu.algos.sac.sac import _make_optimizer
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.core.interact import InteractionPipeline
from sheeprl_tpu.core.resilience import watch
from sheeprl_tpu.core import mesh as mesh_lib
from sheeprl_tpu.core.mesh import DATA_AXIS
from sheeprl_tpu.core.player import PlayerPlacement
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.data.device_buffer import DeviceReplayRing
from sheeprl_tpu.core.runtime import DispatchThrottle
from sheeprl_tpu.registry import register_algorithm
from sheeprl_tpu.telemetry.health import health_probe, probes_enabled
from sheeprl_tpu.utils.checkpoint import load_checkpoint, restore_opt_state, save_checkpoint
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs


def make_critic_step(agent: DROQAgent, txs: Dict[str, optax.GradientTransformation], cfg: Dict[str, Any]):
    """Build the pure one-minibatch critic update (scan body) shared by the
    host-batched and ring-sampled train steps."""
    gamma = float(cfg.algo.gamma)

    def critic_step(carry, batch):
        state, qf_opt = carry
        k_target, k_drop = jax.random.split(batch.pop("_key"))

        # Fixed soft target for this minibatch (reference: droq.py:99-104)
        next_target = agent.next_target_q_values(
            state, batch["next_observations"], batch["rewards"], batch["terminated"], gamma, k_target
        )

        def qf_loss_fn(qf_params):
            qf_values = agent.q_values(
                qf_params, batch["observations"], batch["actions"], dropout_key=k_drop
            )
            # Per-member MSE against the shared target, summed: identical
            # gradients to the reference's sequential per-critic steps.
            return ((qf_values - next_target) ** 2).mean(0).sum()

        qf_l, qf_grads = jax.value_and_grad(qf_loss_fn)(state["qfs"])
        qf_updates, qf_opt = txs["qf"].update(qf_grads, qf_opt, state["qfs"])
        state["qfs"] = optax.apply_updates(state["qfs"], qf_updates)
        # EMA after every critic update (reference: droq.py:117)
        state["qfs_target"] = agent.target_ema(state["qfs"], state["qfs_target"])
        metrics = {"value_loss": qf_l}
        if probes_enabled(cfg):
            # In-jit health probe over the critic grads/updates; the mean
            # over the scan axis keeps nonfinite counts > 0 (see
            # telemetry/health.py), so nothing is lost to the reduction.
            metrics.update(health_probe(params=state["qfs"], grads=qf_grads, updates=qf_updates))
        return (state, qf_opt), metrics

    return critic_step


def make_actor_alpha_update(
    agent: DROQAgent, txs: Dict[str, optax.GradientTransformation], cfg: Dict[str, Any]
):
    """Build the pure actor+alpha update over one [B, ...] observation batch
    (reference: droq.py:120-134). Returns a trailing health-aux dict (empty
    unless cfg.health probes are on) so the actor-side probe rides the same
    metrics tree as the critic scan's."""

    def actor_alpha_update(state, actor_opt_in, alpha_opt_in, observations, k_actor, k_actor_drop):
        alpha = jnp.exp(state["log_alpha"])

        def actor_loss_fn(actor_params):
            actions, logprobs = agent.actions_and_log_probs(actor_params, observations, k_actor)
            qf_values = agent.q_values(
                state["qfs"], observations, actions, dropout_key=k_actor_drop
            )
            mean_qf = jnp.mean(qf_values, axis=-1, keepdims=True)
            return policy_loss(alpha, logprobs, mean_qf), logprobs

        (actor_l, logprobs), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(state["actor"])
        actor_updates, actor_opt = txs["actor"].update(actor_grads, actor_opt_in, state["actor"])
        state["actor"] = optax.apply_updates(state["actor"], actor_updates)

        def alpha_loss_fn(log_alpha):
            return entropy_loss(log_alpha, logprobs, agent.target_entropy)

        alpha_l, alpha_grads = jax.value_and_grad(alpha_loss_fn)(state["log_alpha"])
        alpha_updates, alpha_opt = txs["alpha"].update(alpha_grads, alpha_opt_in, state["log_alpha"])
        state["log_alpha"] = optax.apply_updates(state["log_alpha"], alpha_updates)
        health_aux = {}
        if probes_enabled(cfg):
            probe = health_probe(
                params=(state["actor"], state["log_alpha"]),
                grads=(actor_grads, alpha_grads),
                updates=(actor_updates, alpha_updates),
            )
            # Prefix the actor-side probe so it doesn't collide with the
            # critic scan's standard health/ keys.
            health_aux = {k.replace("health/", "health/actor_"): v for k, v in probe.items()}
            health_aux.update(health_probe(aux={"alpha": alpha, "entropy": -jnp.mean(logprobs)}))
        return state, actor_opt, alpha_opt, actor_l, alpha_l, health_aux

    return actor_alpha_update


def partition_specs(mesh) -> mesh_lib.PartitionPlan:
    """DroQ's partition-spec hook: scanned critic minibatches are
    ``[G, B, ...]`` (batch dim 1 over `data`), the actor batch and
    ring-sampled batches are flat ``[B, ...]``; params follow the default
    wide-param model-sharding rule."""
    from jax.sharding import PartitionSpec as P

    return mesh_lib.default_partition_plan(
        mesh,
        batch_specs={"scan_batch": P(None, DATA_AXIS), "batch": P(DATA_AXIS)},
    )


def make_train_step(
    agent: DROQAgent,
    txs: Dict[str, optax.GradientTransformation],
    cfg: Dict[str, Any],
    mesh,
    state=None,
    opt_states=None,
):
    """Build the jitted (G critic steps + 1 actor step) update. With the
    placed ``state``/``opt_states`` trees given, the jit compiles with
    explicit ``in_shardings``/``out_shardings`` over the mesh."""
    critic_step = make_critic_step(agent, txs, cfg)
    actor_alpha_update = make_actor_alpha_update(agent, txs, cfg)
    plan = partition_specs(mesh)
    batch_sharding = plan.sharding("scan_batch")
    flat_sharding = plan.sharding("batch")

    jit_kwargs = {}
    if (
        state is not None
        and opt_states is not None
        and int(cfg.algo.per_rank_batch_size) % plan.data_size == 0
    ):
        state_sh = mesh_lib.tree_shardings(state)
        opt_sh = mesh_lib.tree_shardings(opt_states)
        repl = plan.replicated()
        jit_kwargs = dict(
            in_shardings=(state_sh, opt_sh, batch_sharding, flat_sharding, repl),
            out_shardings=(state_sh, opt_sh, None, repl),
        )

    @partial(jax.jit, donate_argnums=(0, 1), **jit_kwargs)
    def train_step(state, opt_states, critic_data, actor_data, key):
        """critic_data: dict of [G, B, ...]; actor_data: dict of [B, ...]."""
        next_key, key = jax.random.split(key)

        critic_data = jax.lax.with_sharding_constraint(
            critic_data, {k: batch_sharding for k in critic_data}
        )
        actor_data = jax.lax.with_sharding_constraint(
            actor_data, {k: flat_sharding for k in actor_data}
        )
        k_scan, k_actor, k_actor_drop = jax.random.split(key, 3)
        keys = jax.random.split(k_scan, critic_data["rewards"].shape[0])
        critic_data = dict(critic_data, _key=keys)
        (state, qf_opt), qf_metrics = jax.lax.scan(
            critic_step, (state, opt_states["qf"]), critic_data
        )

        state, actor_opt, alpha_opt, actor_l, alpha_l, health_aux = actor_alpha_update(
            state, opt_states["actor"], opt_states["alpha"], actor_data["observations"],
            k_actor, k_actor_drop,
        )

        opt_states = {"qf": qf_opt, "actor": actor_opt, "alpha": alpha_opt}
        metrics = jax.tree_util.tree_map(lambda m: m.mean(0), qf_metrics)
        metrics["policy_loss"] = actor_l
        metrics["alpha_loss"] = alpha_l
        metrics.update(health_aux)
        return state, opt_states, metrics, next_key

    return train_step


def make_fused_train_step(
    agent: DROQAgent,
    txs: Dict[str, optax.GradientTransformation],
    cfg: Dict[str, Any],
    mesh,
    sample_fn,
    state=None,
    opt_states=None,
    ring_shardings=None,
):
    """Build the ring-sampled K-critic-step update: every critic minibatch —
    and the actor's separate batch — is drawn from the device-resident
    replay ring inside the jit. ``with_actor`` (static) runs the single
    actor+alpha update, so the caller enables it only on the LAST bucket of
    an iteration, preserving the one-actor-step-per-env-step cadence.

    With the placed ``state``/``opt_states`` given, the jit compiles with
    explicit ``in_shardings``/``out_shardings``; ``ring_shardings`` pins the
    `data`-sharded ring layout across calls."""
    critic_step = make_critic_step(agent, txs, cfg)
    actor_alpha_update = make_actor_alpha_update(agent, txs, cfg)
    plan = partition_specs(mesh)
    flat_sharding = plan.sharding("batch")

    def _shard(batch):
        return jax.lax.with_sharding_constraint(batch, {k: flat_sharding for k in batch})

    jit_kwargs = {}
    if (
        state is not None
        and opt_states is not None
        and int(cfg.algo.per_rank_batch_size) % plan.data_size == 0
    ):
        state_sh = mesh_lib.tree_shardings(state)
        opt_sh = mesh_lib.tree_shardings(opt_states)
        repl = plan.replicated()
        # static args (k_steps, with_actor) are excluded from in_shardings.
        jit_kwargs = dict(
            in_shardings=(state_sh, opt_sh, ring_shardings, repl),
            out_shardings=(state_sh, opt_sh, None, repl),
        )

    @partial(jax.jit, donate_argnums=(0, 1), static_argnums=(4, 5), **jit_kwargs)
    def fused_train_step(state, opt_states, ring_state, key, k_steps, with_actor):
        next_key, key = jax.random.split(key)
        k_scan, k_actor_sample, k_actor, k_actor_drop = jax.random.split(key, 4)
        step_keys = jax.random.split(k_scan, k_steps)

        def body(carry, k):
            k_sample, k_step = jax.random.split(k)
            batch = _shard(sample_fn(ring_state, k_sample))
            batch = dict(batch, _key=k_step)
            return critic_step(carry, batch)

        (state, qf_opt), qf_metrics = jax.lax.scan(body, (state, opt_states["qf"]), step_keys)
        metrics = jax.tree_util.tree_map(lambda m: m.mean(0), qf_metrics)
        if with_actor:
            actor_batch = _shard(sample_fn(ring_state, k_actor_sample))
            state, actor_opt, alpha_opt, actor_l, alpha_l, health_aux = actor_alpha_update(
                state, opt_states["actor"], opt_states["alpha"], actor_batch["observations"],
                k_actor, k_actor_drop,
            )
            opt_states = {"qf": qf_opt, "actor": actor_opt, "alpha": alpha_opt}
            metrics["policy_loss"] = actor_l
            metrics["alpha_loss"] = alpha_l
            metrics.update(health_aux)
        else:
            opt_states = {"qf": qf_opt, "actor": opt_states["actor"], "alpha": opt_states["alpha"]}
        return state, opt_states, metrics, next_key

    return fused_train_step


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    mesh = runtime.mesh
    rank = runtime.global_rank
    world_size = jax.process_count()

    if "minedojo" in str(cfg.env.wrapper.get("_target_", "")).lower():
        raise ValueError(
            "MineDojo is not currently supported by DroQ agent, since it does not take "
            "into consideration the action masks provided by the environment, but needed "
            "in order to play correctly the game. "
            "As an alternative you can use one of the Dreamers' agents."
        )

    state_ckpt = None
    if cfg.checkpoint.resume_from:
        state_ckpt = load_checkpoint(cfg.checkpoint.resume_from)

    if len(cfg.algo.cnn_keys.encoder) > 0:
        warnings.warn("DroQ algorithm cannot allow to use images as observations, the CNN keys will be ignored")
        cfg.algo.cnn_keys.encoder = []

    logger = get_logger(runtime, cfg)
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name, logger=logger)
    telemetry = runtime.telemetry.open(log_dir, rank_zero=runtime.is_global_zero, device=runtime.device)
    guard = runtime.resilience.guard(rank_zero=runtime.is_global_zero)
    watchdog = runtime.resilience.watchdog
    health = runtime.health
    runtime.print(f"Log dir: {log_dir}")

    envs = make_vector_env(cfg, rank, log_dir)
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("Only continuous action space is supported for the DroQ agent")
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if len(cfg.algo.mlp_keys.encoder) == 0:
        raise RuntimeError("You should specify at least one MLP key for the encoder: `mlp_keys.encoder=[state]`")
    for k in cfg.algo.mlp_keys.encoder:
        if len(observation_space[k].shape) > 1:
            raise ValueError(
                "Only environments with vector-only observations are supported by the DroQ agent. "
                f"The observation with key '{k}' has shape {observation_space[k].shape}. "
                f"Provided environment: {cfg.env.id}"
            )
    if cfg.metric.log_level > 0:
        runtime.print("Encoder MLP keys:", cfg.algo.mlp_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)

    # Eager flax/optax init runs host-side (each eager dispatch pays a
    # host-device round trip); the finished trees then move to the mesh.
    with runtime.host_init():
        agent, agent_state = build_agent(
            runtime, cfg, observation_space, action_space,
            state_ckpt["agent"] if state_ckpt is not None else None,
        )

        txs = {
            "qf": _make_optimizer(cfg.algo.critic.optimizer),
            "actor": _make_optimizer(cfg.algo.actor.optimizer),
            "alpha": _make_optimizer(cfg.algo.alpha.optimizer),
        }
        opt_states = {
            "qf": txs["qf"].init(agent_state["qfs"]),
            "actor": txs["actor"].init(agent_state["actor"]),
            "alpha": txs["alpha"].init(agent_state["log_alpha"]),
        }
        if state_ckpt is not None:
            for name, ckpt_key in (("qf", "qf_optimizer"), ("actor", "actor_optimizer"), ("alpha", "alpha_optimizer")):
                opt_states[name] = restore_opt_state(opt_states[name], state_ckpt[ckpt_key])
    agent_state = runtime.shard_params(agent_state)
    opt_states = runtime.shard_params(opt_states)
    # Arm per-shard goodput accounting and record the topology + param
    # layouts for the `telemetry mesh` inspector, now that both exist.
    telemetry.set_mesh(mesh)
    telemetry.record_param_layouts(agent_state)

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    buffer_size = cfg.buffer.size // int(cfg.env.num_envs * world_size) if not cfg.dry_run else 1
    rb = ReplayBuffer(
        buffer_size,
        cfg.env.num_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
    )
    if state_ckpt is not None and cfg.buffer.checkpoint and state_ckpt.get("rb") is not None:
        rb = state_ckpt["rb"]

    last_train = 0
    train_step_count = 0
    start_iter = (state_ckpt["iter_num"] // world_size) + 1 if state_ckpt is not None else 1
    policy_step = state_ckpt["iter_num"] * cfg.env.num_envs if state_ckpt is not None else 0
    last_log = state_ckpt["last_log"] if state_ckpt is not None else 0
    last_checkpoint = state_ckpt["last_checkpoint"] if state_ckpt is not None else 0
    policy_steps_per_iter = int(cfg.env.num_envs * world_size)
    total_iters = int(cfg.algo.total_steps // policy_steps_per_iter) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_iter if not cfg.dry_run else 0
    prefill_steps = learning_starts - int(learning_starts > 0)
    if state_ckpt is not None:
        cfg.algo.per_rank_batch_size = state_ckpt["batch_size"] // world_size
        learning_starts += start_iter
        prefill_steps += start_iter

    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if state_ckpt is not None:
        ratio.load_state_dict(state_ckpt["ratio"])

    if cfg.metric.log_level > 0 and cfg.metric.log_every % policy_steps_per_iter != 0:
        warnings.warn(
            f"The metric.log_every parameter ({cfg.metric.log_every}) is not a multiple of the "
            f"policy_steps_per_iter value ({policy_steps_per_iter}), so "
            "the metrics will be logged at the nearest greater multiple of the policy_steps_per_iter value."
        )
    if cfg.checkpoint.every % policy_steps_per_iter != 0:
        warnings.warn(
            f"The checkpoint.every parameter ({cfg.checkpoint.every}) is not a multiple of the "
            f"policy_steps_per_iter value ({policy_steps_per_iter}), so "
            "the checkpoint will be saved at the nearest greater multiple of the policy_steps_per_iter value."
        )

    def _player(p, o, k):
        next_k, sub = jax.random.split(k)
        return agent.get_actions(p, o, sub, greedy=False), next_k

    player_fn = jax.jit(_player)
    train_fn = make_train_step(agent, txs, cfg, mesh, state=agent_state, opt_states=opt_states)

    # Device-resident replay ring (data/device_buffer.py): transitions are
    # mirrored into HBM and sampled inside the fused train jit — the host
    # [G*B] critic sample + transfer drop out of the hot path. Falls back
    # to the host buffer when the ring won't fit the HBM budget.
    use_device_buffer = bool(cfg.buffer.get("device", False))
    fused_train_steps = max(int(cfg.algo.get("fused_train_steps", 1)), 1)
    ring = None
    fused_train_fn = None
    ring_span = 1 + int(bool(cfg.buffer.sample_next_obs))
    if use_device_buffer:
        ring = DeviceReplayRing(
            buffer_size,
            cfg.env.num_envs,
            obs_keys=("observations",),
            hbm_fraction=float(cfg.buffer.get("device_hbm_fraction", 0.4)),
            device=mesh.devices.flat[0],
            mesh=mesh,
        )
        if state_ckpt is not None and cfg.buffer.checkpoint and state_ckpt.get("rb") is not None:
            ring.load_host_buffer(rb)
        ring_sample_fn = ring.make_sample_fn(
            cfg.algo.per_rank_batch_size,
            sequence_length=1,
            sample_next_obs=bool(cfg.buffer.sample_next_obs),
        )
        fused_train_fn = make_fused_train_step(
            agent, txs, cfg, mesh, ring_sample_fn,
            state=agent_state, opt_states=opt_states, ring_shardings=ring.state_shardings(),
        )

    # Latency-aware player placement (core/player.py); off-policy: honors
    # fabric.player_sync=async.
    placement = PlayerPlacement.resolve(cfg, mesh.devices.flat[0], params=agent_state["actor"])
    placement.push(agent_state["actor"])

    rollout_key, train_key = jax.random.split(jax.random.fold_in(runtime.root_key, rank))
    rollout_key = placement.put(rollout_key)

    # Pipelined interaction (core/interact.py): per-slice policy dispatch +
    # async action fetch + double-buffered obs staging. slices=1/async off is
    # bit-identical to the serial loop.
    pipeline = InteractionPipeline.from_config(cfg)
    pipeline.watchdog = watchdog
    pipeline.set_key(rollout_key)
    single_action_shape = envs.single_action_space.shape

    def _pipeline_policy(np_obs, state, key):
        with placement.ctx():
            actions_j, next_key = player_fn(placement.params(), np_obs, key)
        return actions_j, state, next_key

    def _prepare_slice(obs_slice, out=None):
        n = len(next(iter(obs_slice.values())))
        return prepare_obs(obs_slice, mlp_keys=mlp_keys, num_envs=n, out=out)

    def _to_env_actions(host_actions, n_envs):
        return host_actions.reshape((n_envs, *single_action_shape))

    step_data = {}
    obs = pipeline.stash_obs(envs.reset(seed=cfg.seed)[0])

    cumulative_per_rank_gradient_steps = 0
    # Bound async in-flight train dispatches (core/runtime.py: an
    # unbounded queue pins every pending call's sampled batch on host).
    dispatch_throttle = DispatchThrottle()
    # Coalesced loss fetch + interval bounding (telemetry/step_timer.py):
    # ONE block_until_ready + ONE device_get per log interval.
    train_timer = telemetry.step_timer("train", timer_key="Time/train_time")
    perf = telemetry.perf
    keep_train_metrics = (aggregator is not None and not aggregator.disabled) or health.enabled

    # The iteration's gradient steps, factored out so the pipelined
    # interaction can dispatch them between the action-fetch submit and its
    # harvest (pipeline.overlap_train): train compute then overlaps the D2H
    # copy and the host env step, at the cost of train batches lagging the
    # buffer by one transition.
    def run_train(iter_num: int) -> None:
        nonlocal agent_state, opt_states, train_key, train_step_count, cumulative_per_rank_gradient_steps
        if iter_num < learning_starts:
            return
        per_rank_gradient_steps = ratio((policy_step - prefill_steps + policy_steps_per_iter) / world_size)
        if per_rank_gradient_steps > 0:
            if ring is not None and ring.active:
                ring.flush()
            use_ring = ring is not None and ring.active and ring.ready(ring_span)
            if use_ring:
                with timer("Time/train_time"):
                    remaining = per_rank_gradient_steps
                    while remaining > 0:
                        # Power-of-two buckets bound the fused graphs to
                        # log2(fused_train_steps) variants; the actor
                        # (trained once per env step in the reference)
                        # rides only on the LAST bucket.
                        k = 1 << (min(remaining, fused_train_steps).bit_length() - 1)
                        with_actor = remaining - k == 0
                        # Goodput accounting BEFORE the dispatch: arg shape
                        # specs must be captured while the buffers are alive
                        # (the jit donates them).
                        perf.note(
                            f"train/fused_k{k}_a{int(with_actor)}", fused_train_fn,
                            (agent_state, opt_states, ring.state, train_key, k, with_actor),
                            steps=k,
                        )
                        with train_timer.step(), watch(watchdog, "train_dispatch"):
                            agent_state, opt_states, train_metrics, train_key = fused_train_fn(
                                agent_state, opt_states, ring.state, train_key, k, with_actor
                            )
                        train_timer.pend(
                            agent_state["actor"], train_metrics if keep_train_metrics else None
                        )
                        dispatch_throttle.add(train_metrics)
                        cumulative_per_rank_gradient_steps += k
                        remaining -= k
                    placement.push(agent_state["actor"])
                train_step_count += world_size
            else:
                # One big critic sample + one separate actor sample
                # (reference: droq.py:44-94).
                critic_sample = rb.sample_tensors(
                    batch_size=per_rank_gradient_steps * cfg.algo.per_rank_batch_size,
                    sample_next_obs=cfg.buffer.sample_next_obs,
                )
                critic_data = {
                    k: np.asarray(v)
                    .astype(np.float32)
                    .reshape(per_rank_gradient_steps, cfg.algo.per_rank_batch_size, *np.asarray(v).shape[2:])
                    for k, v in critic_sample.items()
                }
                actor_sample = rb.sample_tensors(
                    batch_size=cfg.algo.per_rank_batch_size,
                    sample_next_obs=cfg.buffer.sample_next_obs,
                )
                actor_data = {
                    k: np.asarray(v)
                    .astype(np.float32)
                    .reshape(cfg.algo.per_rank_batch_size, *np.asarray(v).shape[2:])
                    for k, v in actor_sample.items()
                }
                with timer("Time/train_time"):
                    perf.note(
                        f"train/g{per_rank_gradient_steps}", train_fn,
                        (agent_state, opt_states, critic_data, actor_data, train_key),
                        steps=per_rank_gradient_steps,
                    )
                    with train_timer.step(), watch(watchdog, "train_dispatch"):
                        agent_state, opt_states, train_metrics, train_key = train_fn(
                            agent_state, opt_states, critic_data, actor_data, train_key
                        )
                    # No sync here: the StepTimer queues the loss scalars
                    # device-side and bounds the interval with ONE block at
                    # the log-interval flush.
                    train_timer.pend(
                        agent_state["actor"], train_metrics if keep_train_metrics else None
                    )
                    dispatch_throttle.add(train_metrics)
                    placement.push(agent_state["actor"])
                    cumulative_per_rank_gradient_steps += per_rank_gradient_steps
                train_step_count += world_size

    for iter_num in range(start_iter, total_iters + 1):
        policy_step += policy_steps_per_iter
        telemetry.advance(policy_step)
        guard.advance(policy_step)

        trained_in_flight = False
        with timer("Time/env_interaction_time"), perf.infeed():
            if iter_num <= learning_starts:
                actions = envs.action_space.sample()
                next_obs, rewards, terminated, truncated, infos = envs.step(
                    actions.reshape(envs.action_space.shape)
                )
                next_obs = pipeline.stash_obs(next_obs)
            else:
                # Overlap the train dispatch with the action copy + env step
                # only once the buffer has at least one post-prefill
                # transition (at the very first train the buffer would
                # otherwise be one step short).
                trained_in_flight = pipeline.overlap_train and iter_num > learning_starts + 1
                res = pipeline.interact(
                    envs,
                    obs,
                    _pipeline_policy,
                    prepare=_prepare_slice,
                    to_env_actions=_to_env_actions,
                    before_harvest=(lambda: run_train(iter_num)) if trained_in_flight else None,
                )
                actions, next_obs, rewards, terminated, truncated, infos = (
                    res.outputs,
                    res.obs,
                    res.rewards,
                    res.terminated,
                    res.truncated,
                    res.infos,
                )
            rewards = rewards.reshape(cfg.env.num_envs, -1)

        if cfg.metric.log_level > 0 and "final_info" in infos:
            fi = infos["final_info"]
            for i in np.nonzero(fi.get("_episode", []))[0]:
                ep_rew = float(fi["episode"]["r"][i])
                ep_len = float(fi["episode"]["l"][i])
                if aggregator and not aggregator.disabled:
                    aggregator.update("Rewards/rew_avg", ep_rew)
                    aggregator.update("Game/ep_len_avg", ep_len)
                runtime.print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={ep_rew}")

        real_next_obs = copy.deepcopy(next_obs)
        if "final_obs" in infos:
            done_mask = np.logical_or(terminated, truncated)
            for idx in np.nonzero(done_mask)[0]:
                final = infos["final_obs"][idx]
                if final is not None:
                    for k, v in final.items():
                        real_next_obs[k][idx] = v
        real_next_obs_cat = np.concatenate([real_next_obs[k] for k in mlp_keys], axis=-1).astype(np.float32)

        step_data["terminated"] = terminated.reshape(1, cfg.env.num_envs, -1).astype(np.uint8)
        step_data["truncated"] = truncated.reshape(1, cfg.env.num_envs, -1).astype(np.uint8)
        step_data["actions"] = actions.reshape(1, cfg.env.num_envs, -1)
        step_data["observations"] = np.concatenate([obs[k] for k in mlp_keys], axis=-1).astype(np.float32)[np.newaxis]
        if not cfg.buffer.sample_next_obs:
            step_data["next_observations"] = real_next_obs_cat[np.newaxis]
        step_data["rewards"] = rewards[np.newaxis].astype(np.float32)
        rb.add(step_data, validate_args=cfg.buffer.validate_args)
        if ring is not None:
            ring.add(step_data)

        obs = next_obs

        if not trained_in_flight:
            run_train(iter_num)

        should_log = cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters
        )
        if should_log:
            # ONE bounding block + ONE device->host transfer for the whole
            # interval (StepTimer.flush) — the coalesced GL002 pattern.
            fetched_train_metrics = train_timer.flush()
            # Health sentinels inspect the same coalesced fetch — no extra
            # transfer; a nonfinite hit taints the run and escalates.
            health.observe(policy_step, fetched_train_metrics, telemetry=telemetry)
            if aggregator and not aggregator.disabled:
                for tm in fetched_train_metrics:
                    aggregator.update("Loss/value_loss", tm["value_loss"])
                    # Ring-path buckets without the actor step carry no
                    # policy/alpha losses.
                    if "policy_loss" in tm:
                        aggregator.update("Loss/policy_loss", tm["policy_loss"])
                        aggregator.update("Loss/alpha_loss", tm["alpha_loss"])
                # Collective when sync_on_compute is on: every rank joins;
                # only rank 0 (the only rank with a logger) writes.
                aggregator.log_and_reset(logger, policy_step)
            telemetry.log_counters(logger, policy_step)
        if should_log and logger is not None:
            logger.log(
                "Params/replay_ratio", cumulative_per_rank_gradient_steps * world_size / policy_step, policy_step
            )
            if not timer.disabled:
                timer_metrics = timer.compute()
                if timer_metrics.get("Time/train_time", 0) > 0:
                    logger.log(
                        "Time/sps_train",
                        (train_step_count - last_train) / timer_metrics["Time/train_time"],
                        policy_step,
                    )
                if timer_metrics.get("Time/env_interaction_time", 0) > 0:
                    logger.log(
                        "Time/sps_env_interaction",
                        ((policy_step - last_log) / world_size * cfg.env.action_repeat)
                        / timer_metrics["Time/env_interaction_time"],
                        policy_step,
                    )
                timer.reset()
        if should_log:
            last_log = policy_step
            last_train = train_step_count

        if health.allow_save() and (
            (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every)
            or ((iter_num == total_iters or guard.preempted) and cfg.checkpoint.save_last)
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": agent_state,
                "qf_optimizer": opt_states["qf"],
                "actor_optimizer": opt_states["actor"],
                "alpha_optimizer": opt_states["alpha"],
                "ratio": ratio.state_dict(),
                "iter_num": iter_num * world_size,
                "batch_size": cfg.algo.per_rank_batch_size * world_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
            }
            ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{rank}.ckpt")
            saved_tail = None
            tail = (rb._pos - 1) % rb.buffer_size
            if cfg.buffer.checkpoint:
                if rb["truncated"] is not None:
                    saved_tail = np.asarray(rb["truncated"][tail, :]).copy()
                    rb["truncated"][tail, :] = 1
                ckpt_state["rb"] = rb
            if runtime.is_global_zero:
                save_checkpoint(ckpt_path, ckpt_state, keep_last=cfg.checkpoint.keep_last)
            if saved_tail is not None:
                rb["truncated"][tail, :] = saved_tail

        if guard.preempted:
            runtime.print(f"Preemption: exiting cleanly after final checkpoint at policy step {policy_step}")
            break
    pipeline.publish()
    envs.close()
    if runtime.is_global_zero and cfg.algo.run_test and not guard.preempted:
        test(agent, agent_state, runtime, cfg, log_dir, logger)

    guard.close()
    telemetry.close()
    if logger is not None:
        logger.close()
