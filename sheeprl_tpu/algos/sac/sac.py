"""SAC, coupled training loop (reference: sheeprl/algos/sac/sac.py:32-427).

TPU-first structure:
- Per iteration: one vectorized env step (host), then `G = Ratio(policy_steps)`
  gradient steps executed inside ONE jitted call — a `lax.scan` over G
  pre-sampled minibatches with the three optimizer states (critic, actor,
  alpha) in the carry. The reference's per-minibatch python loop with three
  backward/step calls (sac.py:32-80) becomes one compiled program.
- The target-EMA cadence (every target_network_frequency policy steps,
  sac.py:56-57) is a traced scalar: tau_eff = tau * do_ema lerps either way,
  no control flow.
- The alpha-gradient all_reduce of the reference (sac.py:72) is implicit:
  the minibatch is sharded over the mesh `data` axis, so XLA psums every
  gradient, including log_alpha's.
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

from sheeprl_tpu.algos.sac.agent import SACAgent, build_agent
from sheeprl_tpu.algos.sac.loss import critic_loss, entropy_loss, policy_loss
from sheeprl_tpu.algos.sac.utils import prepare_obs, test
from sheeprl_tpu.config.instantiate import instantiate, locate
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


def _make_optimizer(optim_cfg: Dict[str, Any]) -> optax.GradientTransformation:
    optim_cfg = dict(optim_cfg)
    target = optim_cfg.pop("_target_")
    return locate(target)(**optim_cfg)


def make_gradient_step(agent: SACAgent, txs: Dict[str, optax.GradientTransformation], cfg: Dict[str, Any]):
    """Build the pure one-minibatch update ``gradient_step(carry, batch,
    tau_eff)`` shared by the host-batched and ring-sampled train steps."""
    gamma = float(cfg.algo.gamma)

    def gradient_step(carry, batch, tau_eff):
        state, opt_states = carry
        k1, k2 = jax.random.split(batch.pop("_key"))

        # --- critic update (reference: sac.py:45-53)
        next_target = agent.next_target_q_values(
            state, batch["next_observations"], batch["rewards"], batch["terminated"], gamma, k1
        )

        def qf_loss_fn(qf_params):
            qf_values = agent.q_values(qf_params, batch["observations"], batch["actions"])
            return critic_loss(qf_values, next_target, agent.num_critics)

        qf_l, qf_grads = jax.value_and_grad(qf_loss_fn)(state["qfs"])
        qf_updates, qf_opt = txs["qf"].update(qf_grads, opt_states["qf"], state["qfs"])
        state["qfs"] = optax.apply_updates(state["qfs"], qf_updates)

        # --- target EMA (reference: sac.py:56-57)
        state["qfs_target"] = agent.target_ema(state["qfs"], state["qfs_target"], tau_eff)

        # --- actor update (reference: sac.py:59-66)
        alpha = jnp.exp(state["log_alpha"])

        def actor_loss_fn(actor_params):
            actions, logprobs = agent.actions_and_log_probs(actor_params, batch["observations"], k2)
            qf_values = agent.q_values(state["qfs"], batch["observations"], actions)
            min_qf = jnp.min(qf_values, axis=-1, keepdims=True)
            return policy_loss(alpha, logprobs, min_qf), logprobs

        (actor_l, logprobs), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(state["actor"])
        actor_updates, actor_opt = txs["actor"].update(actor_grads, opt_states["actor"], state["actor"])
        state["actor"] = optax.apply_updates(state["actor"], actor_updates)

        # --- alpha update (reference: sac.py:68-74)
        def alpha_loss_fn(log_alpha):
            return entropy_loss(log_alpha, logprobs, agent.target_entropy)

        alpha_l, alpha_grads = jax.value_and_grad(alpha_loss_fn)(state["log_alpha"])
        alpha_updates, alpha_opt = txs["alpha"].update(alpha_grads, opt_states["alpha"], state["log_alpha"])
        state["log_alpha"] = optax.apply_updates(state["log_alpha"], alpha_updates)

        opt_states = {"qf": qf_opt, "actor": actor_opt, "alpha": alpha_opt}
        metrics = {"value_loss": qf_l, "policy_loss": actor_l, "alpha_loss": alpha_l}
        if probes_enabled(cfg):
            # In-jit health probe: pure reductions over the already-live grad
            # and update trees — the scalars ride the StepTimer's coalesced
            # per-interval transfer, zero extra host syncs.
            metrics.update(
                health_probe(
                    params=(state["qfs"], state["actor"], state["log_alpha"]),
                    grads=(qf_grads, actor_grads, alpha_grads),
                    updates=(qf_updates, actor_updates, alpha_updates),
                    aux={"alpha": alpha, "entropy": -jnp.mean(logprobs)},
                )
            )
        return (state, opt_states), metrics

    return gradient_step


def partition_specs(mesh) -> mesh_lib.PartitionPlan:
    """SAC's partition-spec hook: scanned host minibatches are ``[G, B, ...]``
    (batch dim 1 over `data`), ring-sampled batches are flat ``[B, ...]``;
    params follow the default wide-param model-sharding rule."""
    from jax.sharding import PartitionSpec as P

    return mesh_lib.default_partition_plan(
        mesh,
        batch_specs={"scan_batch": P(None, DATA_AXIS), "batch": P(DATA_AXIS)},
    )


def _explicit_shardings(plan, state, opt_states, data_sharding):
    """jit ``in_shardings``/``out_shardings`` for the (state, opt_states,
    data, key, tau/taus) train-step signature, derived from the *placed*
    trees so the compiled layout matches the placement byte for byte.
    Gradient sync then lowers to XLA-inserted collectives over `data`
    instead of relying on implicit layout propagation. ``data_sharding``
    covers the third arg — a batch sharding prefix, a ring-state sharding
    tree, or None (unconstrained)."""
    state_sh = mesh_lib.tree_shardings(state)
    opt_sh = mesh_lib.tree_shardings(opt_states)
    repl = plan.replicated()
    return dict(
        in_shardings=(state_sh, opt_sh, data_sharding, repl, repl),
        out_shardings=(state_sh, opt_sh, None, repl),
    )


def make_train_step(
    agent: SACAgent,
    txs: Dict[str, optax.GradientTransformation],
    cfg: Dict[str, Any],
    mesh,
    state=None,
    opt_states=None,
):
    """Build the jitted G-gradient-steps update. With the placed ``state`` /
    ``opt_states`` trees given, the jit compiles with explicit
    ``in_shardings``/``out_shardings`` over the mesh (data-sharded batch +
    the params' own committed layouts)."""
    gradient_step = make_gradient_step(agent, txs, cfg)
    plan = partition_specs(mesh)
    batch_sharding = plan.sharding("scan_batch")

    jit_kwargs = {}
    divisible = int(cfg.algo.per_rank_batch_size) % plan.data_size == 0
    if state is not None and opt_states is not None and divisible:
        jit_kwargs = _explicit_shardings(plan, state, opt_states, batch_sharding)

    @partial(jax.jit, donate_argnums=(0, 1), **jit_kwargs)
    def train_step(state, opt_states, data, key, tau_eff):
        """data: dict of [G, B, ...] minibatches; tau_eff: tau or 0.
        Returns the split-off next key so the caller never runs an eager
        (host-blocking) split between calls — the key stays device-resident."""
        next_key, key = jax.random.split(key)
        data = jax.lax.with_sharding_constraint(data, {k: batch_sharding for k in data})
        keys = jax.random.split(key, data["rewards"].shape[0])
        data = dict(data, _key=keys)
        (state, opt_states), metrics = jax.lax.scan(
            lambda carry, batch: gradient_step(carry, batch, tau_eff), (state, opt_states), data
        )
        metrics = jax.tree_util.tree_map(lambda m: m.mean(0), metrics)
        return state, opt_states, metrics, next_key

    return train_step


def make_fused_train_step(
    agent: SACAgent,
    txs: Dict[str, optax.GradientTransformation],
    cfg: Dict[str, Any],
    mesh,
    sample_fn,
    state=None,
    opt_states=None,
    ring_shardings=None,
):
    """Build the ring-sampled K-step update: each scan iteration draws its
    minibatch from the device-resident replay ring with the JAX PRNG, so the
    host samples nothing and ships no batch bytes. K rides on ``taus``'s
    length (one EMA coefficient per step — the host fills them all with the
    iteration's tau_eff), so each power-of-two bucket compiles once.

    With the placed ``state``/``opt_states`` given, the jit compiles with
    explicit ``in_shardings``/``out_shardings``; ``ring_shardings`` (from
    :meth:`DeviceReplayRing.state_shardings`) pins the carried ring layout
    so a `data`-sharded ring stays sharded across supersteps."""
    gradient_step = make_gradient_step(agent, txs, cfg)
    plan = partition_specs(mesh)
    flat_sharding = plan.sharding("batch")

    jit_kwargs = {}
    divisible = int(cfg.algo.per_rank_batch_size) % plan.data_size == 0
    if state is not None and opt_states is not None and divisible:
        jit_kwargs = _explicit_shardings(plan, state, opt_states, ring_shardings)

    @partial(jax.jit, donate_argnums=(0, 1), **jit_kwargs)
    def fused_train_step(state, opt_states, ring_state, key, taus):
        next_key, key = jax.random.split(key)
        step_keys = jax.random.split(key, taus.shape[0])

        def body(carry, x):
            k, tau_eff = x
            k_sample, k_step = jax.random.split(k)
            batch = sample_fn(ring_state, k_sample)
            batch = jax.lax.with_sharding_constraint(batch, {name: flat_sharding for name in batch})
            batch = dict(batch, _key=k_step)
            return gradient_step(carry, batch, tau_eff)

        (state, opt_states), metrics = jax.lax.scan(body, (state, opt_states), (step_keys, taus))
        metrics = jax.tree_util.tree_map(lambda m: m.mean(0), metrics)
        return state, opt_states, metrics, next_key

    return fused_train_step


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    from sheeprl_tpu.core.fused_loop import fused_enabled, sac_fused_main

    if fused_enabled(cfg):
        # Anakin lane: pure-JAX env, rollout AND train inside one jit
        # (core/fused_loop.py). The host-interaction path below is untouched.
        return sac_fused_main(runtime, cfg)

    mesh = runtime.mesh
    rank = runtime.global_rank
    world_size = jax.process_count()

    state_ckpt = None
    if cfg.checkpoint.resume_from:
        state_ckpt = load_checkpoint(cfg.checkpoint.resume_from)

    if len(cfg.algo.cnn_keys.encoder) > 0:
        warnings.warn("SAC algorithm cannot allow to use images as observations, the CNN keys will be ignored")
        cfg.algo.cnn_keys.encoder = []

    logger = get_logger(runtime, cfg)
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name, logger=logger)
    runtime.print(f"Log dir: {log_dir}")
    telemetry = runtime.telemetry.open(log_dir, rank_zero=runtime.is_global_zero, device=runtime.device)
    guard = runtime.resilience.guard(rank_zero=runtime.is_global_zero)
    watchdog = runtime.resilience.watchdog
    health = runtime.health

    envs = make_vector_env(cfg, rank, log_dir)
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("Only continuous action space is supported for the SAC agent")
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if len(cfg.algo.mlp_keys.encoder) == 0:
        raise RuntimeError("You should specify at least one MLP key for the encoder: `mlp_keys.encoder=[state]`")
    for k in cfg.algo.mlp_keys.encoder:
        if len(observation_space[k].shape) > 1:
            raise ValueError(
                "Only environments with vector-only observations are supported by the SAC agent. "
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
        # PRNG split in-graph: the jitted call is the step's only dispatch.
        next_k, sub = jax.random.split(k)
        return agent.get_actions(p, o, sub, greedy=False), next_k

    player_fn = jax.jit(_player)
    train_fn = make_train_step(agent, txs, cfg, mesh, state=agent_state, opt_states=opt_states)
    target_freq_iters = cfg.algo.critic.target_network_frequency // policy_steps_per_iter + 1

    # Device-resident replay ring (data/device_buffer.py): transitions are
    # mirrored into HBM and sampled inside the fused train jit — the host
    # sample + [G*B] batch transfer above drop out of the hot path. Falls
    # back to the host buffer when the ring won't fit the HBM budget.
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

    # Latency-aware player placement (core/player.py). Off-policy: honors
    # fabric.player_sync=async (the player may act on weights one update
    # stale, never blocking the interaction loop on the mirror transfer).
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
    # Train losses stay device-resident between log intervals; the StepTimer
    # coalesces them into ONE jax.device_get per interval and bounds the
    # interval's wall-clock with ONE block_until_ready (each sync stalls the
    # host for a full device round trip). Scalars only, so the pinned device
    # memory is negligible.
    train_timer = telemetry.step_timer("train", timer_key="Time/train_time")
    perf = telemetry.perf
    keep_train_metrics = (
        aggregator is not None and not aggregator.disabled and cfg.metric.log_level > 0
    ) or health.enabled

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
                    do_ema = iter_num % target_freq_iters == 0
                    tau_eff = np.float32(agent.tau if do_ema else 0.0)
                    remaining = per_rank_gradient_steps
                    while remaining > 0:
                        # Power-of-two buckets bound the fused graphs to
                        # log2(fused_train_steps) variants.
                        k = 1 << (min(remaining, fused_train_steps).bit_length() - 1)
                        taus = np.full(k, tau_eff, np.float32)
                        # Goodput accounting BEFORE the dispatch: arg shape
                        # specs must be captured while the buffers are alive
                        # (the jit donates them).
                        perf.note(
                            f"train/fused_k{k}", fused_train_fn,
                            (agent_state, opt_states, ring.state, train_key, taus), steps=k,
                        )
                        with train_timer.step(), watch(watchdog, "train_dispatch"):
                            agent_state, opt_states, train_metrics, train_key = fused_train_fn(
                                agent_state, opt_states, ring.state, train_key, taus,
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
                sample = rb.sample_tensors(
                    batch_size=per_rank_gradient_steps * cfg.algo.per_rank_batch_size,
                    sample_next_obs=cfg.buffer.sample_next_obs,
                )
                data = {
                    k: np.asarray(v)
                    .astype(np.float32)
                    .reshape(per_rank_gradient_steps, cfg.algo.per_rank_batch_size, *np.asarray(v).shape[2:])
                    for k, v in sample.items()
                }
                with timer("Time/train_time"):
                    do_ema = iter_num % target_freq_iters == 0
                    # tau as numpy (an eager jnp.asarray would dispatch);
                    # the PRNG split happens inside the jit.
                    tau_arr = np.asarray(agent.tau if do_ema else 0.0, np.float32)
                    perf.note(
                        f"train/g{per_rank_gradient_steps}", train_fn,
                        (agent_state, opt_states, data, train_key, tau_arr),
                        steps=per_rank_gradient_steps,
                    )
                    with train_timer.step(), watch(watchdog, "train_dispatch"):
                        agent_state, opt_states, train_metrics, train_key = train_fn(
                            agent_state,
                            opt_states,
                            data,
                            train_key,
                            tau_arr,
                        )
                    # No sync here: the dispatch stays fully async — the
                    # StepTimer queues the loss scalars device-side and
                    # bounds the interval with ONE block at the flush below.
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

        # Real next obs for the buffer: replace autoreset obs with final_obs
        # (reference: sac.py:276-284).
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
            # The interval's ONE bounding block + ONE coalesced device->host
            # transfer of every queued loss tree (StepTimer.flush) — the
            # pattern GL002 asks for, now owned by telemetry.
            fetched_train_metrics = train_timer.flush()
            # Health sentinels inspect the same coalesced fetch — no extra
            # transfer. A nonfinite hit taints the run (vetoing further
            # checkpoint saves) and escalates per cfg.health.policy.
            health.observe(policy_step, fetched_train_metrics, telemetry=telemetry)
            if aggregator and not aggregator.disabled:
                for tm in fetched_train_metrics:
                    aggregator.update("Loss/value_loss", tm["value_loss"])
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
                # Buffer-tail consistency trick: mark the episode open at the
                # write head truncated inside the snapshot, then restore
                # (reference: callback.py:87-142).
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
