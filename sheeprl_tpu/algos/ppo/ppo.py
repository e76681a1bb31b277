"""PPO, coupled training loop (reference: sheeprl/algos/ppo/ppo.py:30-453).

TPU-first structure:
- Rollout: the jitted `player_step` samples actions on device; env stepping
  stays host python (gymnasium vector env). Pixels travel host→device as
  uint8; normalization happens inside jit.
- GAE: one reverse `lax.scan` on device (the reference loops in python,
  utils.py:63-100).
- Update: ALL epochs × minibatches run inside ONE jitted call — permutations
  drawn in-graph, `lax.scan` over minibatches, `lax.scan` over epochs. The
  batch is sharded over the mesh's `data` axis and params are replicated, so
  XLA inserts the gradient all-reduce exactly where DDP would (SURVEY §2.1).
- Annealing (lr / clip / entropy coefs): host-computed scalars passed as
  traced args — no retrace per iteration.

Minibatching divergence (documented): the reference keeps a smaller final
minibatch (BatchSampler(drop_last=False), ppo.py:50). Static shapes require
equal minibatches, so when batch_size does not divide the rollout the index
permutation wraps modulo N — a few samples are seen twice per epoch instead.
"""

from __future__ import annotations

import os
import warnings
from functools import partial
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.ppo.agent import PPOAgent, actions_metadata, build_agent
from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.algos.ppo.utils import normalize_obs, prepare_obs, test
from sheeprl_tpu.core.interact import InteractionPipeline
from sheeprl_tpu.core.resilience import watch
from sheeprl_tpu.core import mesh as mesh_lib
from sheeprl_tpu.core.mesh import DATA_AXIS
from sheeprl_tpu.core.player import PlayerPlacement
from sheeprl_tpu.core.rollout import fuse_gae_pool, ship_rollout
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.registry import register_algorithm
from sheeprl_tpu.telemetry.health import health_probe, probes_enabled
from sheeprl_tpu.utils.checkpoint import load_checkpoint, restore_opt_state, save_checkpoint
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.ops import normalize_tensor
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import polynomial_decay, save_configs
from sheeprl_tpu.config.instantiate import instantiate


def make_optimizer(cfg: Dict[str, Any]) -> tuple:
    """Build the PPO optimizer with the lr injected as a hyperparam (so
    annealing is a hyperparam update, not a rebuild). Returns (tx, base_lr)
    — shared by the host-interaction main and the fused Anakin lane so both
    produce byte-compatible optimizer states."""
    optim_cfg = dict(cfg.algo.optimizer)
    optim_target = optim_cfg.pop("_target_")
    base_lr = float(optim_cfg.pop("lr"))

    def make_tx(lr):
        from sheeprl_tpu.config.instantiate import locate

        inner = locate(optim_target)(lr=lr, **optim_cfg)
        if cfg.algo.max_grad_norm > 0.0:
            return optax.chain(optax.clip_by_global_norm(cfg.algo.max_grad_norm), inner)
        return inner

    return optax.inject_hyperparams(make_tx)(lr=base_lr), base_lr


def partition_specs(mesh) -> mesh_lib.PartitionPlan:
    """PPO's partition-spec hook: the flat sample pool and its minibatches
    split their leading dim over `data`; raw rollouts are ``[T, E, ...]``
    with the env dim (1) over `data`; params follow the default wide-param
    model-sharding rule."""
    from jax.sharding import PartitionSpec as P

    return mesh_lib.default_partition_plan(
        mesh,
        batch_specs={"batch": P(DATA_AXIS), "rollout": P(None, DATA_AXIS)},
    )


def make_update_pool(
    agent: PPOAgent,
    tx: optax.GradientTransformation,
    cfg: Dict[str, Any],
    mesh,
):
    """Build the pure (un-jitted) full PPO update over a flat sample pool:
    ALL epochs × minibatches as nested `lax.scan`s, permutations drawn
    in-graph. Shared by :func:`make_train_step` (which jits it standalone)
    and core/fused_loop.py (which inlines it after the in-jit rollout)."""
    update_epochs = int(cfg.algo.update_epochs)
    mb_size = int(cfg.algo.per_rank_batch_size)
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    obs_keys = cnn_keys + list(cfg.algo.mlp_keys.encoder)
    normalize_advantages = bool(cfg.algo.normalize_advantages)
    clip_vloss = bool(cfg.algo.clip_vloss)
    reduction = cfg.algo.loss_reduction
    vf_coef = float(cfg.algo.vf_coef)

    gamma = float(cfg.algo.gamma)
    gae_lambda = float(cfg.algo.gae_lambda)

    plan = partition_specs(mesh)

    def loss_fn(params, batch, clip_coef, ent_coef):
        obs = normalize_obs({k: batch[k] for k in obs_keys}, cnn_keys, obs_keys)
        new_logprobs, entropy, new_values = agent.evaluate_actions(params, obs, batch["actions"])
        advantages = batch["advantages"]
        if normalize_advantages:
            advantages = normalize_tensor(advantages)
        pg_loss = policy_loss(new_logprobs, batch["logprobs"], advantages, clip_coef, reduction)
        v_loss = value_loss(new_values, batch["values"], batch["returns"], clip_coef, clip_vloss, reduction)
        ent_loss = entropy_loss(entropy, reduction)
        total = pg_loss + vf_coef * v_loss + ent_coef * ent_loss
        # Mean entropy and the standard approx-KL estimator ride along for
        # the health probe (free: both tensors are already live).
        approx_kl = jnp.mean(batch["logprobs"] - new_logprobs)
        return total, (pg_loss, v_loss, ent_loss, jnp.mean(entropy), approx_kl)

    batch_sharding = plan.sharding("batch")

    def update_pool(params, opt_state, pool, key, clip_coef, ent_coef):
        """Epoch × minibatch scans over the flat sample pool."""
        n = pool["actions"].shape[0]
        next_key, key = jax.random.split(key)
        num_mb = max(1, -(-n // mb_size))  # ceil

        def epoch_body(carry, epoch_key):
            params, opt_state = carry
            perm = jax.random.permutation(epoch_key, n)
            # wrap modulo n so every minibatch has static size mb_size
            idx = jnp.arange(num_mb * mb_size) % n
            idx = perm[idx].reshape(num_mb, mb_size)

            def mb_body(carry, mb_idx):
                params, opt_state = carry
                batch = {k: jnp.take(v, mb_idx, axis=0) for k, v in pool.items()}
                batch = jax.lax.with_sharding_constraint(
                    batch, {k: batch_sharding for k in batch}
                )
                (loss, (pg, vl, ent, ent_mean, approx_kl)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params, batch, clip_coef, ent_coef)
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                metrics = {"policy_loss": pg, "value_loss": vl, "entropy_loss": ent}
                if probes_enabled(cfg):
                    # In-jit health probe: pure reductions over the grads and
                    # updates already in scope; the scalars ride the interval's
                    # coalesced transfer (zero extra host syncs).
                    metrics.update(
                        health_probe(
                            params=params,
                            grads=grads,
                            updates=updates,
                            aux={"entropy": ent_mean, "approx_kl": approx_kl},
                        )
                    )
                return (params, opt_state), metrics

            (params, opt_state), metrics = jax.lax.scan(mb_body, (params, opt_state), idx)
            return (params, opt_state), jax.tree_util.tree_map(lambda m: m.mean(0), metrics)

        keys = jax.random.split(key, update_epochs)
        (params, opt_state), metrics = jax.lax.scan(epoch_body, (params, opt_state), keys)
        return params, opt_state, jax.tree_util.tree_map(lambda m: m.mean(0), metrics), next_key

    return update_pool


def make_train_step(
    agent: PPOAgent,
    tx: optax.GradientTransformation,
    cfg: Dict[str, Any],
    mesh,
    fused_gae: bool = True,
    params=None,
    opt_state=None,
):
    """Build the jitted full-update function (epochs × minibatches in-graph).

    ``fused_gae=True`` (the coupled loop): the jit takes the raw rollout —
    big tensors flat ``(T*E, ...)``, per-step scalars ``(T, E, 1)``, the
    final obs — and runs bootstrap + GAE in-graph before the scans (see
    core/rollout.py for the transfer layout). ``fused_gae=False``
    (ppo_decoupled, which computes GAE on the PLAYER device and scatters
    the finished pool to the trainer partition): the jit takes the flat
    pool with returns/advantages already present.

    With the placed ``params``/``opt_state`` trees given, the jit compiles
    with explicit ``in_shardings``/``out_shardings`` over the mesh (env dim
    of the rollout over `data`, the params' own committed layouts carried
    through), so gradient sync is XLA-inserted collectives by construction.
    """
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    obs_keys = cnn_keys + list(cfg.algo.mlp_keys.encoder)
    gamma = float(cfg.algo.gamma)
    gae_lambda = float(cfg.algo.gae_lambda)
    update_pool = make_update_pool(agent, tx, cfg, mesh)
    plan = partition_specs(mesh)

    explicit = params is not None and opt_state is not None
    params_sh = mesh_lib.tree_shardings(params) if explicit else None
    opt_sh = mesh_lib.tree_shardings(opt_state) if explicit else None
    repl = plan.replicated()

    if not fused_gae:
        jit_kwargs = {}
        if explicit:
            # The decoupled pool arrives pre-placed by the player->trainer
            # scatter; leave it unconstrained and pin only state + scalars.
            jit_kwargs = dict(
                in_shardings=(params_sh, opt_sh, None, repl, repl, repl),
                out_shardings=(params_sh, opt_sh, None, repl),
            )

        @partial(jax.jit, donate_argnums=(0, 1), **jit_kwargs)
        def train_step(params, opt_state, pool, key, clip_coef, ent_coef):
            return update_pool(params, opt_state, pool, key, clip_coef, ent_coef)

        return train_step

    jit_kwargs = {}
    if explicit and int(cfg.env.num_envs) % plan.data_size == 0:
        jit_kwargs = dict(
            in_shardings=(
                params_sh,
                opt_sh,
                plan.sharding("rollout"),  # [T, E, ...]: env dim over `data`
                plan.sharding("batch"),  # next_obs [E, ...]
                repl,
                repl,
                repl,
            ),
            out_shardings=(params_sh, opt_sh, None, repl),
        )

    @partial(jax.jit, donate_argnums=(0, 1), **jit_kwargs)
    def train_step(params, opt_state, data, next_obs, key, clip_coef, ent_coef):
        # data is (T, E, ...) env-sharded (core/rollout.py); bootstrap +
        # GAE + flattening happen in-graph via the shared prologue.
        pool = fuse_gae_pool(
            agent, params, data, next_obs, (*obs_keys, "actions", "logprobs"),
            gamma, gae_lambda, include_values=True,
        )
        return update_pool(params, opt_state, pool, key, clip_coef, ent_coef)

    return train_step


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    from sheeprl_tpu.core.fused_loop import fused_enabled, ppo_fused_main

    if fused_enabled(cfg):
        # Anakin lane: pure-JAX env, rollout AND train inside one jit
        # (core/fused_loop.py). The host-interaction path below is untouched.
        return ppo_fused_main(runtime, cfg)

    initial_ent_coef = float(cfg.algo.ent_coef)
    initial_clip_coef = float(cfg.algo.clip_coef)
    mesh = runtime.mesh

    state = None
    if cfg.checkpoint.resume_from:
        state = load_checkpoint(cfg.checkpoint.resume_from)

    logger = get_logger(runtime, cfg)
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name, logger=logger)
    runtime.print(f"Log dir: {log_dir}")
    telemetry = runtime.telemetry.open(log_dir, rank_zero=runtime.is_global_zero, device=runtime.device)
    guard = runtime.resilience.guard(rank_zero=runtime.is_global_zero)
    watchdog = runtime.resilience.watchdog
    health = runtime.health

    # ----------------------------------------------------------------- envs
    rank = runtime.global_rank
    envs = make_vector_env(cfg, rank, log_dir)
    observation_space = envs.single_observation_space
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if cfg.algo.cnn_keys.encoder + cfg.algo.mlp_keys.encoder == []:
        raise RuntimeError(
            "You should specify at least one CNN keys or MLP keys from the cli: "
            "`algo.cnn_keys.encoder=[rgb]` or `algo.mlp_keys.encoder=[state]`"
        )
    if cfg.metric.log_level > 0:
        runtime.print("Encoder CNN keys:", cfg.algo.cnn_keys.encoder)
        runtime.print("Encoder MLP keys:", cfg.algo.mlp_keys.encoder)
    obs_keys = cfg.algo.cnn_keys.encoder + cfg.algo.mlp_keys.encoder
    cnn_keys = cfg.algo.cnn_keys.encoder

    actions_dim, is_continuous = actions_metadata(envs.single_action_space)
    clip_rewards_fn = (lambda r: np.tanh(r)) if cfg.env.clip_rewards else (lambda r: r)

    # ---------------------------------------------------------------- agent
    # Eager flax/optax init runs host-side (each eager dispatch pays a
    # host-device round trip); the finished trees then move to the mesh.
    with runtime.host_init():
        agent, params = build_agent(
            runtime, actions_dim, is_continuous, cfg, observation_space,
            state["agent"] if state is not None else None,
        )

        tx, base_lr = make_optimizer(cfg)
        opt_state = tx.init(params)
        if state is not None:
            opt_state = restore_opt_state(opt_state, state["optimizer"])
    params = runtime.shard_params(params)
    opt_state = runtime.shard_params(opt_state)
    # Arm per-shard goodput accounting and record the topology + param
    # layouts for the `telemetry mesh` inspector, now that both exist.
    telemetry.set_mesh(mesh)
    telemetry.record_param_layouts(params)

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    # -------------------------------------------------------------- metrics
    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    # --------------------------------------------------------------- buffer
    if cfg.buffer.size < cfg.algo.rollout_steps:
        raise ValueError(
            f"The size of the buffer ({cfg.buffer.size}) cannot be lower "
            f"than the rollout steps ({cfg.algo.rollout_steps})"
        )
    rb = ReplayBuffer(
        cfg.buffer.size,
        cfg.env.num_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        obs_keys=obs_keys,
    )

    # ------------------------------------------------------------- counters
    world_size = jax.process_count()
    last_train = 0
    train_step_count = 0
    start_iter = (state["iter_num"] // world_size) + 1 if state is not None else 1
    policy_step = state["iter_num"] * cfg.env.num_envs * cfg.algo.rollout_steps if state is not None else 0
    last_log = state["last_log"] if state is not None else 0
    last_checkpoint = state["last_checkpoint"] if state is not None else 0
    policy_steps_per_iter = int(cfg.env.num_envs * cfg.algo.rollout_steps * world_size)
    total_iters = cfg.algo.total_steps // policy_steps_per_iter if not cfg.dry_run else 1
    if state is not None:
        cfg.algo.per_rank_batch_size = state["batch_size"] // world_size

    rollout_size = int(cfg.algo.rollout_steps * cfg.env.num_envs)
    if rollout_size % int(cfg.algo.per_rank_batch_size) != 0:
        warnings.warn(
            f"rollout size ({rollout_size}) is not divisible by per_rank_batch_size "
            f"({cfg.algo.per_rank_batch_size}): static minibatch shapes require wrapping the "
            "index permutation, so a few samples will be used twice per epoch."
        )

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

    # ---------------------------------------------------------- jitted fns
    player_step_fn = jax.jit(agent.player_step)
    # get_values_fn survives only for the (rare) mid-rollout truncation
    # bootstrap; end-of-rollout bootstrap + GAE live inside train_fn.
    get_values_fn = jax.jit(agent.get_values)
    train_fn = make_train_step(agent, tx, cfg, mesh, params=params, opt_state=opt_state)

    # Latency-aware player placement: the per-step policy forward runs where
    # dispatch is cheapest (core/player.py). On-policy => always-fresh mirror
    # (the rollout must see the post-update weights).
    placement = PlayerPlacement.resolve(
        cfg, mesh.devices.flat[0], params=params, force_fresh=True
    )
    placement.push(params)

    rollout_key, train_key = jax.random.split(jax.random.fold_in(runtime.root_key, rank))
    rollout_key = placement.put(rollout_key)

    # Pipelined interaction (core/interact.py): per-slice policy dispatch +
    # async action fetch + double-buffered obs staging. No train overlap here:
    # on-policy keeps fresh-weights semantics (the whole rollout must see the
    # post-update params, so train stays strictly between rollouts).
    pipeline = InteractionPipeline.from_config(cfg)
    pipeline.watchdog = watchdog
    pipeline.set_key(rollout_key)
    single_action_shape = envs.single_action_space.shape

    def _pipeline_policy(np_obs, state, key):
        with placement.ctx():
            *step_out, next_key = player_step_fn(placement.params(), np_obs, key)
        return tuple(step_out), state, next_key

    def _prepare_slice(obs_slice, out=None):
        n = len(next(iter(obs_slice.values())))
        return prepare_obs(obs_slice, cnn_keys=cnn_keys, num_envs=n, out=out)

    def _to_env_actions(host_outputs, n_envs):
        return host_outputs[1].reshape((n_envs, *single_action_shape))

    # --------------------------------------------------------------- loop
    # Coalesced loss fetch + interval bounding (telemetry/step_timer.py):
    # ONE block_until_ready + ONE device_get per log interval.
    train_timer = telemetry.step_timer("train", timer_key="Time/train_time")
    perf = telemetry.perf
    # One train_fn call runs ALL epochs × minibatches in-graph; that many
    # gradient steps per dispatch for the goodput steps/s gauge.
    gradient_steps_per_update = int(cfg.algo.update_epochs) * max(
        1, -(-int(cfg.algo.rollout_steps) * int(cfg.env.num_envs) // int(cfg.algo.per_rank_batch_size))
    )
    keep_train_metrics = (aggregator is not None and not aggregator.disabled) or health.enabled
    step_data = {}
    next_obs = pipeline.stash_obs(envs.reset(seed=cfg.seed)[0])
    for k in obs_keys:
        step_data[k] = next_obs[k][np.newaxis]

    for iter_num in range(start_iter, total_iters + 1):
        telemetry.advance(policy_step)
        guard.advance(policy_step)
        for _ in range(0, cfg.algo.rollout_steps):
            policy_step += cfg.env.num_envs * world_size

            with timer("Time/env_interaction_time"), perf.infeed():
                # prepare_obs is pure numpy and the PRNG split + pixel
                # normalization live inside player_step: the jitted call is
                # the step's only device dispatch, and ONE (possibly async)
                # fetch collects all outputs.
                res = pipeline.interact(
                    envs,
                    next_obs,
                    _pipeline_policy,
                    prepare=_prepare_slice,
                    to_env_actions=_to_env_actions,
                )
                actions, real_actions_np, logprobs, values = res.outputs
                obs, rewards, terminated, truncated, info = (
                    res.obs,
                    res.rewards,
                    res.terminated,
                    res.truncated,
                    res.infos,
                )
                truncated_envs = np.nonzero(truncated)[0]
                if len(truncated_envs) > 0:
                    # Bootstrap truncated episodes with V(final_obs)
                    # (reference: ppo.py:287-306).
                    final_obs = info["final_obs"]
                    real_next_obs = {
                        k: np.stack([np.asarray(final_obs[e][k], np.float32) for e in truncated_envs])
                        for k in obs_keys
                    }
                    with placement.ctx():
                        jnp_next = prepare_obs(real_next_obs, cnn_keys=cnn_keys, num_envs=len(truncated_envs))
                        vals_pending = pipeline.fetch(
                            get_values_fn(placement.params(), jnp_next), label="trunc_bootstrap"
                        )
                    vals = np.asarray(vals_pending.harvest())
                    rewards[truncated_envs] += cfg.algo.gamma * vals.reshape(rewards[truncated_envs].shape)
                dones = np.logical_or(terminated, truncated).reshape(cfg.env.num_envs, -1).astype(np.uint8)
                rewards = clip_rewards_fn(rewards).reshape(cfg.env.num_envs, -1).astype(np.float32)

            step_data["dones"] = dones[np.newaxis]
            step_data["values"] = values[np.newaxis]
            step_data["actions"] = actions[np.newaxis]
            step_data["logprobs"] = logprobs[np.newaxis]
            step_data["rewards"] = rewards[np.newaxis]
            # returns/advantages are computed INSIDE the train jit — no
            # buffer placeholders, no host round-trip.

            rb.add(step_data, validate_args=cfg.buffer.validate_args)

            next_obs = {}
            for k in obs_keys:
                step_data[k] = obs[k][np.newaxis]
                next_obs[k] = obs[k]

            if cfg.metric.log_level > 0 and "final_info" in info:
                fi = info["final_info"]
                for i in np.nonzero(fi.get("_episode", []))[0]:
                    ep_rew = float(fi["episode"]["r"][i])
                    ep_len = float(fi["episode"]["l"][i])
                    if aggregator and "Rewards/rew_avg" in aggregator:
                        aggregator.update("Rewards/rew_avg", ep_rew)
                    if aggregator and "Game/ep_len_avg" in aggregator:
                        aggregator.update("Game/ep_len_avg", ep_len)
                    runtime.print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={ep_rew}")

        # ------------------------- ship rollout; bootstrap+GAE run in-jit
        # ((T, E) tensors env-sharded over `data`, pixels uint8 —
        # core/rollout.py). share_data is the reference's
        # every-process-trains-on-the-union mode (fabric.all_gather,
        # ppo.py:363-367), a DCN-level host gather along the env axis.
        local_data = rb.to_tensor()
        next_obs_np = prepare_obs(next_obs, cnn_keys=cnn_keys, num_envs=cfg.env.num_envs)
        data, jnp_next = ship_rollout(
            runtime,
            local_data,
            (*obs_keys, "actions", "logprobs"),
            next_obs_np,
            share_data=bool(cfg.buffer.get("share_data", False)),
        )

        with timer("Time/train_time"):
            # PRNG split runs inside the jit (an eager split is one more
            # dispatch the host waits on); coefs travel as numpy.
            clip_arr = np.asarray(cfg.algo.clip_coef, np.float32)
            ent_arr = np.asarray(cfg.algo.ent_coef, np.float32)
            # Goodput accounting BEFORE the dispatch: arg shape specs must
            # be captured while the buffers are alive (the jit donates them).
            perf.note(
                "train/update", train_fn,
                (params, opt_state, data, jnp_next, train_key, clip_arr, ent_arr),
                steps=gradient_steps_per_update,
            )
            with train_timer.step(), watch(watchdog, "train_dispatch"):
                params, opt_state, train_metrics, train_key = train_fn(
                    params,
                    opt_state,
                    data,
                    jnp_next,
                    train_key,
                    clip_arr,
                    ent_arr,
                )
            # No sync here: the dispatch stays fully async — the StepTimer
            # queues the loss scalars device-side and bounds the interval
            # with ONE block at the log-interval flush.
            train_timer.pend(params, train_metrics if keep_train_metrics else None)
        placement.push(params)
        train_step_count += world_size

        # ------------------------------------------------------- logging
        should_log = cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters
        )
        if should_log:
            # The interval's losses in ONE bounding block + ONE device->host
            # transfer (StepTimer.flush) — the coalesced pattern GL002 asks
            # for, now owned by telemetry.
            fetched_train_metrics = train_timer.flush()
            # Health sentinels inspect the same coalesced fetch — no extra
            # transfer; a nonfinite hit taints the run and escalates.
            health.observe(policy_step, fetched_train_metrics, telemetry=telemetry)
            if aggregator and not aggregator.disabled:
                for tm in fetched_train_metrics:
                    aggregator.update("Loss/policy_loss", tm["policy_loss"])
                    aggregator.update("Loss/value_loss", tm["value_loss"])
                    aggregator.update("Loss/entropy_loss", tm["entropy_loss"])
                # Collective when sync_on_compute is on: every rank joins;
                # only rank 0 (the only rank with a logger) writes.
                aggregator.log_and_reset(logger, policy_step)
            telemetry.log_counters(logger, policy_step)
        if cfg.metric.log_level > 0 and logger is not None:
            logger.log("Info/learning_rate", _current_lr(opt_state, base_lr), policy_step)
            logger.log("Info/clip_coef", cfg.algo.clip_coef, policy_step)
            logger.log("Info/ent_coef", cfg.algo.ent_coef, policy_step)

            if should_log:
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

        # ----------------------------------------------------- annealing
        if cfg.algo.anneal_lr:
            new_lr = polynomial_decay(iter_num, initial=base_lr, final=0.0, max_decay_steps=total_iters, power=1.0)
            opt_state.hyperparams["lr"] = jnp.asarray(new_lr, jnp.float32)
        if cfg.algo.anneal_clip_coef:
            cfg.algo.clip_coef = polynomial_decay(
                iter_num, initial=initial_clip_coef, final=0.0, max_decay_steps=total_iters, power=1.0
            )
        if cfg.algo.anneal_ent_coef:
            cfg.algo.ent_coef = polynomial_decay(
                iter_num, initial=initial_ent_coef, final=0.0, max_decay_steps=total_iters, power=1.0
            )

        # ---------------------------------------------------- checkpoint
        if health.allow_save() and (
            (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every)
            or ((iter_num == total_iters or guard.preempted) and cfg.checkpoint.save_last)
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": params,
                "optimizer": opt_state,
                "iter_num": iter_num * world_size,
                "batch_size": cfg.algo.per_rank_batch_size * world_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
            }
            ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{rank}.ckpt")
            if runtime.is_global_zero:
                save_checkpoint(ckpt_path, ckpt_state, keep_last=cfg.checkpoint.keep_last)

        if guard.preempted:
            runtime.print(f"Preemption: exiting cleanly after final checkpoint at policy step {policy_step}")
            break
    pipeline.publish()
    envs.close()
    if runtime.is_global_zero and cfg.algo.run_test and not guard.preempted:
        test(agent, params, runtime, cfg, log_dir, logger)

    guard.close()
    telemetry.close()
    if logger is not None:
        logger.close()


def _current_lr(opt_state, base_lr: float) -> float:
    try:
        return float(np.asarray(opt_state.hyperparams["lr"]))
    except Exception:
        return base_lr
