"""The Anakin lane: rollout AND training fused into single-jit supersteps.

Podracer/Anakin (arXiv:2104.06272) observes that when the environment is a
pure-JAX transform (sheeprl_tpu/envs/jax/), the entire RL loop — vmapped env
batch, policy forward, autoreset, trajectory accumulation, gradient steps —
compiles into ONE XLA program. The host's only job per *superstep* (T env
steps × E envs + the attached training work) is dispatching one or two jits
and threading counters; interaction cost disappears into the schedule and
`core/interact.py` is bypassed entirely.

Enabled per run with ``env.jax_native=true`` + ``algo.fused_rollout=true``
(see :func:`fused_enabled`; the Gymnasium lane is untouched otherwise).
Three drivers, mirroring their host-interaction mains step for step:

- :func:`ppo_fused_main`: one donated jit per iteration = T-step rollout
  scan (SAME_STEP in-scan autoreset + per-step truncation bootstrap) feeding
  the shared ``fuse_gae_pool`` prologue and the epochs×minibatches update
  scans (algos/ppo/ppo.py:make_update_pool). 1 dispatch per superstep.
- :func:`sac_fused_main`: a T-step rollout jit writing transitions straight
  into the device replay ring (data/device_buffer.py:make_step_write_fn),
  then the existing ring-sampled K-step fused train jit. 2 dispatches.
- :func:`dreamer_v3_fused_main`: rollout scan threading the recurrent
  player latents (masked in-scan reset) with the dreamer row convention
  (main row + sparse episode-boundary reset rows), then the fused
  sequence-model train jit. 2 dispatches.

Counters, telemetry (per-superstep tracer span, StepTimer's coalesced
metrics fetch, in-jit health probes), resilience (iteration-boundary
preemption drain, health-gated checkpoints) and checkpoint layouts are kept
identical to the host lane, so fused-lane checkpoints resume on the
Gymnasium lane and vice versa.

Caveats (howto/anakin_lane.md): episode stats surface once per log interval
(one coalesced transfer) instead of per step; SAC/dreamer supersteps cover
``algo.fused_superstep_steps`` host-lane iterations, so replay-ratio and
target-EMA cadences are reproduced at superstep granularity (within one
superstep of the host lane's schedule).
"""

from __future__ import annotations

import os
import warnings
from typing import Any, Dict, List, Tuple

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.core.mesh import DATA_AXIS, MODEL_AXIS
from sheeprl_tpu.core.resilience import watch
from sheeprl_tpu.core.rollout import fuse_gae_pool
from sheeprl_tpu.data.device_buffer import DeviceReplayRing
from sheeprl_tpu.envs.jax import JaxEnv, action_to_env, canonical_action_space, make_jax_env
from sheeprl_tpu.telemetry import tracer as tracer_mod
from sheeprl_tpu.utils.checkpoint import load_checkpoint, restore_opt_state, save_checkpoint
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, polynomial_decay, save_configs

__all__ = [
    "fused_enabled",
    "last_run_stats",
    "ppo_fused_main",
    "sac_fused_main",
    "dreamer_v3_fused_main",
]


def fused_enabled(cfg) -> bool:
    """True when this run opted into the Anakin lane."""
    return bool(cfg.env.get("jax_native", False)) and bool(cfg.algo.get("fused_rollout", False))


# Dispatch accounting: supersteps run, jit dispatches issued, env steps
# covered (chip_smoke.py and the lane's tests read these).
_RUN_STATS: Dict[str, int] = {"supersteps": 0, "jit_dispatches": 0, "env_steps": 0}


def last_run_stats() -> Dict[str, int]:
    """Counters from the most recent fused run."""
    return dict(_RUN_STATS)


def _reset_run_stats() -> None:
    _RUN_STATS.update(supersteps=0, jit_dispatches=0, env_steps=0)


# --------------------------------------------------------------- shared bits
def _shard_superstep_enabled(cfg, mesh, num_envs: int) -> bool:
    """True when the fused supersteps run SPMD via shard_map over ``data``.

    The sharded program is the SAME program on every topology — a data axis of
    size 1 still goes through shard_map (an identity partitioning), and every
    per-env PRNG stream is keyed by the env's GLOBAL id — so enabling more
    shards never changes the math, only where each env's rows live."""
    if not bool(cfg.fabric.get("shard_superstep", True)):
        return False
    if int(mesh.shape[MODEL_AXIS]) > 1:
        # Params enter the superstep replicated (in_spec P()); a model-sharded
        # tree would be all-gathered every dispatch. Keep GSPMD placement.
        return False
    data_size = int(mesh.shape[DATA_AXIS])
    if num_envs % data_size != 0:
        warnings.warn(
            f"fabric.shard_superstep: env.num_envs={num_envs} is not divisible by the "
            f"`{DATA_AXIS}` mesh axis (size {data_size}); the superstep stays replicated."
        )
        return False
    return True


def _fold_env_keys(key: jax.Array, genv: jax.Array) -> jax.Array:
    """One PRNG key per env, derived from the env's GLOBAL id (GL017): the
    stream an env sees is invariant to how envs are split across shards."""
    return jax.vmap(jax.random.fold_in, (None, 0))(key, genv)


def _global_env_ids(e_local: int, sharded: bool) -> jax.Array:
    """Global env ids for this shard's ``e_local`` rows. Under shard_map the
    axis index recovers the shard's offset; unsharded it's just arange."""
    if sharded:
        return jax.lax.axis_index(DATA_AXIS) * e_local + jnp.arange(e_local)
    return jnp.arange(e_local)


def _where_done(done: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """Per-env select on the done mask, broadcasting over feature dims."""
    return jnp.where(done.reshape(done.shape + (1,) * (a.ndim - 1)), a, b)


def _resolve_env(cfg) -> JaxEnv:
    env = make_jax_env(cfg.env.id)
    limit = cfg.env.get("max_episode_steps", None)
    if limit is not None:
        env.max_episode_steps = int(limit)
    return env


def _single_obs_key(cfg, env: JaxEnv) -> Tuple[str, bool]:
    """The dict key make_env would file this obs under (pixel vs vector), so
    fused-lane agents get byte-identical param trees to Gymnasium-lane ones."""
    pixel = len(env.observation_space.shape) >= 2
    keys = list(cfg.algo.cnn_keys.encoder if pixel else cfg.algo.mlp_keys.encoder)
    other = list(cfg.algo.mlp_keys.encoder if pixel else cfg.algo.cnn_keys.encoder)
    if len(keys) != 1 or other:
        raise ValueError(
            "The fused lane supports exactly one encoder key matching the env's observation "
            f"kind; got cnn={list(cfg.algo.cnn_keys.encoder)} mlp={list(cfg.algo.mlp_keys.encoder)} "
            f"for an observation of shape {env.observation_space.shape}"
        )
    return keys[0], pixel


def _env_actions(real_actions: jax.Array, env: JaxEnv, to_env, is_continuous: bool, num_envs: int):
    shape = env.action_space.shape
    actions = real_actions.reshape((num_envs, *shape)) if shape else real_actions.reshape((num_envs,))
    if is_continuous:
        return to_env(actions)
    return actions.astype(jnp.int32)


def _fetch_row_counts(rows_written: jax.Array) -> np.ndarray:
    """ONE coalesced device->host transfer per superstep: the [E] per-env
    written-row counts (dreamer's sparse reset rows make ring occupancy
    data-dependent, and the host mirror must track it for ready())."""
    return np.asarray(jax.device_get(rows_written), dtype=np.int64)


def _drain_episode_stats(pending: List[Dict[str, Any]]) -> List[Tuple[int, float, float]]:
    """(env_idx, return, length) for every episode that ended in the interval.
    ONE coalesced device->host transfer for all queued supersteps."""
    if not pending:
        return []
    fetched = jax.device_get(pending)
    episodes: List[Tuple[int, float, float]] = []
    for ep in fetched:
        done = np.asarray(ep["done"])
        for t, e in zip(*np.nonzero(done)):
            episodes.append((int(e), float(ep["returns"][t, e]), float(ep["lengths"][t, e])))
    return episodes


def _log_episode_stats(pending, aggregator, runtime, policy_step, log_level) -> None:
    if log_level <= 0:
        pending.clear()
        return
    for env_i, ep_rew, ep_len in _drain_episode_stats(pending):
        if aggregator and not aggregator.disabled:
            if "Rewards/rew_avg" in aggregator:
                aggregator.update("Rewards/rew_avg", ep_rew)
            if "Game/ep_len_avg" in aggregator:
                aggregator.update("Game/ep_len_avg", ep_len)
        runtime.print(f"Rank-0: policy_step={policy_step}, reward_env_{env_i}={ep_rew}")
    pending.clear()


def _superstep_taus(iter_start: int, iter_end: int, freq_iters: int, tau: float, k: int) -> np.ndarray:
    """Spread the host lane's per-iteration EMA cadence over a K-step fused
    train scan: one ``tau`` entry per EMA-eligible iteration in
    ``(iter_start, iter_end]``, evenly placed (SAC's iteration-based cadence
    reproduced at superstep granularity)."""
    taus = np.zeros(max(k, 1), np.float32)
    if k <= 0 or freq_iters <= 0:
        return taus
    n_ema = sum(1 for i in range(iter_start + 1, iter_end + 1) if i % freq_iters == 0)
    if n_ema == 0:
        return taus
    for idx in np.unique(np.linspace(0, k - 1, num=min(n_ema, k)).round().astype(int)):
        taus[idx] = tau
    return taus


# ----------------------------------------------------------------------- PPO
def ppo_fused_main(runtime, cfg: Dict[str, Any]):
    from sheeprl_tpu.algos.ppo.agent import actions_metadata, build_agent
    from sheeprl_tpu.algos.ppo.ppo import _current_lr, make_optimizer, make_update_pool
    from sheeprl_tpu.algos.ppo.utils import test

    _reset_run_stats()
    initial_ent_coef = float(cfg.algo.ent_coef)
    initial_clip_coef = float(cfg.algo.clip_coef)
    mesh = runtime.mesh
    rank = runtime.global_rank
    world_size = jax.process_count()

    state = None
    if cfg.checkpoint.resume_from:
        state = load_checkpoint(cfg.checkpoint.resume_from)

    logger = get_logger(runtime, cfg)
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name, logger=logger)
    runtime.print(f"Log dir: {log_dir} (fused Anakin lane)")
    telemetry = runtime.telemetry.open(log_dir, rank_zero=runtime.is_global_zero, device=runtime.device)
    guard = runtime.resilience.guard(rank_zero=runtime.is_global_zero)
    watchdog = runtime.resilience.watchdog
    health = runtime.health

    # ------------------------------------------------------------------ env
    env = _resolve_env(cfg)
    num_envs = int(cfg.env.num_envs)
    obs_key, _pixel = _single_obs_key(cfg, env)
    observation_space = gym.spaces.Dict({obs_key: env.observation_space})
    action_space = canonical_action_space(env)
    actions_dim, is_continuous = actions_metadata(action_space)
    to_env = action_to_env(env)
    clip_rewards = bool(cfg.env.clip_rewards)

    # ---------------------------------------------------------------- agent
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

    # Arm per-shard goodput accounting: the observatory needs the mesh and the
    # realised param layouts to attribute MFU/imbalance per data-shard.
    telemetry.set_mesh(mesh)
    telemetry.record_param_layouts(params)

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    # ------------------------------------------------------------- counters
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

    T = int(cfg.algo.rollout_steps)
    E = num_envs
    gamma = float(cfg.algo.gamma)
    gae_lambda = float(cfg.algo.gae_lambda)
    flat_keys = (obs_key, "actions", "logprobs")

    # ------------------------------------------------------------ superstep
    update_pool = make_update_pool(agent, tx, cfg, mesh)
    step_v = jax.vmap(env.step)
    reset_v = jax.vmap(env.reset)
    use_shard = _shard_superstep_enabled(cfg, mesh, num_envs)

    def rollout_core(params, env_state, obs, ep_ret, ep_len, k_roll):
        # Local (per-shard) env width: under shard_map each shard traces with
        # its E/data rows; unsharded this is just E. Every per-env PRNG stream
        # is keyed by the env's GLOBAL id so both trace to the same streams.
        e_local = obs.shape[0]
        genv = _global_env_ids(e_local, use_shard)

        def body(carry, step_key):
            env_state, obs, ep_ret, ep_len = carry
            k_policy, k_step, k_reset = jax.random.split(step_key, 3)

            def _policy(o, k):
                a_cat, a_real, lp, v, _next_k = agent.player_step(params, {obs_key: o[None]}, k)
                return a_cat[0], a_real[0], lp[0], v[0]

            # Per-env singleton-batch policy step: action sampling consumes
            # the env's own key, so the draw is independent of batch width
            # (the deterministic forward is row-independent either way).
            actions_cat, real_actions, logprobs, values = jax.vmap(_policy)(
                obs, _fold_env_keys(k_policy, genv)
            )
            new_state, new_obs, reward, done, info = step_v(
                env_state, _env_actions(real_actions, env, to_env, is_continuous, e_local),
                _fold_env_keys(k_step, genv),
            )
            # Truncation bootstrap on the TRUE next obs (pre-reset), exactly
            # the host lane's final_obs path; raw rewards feed episode stats.
            boot = agent.get_values(params, {obs_key: new_obs})[:, 0]
            buf_reward = reward + gamma * boot * info["truncated"].astype(jnp.float32)
            if clip_rewards:
                buf_reward = jnp.tanh(buf_reward)
            ep_ret = ep_ret + reward
            ep_len = ep_len + 1
            # SAME_STEP autoreset: done envs restart immediately; the stored
            # transition keeps the pre-reset obs/reward.
            r_state, r_obs = reset_v(_fold_env_keys(k_reset, genv))
            env_state = jax.tree_util.tree_map(
                lambda r, n: _where_done(done, r, n), r_state, new_state
            )
            obs_next = _where_done(done, r_obs, new_obs)
            traj = {
                obs_key: obs,
                "actions": actions_cat.astype(jnp.float32),
                "logprobs": logprobs,
                "values": values,
                "rewards": buf_reward[:, None],
                "dones": done.astype(jnp.float32)[:, None],
            }
            ep_info = {"done": done, "returns": ep_ret, "lengths": ep_len.astype(jnp.float32)}
            ep_ret = jnp.where(done, 0.0, ep_ret)
            ep_len = jnp.where(done, 0, ep_len)
            return (env_state, obs_next, ep_ret, ep_len), (traj, ep_info)

        (env_state, obs, ep_ret, ep_len), (traj, ep_info) = jax.lax.scan(
            body, (env_state, obs, ep_ret, ep_len), jax.random.split(k_roll, T)
        )
        return env_state, obs, ep_ret, ep_len, traj, ep_info

    rollout_fn = rollout_core
    if use_shard:
        # SPMD rollout: each shard steps its own envs and accumulates its own
        # [T, E/data] trajectory columns; GAE + the update pool downstream
        # stay GSPMD over the `data`-sharded pool. check_vma=False because
        # the unmentioned `model` axis (size 1 here) defeats replication
        # inference; params and keys arrive replicated by construction.
        p_env = P(DATA_AXIS)
        p_traj = P(None, DATA_AXIS)
        rollout_fn = jax.shard_map(
            rollout_core,
            mesh=mesh,
            in_specs=(P(), p_env, p_env, p_env, p_env, P()),
            out_specs=(p_env, p_env, p_env, p_env, p_traj, p_traj),
            check_vma=False,
        )

    def rollout_and_train(params, opt_state, env_state, obs, ep_ret, ep_len, key, clip_coef, ent_coef):
        next_key, k_roll, k_train = jax.random.split(key, 3)
        env_state, obs, ep_ret, ep_len, traj, ep_info = rollout_fn(
            params, env_state, obs, ep_ret, ep_len, k_roll
        )
        pool = fuse_gae_pool(
            agent, params, traj, {obs_key: obs}, flat_keys, gamma, gae_lambda, include_values=True
        )
        params, opt_state, metrics, _unused_key = update_pool(
            params, opt_state, pool, k_train, clip_coef, ent_coef
        )
        return params, opt_state, env_state, obs, ep_ret, ep_len, ep_info, metrics, next_key

    superstep = jax.jit(rollout_and_train, donate_argnums=(0, 1, 2, 3, 4, 5))

    init_key, loop_key = jax.random.split(jax.random.fold_in(runtime.root_key, rank))
    # Env init is computed from GLOBAL per-env keys (identical on every
    # topology), then the carries land on their `data`-axis shards.
    env_state, obs = jax.jit(reset_v)(jax.random.split(init_key, E))
    ep_ret = jnp.zeros((E,), jnp.float32)
    ep_len = jnp.zeros((E,), jnp.int32)
    if use_shard:
        env_sharding = NamedSharding(mesh, P(DATA_AXIS))
        env_state, obs, ep_ret, ep_len = jax.device_put(
            (env_state, obs, ep_ret, ep_len), env_sharding
        )

    train_timer = telemetry.step_timer("train", timer_key="Time/train_time")
    perf = telemetry.perf
    keep_train_metrics = (aggregator is not None and not aggregator.disabled) or health.enabled
    pending_eps: List[Dict[str, Any]] = []
    tracer = tracer_mod.current()

    for iter_num in range(start_iter, total_iters + 1):
        telemetry.advance(policy_step)
        guard.advance(policy_step)
        policy_step += policy_steps_per_iter

        with timer("Time/train_time"):
            clip_arr = np.asarray(cfg.algo.clip_coef, np.float32)
            ent_arr = np.asarray(cfg.algo.ent_coef, np.float32)
            # Goodput accounting BEFORE the dispatch (the superstep donates
            # its carry): the whole rollout+train program is one key.
            perf.note(
                "rollout/superstep", superstep,
                (params, opt_state, env_state, obs, ep_ret, ep_len, loop_key, clip_arr, ent_arr),
                steps=1,
            )
            with tracer.span("fused/superstep", "train"), train_timer.step(), watch(
                watchdog, "train_dispatch"
            ):
                (
                    params, opt_state, env_state, obs, ep_ret, ep_len, ep_info, train_metrics, loop_key,
                ) = superstep(
                    params, opt_state, env_state, obs, ep_ret, ep_len, loop_key,
                    clip_arr, ent_arr,
                )
            train_timer.pend(params, train_metrics if keep_train_metrics else None)
        pending_eps.append(ep_info)
        train_step_count += world_size
        _RUN_STATS["supersteps"] += 1
        _RUN_STATS["jit_dispatches"] += 1
        _RUN_STATS["env_steps"] += T * E

        # ----------------------------------------------------------- logging
        should_log = cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters
        )
        if should_log:
            fetched_train_metrics = train_timer.flush()
            health.observe(policy_step, fetched_train_metrics, telemetry=telemetry)
            _log_episode_stats(pending_eps, aggregator, runtime, policy_step, cfg.metric.log_level)
            if aggregator and not aggregator.disabled:
                for tm in fetched_train_metrics:
                    aggregator.update("Loss/policy_loss", tm["policy_loss"])
                    aggregator.update("Loss/value_loss", tm["value_loss"])
                    aggregator.update("Loss/entropy_loss", tm["entropy_loss"])
                aggregator.log_and_reset(logger, policy_step)
            telemetry.log_counters(logger, policy_step)
        if cfg.metric.log_level > 0 and logger is not None:
            logger.log("Info/learning_rate", _current_lr(opt_state, base_lr), policy_step)
            logger.log("Info/clip_coef", cfg.algo.clip_coef, policy_step)
            logger.log("Info/ent_coef", cfg.algo.ent_coef, policy_step)
            if should_log and not timer.disabled:
                timer_metrics = timer.compute()
                if timer_metrics.get("Time/train_time", 0) > 0:
                    logger.log(
                        "Time/sps_train",
                        (train_step_count - last_train) / timer_metrics["Time/train_time"],
                        policy_step,
                    )
                timer.reset()
        if should_log:
            last_log = policy_step
            last_train = train_step_count

        # --------------------------------------------------------- annealing
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

        # -------------------------------------------------------- checkpoint
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

    if runtime.is_global_zero and cfg.algo.run_test and not guard.preempted:
        test(agent, params, runtime, cfg, log_dir, logger)

    guard.close()
    telemetry.close()
    if logger is not None:
        logger.close()


# ----------------------------------------------------------------------- SAC
def sac_fused_main(runtime, cfg: Dict[str, Any]):
    from sheeprl_tpu.algos.sac.agent import build_agent
    from sheeprl_tpu.algos.sac.sac import _make_optimizer, make_fused_train_step
    from sheeprl_tpu.algos.sac.utils import test
    from sheeprl_tpu.core.runtime import DispatchThrottle

    _reset_run_stats()
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
    runtime.print(f"Log dir: {log_dir} (fused Anakin lane)")
    telemetry = runtime.telemetry.open(log_dir, rank_zero=runtime.is_global_zero, device=runtime.device)
    guard = runtime.resilience.guard(rank_zero=runtime.is_global_zero)
    watchdog = runtime.resilience.watchdog
    health = runtime.health

    env = _resolve_env(cfg)
    num_envs = int(cfg.env.num_envs)
    obs_key, pixel = _single_obs_key(cfg, env)
    if pixel:
        raise ValueError("Only vector observations are supported by the SAC agent")
    observation_space = gym.spaces.Dict({obs_key: env.observation_space})
    action_space = canonical_action_space(env)
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("Only continuous action space is supported for the SAC agent")
    to_env = action_to_env(env)
    clip_rewards = bool(cfg.env.clip_rewards)
    obs_dim = int(np.prod(env.observation_space.shape))
    act_dim = int(np.prod(action_space.shape))

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

    # Arm per-shard goodput accounting: the observatory needs the mesh and the
    # realised param layouts to attribute MFU/imbalance per data-shard.
    telemetry.set_mesh(mesh)
    telemetry.record_param_layouts(agent_state)

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    use_shard = _shard_superstep_enabled(cfg, mesh, num_envs)

    # ----------------------------------------------------------------- ring
    # The fused lane is ring-only: transitions are written in-scan and never
    # leave the device, so the ring must allocate up front (and fit HBM).
    # Under the sharded superstep the ring storage itself is sharded over
    # envs: each shard's in-scan writes land on the rows it owns.
    buffer_size = cfg.buffer.size // int(num_envs * world_size) if not cfg.dry_run else 1
    sample_next_obs = bool(cfg.buffer.sample_next_obs)
    ring = DeviceReplayRing(
        buffer_size,
        num_envs,
        obs_keys=("observations",),
        hbm_fraction=float(cfg.buffer.get("device_hbm_fraction", 0.4)),
        device=mesh.devices.flat[0],
        mesh=mesh if use_shard else None,
    )
    specs = {
        "observations": ((obs_dim,), np.float32),
        "actions": ((act_dim,), np.float32),
        "rewards": ((1,), np.float32),
        "terminated": ((1,), np.uint8),
        "truncated": ((1,), np.uint8),
    }
    if not sample_next_obs:
        specs["next_observations"] = ((obs_dim,), np.float32)
    ring.allocate(specs)
    if state_ckpt is not None and cfg.buffer.checkpoint and state_ckpt.get("rb") is not None:
        # A Gymnasium-lane checkpoint carries its host replay buffer: seed
        # the ring with it so the resumed run trains on its history (specs
        # are fixed above, so mismatched host dtypes cast on the way in).
        ring.load_host_buffer(state_ckpt["rb"])
        ring.flush()
    if not ring.active:
        raise RuntimeError(
            f"algo.fused_rollout needs the device replay ring, which declined its "
            f"allocation: {ring.inactive_reason}"
        )
    write_fn = ring.make_step_write_fn()
    ring_sample_fn = ring.make_sample_fn(
        cfg.algo.per_rank_batch_size, sequence_length=1, sample_next_obs=sample_next_obs
    )
    ring_span = 1 + int(sample_next_obs)
    fused_train_fn = make_fused_train_step(
        agent,
        txs,
        cfg,
        mesh,
        ring_sample_fn,
        state=agent_state,
        opt_states=opt_states,
        ring_shardings=ring.state_shardings(),
    )
    fused_train_steps = max(int(cfg.algo.get("fused_train_steps", 1)), 1)

    # ------------------------------------------------------------- counters
    last_train = 0
    train_step_count = 0
    start_iter = (state_ckpt["iter_num"] // world_size) + 1 if state_ckpt is not None else 1
    policy_step = state_ckpt["iter_num"] * num_envs if state_ckpt is not None else 0
    last_log = state_ckpt["last_log"] if state_ckpt is not None else 0
    last_checkpoint = state_ckpt["last_checkpoint"] if state_ckpt is not None else 0
    policy_steps_per_iter = int(num_envs * world_size)
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
    target_freq_iters = cfg.algo.critic.target_network_frequency // policy_steps_per_iter + 1
    superstep_iters = max(int(cfg.algo.get("fused_superstep_steps", 64)), 1)

    E = num_envs

    # ------------------------------------------------------------ supersteps
    step_v = jax.vmap(env.step)
    reset_v = jax.vmap(env.reset)

    def _make_rollout(steps: int, random_actions: bool):
        def rollout_core(actor_params, ring_state, env_state, obs, ep_ret, ep_len, k_roll):
            # Local (per-shard) env width: under shard_map each shard traces
            # with its E/data rows (the ring's in-scan writes then touch only
            # the rows this shard owns); unsharded this is just E. Per-env
            # PRNG streams are keyed by the env's GLOBAL id on both paths.
            e_local = obs.shape[0]
            genv = _global_env_ids(e_local, use_shard)

            def body(carry, step_key):
                env_state, obs, ep_ret, ep_len, ring_state = carry
                k_act, k_step, k_reset = jax.random.split(step_key, 3)
                act_keys = _fold_env_keys(k_act, genv)
                if random_actions:
                    # Uniform over the canonical [-1, 1] box == the host
                    # lane's envs.action_space.sample() after RescaleAction,
                    # drawn per env from the env's own key.
                    actions = jax.vmap(
                        lambda k: jax.random.uniform(k, (act_dim,), minval=-1.0, maxval=1.0)
                    )(act_keys)
                else:
                    # Per-env singleton-batch policy call: the exploration
                    # noise comes from the env's own key, so the draw is
                    # independent of how envs are batched across shards.
                    actions = jax.vmap(
                        lambda o, k: agent.get_actions(actor_params, o[None, :], k, greedy=False)[0]
                    )(obs.reshape(e_local, obs_dim), act_keys)
                new_state, new_obs, reward, done, info = step_v(
                    env_state, to_env(actions.reshape((e_local, *action_space.shape))),
                    _fold_env_keys(k_step, genv),
                )
                buf_reward = jnp.tanh(reward) if clip_rewards else reward
                row = {
                    "observations": obs.reshape(e_local, obs_dim),
                    "actions": actions,
                    "rewards": buf_reward[:, None],
                    "terminated": info["terminated"][:, None],
                    "truncated": info["truncated"][:, None],
                }
                if not sample_next_obs:
                    # TRUE next obs (pre-reset): the host lane's real_next_obs.
                    row["next_observations"] = new_obs.reshape(e_local, obs_dim)
                ring_state = write_fn(ring_state, row, jnp.ones((e_local,), jnp.bool_))
                ep_ret = ep_ret + reward
                ep_len = ep_len + 1
                r_state, r_obs = reset_v(_fold_env_keys(k_reset, genv))
                env_state = jax.tree_util.tree_map(
                    lambda r, n: _where_done(done, r, n), r_state, new_state
                )
                obs_next = _where_done(done, r_obs, new_obs)
                ep_info = {"done": done, "returns": ep_ret, "lengths": ep_len.astype(jnp.float32)}
                ep_ret = jnp.where(done, 0.0, ep_ret)
                ep_len = jnp.where(done, 0, ep_len)
                return (env_state, obs_next, ep_ret, ep_len, ring_state), ep_info

            (env_state, obs, ep_ret, ep_len, ring_state), ep_info = jax.lax.scan(
                body, (env_state, obs, ep_ret, ep_len, ring_state), jax.random.split(k_roll, steps)
            )
            return env_state, obs, ep_ret, ep_len, ring_state, ep_info

        core = rollout_core
        if use_shard:
            # SPMD superstep: each shard steps its own envs and writes its own
            # ring rows; no cross-shard traffic inside the scan. check_vma is
            # off because the unmentioned `model` axis (size 1 here) defeats
            # replication inference; params/keys arrive replicated.
            p_env = P(DATA_AXIS)
            ring_specs = jax.tree_util.tree_map(lambda s: s.spec, ring.state_shardings())
            core = jax.shard_map(
                rollout_core,
                mesh=mesh,
                in_specs=(P(), ring_specs, p_env, p_env, p_env, p_env, P()),
                out_specs=(p_env, p_env, p_env, p_env, ring_specs, P(None, DATA_AXIS)),
                check_vma=False,
            )

        def rollout(actor_params, ring_state, env_state, obs, ep_ret, ep_len, key):
            next_key, k_roll = jax.random.split(key)
            env_state, obs, ep_ret, ep_len, ring_state, ep_info = core(
                actor_params, ring_state, env_state, obs, ep_ret, ep_len, k_roll
            )
            return env_state, obs, ep_ret, ep_len, ring_state, ep_info, next_key

        return jax.jit(rollout, donate_argnums=(1, 2, 3, 4, 5))

    rollout_fns: Dict[Tuple[int, bool], Any] = {}

    def _rollout_fn(steps: int, random_actions: bool):
        fn = rollout_fns.get((steps, random_actions))
        if fn is None:
            fn = _make_rollout(steps, random_actions)
            rollout_fns[(steps, random_actions)] = fn
        return fn

    init_key, loop_key = jax.random.split(jax.random.fold_in(runtime.root_key, rank))
    rollout_key, train_key = jax.random.split(loop_key)
    # Env init is computed from GLOBAL per-env keys (identical on every
    # topology), then the carries land on their `data`-axis shards.
    env_state, obs = jax.jit(reset_v)(jax.random.split(init_key, E))
    ep_ret = jnp.zeros((E,), jnp.float32)
    ep_len = jnp.zeros((E,), jnp.int32)
    if use_shard:
        env_sharding = NamedSharding(mesh, P(DATA_AXIS))
        env_state, obs, ep_ret, ep_len = jax.device_put(
            (env_state, obs, ep_ret, ep_len), env_sharding
        )
    ring_state = ring.state

    cumulative_per_rank_gradient_steps = 0
    dispatch_throttle = DispatchThrottle()
    train_timer = telemetry.step_timer("train", timer_key="Time/train_time")
    perf = telemetry.perf
    keep_train_metrics = (
        aggregator is not None and not aggregator.disabled and cfg.metric.log_level > 0
    ) or health.enabled
    pending_eps: List[Dict[str, Any]] = []
    tracer = tracer_mod.current()

    iter_num = start_iter - 1  # last completed host-lane iteration
    while iter_num < total_iters:
        if iter_num < learning_starts:
            chunk = min(superstep_iters, learning_starts - iter_num, total_iters - iter_num)
            random_phase = True
        else:
            chunk = min(superstep_iters, total_iters - iter_num)
            random_phase = False
        telemetry.advance(policy_step)
        guard.advance(policy_step)
        iter_start = iter_num
        iter_num += chunk
        policy_step += chunk * policy_steps_per_iter

        with timer("Time/env_interaction_time" if random_phase else "Time/train_time"):
            rollout_fn = _rollout_fn(chunk, random_phase)
            # Goodput accounting BEFORE the dispatch (the rollout jit donates
            # its carry).
            perf.note(
                f"rollout/c{chunk}_r{int(random_phase)}", rollout_fn,
                (agent_state["actor"], ring_state, env_state, obs, ep_ret, ep_len, rollout_key),
                steps=0,
            )
            with tracer.span("fused/superstep", "train"), train_timer.step(), watch(
                watchdog, "train_dispatch"
            ):
                env_state, obs, ep_ret, ep_len, ring_state, ep_info, rollout_key = rollout_fn(
                    agent_state["actor"], ring_state, env_state, obs, ep_ret, ep_len, rollout_key
                )
            train_timer.pend(ep_info["done"], None)
        pending_eps.append(ep_info)
        ring.adopt_state(ring_state, chunk)
        ring_state = ring.state
        _RUN_STATS["supersteps"] += 1
        _RUN_STATS["jit_dispatches"] += 1
        _RUN_STATS["env_steps"] += chunk * E

        # ------------------------------------------------------ train phase
        if iter_num >= learning_starts:
            per_rank_gradient_steps = ratio(
                (policy_step - prefill_steps + policy_steps_per_iter) / world_size
            )
            if per_rank_gradient_steps > 0 and ring.ready(ring_span):
                taus_full = _superstep_taus(
                    iter_start, iter_num, target_freq_iters, float(agent.tau), per_rank_gradient_steps
                )
                with timer("Time/train_time"):
                    remaining = per_rank_gradient_steps
                    offset = 0
                    while remaining > 0:
                        k = 1 << (min(remaining, fused_train_steps).bit_length() - 1)
                        taus_k = taus_full[offset:offset + k]
                        perf.note(
                            f"train/fused_k{k}", fused_train_fn,
                            (agent_state, opt_states, ring_state, train_key, taus_k),
                            steps=k,
                        )
                        with tracer.span("fused/train", "train"), train_timer.step(), watch(
                            watchdog, "train_dispatch"
                        ):
                            agent_state, opt_states, train_metrics, train_key = fused_train_fn(
                                agent_state, opt_states, ring_state, train_key, taus_k,
                            )
                        train_timer.pend(
                            agent_state["actor"], train_metrics if keep_train_metrics else None
                        )
                        dispatch_throttle.add(train_metrics)
                        cumulative_per_rank_gradient_steps += k
                        remaining -= k
                        offset += k
                        _RUN_STATS["jit_dispatches"] += 1
                train_step_count += world_size

        # ----------------------------------------------------------- logging
        should_log = cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or iter_num >= total_iters
        )
        if should_log:
            fetched_train_metrics = train_timer.flush()
            health.observe(policy_step, fetched_train_metrics, telemetry=telemetry)
            _log_episode_stats(pending_eps, aggregator, runtime, policy_step, cfg.metric.log_level)
            if aggregator and not aggregator.disabled:
                for tm in fetched_train_metrics:
                    aggregator.update("Loss/value_loss", tm["value_loss"])
                    aggregator.update("Loss/policy_loss", tm["policy_loss"])
                    aggregator.update("Loss/alpha_loss", tm["alpha_loss"])
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
                timer.reset()
        if should_log:
            last_log = policy_step
            last_train = train_step_count

        # -------------------------------------------------------- checkpoint
        if health.allow_save() and (
            (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every)
            or ((iter_num >= total_iters or guard.preempted) and cfg.checkpoint.save_last)
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
            # No "rb": the ring is device-resident; the Gymnasium lane
            # tolerates a missing buffer on resume (state_ckpt.get("rb")).
            ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{rank}.ckpt")
            if runtime.is_global_zero:
                save_checkpoint(ckpt_path, ckpt_state, keep_last=cfg.checkpoint.keep_last)

        if guard.preempted:
            runtime.print(f"Preemption: exiting cleanly after final checkpoint at policy step {policy_step}")
            break

    if runtime.is_global_zero and cfg.algo.run_test and not guard.preempted:
        test(agent, agent_state, runtime, cfg, log_dir, logger)

    guard.close()
    telemetry.close()
    if logger is not None:
        logger.close()


# ----------------------------------------------------------------- DreamerV3
def dreamer_v3_fused_main(runtime, cfg: Dict[str, Any]):
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import (
        _make_optimizer,
        _target_update_taus,
        make_fused_train_step,
    )
    from sheeprl_tpu.algos.dreamer_v3.utils import normalize_player_obs, test
    from sheeprl_tpu.algos.ppo.agent import actions_metadata
    from sheeprl_tpu.core.runtime import DispatchThrottle
    from sheeprl_tpu.utils.ops import init_moments

    _reset_run_stats()
    mesh = runtime.mesh
    rank = runtime.global_rank
    world_size = jax.process_count()

    state_ckpt = None
    if cfg.checkpoint.resume_from:
        state_ckpt = load_checkpoint(cfg.checkpoint.resume_from)

    logger = get_logger(runtime, cfg)
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name, logger=logger)
    runtime.print(f"Log dir: {log_dir} (fused Anakin lane)")
    telemetry = runtime.telemetry.open(log_dir, rank_zero=runtime.is_global_zero, device=runtime.device)
    guard = runtime.resilience.guard(rank_zero=runtime.is_global_zero)
    watchdog = runtime.resilience.watchdog
    health = runtime.health

    env = _resolve_env(cfg)
    num_envs = int(cfg.env.num_envs)
    obs_key, pixel = _single_obs_key(cfg, env)
    observation_space = gym.spaces.Dict({obs_key: env.observation_space})
    action_space = canonical_action_space(env)
    actions_dim, is_continuous = actions_metadata(action_space)
    act_sum = int(np.sum(actions_dim))
    to_env = action_to_env(env)
    clip_rewards = bool(cfg.env.clip_rewards)
    cnn_keys = (obs_key,) if pixel else ()
    obs_keys = [obs_key]

    with runtime.host_init():
        agent, agent_state = build_agent(
            runtime,
            actions_dim,
            is_continuous,
            cfg,
            observation_space,
            state_ckpt["world_model"] if state_ckpt is not None else None,
            state_ckpt["actor"] if state_ckpt is not None else None,
            state_ckpt["critic"] if state_ckpt is not None else None,
            state_ckpt["target_critic"] if state_ckpt is not None else None,
        )
        txs = {
            "world_model": _make_optimizer(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients),
            "actor": _make_optimizer(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients),
            "critic": _make_optimizer(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients),
        }
        opt_states = {
            "world_model": txs["world_model"].init(agent_state["world_model"]),
            "actor": txs["actor"].init(agent_state["actor"]),
            "critic": txs["critic"].init(agent_state["critic"]),
        }
        if state_ckpt is not None:
            for name, ckpt_key in (
                ("world_model", "world_optimizer"),
                ("actor", "actor_optimizer"),
                ("critic", "critic_optimizer"),
            ):
                opt_states[name] = restore_opt_state(opt_states[name], state_ckpt[ckpt_key])
    agent_state = runtime.shard_params(agent_state)
    opt_states = runtime.shard_params(opt_states)

    # Arm per-shard goodput accounting: the observatory needs the mesh and the
    # realised param layouts to attribute MFU/imbalance per data-shard.
    telemetry.set_mesh(mesh)
    telemetry.record_param_layouts(agent_state)

    moments_state = init_moments()
    if state_ckpt is not None and "moments" in state_ckpt:
        moments_state = jax.tree_util.tree_map(jnp.asarray, state_ckpt["moments"])

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    # ----------------------------------------------------------------- ring
    # Dreamer's superstep keeps GSPMD placement (the recurrent player latents
    # and sparse reset rows make its carry sharding XLA's call); the ring is
    # still env-sharded so the fused train jit samples per-shard minibatches.
    buffer_size = cfg.buffer.size // int(num_envs * world_size) if not cfg.dry_run else 2
    ring = DeviceReplayRing(
        buffer_size,
        num_envs,
        cnn_keys=cnn_keys,
        obs_keys=tuple(obs_keys),
        hbm_fraction=float(cfg.buffer.get("device_hbm_fraction", 0.4)),
        device=mesh.devices.flat[0],
        mesh=mesh,
    )
    obs_dtype = np.uint8 if pixel else np.float32
    specs = {
        obs_key: (tuple(env.observation_space.shape), obs_dtype),
        "actions": ((act_sum,), np.float32),
        "rewards": ((1,), np.float32),
        "terminated": ((1,), np.float32),
        "truncated": ((1,), np.float32),
        "is_first": ((1,), np.float32),
    }
    ring.allocate(specs)
    if state_ckpt is not None and cfg.buffer.checkpoint and state_ckpt.get("rb") is not None:
        ring.load_host_buffer(state_ckpt["rb"])
        ring.flush()
    if not ring.active:
        raise RuntimeError(
            f"algo.fused_rollout needs the device replay ring, which declined its "
            f"allocation: {ring.inactive_reason}"
        )
    write_fn = ring.make_step_write_fn()
    ring_sample_fn = ring.make_sample_fn(
        cfg.algo.per_rank_batch_size,
        sequence_length=cfg.algo.per_rank_sequence_length,
        time_major=True,
    )
    fused_train_fn = make_fused_train_step(
        agent,
        txs,
        cfg,
        mesh,
        ring_sample_fn,
        state=agent_state,
        opt_states=opt_states,
        ring_shardings=ring.state_shardings(),
    )
    fused_train_steps = max(int(cfg.algo.get("fused_train_steps", 1)), 1)

    # ------------------------------------------------------------- counters
    last_train = 0
    train_step_count = 0
    start_iter = (state_ckpt["iter_num"] // world_size) + 1 if state_ckpt is not None else 1
    policy_step = state_ckpt["iter_num"] * num_envs if state_ckpt is not None else 0
    last_log = state_ckpt["last_log"] if state_ckpt is not None else 0
    last_checkpoint = state_ckpt["last_checkpoint"] if state_ckpt is not None else 0
    policy_steps_per_iter = int(num_envs * world_size)
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
    superstep_iters = max(int(cfg.algo.get("fused_superstep_steps", 16)), 1)

    E = num_envs

    # ------------------------------------------------------------ supersteps
    step_v = jax.vmap(env.step)
    reset_v = jax.vmap(env.reset)

    def _make_rollout(steps: int, random_actions: bool):
        def rollout(wm_params, actor_params, player_state, env_state, obs, prev, ep_ret, ep_len, ring_state, key):
            next_key, k_roll = jax.random.split(key)

            def body(carry, step_key):
                env_state, obs, player_state, prev, ep_ret, ep_len, ring_state = carry
                k_act, k_step, k_reset = jax.random.split(step_key, 3)
                if random_actions:
                    if is_continuous:
                        actions_cat = jax.random.uniform(k_act, (E, act_sum), minval=-1.0, maxval=1.0)
                        real_actions = actions_cat
                    else:
                        subkeys = jax.random.split(k_act, len(actions_dim))
                        parts, reals = [], []
                        for ad, sk in zip(actions_dim, subkeys):
                            idx = jax.random.randint(sk, (E,), 0, ad)
                            parts.append(jax.nn.one_hot(idx, ad, dtype=jnp.float32))
                            reals.append(idx)
                        actions_cat = jnp.concatenate(parts, -1)
                        real_actions = jnp.stack(reals, -1)
                else:
                    actions_cat, real_actions, player_state = agent.player_step(
                        wm_params, actor_params, player_state,
                        normalize_player_obs({obs_key: obs}, cnn_keys), k_act, greedy=False,
                    )
                # Dreamer row convention: step t's row = (obs_t, action_t,
                # reward_{t-1}, flags_{t-1}, is_first) — exactly the host
                # lane's step_data ordering.
                row = dict(prev)
                row[obs_key] = obs
                row["actions"] = actions_cat.astype(jnp.float32)
                ring_state = write_fn(ring_state, row, jnp.ones((E,), jnp.bool_))
                new_state, new_obs, reward, done, info = step_v(
                    env_state, _env_actions(real_actions, env, to_env, is_continuous, E),
                    jax.random.split(k_step, E),
                )
                buf_reward = (jnp.tanh(reward) if clip_rewards else reward)[:, None]
                terminated = info["terminated"][:, None].astype(jnp.float32)
                truncated = info["truncated"][:, None].astype(jnp.float32)
                # Episode-boundary reset row (host lane's reset_data): the
                # TRUE final obs + the real flags + this step's reward.
                reset_row = {
                    obs_key: new_obs,
                    "actions": jnp.zeros((E, act_sum), jnp.float32),
                    "rewards": buf_reward,
                    "terminated": terminated,
                    "truncated": truncated,
                    "is_first": jnp.zeros((E, 1), jnp.float32),
                }
                ring_state = write_fn(ring_state, reset_row, done)
                d1 = done[:, None].astype(jnp.float32)
                prev = {
                    "rewards": (1.0 - d1) * buf_reward,
                    "terminated": (1.0 - d1) * terminated,
                    "truncated": (1.0 - d1) * truncated,
                    "is_first": d1,
                }
                if not random_actions:
                    player_state = agent.reset_player_state(
                        wm_params, player_state, done.astype(jnp.float32)
                    )
                ep_ret = ep_ret + reward
                ep_len = ep_len + 1
                r_state, r_obs = reset_v(jax.random.split(k_reset, E))
                env_state = jax.tree_util.tree_map(
                    lambda r, n: _where_done(done, r, n), r_state, new_state
                )
                obs_next = _where_done(done, r_obs, new_obs)
                ep_info = {"done": done, "returns": ep_ret, "lengths": ep_len.astype(jnp.float32)}
                ep_ret = jnp.where(done, 0.0, ep_ret)
                ep_len = jnp.where(done, 0, ep_len)
                return (env_state, obs_next, player_state, prev, ep_ret, ep_len, ring_state), ep_info

            (env_state, obs, player_state, prev, ep_ret, ep_len, ring_state), ep_info = jax.lax.scan(
                body, (env_state, obs, player_state, prev, ep_ret, ep_len, ring_state),
                jax.random.split(k_roll, steps),
            )
            rows_written = steps + ep_info["done"].astype(jnp.int32).sum(0)
            return env_state, obs, player_state, prev, ep_ret, ep_len, ring_state, ep_info, rows_written, next_key

        return jax.jit(rollout, donate_argnums=(2, 3, 4, 5, 6, 7, 8))

    rollout_fns: Dict[Tuple[int, bool], Any] = {}

    def _rollout_fn(steps: int, random_actions: bool):
        fn = rollout_fns.get((steps, random_actions))
        if fn is None:
            fn = _make_rollout(steps, random_actions)
            rollout_fns[(steps, random_actions)] = fn
        return fn

    init_key, loop_key = jax.random.split(jax.random.fold_in(runtime.root_key, rank))
    rollout_key, train_key = jax.random.split(loop_key)
    env_state, obs = jax.jit(reset_v)(jax.random.split(init_key, E))
    player_state = jax.jit(agent.init_player_state, static_argnums=(1,))(agent_state["world_model"], E)
    prev = {
        "rewards": jnp.zeros((E, 1), jnp.float32),
        "terminated": jnp.zeros((E, 1), jnp.float32),
        "truncated": jnp.zeros((E, 1), jnp.float32),
        "is_first": jnp.ones((E, 1), jnp.float32),
    }
    ep_ret = jnp.zeros((E,), jnp.float32)
    ep_len = jnp.zeros((E,), jnp.int32)
    ring_state = ring.state

    cumulative_per_rank_gradient_steps = 0
    dispatch_throttle = DispatchThrottle()
    train_timer = telemetry.step_timer("train", timer_key="Time/train_time")
    perf = telemetry.perf
    keep_train_metrics = (
        aggregator is not None and not aggregator.disabled and cfg.metric.log_level > 0
    ) or health.enabled
    pending_eps: List[Dict[str, Any]] = []
    tracer = tracer_mod.current()
    resumed = state_ckpt is not None

    iter_num = start_iter - 1  # last completed host-lane iteration
    while iter_num < total_iters:
        random_phase = iter_num < learning_starts and not resumed
        bound = total_iters - iter_num
        if iter_num < learning_starts:
            # Never straddle the learning_starts boundary: training begins
            # exactly where the host lane's does.
            bound = min(bound, learning_starts - iter_num)
        chunk = min(superstep_iters, bound)
        telemetry.advance(policy_step)
        guard.advance(policy_step)
        iter_num += chunk
        policy_step += chunk * policy_steps_per_iter

        with timer("Time/env_interaction_time" if random_phase else "Time/train_time"):
            rollout_fn = _rollout_fn(chunk, random_phase)
            # Goodput accounting BEFORE the dispatch (the rollout jit donates
            # its carry).
            perf.note(
                f"rollout/c{chunk}_r{int(random_phase)}", rollout_fn,
                (agent_state["world_model"], agent_state["actor"], player_state,
                 env_state, obs, prev, ep_ret, ep_len, ring_state, rollout_key),
                steps=0,
            )
            with tracer.span("fused/superstep", "train"), train_timer.step(), watch(
                watchdog, "train_dispatch"
            ):
                (
                    env_state, obs, player_state, prev, ep_ret, ep_len, ring_state, ep_info,
                    rows_written, rollout_key,
                ) = rollout_fn(
                    agent_state["world_model"], agent_state["actor"], player_state,
                    env_state, obs, prev, ep_ret, ep_len, ring_state, rollout_key,
                )
            train_timer.pend(ep_info["done"], None)
        pending_eps.append(ep_info)
        ring.adopt_state(ring_state, _fetch_row_counts(rows_written))
        ring_state = ring.state
        _RUN_STATS["supersteps"] += 1
        _RUN_STATS["jit_dispatches"] += 1
        _RUN_STATS["env_steps"] += chunk * E

        # ------------------------------------------------------ train phase
        if iter_num >= learning_starts:
            ratio_steps = policy_step - prefill_steps * policy_steps_per_iter
            per_rank_gradient_steps = ratio(ratio_steps / world_size)
            if per_rank_gradient_steps > 0 and ring.ready(cfg.algo.per_rank_sequence_length):
                with timer("Time/train_time"):
                    remaining = per_rank_gradient_steps
                    while remaining > 0:
                        k = 1 << (min(remaining, fused_train_steps).bit_length() - 1)
                        taus = _target_update_taus(
                            cumulative_per_rank_gradient_steps,
                            k,
                            cfg.algo.critic.per_rank_target_network_update_freq,
                            cfg.algo.critic.tau,
                        )
                        perf.note(
                            f"train/fused_k{k}", fused_train_fn,
                            (agent_state, opt_states, moments_state, ring_state, train_key, taus),
                            steps=k,
                        )
                        with tracer.span("fused/train", "train"), train_timer.step(), watch(
                            watchdog, "train_dispatch"
                        ):
                            agent_state, opt_states, moments_state, train_metrics, train_key = fused_train_fn(
                                agent_state, opt_states, moments_state, ring_state, train_key, taus
                            )
                        train_timer.pend(
                            agent_state["world_model"], train_metrics if keep_train_metrics else None
                        )
                        dispatch_throttle.add(train_metrics)
                        cumulative_per_rank_gradient_steps += k
                        remaining -= k
                        _RUN_STATS["jit_dispatches"] += 1
                train_step_count += world_size

        # ----------------------------------------------------------- logging
        should_log = cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or iter_num >= total_iters
        )
        if should_log:
            fetched_train_metrics = train_timer.flush()
            health.observe(policy_step, fetched_train_metrics, telemetry=telemetry)
            _log_episode_stats(pending_eps, aggregator, runtime, policy_step, cfg.metric.log_level)
            if aggregator and not aggregator.disabled:
                for m in fetched_train_metrics:
                    for mk, v in m.items():
                        if mk in aggregator:
                            aggregator.update(mk, v)
                aggregator.log_and_reset(logger, policy_step)
            telemetry.log_counters(logger, policy_step)
        if should_log and logger is not None:
            if policy_step > 0:
                logger.log(
                    "Params/replay_ratio",
                    cumulative_per_rank_gradient_steps * world_size / policy_step,
                    policy_step,
                )
            if not timer.disabled:
                timer_metrics = timer.compute()
                if timer_metrics.get("Time/train_time", 0) > 0:
                    logger.log(
                        "Time/sps_train",
                        (train_step_count - last_train) / timer_metrics["Time/train_time"],
                        policy_step,
                    )
                timer.reset()
        if should_log:
            last_log = policy_step
            last_train = train_step_count

        # -------------------------------------------------------- checkpoint
        if health.allow_save() and (
            (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every)
            or ((iter_num >= total_iters or guard.preempted) and cfg.checkpoint.save_last)
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "world_model": agent_state["world_model"],
                "actor": agent_state["actor"],
                "critic": agent_state["critic"],
                "target_critic": agent_state["target_critic"],
                "world_optimizer": opt_states["world_model"],
                "actor_optimizer": opt_states["actor"],
                "critic_optimizer": opt_states["critic"],
                "moments": moments_state,
                "ratio": ratio.state_dict(),
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

    if runtime.is_global_zero and cfg.algo.run_test and not guard.preempted:
        test(agent, agent_state, runtime, cfg, log_dir, logger)

    guard.close()
    telemetry.close()
    if logger is not None:
        logger.close()
