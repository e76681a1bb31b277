"""CLI dispatcher: config composition → registry lookup → Runtime → algorithm.

Parity with the reference CLI (sheeprl/cli.py:23-450): `run` composes the
config (native composition engine instead of Hydra), handles resume-config
merging, prunes metric/model-manager keys against the algorithm's declared
sets, instantiates the substrate (Runtime instead of Fabric), seeds, and
invokes the registered entrypoint. `evaluation` rebuilds a single-device
runtime from a checkpoint's saved config and calls the registered eval fn.
"""

from __future__ import annotations

import importlib
import os
import pathlib
import sys
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.config.loader import compose
from sheeprl_tpu.registry import algorithm_registry, evaluation_registry
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import dotdict, print_config


def _load_ckpt_config(ckpt_path: pathlib.Path) -> dotdict:
    """Load the config.yaml saved next to a run's checkpoint directory."""
    import yaml

    with open(ckpt_path.parent.parent / "config.yaml") as fp:
        return dotdict(yaml.safe_load(fp))


def resume_from_checkpoint(cfg: dotdict) -> dotdict:
    """Force-merge the original run's config.yaml, keeping the new run's
    total_steps/paths (reference: cli.py:23-57)."""
    ckpt_path = pathlib.Path(cfg.checkpoint.resume_from)
    old_cfg = _load_ckpt_config(ckpt_path)
    if old_cfg.env.id != cfg.env.id:
        raise ValueError(
            "This experiment is run with a different environment from the one of the experiment you want to restart. "
            f"Got '{cfg.env.id}', but the environment of the experiment of the checkpoint was {old_cfg.env.id}. "
            "Set properly the environment for restarting the experiment."
        )
    if old_cfg.algo.name != cfg.algo.name:
        raise ValueError(
            "This experiment is run with a different algorithm from the one of the experiment you want to restart. "
            f"Got '{cfg.algo.name}', but the algorithm of the experiment of the checkpoint was {old_cfg.algo.name}. "
            "Set properly the algorithm name for restarting the experiment."
        )
    if old_cfg.algo.get("learning_starts", 0) > 0:
        warnings.warn(
            "The `algo.learning_starts` parameter is greater than zero. "
            "This means that the resuming experiment will pre-fill the buffer for `algo.learning_starts` steps. "
            "If this is not intended please set the `algo.learning_starts=0` parameter in the experiment "
            "configuration or through the CLI."
        )
    old = old_cfg.as_dict()
    old.pop("root_dir", None)
    old.pop("run_name", None)
    old.get("algo", {}).pop("total_steps", None)
    old.get("algo", {}).pop("learning_starts", None)
    old.get("checkpoint", {}).pop("resume_from", None)
    # Chaos injectors are one-shot experiment artifacts: re-inheriting them
    # from the preempted run's config would replay the same fault right after
    # resume (a SIGTERM-at-step-N injector becomes a preemption loop). The
    # resuming invocation's own chaos config stays authoritative.
    old.get("resilience", {}).pop("chaos", None)

    def merge(dst: Dict[str, Any], src: Dict[str, Any]) -> None:
        for k, v in src.items():
            if isinstance(v, dict) and isinstance(dst.get(k), dict):
                merge(dst[k], v)
            else:
                dst[k] = v

    merged = cfg.as_dict()
    merge(merged, old)
    return dotdict(merged)


def check_configs(cfg: dotdict) -> None:
    """Imperative config validation (reference: cli.py:271-345, minus the
    DDP-strategy matrix that has no JAX counterpart)."""
    if cfg.algo.name not in algorithm_registry:
        raise RuntimeError(
            f"Given the algorithm named '{cfg.algo.name}', no entrypoint has been registered. "
            f"Available: {sorted(algorithm_registry)}"
        )
    accelerator = str(cfg.fabric.get("accelerator", "auto")).lower()
    if accelerator not in ("auto", "cpu", "tpu"):
        raise ValueError(f"Unknown fabric.accelerator '{accelerator}'. Valid: auto | cpu | tpu")
    player_device = str(cfg.fabric.get("player_device", "auto") or "auto").lower()
    if player_device not in ("auto", "host", "mesh"):
        raise ValueError(f"Unknown fabric.player_device '{player_device}'. Valid: auto | host | mesh")
    player_sync = str(cfg.fabric.get("player_sync", "fresh") or "fresh").lower()
    if player_sync not in ("fresh", "async"):
        raise ValueError(f"Unknown fabric.player_sync '{player_sync}'. Valid: fresh | async")
    tele = cfg.get("telemetry")
    if tele is not None and tele.get("profiler") is not None:
        start = int(tele.profiler.get("start_step", -1))
        stop = int(tele.profiler.get("stop_step", -1))
        if (start >= 0) != (stop >= 0) or (start >= 0 and stop <= start):
            raise ValueError(
                "telemetry.profiler window must satisfy 0 <= start_step < stop_step "
                f"(or both -1 to disable); got [{start}, {stop})"
            )
    res = cfg.get("resilience")
    if res is not None:
        wd = res.get("watchdog")
        if wd is not None:
            on_trip = str(wd.get("on_trip", "warn") or "warn").lower()
            if on_trip not in ("warn", "preempt", "abort"):
                raise ValueError(
                    f"Unknown resilience.watchdog.on_trip '{on_trip}'. Valid: warn | preempt | abort"
                )
            if bool(wd.get("enabled", False)) and float(wd.get("timeout_s", 120.0) or 0.0) <= 0:
                raise ValueError("resilience.watchdog.enabled=True requires timeout_s > 0")
        ch = res.get("chaos")
        if ch is not None and bool(ch.get("enabled", False)):
            from sheeprl_tpu.core.chaos import STEP_INJECTOR_KINDS

            known = ("env_step_raise", "nan_reward") + tuple(STEP_INJECTOR_KINDS)
            for inj in ch.get("injectors") or []:
                if str(inj.get("kind", "")) not in known:
                    raise ValueError(
                        f"Unknown resilience.chaos injector kind {inj.get('kind')!r}. Valid: {known}"
                    )
    fleet = cfg.get("fleet")
    if fleet is not None:
        replicas = int(fleet.get("replicas", 1) or 1)
        if replicas < 1:
            raise ValueError(f"fleet.replicas must be >= 1, got {replicas}")
        quorum = int(fleet.get("quorum", 1) or 1)
        if not 1 <= quorum <= replicas:
            raise ValueError(f"fleet.quorum must be in [1, fleet.replicas={replicas}], got {quorum}")
        start_method = str(fleet.get("start_method", "spawn") or "spawn")
        if start_method != "spawn":
            # Forking after JAX initializes inherits locked runtime state in
            # every replica; only spawn gives each one a clean interpreter.
            raise ValueError(f"fleet.start_method must be 'spawn', got {start_method!r}")
        from sheeprl_tpu.core.fleet import fleet_active

        if fleet_active(cfg) and not str(cfg.algo.name).endswith("_decoupled"):
            raise ValueError(
                "Fleet mode (fleet.replicas > 1 or fleet.enabled=True) requires a decoupled "
                f"algorithm (the replicas own the envs); got algo.name={cfg.algo.name!r}"
            )
    health = cfg.get("health")
    if health is not None:
        for knob in ("policy", "anomaly_policy"):
            value = str(health.get(knob, "warn") or "warn").lower()
            if value not in ("warn", "preempt", "abort"):
                raise ValueError(f"Unknown health.{knob} '{value}'. Valid: warn | preempt | abort")
        ewma = health.get("ewma")
        if ewma is not None:
            alpha = float(ewma.get("alpha", 0.1) or 0.0)
            if not 0.0 < alpha <= 1.0:
                raise ValueError(f"health.ewma.alpha must be in (0, 1], got {alpha}")
            if float(ewma.get("k", 6.0) or 0.0) <= 0.0:
                raise ValueError("health.ewma.k must be > 0")
        if bool(health.get("enabled", False)) and int(cfg.metric.get("log_level", 1)) <= 0:
            warnings.warn(
                "health.enabled=True but metric.log_level=0: sentinels observe at the metric "
                "log cadence, so nothing will be watched. Set metric.log_level >= 1.",
            )
    # Anakin lane (core/fused_loop.py): fused rollout+train needs a pure-JAX
    # env and an algorithm with a fused driver.
    if bool(cfg.algo.get("fused_rollout", False)):
        if not bool(cfg.env.get("jax_native", False)):
            raise ValueError(
                "algo.fused_rollout=True requires env.jax_native=True: the fused superstep "
                "steps the env inside the training jit, so it must be a pure-JAX env "
                "(sheeprl_tpu/envs/jax — e.g. env=jax_cartpole, env=jax_pendulum)."
            )
        if cfg.algo.name not in ("ppo", "sac", "dreamer_v3"):
            raise ValueError(
                f"algo.fused_rollout is implemented for ppo, sac and dreamer_v3; got '{cfg.algo.name}'. "
                "Run this algorithm on a jax env through the host lane (env.jax_native with "
                "algo.fused_rollout=false uses the JaxToGymnasium wrapper) instead."
            )
        if int(cfg.algo.get("fused_superstep_steps", 64)) < 1:
            raise ValueError("algo.fused_superstep_steps must be >= 1")
    if bool(cfg.env.get("jax_native", False)):
        from sheeprl_tpu.envs.jax import make_jax_env

        try:
            make_jax_env(cfg.env.id)
        except ValueError as err:
            raise ValueError(f"env.jax_native=True but env.id is not a registered jax env: {err}") from err
    entry = algorithm_registry[cfg.algo.name]
    if (
        entry.decoupled
        and player_device == "mesh"
        and int(os.environ.get("SHEEPRL_NUM_PROCS", "1")) < 2
        and cfg.fabric.get("devices", 1) in (1, "1")
    ):
        # player_device=host always works on one device (the full mesh
        # trains); =auto is resolved at runtime and may pick host, so only
        # the explicit on-mesh split is rejected here — auto that resolves
        # to mesh fails later in split_player_trainer with the same message.
        raise RuntimeError(
            f"The decoupled algorithm '{cfg.algo.name}' requires at least 2 devices/processes "
            "(one player + at least one trainer), or fabric.player_device=host to run the "
            "player on the host CPU and train on every device."
        )


def _prune_metric_and_model_keys(cfg: dotdict, utils_module) -> None:
    """Keep only the metric/model keys the algorithm declares
    (reference: cli.py:151-181)."""
    if cfg.get("metric") is not None:
        predefined = set()
        if not hasattr(utils_module, "AGGREGATOR_KEYS"):
            warnings.warn(
                f"No 'AGGREGATOR_KEYS' set found for the {cfg.algo.name} algorithm. No metric will be logged.",
                UserWarning,
            )
        else:
            predefined = utils_module.AGGREGATOR_KEYS
        timer.disabled = cfg.metric.log_level == 0 or cfg.metric.disable_timer
        for k in set(cfg.metric.aggregator.metrics.keys()) - predefined:
            cfg.metric.aggregator.metrics.pop(k, None)
        MetricAggregator.disabled = cfg.metric.log_level == 0 or len(cfg.metric.aggregator.metrics) == 0

    if cfg.get("model_manager") is not None and not cfg.model_manager.disabled:
        _prune_model_keys(cfg, utils_module)


def _prune_model_keys(cfg: dotdict, utils_module) -> None:
    """Drop model-manager entries the algorithm does not checkpoint; warn and
    disable when nothing remains."""
    predefined = set()
    if not hasattr(utils_module, "MODELS_TO_REGISTER"):
        warnings.warn(
            f"No 'MODELS_TO_REGISTER' set found for the {cfg.algo.name} algorithm. "
            "No model will be registered.",
            UserWarning,
        )
    else:
        predefined = utils_module.MODELS_TO_REGISTER
    for k in set(cfg.model_manager.models.keys()) - predefined:
        cfg.model_manager.models.pop(k, None)
    if len(cfg.model_manager.models) == 0:
        warnings.warn(
            f"No model-manager entries match the '{cfg.algo.name}' algorithm's registered-model "
            f"contract ({sorted(predefined)}); model registration is disabled.",
            UserWarning,
        )
        cfg.model_manager.disabled = True


def _launch(cfg: dotdict, telemetry: Any) -> Tuple[Any, Any, Dict[str, Any]]:
    """Registry lookup + Runtime construction: ``(runtime, entrypoint, the
    entrypoint's extra kwargs)`` (reference: cli.py:60-199; fabric.launch
    collapses to a plain call — JAX multi-host processes are launched
    externally, one per host)."""
    entry = algorithm_registry[cfg.algo.name]
    task = importlib.import_module(entry.module)
    utils_module = importlib.import_module(entry.module.rsplit(".", 1)[0] + ".utils")
    command = task.__dict__[entry.entrypoint.__name__]

    _prune_metric_and_model_keys(cfg, utils_module)

    kwargs = {}
    if "finetuning" in cfg.algo.name and "p2e" in entry.module:
        # P2E chaining: the finetuning phase inherits the exploration run's
        # environment setup from the checkpoint's saved config
        # (reference: cli.py:117-148).
        expl_ckpt = cfg.checkpoint.get("exploration_ckpt_path")
        if not expl_ckpt or str(expl_ckpt) == "???":
            raise ValueError(
                "P2E finetuning needs the exploration phase's checkpoint: set "
                "'checkpoint.exploration_ckpt_path=<path-to-exploration-ckpt>'."
            )
        ckpt_path = pathlib.Path(expl_ckpt)
        exploration_cfg = _load_ckpt_config(ckpt_path)
        if exploration_cfg.env.id != cfg.env.id:
            raise ValueError(
                "This experiment is run with a different environment from "
                "the one of the exploration you want to finetune. "
                f"Got '{cfg.env.id}', but the environment used during exploration "
                f"was {exploration_cfg.env.id}. "
                "Set properly the environment for finetuning the experiment."
            )
        kwargs["exploration_cfg"] = exploration_cfg
        for env_key in (
            "frame_stack",
            "screen_size",
            "action_repeat",
            "grayscale",
            "clip_rewards",
            "frame_stack_dilation",
            "max_episode_steps",
            "reward_as_observation",
        ):
            cfg.env[env_key] = exploration_cfg.env[env_key]
        _env_target = str(cfg.env.wrapper.get("_target_", "")).lower()
        if "minerl" in _env_target or "minedojo" in _env_target:
            for env_key in (
                "max_pitch",
                "min_pitch",
                "sticky_jump",
                "sticky_attack",
                "break_speed_multiplier",
            ):
                cfg.env[env_key] = exploration_cfg.env[env_key]
        if cfg.buffer.load_from_exploration:
            cfg.fabric.devices = exploration_cfg.fabric.devices
            cfg.fabric.num_nodes = exploration_cfg.fabric.num_nodes

    if cfg.get("xla_deterministic"):
        # Reference: the reproducible() wrapper around every entrypoint
        # (sheeprl/cli.py:187-197). Must precede launch(): XLA_FLAGS are
        # read when the backend is constructed.
        from sheeprl_tpu.core.runtime import enable_xla_determinism

        enable_xla_determinism()
    runtime = instantiate(cfg.fabric)
    runtime.launch()
    runtime.seed_everything(cfg.seed)
    runtime.telemetry = telemetry
    # The run's fault-tolerance surface: preemption guard + env supervisor +
    # dispatch watchdog + chaos injectors (howto/fault_tolerance.md).
    from sheeprl_tpu.core.resilience import Resilience

    runtime.resilience = Resilience.from_config(cfg)
    # The run's training-health sentinels: in-jit probes + host anomaly
    # detection with warn|preempt|abort escalation (howto/observability.md).
    from sheeprl_tpu.telemetry.health import HealthMonitor

    runtime.health = HealthMonitor.from_config(cfg)
    return runtime, command, kwargs


def run_algorithm(cfg: dotdict, setup: Optional[Tuple[float, float]] = None) -> None:
    """Runtime construction + entrypoint call.

    ``setup``: the ``perf_counter`` start and end of ``setup/config`` where
    :func:`run` composed the config; set-up's root span starts there."""
    # The run's observability surface, built first so that set-up's compiles
    # are recorded from here on: every algorithm opens it against its log dir
    # and threads it through the train loop (howto/observability.md).
    from sheeprl_tpu.telemetry import Telemetry

    telemetry = Telemetry.from_config(cfg)
    telemetry.begin_setup(setup[0] if setup else time.perf_counter(), (("setup/config", *setup),) if setup else ())
    with telemetry.span("setup/runtime", "setup"):
        runtime, command, kwargs = _launch(cfg, telemetry)
    import jax

    # Eager ops and un-sharded jits must land on the chosen accelerator: the
    # process's default backend may differ from it (fabric.accelerator=cpu on
    # a machine whose default is the TPU).
    with jax.default_device(runtime.device):
        command(runtime, cfg, **kwargs)


def run(args: Optional[Sequence[str]] = None) -> None:
    """Training entry: `python -m sheeprl_tpu exp=... [overrides...]`
    (reference: cli.run, cli.py:358-366)."""
    started = time.perf_counter()  # set-up's root span starts here
    import sheeprl_tpu

    sheeprl_tpu.register_all()
    overrides = list(args) if args is not None else sys.argv[1:]
    cfg = compose("config", overrides)
    os.environ.setdefault("OMP_NUM_THREADS", str(cfg.get("num_threads", 1)))
    if str(cfg.checkpoint.resume_from or "").startswith("auto"):
        # `checkpoint.resume_from=auto[:<dir>]` — follow the preemption
        # guard's autoresume.json pointer, or fall back to the newest
        # manifest-valid checkpoint under the search root (skipping torn or
        # corrupt saves). See howto/fault_tolerance.md.
        from sheeprl_tpu.core.resilience import resolve_auto_resume

        resolved = resolve_auto_resume(str(cfg.checkpoint.resume_from), cfg.get("log_root"))
        if resolved is None:
            raise FileNotFoundError(
                f"checkpoint.resume_from={cfg.checkpoint.resume_from!r}: no valid checkpoint "
                "found (no autoresume.json pointer and no manifest-valid ckpt_*.ckpt)"
            )
        print(f"Auto-resume: resolved {cfg.checkpoint.resume_from!r} -> {resolved}")
        cfg.checkpoint.resume_from = resolved
    if cfg.checkpoint.resume_from:
        cfg = resume_from_checkpoint(cfg)
    if cfg.metric.log_level > 0:
        print_config(cfg)
    check_configs(cfg)
    run_algorithm(cfg, setup=(started, time.perf_counter()))


def registration(args: Optional[Sequence[str]] = None) -> None:
    """Model-registration entry: `python -m sheeprl_tpu.registration
    checkpoint_path=<ckpt> model_manager=<algo> [overrides...]` — logs the
    checkpoint's models to MLflow and registers the ones selected by the
    model_manager config (reference: cli.registration, cli.py:408-450)."""
    import sheeprl_tpu

    sheeprl_tpu.register_all()
    overrides = list(args) if args is not None else sys.argv[1:]
    ckpt_override = [o for o in overrides if o.startswith("checkpoint_path=")]
    if not ckpt_override:
        raise ValueError("You must specify checkpoint_path=<path-to-checkpoint>")
    checkpoint_path = pathlib.Path(ckpt_override[-1].split("=", 1)[1])
    ckpt_cfg = _load_ckpt_config(checkpoint_path)

    # The model_manager configs interpolate ${exp_name}/${env.id}: supply them
    # from the checkpoint's run identity before composing.
    cfg = compose(
        "model_manager_config",
        overrides + [f"+exp_name={ckpt_cfg.exp_name}", f"+env.id={ckpt_cfg.env.id}"],
    )
    # Inherit the rest of the run's identity from the checkpoint's config
    for key in ("env", "algo", "distribution", "seed"):
        cfg[key] = ckpt_cfg[key]
    cfg.to_log = ckpt_cfg

    # The models to register are the algorithm's registered-model contract
    entry = algorithm_registry.get(cfg.algo.name)
    if entry is None:
        raise RuntimeError(f"Unknown algorithm '{cfg.algo.name}' in the checkpoint config")
    utils_module = importlib.import_module(entry.module.rsplit(".", 1)[0] + ".utils")
    models_keys = sorted(getattr(utils_module, "MODELS_TO_REGISTER", set()))
    cfg.model_manager.disabled = False
    _prune_model_keys(cfg, utils_module)

    from sheeprl_tpu.utils.checkpoint import load_checkpoint
    from sheeprl_tpu.utils.mlflow import register_model_from_checkpoint

    state = load_checkpoint(str(checkpoint_path))
    runtime = instantiate(
        dotdict(
            {
                "_target_": "sheeprl_tpu.core.runtime.Runtime",
                "devices": 1,
                "accelerator": "cpu",
                "precision": str(ckpt_cfg.fabric.get("precision", "32-true")),
            }
        )
    )
    runtime.launch()
    runtime.seed_everything(cfg.seed)
    register_model_from_checkpoint(runtime, cfg, state, models_keys)


def evaluation(args: Optional[Sequence[str]] = None) -> None:
    """Evaluation entry: `python -m sheeprl_tpu.eval checkpoint_path=... [overrides]`
    (reference: cli.evaluation, cli.py:369-405 + eval_algorithm 202-268)."""
    import yaml

    import sheeprl_tpu

    sheeprl_tpu.register_all()
    overrides = list(args) if args is not None else sys.argv[1:]
    ckpt_override = [o for o in overrides if o.startswith("checkpoint_path=")]
    if not ckpt_override:
        raise ValueError("You must specify checkpoint_path=<path-to-checkpoint>")
    checkpoint_path = pathlib.Path(ckpt_override[-1].split("=", 1)[1])
    rest: List[str] = [o for o in overrides if not o.startswith("checkpoint_path=")]

    with open(checkpoint_path.parent.parent / "config.yaml") as fp:
        ckpt_cfg = dotdict(yaml.safe_load(fp))

    # Start from the run's config, let CLI overrides win, force eval-time keys.
    from sheeprl_tpu.config.loader import _parse_value
    from sheeprl_tpu.utils.utils import set_by_path

    cfg = ckpt_cfg
    user_keys = set()
    for ov in rest:
        k, v = ov.split("=", 1)
        k = k.lstrip("+")
        user_keys.add(k)
        set_by_path(cfg, k, _parse_value(v))
    # <run_name>/<version_N>/evaluation next to the original run
    # (reference: cli.py:393-401 — root_dir becomes the absolute run root).
    cfg.root_dir = str(checkpoint_path.parent.parent.parent.parent)
    cfg.run_name = str(
        os.path.join(
            os.path.basename(checkpoint_path.parent.parent.parent),
            os.path.basename(checkpoint_path.parent.parent),
            "evaluation",
        )
    )
    cfg.checkpoint.resume_from = str(checkpoint_path)
    # Eval-time defaults (single env, single local device) apply only where
    # the user did not explicitly override: `env.num_envs=4` or `fabric.*`
    # on the command line must survive this block, not be clobbered by it.
    if "env.num_envs" not in user_keys:
        cfg.env.num_envs = 1
    user_fabric_keys = {k.split(".", 1)[1] for k in user_keys if k.startswith("fabric.")}
    eval_fabric = dotdict(
        {
            "_target_": cfg.fabric.get("_target_", "sheeprl_tpu.core.runtime.Runtime"),
            "devices": 1,
            "num_nodes": 1,
            "strategy": "single_device",
            "accelerator": cfg.fabric.get("accelerator", "auto"),
            "precision": cfg.fabric.get("precision", "32-true"),
            "model_axis": 1,
        }
    )
    dropped = []
    for key in sorted(user_fabric_keys):
        if key in cfg.fabric:
            eval_fabric[key] = cfg.fabric[key]
        else:
            dropped.append(f"fabric.{key}")
    if dropped:
        warnings.warn(
            f"Evaluation ignores unknown fabric overrides: {', '.join(dropped)}",
            stacklevel=2,
        )
    cfg.fabric = eval_fabric

    if cfg.algo.name not in evaluation_registry:
        raise RuntimeError(
            f"Given the algorithm named '{cfg.algo.name}', no evaluation entrypoint has been registered. "
            f"Available: {sorted(evaluation_registry)}"
        )
    entry = evaluation_registry[cfg.algo.name]
    task = importlib.import_module(entry.module)
    command = task.__dict__[entry.entrypoint.__name__]

    from sheeprl_tpu.utils.checkpoint import load_checkpoint

    state = load_checkpoint(str(checkpoint_path))

    runtime = instantiate(cfg.fabric)
    runtime.launch()
    runtime.seed_everything(cfg.seed)
    command(runtime, cfg, state)
