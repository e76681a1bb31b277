#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that sheeprl-tpu still starts on the chip.

Drives the system's main path once on an attached TPU, in ONE process (a chip
belongs to one process), through the entry points a user calls:

- **A. trainer** — DreamerV3-S at full width (``exp=dreamer_v3_100k_ms_pacman``:
  recurrent 512, dense 512, CNN multiplier 32, 32x32 latents; batch 16 x
  sequence 64, replay ratio and bf16-mixed as the recipe sets them) on the
  64x64x3 dummy pixel env, through ``compose`` + ``check_configs`` +
  ``run_algorithm``. Only prefill length, total steps, buffer size and the
  checkpoint/log cadence are cut.
- **B. fused lane** — ``ppo_anakin`` on the in-repo JAX CartPole for a few
  supersteps (``shard_map`` + in-jit rollout), held to the lane's own
  contract of at most two jit dispatches per superstep.
- **C. server** — ``export_artifact`` on A's checkpoint,
  ``InferenceEngine.load``, 16 ``act`` requests over 2 sessions.

``python chip_smoke.py`` needs exactly one TPU chip; ``--chips 4`` runs the
mesh comparisons instead (fused-lane 1 <-> 4 parity, DreamerV3-S sharded over
four chips) and no other phase; ``--aot`` needs no chip at all and asks the
TPU compiler, from a CPU-only sandbox, whether the kernels and the real train
step compile for a described v5e (a rehearsal, never reported as a chip run).

Every phase raises on failure, so the process exits non-zero; only a run in
which every phase passed prints, as its LAST line,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Timings printed above that line are smoke timings of one short run, not
benchmark numbers. Everything is written under ``chiprun_out/chip_smoke/``
next to this file; nothing is read from ``logs/``, ``~/.cache`` or an earlier
run, and the observations come from a seed.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import shutil
import statistics
import sys
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
SEED = 5

# ------------------------------------------------------------------- recipes
#: Model widths of the CPU rehearsal (tests/test_chip_smoke.py): control flow
#: only. The chip always runs the recipe's own widths.
DV3_MICRO_MODEL = (
    "algo.dense_units=8",
    "algo.mlp_layers=1",
    "algo.horizon=3",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=8",
    "algo.world_model.representation_model.hidden_size=8",
    "algo.world_model.transition_model.hidden_size=8",
    "algo.world_model.discrete_size=4",
    "algo.world_model.stochastic_size=4",
)

#: What a size fixes: how far the DreamerV3 recipe is cut, how many gradient
#: steps must run, how many supersteps and requests.
FULL = {
    # 96 prefill rows >= the 64-step sequence; then one gradient step per
    # policy step (replay ratio 1): 16 of them, logged in two intervals of 8
    # so that the last one holds steady steps only. The run ends before any
    # steady state the recompile watchdog could judge: its warm-up covers it.
    "dv3": ("algo.learning_starts=96", "algo.total_steps=111", "buffer.size=4096", "metric.log_every=8",
            "telemetry.warmup_iters=111"),
    "min_gradient_steps": 8,
    "ppo": ("algo.total_steps=4096",),  # 8 supersteps of 128 steps x 4 envs
    "requests_per_session": 8,
}
MICRO = {
    "dv3": DV3_MICRO_MODEL + ("dry_run=True", "algo.per_rank_batch_size=4", "algo.per_rank_sequence_length=1",
                              "buffer.size=8", "metric.log_every=1"),
    "min_gradient_steps": 1,
    "ppo": ("algo.total_steps=64", "algo.rollout_steps=8", "algo.per_rank_batch_size=8", "algo.update_epochs=1",
            "algo.dense_units=8", "algo.mlp_layers=1", "algo.encoder.mlp_features_dim=8"),
    "requests_per_session": 2,
}


def dv3_overrides(root_dir: str, run_name: str, accelerator: str, size: Dict[str, Any], extra: Sequence[str] = ()):
    """Phase A's recipe. Untouched: model widths, batch 16 x sequence 64,
    replay ratio, bf16-mixed. ALE is not installed and the chip machine has
    no network, so the 64x64x3 dummy pixel env stands in for MsPacman."""
    return [
        "exp=dreamer_v3_100k_ms_pacman",
        "env=dummy",
        "env.id=discrete",
        "env.capture_video=False",
        "env.sync_env=True",
        "buffer.memmap=False",
        "buffer.checkpoint=False",
        "algo.run_test=False",
        "checkpoint.every=0",
        "checkpoint.save_last=True",
        "metric.log_level=1",
        "telemetry.enabled=True",
        f"seed={SEED}",
        f"fabric.accelerator={accelerator}",
        f"root_dir={root_dir}",
        f"run_name={run_name}",
        *size["dv3"],
        *extra,
    ]


def ppo_anakin_overrides(root_dir: str, run_name: str, accelerator: str, size: Dict[str, Any]):
    """Phase B's recipe: ``exp=ppo_anakin`` with the fused rollout on."""
    return [
        "exp=ppo_anakin",
        "algo.fused_rollout=True",
        "metric.log_level=0",
        "metric.disable_timer=True",
        "algo.run_test=False",
        "env.capture_video=False",
        "env.sync_env=True",
        "checkpoint.every=0",
        "checkpoint.save_last=True",
        f"seed={SEED}",
        f"fabric.accelerator={accelerator}",
        f"root_dir={root_dir}",
        f"run_name={run_name}",
        *size["ppo"],
    ]


def sac_shard_overrides(devices: int, accelerator: str, **extra: Any) -> List[str]:
    """The fused SAC run whose 1 <-> N-device parity the repo pins
    (tests/test_algos/test_sharded_learner.py imports this recipe)."""
    args = [
        "exp=sac_anakin",
        "metric.log_level=0",
        "env.num_envs=8",
        "env.sync_env=True",
        "algo.fused_superstep_steps=4",
        "algo.fused_train_steps=4",
        "algo.total_steps=96",
        "algo.learning_starts=32",
        "algo.per_rank_batch_size=8",
        "algo.hidden_size=8",
        "algo.run_test=False",
        "algo.fused_rollout=True",
        "buffer.size=256",
        "buffer.memmap=False",
        "checkpoint.every=0",
        "checkpoint.save_last=True",
        f"fabric.accelerator={accelerator}",
        f"fabric.devices={devices}",
    ]
    return args + [f"{k}={v}" for k, v in extra.items()]


def ppo_shard_overrides(devices: int, accelerator: str, **extra: Any) -> List[str]:
    """The fused PPO run of the same parity comparison."""
    args = [
        "exp=ppo_anakin",
        "metric.log_level=0",
        "env.num_envs=8",
        "env.sync_env=True",
        "algo.rollout_steps=4",
        "algo.total_steps=64",
        "algo.per_rank_batch_size=8",
        "algo.update_epochs=1",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.encoder.mlp_features_dim=8",
        "algo.run_test=False",
        "algo.fused_rollout=True",
        "buffer.memmap=False",
        "checkpoint.every=0",
        "checkpoint.save_last=True",
        f"fabric.accelerator={accelerator}",
        f"fabric.devices={devices}",
    ]
    return args + [f"{k}={v}" for k, v in extra.items()]


#: The tolerance that comparison is pinned to (howto/sharded_training.md).
PARITY_RTOL, PARITY_ATOL = 2e-4, 1e-5


# ------------------------------------------------------------------- helpers
def say(line: str) -> None:
    print(line, flush=True)


def require_tpu(chips: int) -> Dict[str, Any]:
    """The script's first act: a TPU with exactly ``chips`` chips, or exit
    non-zero with the reason (and no result line). Returns the device as JAX
    reports it — the ``device`` of the result line."""
    import jax

    from sheeprl_tpu.telemetry.perf import peaks_for_device_kind

    devices = jax.devices()
    report = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if report["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found {report} (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
        )
    if report["count"] != chips:
        raise SystemExit(f"chip_smoke: this mode needs {chips} chip(s), JAX sees {report['count']}")
    # A kind the peak table does not hold is an error that names it.
    flops, bw = peaks_for_device_kind(report["kind"])
    say(f"device: {report} peaks: {flops / 1e12:.0f} TFLOP/s bf16, {bw / 1e9:.0f} GB/s (telemetry/perf.py PEAK_TABLE)")
    return report


@contextlib.contextmanager
def recording(owner: Any, name: str) -> Iterator[List[Any]]:
    """Record what every call of ``owner.name`` returns while the block runs.

    The mains build their replay ring, player placement and step timer and
    return none of them; this is how the smoke test reaches those objects
    without a hook in any main."""
    raw = vars(owner)[name]
    made: List[Any] = []
    if isinstance(raw, classmethod):
        func = raw.__func__

        def bound(cls, *args, **kwargs):
            out = func(cls, *args, **kwargs)
            made.append(out)
            return out

        patched: Any = classmethod(bound)
    else:

        def patched(*args, **kwargs):
            out = raw(*args, **kwargs)
            made.append(out)
            return out

    setattr(owner, name, patched)
    try:
        yield made
    finally:
        setattr(owner, name, raw)


def run_recipe(overrides: Sequence[str]):
    """compose + check_configs + run_algorithm: what ``python -m sheeprl_tpu``
    does with the same overrides, in this process."""
    import sheeprl_tpu
    from sheeprl_tpu.cli import check_configs, run_algorithm
    from sheeprl_tpu.config.loader import compose

    sheeprl_tpu.register_all()
    cfg = compose("config", list(overrides))
    check_configs(cfg)
    run_algorithm(cfg)
    return cfg


def newest_run_dir(root_dir: str, run_name: str) -> str:
    versions = sorted(glob.glob(os.path.join(root_dir, run_name, "version_*")), key=os.path.getmtime)
    if not versions:
        raise RuntimeError(f"no run directory under {os.path.join(root_dir, run_name)}")
    return versions[-1]


def newest_checkpoint(run_dir: str) -> str:
    ckpts = sorted(glob.glob(os.path.join(run_dir, "checkpoint", "ckpt_*.ckpt")), key=os.path.getmtime)
    if not ckpts:
        raise RuntimeError(f"the run wrote no checkpoint under {run_dir}")
    return ckpts[-1]


def assert_finite(tree: Any, what: str) -> None:
    import jax
    import numpy as np

    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        arr = np.asarray(leaf)
        if arr.dtype.kind in "fc" and not np.isfinite(arr).all():
            raise AssertionError(f"{what}: non-finite values at {jax.tree_util.keystr(path)}")


def assert_on_platform(tree: Any, platform: str, what: str) -> None:
    """Every array leaf of ``tree`` lives on ``platform`` devices only."""
    import jax

    leaves = [leaf for leaf in jax.tree_util.tree_leaves(tree) if isinstance(leaf, jax.Array)]
    if not leaves:
        raise AssertionError(f"{what}: no device arrays to check")
    platforms = {d.platform for leaf in leaves for d in leaf.devices()}
    if platforms != {platform}:
        raise AssertionError(f"{what}: expected every leaf on {platform!r}, found {sorted(platforms)}")


def assert_one_shard_per_device(array: Any, devices: Sequence[Any], what: str) -> None:
    """``array`` is split over ``devices``: one distinct shard on each, none
    of them the whole array. A ring that sits whole on the first device
    (``DeviceReplayRing(mesh=None)``, or eager work defaulted to device 0)
    trips this."""
    shards = array.addressable_shards
    held_by = [s.device for s in shards]
    if len(shards) != len(devices) or set(held_by) != set(devices):
        raise AssertionError(
            f"{what}: {len(shards)} shard(s) on {sorted(str(d) for d in set(held_by))}, "
            f"expected one on each of {len(devices)} devices"
        )
    if len(devices) > 1:
        if any(tuple(s.data.shape) == tuple(array.shape) for s in shards):
            raise AssertionError(f"{what}: replicated, every device holds the whole {tuple(array.shape)} array")
        if len({str(s.index) for s in shards}) != len(devices):
            raise AssertionError(f"{what}: shards overlap: {[str(s.index) for s in shards]}")


def read_telemetry(run_dir: str) -> List[Dict[str, Any]]:
    with open(os.path.join(run_dir, "telemetry.jsonl")) as fp:
        return [json.loads(line) for line in fp]


def span_list(records: List[Dict[str, Any]], name: str) -> List[Tuple[float, float]]:
    """(start, duration) in seconds of every span called ``name``, in time order."""
    return sorted((r["ts_us"] / 1e6, r["dur_us"] / 1e6) for r in records if r["type"] == "span" and r["name"] == name)


def ms(seconds: float) -> str:
    return f"{seconds * 1e3:.2f} ms"


# ------------------------------------------------------------ phase A: trainer
def phase_trainer(out_dir: str, accelerator: str, size: Dict[str, Any]) -> str:
    """DreamerV3 through the host lane; returns the checkpoint it saved."""
    import jax

    from sheeprl_tpu.core import player as player_mod
    from sheeprl_tpu.telemetry.step_timer import StepTimer

    platform = accelerator  # "tpu" on the chip, "cpu" in the rehearsal
    root_dir = os.path.join(out_dir, "runs")
    with recording(player_mod.PlayerPlacement, "resolve") as placements, recording(StepTimer, "flush") as flushes:
        started = time.perf_counter()
        cfg = run_recipe(dv3_overrides(root_dir, "trainer", accelerator, size))
        wall = time.perf_counter() - started
    run_dir = newest_run_dir(root_dir, "trainer")
    records = read_telemetry(run_dir)
    meta = records[0]
    say(
        f"phase A: {cfg.algo.name} ran {cfg.algo.total_steps} policy steps in {wall:.1f} s; meta line: backend={meta['backend']} "
        f"device={meta['device']!r} device_count={meta['device_count']} precision={cfg.fabric.precision} "
        f"batch={cfg.algo.per_rank_batch_size}x{cfg.algo.per_rank_sequence_length}"
    )

    # -- losses: every gradient step's metric tree, fetched by the StepTimer.
    losses = [metrics for batch in flushes for metrics in batch]
    if len(losses) < size["min_gradient_steps"]:
        raise AssertionError(f"phase A: {len(losses)} gradient step(s) ran, need >= {size['min_gradient_steps']}")
    assert_finite(losses, "phase A losses")
    wm_losses = [float(m["Loss/world_model_loss"]) for m in losses]
    say(f"phase A: {len(losses)} gradient steps, losses finite; world-model loss first={wm_losses[0]:.4f} last={wm_losses[-1]:.4f}")

    # -- where the state lives: the recorded mesh + parameter layouts, and the
    # player's own parameters (on the mesh they ARE the train state's arrays).
    mesh_devices = {d["id"]: d["kind"] for r in records if r["type"] == "mesh" for d in r["topology"]["devices"]}
    layouts = [entry for r in records if r["type"] == "param_layouts" for entry in r["layouts"]]
    if not layouts or not mesh_devices:
        raise AssertionError("phase A: the run recorded no mesh / param_layouts")
    strays = {dev for entry in layouts for dev in entry.get("devices", {}) if int(dev) not in mesh_devices}
    if strays or set(mesh_devices.values()) != {meta["device"]}:
        raise AssertionError(f"phase A: train state off the mesh: strays={strays} mesh={mesh_devices}")
    placement = placements[-1]
    player_params = placement.params()
    assert_on_platform(player_params, platform, "phase A player parameters")
    if placement.device.platform != platform:
        raise AssertionError(f"phase A: fabric.player_device=auto put the player on {placement.device}")
    say(f"phase A: train state on mesh devices {mesh_devices}; {len(layouts)} recorded layouts all on the mesh")

    # -- the player: what `auto` resolved to, and the dispatch latency. `auto`
    # leaves a player whose parameters exceed AUTO_MAX_PARAM_BYTES on the mesh
    # unprobed; the same probe then measures here.
    probed_by_run = placement.device in player_mod._latency_cache
    latency = player_mod.dispatch_latency(placement.device)
    say(
        f"phase A: PlayerPlacement(auto) -> {placement.device} on_mesh={placement.on_mesh}; player parameters "
        f"{player_mod.param_bytes(player_params) / 2**20:.0f} MiB (auto mirrors up to "
        f"{player_mod.AUTO_MAX_PARAM_BYTES / 2**20:.0f} MiB); dispatch latency {latency * 1e6:.0f} us, "
        f"{'measured by the run' if probed_by_run else 'measured after the run'} "
        f"(auto moves to the host above {player_mod.AUTO_LATENCY_THRESHOLD_S * 1e6:.0f} us)"
    )

    # -- timings, from the run's own spans.
    t_open = meta["time"]
    t_mesh = next(r["time"] for r in records if r["type"] == "mesh")
    say(f"phase A: env construction + agent init (host_init) + shard_params: {t_mesh - t_open:.1f} s (meta line -> mesh record)")
    train = span_list(records, "train/dispatch")
    bounds = span_list(records, "train/bound")
    player = span_list(records, "interaction/dispatch/slice0")
    fetch = span_list(records, "fetch/player_actions")
    compiles = span_list(records, "compile/backend")
    first_calls = f"phase A: train step first call {train[0][1]:.1f} s"
    if len(train) > 1:
        first_calls += f", second call {train[1][1]:.1f} s (the donated-layout recompile)"
    if player:
        first_calls += f"; player step first call {player[0][1]:.2f} s"
    say(first_calls)
    say(f"phase A: {len(compiles)} XLA compiles, {sum(d for _, d in compiles):.1f} s in the compiler")
    if len(bounds) >= 2 and len(train) >= 4:
        # The last log interval: from the previous interval's bounding
        # block_until_ready to this one's. Steady steps only.
        lo, hi = bounds[-2][0] + bounds[-2][1], bounds[-1][0] + bounds[-1][1]
        steady = [t for t in train if lo <= t[0] <= hi]
        in_window = lambda spans: [d for s, d in spans if lo <= s <= hi]  # noqa: E731
        say(
            f"phase A: steady window: {len(steady)} iterations (env step + player step + gradient step) in "
            f"{hi - lo:.3f} s ending in block_until_ready = {ms((hi - lo) / max(len(steady), 1))}/iteration; medians: "
            f"train enqueue {ms(statistics.median(in_window(train)))}, player dispatch "
            f"{ms(statistics.median(in_window(player)))}, action fetch {ms(statistics.median(in_window(fetch)))}, "
            f"final bound {ms(bounds[-1][1])}"
        )
    counters = [r["values"] for r in records if r["type"] == "counters"]
    gauges = {k: v for k, v in counters[-1].items() if k in ("perf/mfu", "perf/hbm_bw_util", "perf/train_steps_per_s")}
    if platform != "cpu":
        # On the CPU the accountant's ceiling is an sgemm probe: not a device metric.
        say(f"phase A: perf gauges of the last interval (smoke run): {gauges}")
    stats = jax.devices()[0].memory_stats() or {}
    say(f"phase A: device.memory_stats()['peak_bytes_in_use'] = {stats.get('peak_bytes_in_use', 'not reported')}")
    return newest_checkpoint(run_dir)


def time_agent_init_on_device(accelerator: str, size: Dict[str, Any]) -> None:
    """D4's number: the same agent + optimizer init as the main's, run on the
    mesh device instead of under ``Runtime.host_init``."""
    import jax

    import sheeprl_tpu
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import _make_optimizer
    from sheeprl_tpu.algos.ppo.agent import actions_metadata
    from sheeprl_tpu.config.instantiate import instantiate
    from sheeprl_tpu.config.loader import compose
    from sheeprl_tpu.utils.env import make_env

    sheeprl_tpu.register_all()
    cfg = compose("config", dv3_overrides(OUT_DIR, "unused", accelerator, size))
    runtime = instantiate(cfg.fabric).launch()
    runtime.seed_everything(cfg.seed)
    env = make_env(cfg, cfg.seed, 0)()
    obs_space, (actions_dim, is_continuous) = env.observation_space, actions_metadata(env.action_space)
    env.close()
    started = time.perf_counter()
    with jax.default_device(runtime.device):
        _, state = build_agent(runtime, actions_dim, is_continuous, cfg, obs_space)
        for name in ("world_model", "actor", "critic"):
            tx = _make_optimizer(cfg.algo[name].optimizer, cfg.algo[name].clip_gradients)
            state[f"{name}_opt"] = tx.init(state[name])
    state = runtime.shard_params(state)
    jax.block_until_ready(state)
    # On a TPU none of these eager programs has run on that backend before
    # (the main initialised under host_init, on the CPU backend).
    say(
        f"phase A: agent + optimizer init ON THE DEVICE (no host_init) + shard_params: "
        f"{time.perf_counter() - started:.1f} s"
    )


# --------------------------------------------------------- phase B: fused lane
def phase_fused_lane(out_dir: str, accelerator: str, size: Dict[str, Any]) -> None:
    from sheeprl_tpu.core import fused_loop
    from sheeprl_tpu.utils.checkpoint import load_checkpoint

    root_dir = os.path.join(out_dir, "runs")
    started = time.perf_counter()
    cfg = run_recipe(ppo_anakin_overrides(root_dir, "fused", accelerator, size))
    wall = time.perf_counter() - started
    stats = fused_loop.last_run_stats()
    if stats["env_steps"] != int(cfg.algo.total_steps) or stats["supersteps"] < 2:
        raise AssertionError(f"phase B: ran {stats}, wanted {cfg.algo.total_steps} env steps over several supersteps")
    per_superstep = stats["jit_dispatches"] / stats["supersteps"]
    if per_superstep > 2:
        raise AssertionError(f"phase B: {per_superstep:.2f} jit dispatches per superstep, the lane's contract is <= 2")
    state = load_checkpoint(newest_checkpoint(newest_run_dir(root_dir, "fused")))
    assert_finite(state["agent"], "phase B trained parameters")
    say(
        f"phase B: ppo_anakin shard_superstep={cfg.fabric.shard_superstep}: {stats['supersteps']} supersteps, "
        f"{stats['env_steps']} env steps, {per_superstep:.2f} dispatches/superstep, parameters finite; {wall:.1f} s with compile"
    )


# ------------------------------------------------------------- phase C: server
def phase_server(checkpoint_path: str, out_dir: str, platform: str, size: Dict[str, Any]) -> None:
    import numpy as np

    from sheeprl_tpu.serve.artifact import export_artifact
    from sheeprl_tpu.serve.engine import InferenceEngine

    artifact = export_artifact(checkpoint_path, os.path.join(out_dir, "dv3.policy"))
    # max_batch=2: the warm-up compiles the single-session graph and the
    # vmapped two-session graph, in both modes.
    engine = InferenceEngine(max_batch=2, batch_window_s=0.0)
    try:
        started = time.perf_counter()
        card = engine.load("dv3", artifact)
        load_s = time.perf_counter() - started
        rng = np.random.default_rng(SEED)
        episode = [
            {k: rng.integers(0, 256, shape, dtype=np.uint8) for k, shape in card["obs_keys"].items()}
            for _ in range(size["requests_per_session"])
        ]
        # Two fresh sessions fed the same observation sequence: DreamerV3
        # advances a per-session latent with every request, so a repeat
        # WITHIN a session is not the check — two sessions side by side are.
        sessions = [engine.new_session_id(), engine.new_session_id()]
        answers: List[List[Any]] = []
        latencies: List[float] = []
        for session in sessions:
            row = []
            for obs in episode:
                t0 = time.perf_counter()
                row.append(np.asarray(engine.act("dv3", obs, session=session, seed=SEED, timeout=120)))
                latencies.append(time.perf_counter() - t0)
            answers.append(row)
        assert_finite(answers, "phase C answers")
        for step, (a, b) in enumerate(zip(*answers)):
            if a.tobytes() != b.tobytes():
                raise AssertionError(f"phase C: sessions disagree at request {step}: {a} vs {b}")
        hosted = engine._models["dv3"]
        assert_on_platform(hosted.adapter.params, platform, "phase C served parameters")
        assert_on_platform([hosted.sessions[s]["player"] for s in sessions], platform, "phase C session latents")
        say(
            f"phase C: artifact loaded + warmed in {load_s:.1f} s; {len(latencies)} act requests over {len(sessions)} sessions, "
            f"answers finite and byte-identical across sessions; request wall median {ms(statistics.median(latencies))} "
            f"(max {ms(max(latencies))}); served parameters and session latents on {platform}"
        )
    finally:
        engine.close()


# ------------------------------------------------- the DreamerV3 step, ahead of time
def dv3_train_step(cfg: Any, devices: Sequence[Any]):
    """``(train_fn, argument specs)`` of the DreamerV3 gradient step that the
    main builds for ``cfg`` on a mesh of ``devices`` — attached chips or the
    devices of a described topology. State comes from ``jax.eval_shape``, laid
    out as ``partition_specs()`` says, so nothing is allocated."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.ppo.agent import actions_metadata
    from sheeprl_tpu.core import mesh as mesh_lib
    from sheeprl_tpu.core.precision import resolve_precision
    from sheeprl_tpu.serve.adapter import inference_runtime
    from sheeprl_tpu.utils.env import make_env
    from sheeprl_tpu.utils.ops import init_moments

    env = make_env(cfg, cfg.seed, 0)()
    obs_space, (actions_dim, is_continuous) = env.observation_space, actions_metadata(env.action_space)
    env.close()
    mesh = mesh_lib.build_mesh(devices=list(devices))
    plan = dreamer_v3.partition_specs(mesh)
    built = {}

    def init():
        agent, state = build_agent(
            inference_runtime(resolve_precision(str(cfg.fabric.precision))), actions_dim, is_continuous, cfg, obs_space
        )
        built["agent"] = agent
        return state

    def placed(tree, shardings):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree, shardings
        )

    state = jax.eval_shape(init)
    txs = {
        name: dreamer_v3._make_optimizer(cfg.algo[name].optimizer, cfg.algo[name].clip_gradients)
        for name in ("world_model", "actor", "critic")
    }
    opt_states = {name: jax.eval_shape(tx.init, state[name]) for name, tx in txs.items()}
    state = placed(state, plan.param_shardings(state))
    opt_states = placed(opt_states, plan.param_shardings(opt_states))
    replicated = lambda tree: placed(tree, jax.tree_util.tree_map(lambda _: plan.replicated(), tree))  # noqa: E731
    seq, batch = int(cfg.algo.per_rank_sequence_length), int(cfg.algo.per_rank_batch_size)
    data = {key: ((seq, batch, *obs_space[key].shape), obs_space[key].dtype) for key in cfg.algo.cnn_keys.encoder}
    data.update({key: ((seq, batch, *obs_space[key].shape), jnp.float32) for key in cfg.algo.mlp_keys.encoder})
    data["actions"] = ((seq, batch, int(sum(actions_dim))), jnp.float32)
    for key in ("rewards", "terminated", "truncated", "is_first"):
        data[key] = ((seq, batch, 1), jnp.float32)
    data = {k: jax.ShapeDtypeStruct(shape, dtype, sharding=plan.sharding("batch")) for k, (shape, dtype) in data.items()}
    train_fn = dreamer_v3.make_train_step(built["agent"], txs, cfg, mesh, state=state, opt_states=opt_states)
    args = (
        state,
        opt_states,
        replicated(jax.eval_shape(init_moments)),
        data,
        replicated(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
        replicated(jax.ShapeDtypeStruct((), jnp.float32)),
    )
    return train_fn, args


def compile_dv3_train_step(cfg: Any, devices: Sequence[Any], what: str) -> None:
    """Compile that step for ``devices``; print what it needs of each device's
    memory, and require the gradient all-reduce when there are several."""
    train_fn, args = dv3_train_step(cfg, devices)
    started = time.perf_counter()
    compiled = train_fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    per_device = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    say(
        f"{what}: DreamerV3 train step compiled for {len(devices)} device(s) in {time.perf_counter() - started:.1f} s; "
        f"per device: arguments {mem.argument_size_in_bytes / 2**20:.0f} MiB, outputs {mem.output_size_in_bytes / 2**20:.0f} MiB "
        f"(aliased {mem.alias_size_in_bytes / 2**20:.0f}), temporaries {mem.temp_size_in_bytes / 2**20:.0f} MiB "
        f"= {per_device / 2**30:.2f} GiB of 16 GiB"
    )
    if len(devices) > 1:
        text = compiled.as_text()
        if "all-reduce" not in text:
            raise AssertionError(f"{what}: no all-reduce in the compiled {len(devices)}-device train step")
        say(f"{what}: the compiled step holds {text.count('all-reduce(') + text.count('all-reduce-start(')} all-reduce op(s)")


# ----------------------------------------------------------- --chips 4: the mesh
def ring_fields(ring: Any) -> Dict[str, Any]:
    """The arrays of a DeviceReplayRing: pos, added and every data key."""
    state = ring.state
    return {"pos": state["pos"], "added": state["added"], **state["data"]}


def mesh_fused_parity(root_dir: str, accelerator: str, devices: Sequence[Any]) -> None:
    """Fused-lane parity, 1 device <-> n: counters equal, parameters inside
    the tolerance tests/test_algos/test_sharded_learner.py pins."""
    import jax
    import numpy as np

    from sheeprl_tpu.core import fused_loop
    from sheeprl_tpu.utils.checkpoint import load_checkpoint

    n = len(devices)
    for algo, recipe in (("sac", sac_shard_overrides), ("ppo", ppo_shard_overrides)):
        outcome = {}
        for count in (1, n):
            name = f"{algo}_anakin_{count}"
            with recording(fused_loop, "DeviceReplayRing") as rings:
                run_recipe(recipe(count, accelerator, root_dir=root_dir, run_name=name, seed=SEED))
            state = load_checkpoint(newest_checkpoint(newest_run_dir(root_dir, name)))
            outcome[count] = (fused_loop.last_run_stats(), state)
            if count == n and algo == "sac":
                for field, array in ring_fields(rings[-1]).items():
                    assert_one_shard_per_device(array, devices, f"sac_anakin ring {field}")
                say(f"--chips {n}: sac_anakin ring data/pos/added hold one shard on each of {n} devices")
        (stats_1, state_1), (stats_n, state_n) = outcome[1], outcome[n]
        if stats_1 != stats_n or state_1["iter_num"] != state_n["iter_num"]:
            raise AssertionError(f"{algo}_anakin counters differ: 1 device {stats_1}, {n} devices {stats_n}")
        leaves_1, leaves_n = jax.tree_util.tree_leaves(state_1["agent"]), jax.tree_util.tree_leaves(state_n["agent"])
        worst = 0.0
        for a, b in zip(leaves_1, leaves_n):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=PARITY_RTOL, atol=PARITY_ATOL)
            worst = max(worst, float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64)), initial=0.0)))
        say(
            f"--chips {n}: {algo}_anakin 1 <-> {n} devices: counters equal {stats_n}, {len(leaves_1)} parameter leaves "
            f"within rtol {PARITY_RTOL} / atol {PARITY_ATOL} (largest absolute difference {worst:.3g})"
        )


def mesh_dreamer(root_dir: str, accelerator: str, size: Dict[str, Any], devices: Sequence[Any]) -> None:
    """DreamerV3 (phase A's recipe, a few gradient steps) on the mesh: host
    buffer and device ring, beside one device on the host buffer. In this
    framework ``per_rank`` is per PROCESS: one process drives all n chips, so
    the batch of 16 stays ``algo.per_rank_batch_size=16`` and is split n ways."""
    import numpy as np

    import sheeprl_tpu
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3
    from sheeprl_tpu.config.loader import compose
    from sheeprl_tpu.telemetry.step_timer import StepTimer

    n = len(devices)
    sheeprl_tpu.register_all()
    first_loss = {}
    for name, extra in (
        ("dv3_1chip_host", ("fabric.devices=1",)),
        (f"dv3_{n}chip_host", (f"fabric.devices={n}",)),
        # The ring shards over envs, so it needs num_envs divisible by n.
        (f"dv3_{n}chip_ring", (f"fabric.devices={n}", "buffer.device=true", f"env.num_envs={n}")),
    ):
        overrides = dv3_overrides(root_dir, name, accelerator, size, extra)
        cfg = compose("config", overrides)
        # The prefill is counted in policy steps over all envs, a sequence in
        # rows of ONE env: scale it with the envs, then stop 4 iterations into
        # training.
        envs = int(cfg.env.num_envs)
        prefill = int(cfg.algo.learning_starts) * envs
        overrides += [f"algo.learning_starts={prefill}", f"algo.total_steps={prefill + 3 * envs}"]
        with recording(dreamer_v3, "DeviceReplayRing") as rings, recording(StepTimer, "flush") as flushes:
            run_recipe(overrides)
        losses = [metrics for batch in flushes for metrics in batch]
        if not losses:
            raise AssertionError(f"{name}: no gradient step ran")
        assert_finite(losses, f"{name} losses")
        first_loss[name] = float(np.asarray(losses[0]["Loss/world_model_loss"]).reshape(-1)[0])
        say(f"--chips {n}: {name}: {len(losses)} loss record(s) finite, first world-model loss {first_loss[name]:.6f}")
        if rings:
            # The main falls back to the host buffer, silently, while the ring
            # is not ready: a synchronous host batch in the trace means it did.
            host_batches = span_list(read_telemetry(newest_run_dir(root_dir, name)), "transfer/h2d_sync")
            if host_batches:
                raise AssertionError(f"{name}: {len(host_batches)} train call(s) sampled the host buffer, not the ring")
            for field, array in ring_fields(rings[-1]).items():
                assert_one_shard_per_device(array, devices, f"{name} ring {field}")
            say(f"--chips {n}: {name}: trained from the ring; its data/pos/added hold one shard on each of {n} devices")
    base, sharded = first_loss["dv3_1chip_host"], first_loss[f"dv3_{n}chip_host"]
    say(
        f"--chips {n}: first-step world-model loss, same seed and host buffer: 1 device {base:.6f}, {n} devices {sharded:.6f}, "
        f"difference {sharded - base:+.3g} ({abs(sharded - base) / max(abs(base), 1e-12):.2e} relative); no 1 <-> N tolerance "
        "is pinned for DreamerV3, so this is reported, not asserted"
    )

    # The step the mesh run compiled: batch split over `data` with one shard
    # on each device, gradients all-reduced.
    cfg = compose("config", dv3_overrides(root_dir, "unused", accelerator, size, (f"fabric.devices={n}",)))
    _, args = dv3_train_step(cfg, devices)
    batch = args[3][next(iter(cfg.algo.cnn_keys.encoder))]
    if len({str(idx) for idx in batch.sharding.devices_indices_map(batch.shape).values()}) != n:
        raise AssertionError(f"the [T, B] batch is not split {n} ways: {batch.sharding}")
    compile_dv3_train_step(cfg, devices, f"--chips {n}")


def phase_four_chips(out_dir: str, accelerator: str, size: Dict[str, Any], n: int = 4) -> None:
    """What exists only across chips, each beside what it is compared with."""
    import jax

    devices = jax.devices()[:n]
    if len(set(devices)) != n or len({d.platform for d in devices}) != 1:
        raise AssertionError(f"the mesh needs {n} distinct devices of one platform, found {devices}")
    root_dir = os.path.join(out_dir, "runs")
    mesh_fused_parity(root_dir, accelerator, devices)
    mesh_dreamer(root_dir, accelerator, size, devices)
    for device in devices:
        stats = device.memory_stats() or {}
        say(f"--chips {n}: {device} peak_bytes_in_use = {stats.get('peak_bytes_in_use', 'not reported')}")


# --------------------------------------------------------- --aot: ask the compiler
#: (batch, hidden, D) of the LN-GRU cell at the Dreamer sizes: D = hidden +
#: dense units. Train batch 16, imagination batch 16 x 64.
GRU_SHAPES = {
    "S_train": (16, 512, 1024),
    "S_imagination": (1024, 512, 1024),
    "M_train": (16, 1024, 1664),
    "XL_train": (16, 4096, 5120),
    "XL_imagination": (1024, 4096, 5120),
}


#: (sequences, positions, with its backward) of the whole-sequence latent
#: attention at the token policy's benchmark cell (32 heads of 128 + 64 / 128, latent 512):
#: the gradient step's minibatch, and the player's prefill of all 16 prompts.
MLA_SHAPES = {
    "update": (4, 2080, True),
    "prefill_16_prompts": (16, 2048, False),
}
MLA_HEADS, MLA_ROPE, MLA_LATENT = 32, 64, 512

#: (sequences, positions, window, with its backward) of the whole-sequence
#: differential attention at the hybrid token policy's benchmark cell (40 query
#: / 20 key-value heads of 64): the gradient step's minibatch and the player's
#: prefill of all 8 prompts, a whole-context layer and the window layer.
DIFF_SHAPES = {
    "update_full": (2, 4128, None, True),
    "update_window": (2, 4128, 512, True),
    "prefill_8_prompts_full": (8, 4096, None, False),
    "prefill_8_prompts_window": (8, 4096, 512, False),
}
DIFF_HEADS, DIFF_KV_HEADS, DIFF_HEAD_DIM = 40, 20, 64

#: (sequences, positions, with its backward) of the selective scan at the
#: hybrid token policy's benchmark cell (inner width 5120, 16 states): the
#: gradient step's minibatch and the player's prefill of all 8 prompts.
SCAN_SHAPES = {
    "update": (2, 4128, True),
    "prefill_8_prompts": (8, 4096, False),
}
SCAN_WIDTH, SCAN_STATE = 5120, 16


def described_v5e():
    """A 2x2 TPU v5e that is described, not attached: the compiler's target."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


def compile_ln_gru(batch: int, hidden: int, d: int, dtype: Any, sharding: Any):
    """Compile the fused Pallas LN-GRU cell for the device behind ``sharding``
    (a described one will do): raises what the chip's compiler would raise."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.models import pallas_gru

    def spec(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    ln = spec(3 * hidden, dt=jnp.float32)
    return (
        jax.jit(pallas_gru._pallas_ln_gru)
        .lower(spec(batch, d), spec(d, 3 * hidden), spec(3 * hidden), ln, ln, spec(batch, hidden))
        .compile()
    )


def compile_mla_attention(batch: int, seq: int, grad: bool, dtype: Any, sharding: Any):
    """Compile the fused latent-attention kernels (forward, or forward and
    backward under `jax.grad`) for the device behind ``sharding``."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.models import pallas_mla_attention as kernel

    def spec(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    def attend(qn, qr, latent, w_kv, kr, start):
        return kernel.mla_attention(qn, qr, latent, w_kv, kr, start, (kernel.LANES + MLA_ROPE) ** -0.5)

    def grads(*args):
        return jax.grad(lambda *a: attend(*a, args[5]).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3, 4))(*args[:5])

    return (
        jax.jit(grads if grad else attend)
        .lower(spec(batch, seq, MLA_HEADS, kernel.LANES), spec(batch, seq, MLA_HEADS, MLA_ROPE), spec(batch, seq, MLA_LATENT),
               spec(MLA_LATENT, MLA_HEADS * 2 * kernel.LANES), spec(batch, seq, MLA_ROPE), spec(batch, dt=jnp.int32))
        .compile()
    )


def compile_diff_attention(batch: int, seq: int, window: Optional[int], grad: bool, dtype: Any, sharding: Any):
    """Compile the fused differential-attention kernels (forward, or forward
    and backward under `jax.grad`) for the device behind ``sharding``."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.models import pallas_diff_attention as kernel

    def spec(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    def attend(q, k, v, lam, start):
        return kernel.diff_attention(q, k, v, start, lam, window)

    def grads(*args):
        return jax.grad(lambda *a: attend(*a, args[4]).sum(), argnums=(0, 1, 2, 3))(*args[:4])

    keys = spec(batch, seq, DIFF_KV_HEADS, DIFF_HEAD_DIM)
    return (
        jax.jit(grads if grad else attend)
        .lower(spec(batch, seq, DIFF_HEADS, DIFF_HEAD_DIM), keys, keys, spec(dt=jnp.float32), spec(batch, dt=jnp.int32))
        .compile()
    )


def compile_selective_scan(batch: int, seq: int, grad: bool, dtype: Any, sharding: Any, state: int = SCAN_STATE):
    """Compile the selective-scan kernels (forward, or forward and backward
    under `jax.grad`) for the device behind ``sharding``."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.models import pallas_selective_scan as kernel

    def spec(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    def grads(*args):
        return jax.grad(lambda *a: kernel.selective_scan(*a)[0].sum(), argnums=(0, 1, 2, 3, 4))(*args)

    rows, narrow = spec(batch, seq, SCAN_WIDTH), spec(batch, seq, state)
    return (
        jax.jit(grads if grad else kernel.selective_scan)
        .lower(rows, spec(batch, seq, SCAN_WIDTH, dt=jnp.float32), spec(state, SCAN_WIDTH, dt=jnp.float32), narrow, narrow)
        .compile()
    )


def aot_rehearsal() -> int:
    """From a sandbox with no chip: do the kernels and the real train step
    compile for the chip? Nothing runs; this is not a chip run."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    import sheeprl_tpu
    from sheeprl_tpu.config.loader import compose
    from sheeprl_tpu.models import pallas_diff_attention, pallas_gru, pallas_mla_attention, pallas_selective_scan

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = described_v5e()
    one_chip = SingleDeviceSharding(topo.devices[0])
    say(f"aot: compiling for a described {topo.devices[0].device_kind} ({len(topo.devices)} devices); nothing runs")
    for name, (batch, hidden, d) in GRU_SHAPES.items():
        reason = pallas_gru.ineligible_reason(batch, d, hidden, 4)
        if reason is not None:
            say(f"aot: LN-GRU {name} B={batch} H={hidden} D={d}: declared ineligible ({reason})")
            continue
        started = time.perf_counter()
        compile_ln_gru(batch, hidden, d, jnp.float32, one_chip)
        say(f"aot: LN-GRU {name} B={batch} H={hidden} D={d}: compiles ({time.perf_counter() - started:.1f} s)")
    for name, (batch, seq, grad) in MLA_SHAPES.items():
        reason = pallas_mla_attention.shape_ineligible_reason(seq, pallas_mla_attention.LANES, MLA_ROPE, pallas_mla_attention.LANES, jnp.bfloat16)
        if reason is not None:
            say(f"aot: latent attention {name} [{batch}, {seq}]: declared ineligible ({reason})")
            continue
        started = time.perf_counter()
        compile_mla_attention(batch, seq, grad, jnp.bfloat16, one_chip)
        say(f"aot: latent attention {name} [{batch}, {seq}]{' with its backward' if grad else ''}: compiles ({time.perf_counter() - started:.1f} s)")
    for name, (batch, seq, window, grad) in DIFF_SHAPES.items():
        reason = pallas_diff_attention.shape_ineligible_reason(seq, DIFF_HEAD_DIM, window, jnp.bfloat16, DIFF_HEADS // DIFF_KV_HEADS)
        if reason is not None:
            say(f"aot: differential attention {name} [{batch}, {seq}]: declared ineligible ({reason})")
            continue
        started = time.perf_counter()
        compile_diff_attention(batch, seq, window, grad, jnp.bfloat16, one_chip)
        say(f"aot: differential attention {name} [{batch}, {seq}]{' with its backward' if grad else ''}: compiles ({time.perf_counter() - started:.1f} s)")
    for name, (batch, seq, grad) in SCAN_SHAPES.items():
        reason = pallas_selective_scan.shape_ineligible_reason(batch, seq, SCAN_WIDTH, SCAN_STATE, jnp.bfloat16)
        if reason is not None:
            say(f"aot: selective scan {name} [{batch}, {seq}]: declared ineligible ({reason})")
            continue
        started = time.perf_counter()
        compile_selective_scan(batch, seq, grad, jnp.bfloat16, one_chip)
        say(f"aot: selective scan {name} [{batch}, {seq}]{' with its backward' if grad else ''}: compiles ({time.perf_counter() - started:.1f} s)")
    sheeprl_tpu.register_all()
    for count in (1, 4):
        cfg = compose("config", dv3_overrides(OUT_DIR, "unused", "tpu", FULL, (f"fabric.devices={count}",)))
        compile_dv3_train_step(cfg, topo.devices[:count], "aot")
    say('{"aot_ok": true}')
    return 0


# ----------------------------------------------------------------------- main
def print_cache_counters(cache_dir: Optional[str]) -> None:
    from sheeprl_tpu.telemetry.registry import default_registry

    registry = default_registry()
    read = lambda name: registry.counter(name).value  # noqa: E731
    say(
        f"compile cache: dir={cache_dir} "
        f"hits={read('jax/compile_cache_hits'):.0f} misses={read('jax/compile_cache_misses'):.0f} "
        f"backend compiles={read('jax/compiles'):.0f} ({read('jax/compile_secs'):.1f} s)"
    )


def prune_heavy_files(out_dir: str) -> None:
    """Checkpoints and the artifact are hundreds of MiB at full width; what
    the chip tool copies back is capped. Keep the logs, drop the weights."""
    for pattern in ("runs/*/version_*/checkpoint", "runs/*/version_*/artifacts", "*.policy"):
        for path in glob.glob(os.path.join(out_dir, pattern)):
            shutil.rmtree(path, ignore_errors=True)


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1, help="chips the run needs (default 1)")
    parser.add_argument("--aot", action="store_true", help="no chip: ask the TPU compiler from a CPU-only sandbox")
    args = parser.parse_args(argv)
    if args.aot:
        return aot_rehearsal()

    from sheeprl_tpu.core.runtime import configure_compilation_cache
    from sheeprl_tpu.telemetry import jax_events

    cache_dir = configure_compilation_cache()
    jax_events.install_listeners()
    device = require_tpu(args.chips)
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    started = time.perf_counter()
    try:
        if args.chips == 4:
            phase_four_chips(OUT_DIR, "tpu", FULL)
        else:
            checkpoint = phase_trainer(OUT_DIR, "tpu", FULL)
            time_agent_init_on_device("tpu", FULL)
            phase_fused_lane(OUT_DIR, "tpu", FULL)
            phase_server(checkpoint, OUT_DIR, "tpu", FULL)
        print_cache_counters(cache_dir)
    finally:
        prune_heavy_files(OUT_DIR)
    say(f"all phases passed in {time.perf_counter() - started:.0f} s")
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
