"""Driver benchmark. Prints exactly ONE JSON line:
{"metric", "value", "unit", "vs_baseline"} — guaranteed to be the LAST line
on stdout with both streams flushed first (XLA's absl warnings are silenced
via TF_CPP_MIN_LOG_LEVEL; harvest the final line starting with '{'). Every
finished leg also appends a schema-versioned record (git sha, hardware
fingerprint, goodput snapshot) to BENCH_HISTORY.jsonl — the durable bench
trajectory behind `python -m sheeprl_tpu.telemetry perf` (see
telemetry/bench_db.py; SHEEPRL_BENCH_NO_HISTORY=1 skips the append for
smoke runs).

Default workload: **DreamerV3** — the north-star metric (BASELINE.json) — on
the reference benchmark recipe (configs/exp/dreamer_v3_benchmarks.yaml):
16,384 policy steps, 1 env, micro world model, learning_starts=1024,
replay_ratio=0.0625, batch 16 x sequence 64. Reference wall-clock: 1589.30 s
on 4 CPUs (README.md:168-176) -> ~10.31 env-steps/sec.

Every workload is measured by DIFFERENCING two runs of the reference recipe
at different step counts: sps = (steps_long - steps_short) / (t_long -
t_short). Both runs pay the same fixed startup (process-cache executable
loads, agent init, env construction), so the difference isolates the
steady-state training throughput — the quantity the reference's wall-clock
is dominated by (its torch-eager startup is seconds; ours includes XLA
compiles). learning_starts is held at the reference value in BOTH runs, so
the prefill phase cancels too. The long run escalates until the differenced
window is >=120 s (or the full reference workload completes).

Twelve legs pin the CPU platform (the reference's CPU workloads and the
virtual-mesh legs); every other leg wants the accelerator and FAILS without
one — there is no CPU fallback, and a leg's precision is its recipe's.

Divergence (documented): the reference Dreamer benchmarks step MsPacman
through ALE; ALE is not installed in this image, so the env is the
deterministic dummy pixel env (64x64x3 uint8 — one channel MORE than the
reference's grayscale Atari frames). The ALE emulator contributes only a few
seconds of the reference's wall-clock (it runs at ~10k fps), so the
comparison stays dominated by what the benchmark measures: the
world-model/actor/critic training step and the per-step policy latency.

Workloads:
`python bench.py [dreamer_v3|dreamer_v3_devbuf|dreamer_v3_pipe|dreamer_v3_S|
dreamer_v3_S_b32|dreamer_v3_S_b64|dreamer_v3_health|dreamer_v2|dreamer_v1|
dreamer_v3_goodput|ppo|a2c|sac|sac_devbuf|sac_pipe|sac_resilience|sac_fleet|
sac_health|sac_flight|sac_goodput|sac_mesh8|serve_sac|serve_sac_traced|
ppo_anakin|sac_anakin|dreamer_v3_anakin|graftlint_repo]`. `sac_mesh8` is the
per-shard goodput leg: SAC on a virtual 8-device CPU mesh, headline value =
perf/shard_imbalance (max/mean per-shard flops, lower-better) with the full
per-shard MFU map in the history record's `shards` field. The `*_goodput` legs are the
roofline-accounting A/B (telemetry/perf.py armed vs the plain row, <2%
target) and embed the run's mfu / bandwidth-utilization /
compute-infeed-host breakdown snapshot. `graftlint_repo` is the static-analysis leg: whole-package
graftlint wall time vs the 10 s CI-gate budget (no jax import on that path). The `*_pipe` legs are the
pipelined-interaction A/B (fabric.async_fetch, env.pipeline_slices —
core/interact.py); every result embeds the interaction time split and
overlap fraction from the long run. `sac_resilience` is the fault-tolerance
A/B (resilience=on vs the plain `sac` row, <2% target) and also reports the
atomic checkpoint save cost directly. `sac_fleet` is the actor-fleet A/B
(howto/fault_tolerance.md#scale-out-resilience-the-actor-fleet): the same
decoupled SAC recipe with two supervised actor-replica processes feeding
the learner over pipes vs in-process (`fleet.replicas=1`), <2% target,
measured self-relative on the virtual 8-device mesh. `sac_health` and `dreamer_v3_health`
are the training-health A/B legs (health=on vs the plain `sac` /
`dreamer_v3` rows, <2% target): in-jit probes fused into the train step +
host-side sentinels reading the already-coalesced per-interval metric
fetch. `sac_flight` is the distributed-tracing A/B leg (telemetry.enabled=True:
live span ring + per-iteration trace contexts + env-carrier propagation on
top of the always-on flight recorder, vs the plain `sac` row, <2% target).
`serve_sac` is the serving stack's
closed-loop load test (sheeprl_tpu/serve): concurrent clients against the
dynamic micro-batching engine, vs_baseline = batching speedup over one
client. `serve_sac_traced` repeats it with a per-request trace context and
a live tracer installed so request/batch span emission and linking is on
the measured path (<2% of the `serve_sac` peak). The `*_anakin` legs
(`ppo_anakin|sac_anakin|dreamer_v3_anakin`) are the Anakin-lane
head-to-head (howto/anakin_lane.md): the SAME pure-JAX env and recipe
through the fused rollout+train lane (core/fused_loop.py) and through the
JaxToGymnasium host lane, one JSON row with the fused rate as headline,
the host-lane rate embedded (`host_lane`, plus `fused_vs_host` — the fused
lane must be strictly faster), and the fused dispatch accounting from
core/fused_loop.last_run_stats() (`fused.dispatches_per_superstep` <= 2 is
the lane's contract).
Reference baselines from BASELINE.md (README.md:83-180); `dreamer_v3_S` is
the north-star-scale workload (S model at the Atari-100K recipe shape) vs
the RTX 3080's ~1.98 env-steps/s.
"""

import json
import os
import sys
import time

# XLA's C++ logging (absl) writes warnings to stderr — e.g. the CPU AOT
# loader's SIGILL feature-mismatch notes visible in BENCH_r05.json's tail —
# and a `2>&1` harvest then interleaves them with the result line. Level 3
# silences everything below FATAL; it must be in the environment before the
# first jax import (here AND in the subprocess probes, which inherit it).
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

def _setup_jax(platform=None):
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from sheeprl_tpu.core.runtime import configure_compilation_cache, force_cpu_platform

    if platform is not None:
        assert platform == "cpu", platform
        force_cpu_platform()
    # Persistent compile cache: the warmup run's XLA executables are disk-cache
    # hits in the measured run, so timing excludes compilation. Placed by the
    # one function that owns it (JAX_COMPILATION_CACHE_DIR wins when set).
    configure_compilation_cache()


def _run_silent(cfg):
    import io
    import contextlib

    from sheeprl_tpu.cli import run_algorithm

    with contextlib.redirect_stdout(io.StringIO()):
        run_algorithm(cfg)


# Differencing window. SHEEPRL_BENCH_MIN_WINDOW_S shrinks it for smoke
# tests of the plumbing — a shrunk window is NOT a publishable number.
MIN_MEASURE_S = float(os.environ.get("SHEEPRL_BENCH_MIN_WINDOW_S", "120"))


def _timeboxed(
    metric: str,
    exp: str,
    total_steps: int,
    baseline_sps: float,
    *,
    learning_starts: int = 0,
    extra=(),
    warmup_steps: int = 1536,
    start_steps: int = 2048,
):
    from sheeprl_tpu.cli import check_configs
    from sheeprl_tpu.config.loader import compose

    common = [f"exp={exp}", "checkpoint.every=0", "checkpoint.save_last=False", *extra]
    if learning_starts > 0:
        common.append(f"algo.learning_starts={learning_starts}")

    precision = None

    def timed(steps):
        nonlocal precision
        cfg = compose("config", common + [f"algo.total_steps={steps}"])
        check_configs(cfg)
        precision = str(cfg.fabric.precision)
        start = time.perf_counter()
        _run_silent(cfg)
        return time.perf_counter() - start

    # Warm the jit/persistent-compile caches: after this every run only
    # reloads its executables.
    timed(warmup_steps)

    # Short anchor run: captures the fixed per-run overhead.
    s1 = max(start_steps, learning_starts + 512)
    t1 = timed(s1)

    # Long run, escalated until the differenced window is wide enough.
    s2, t2 = s1, t1
    while True:
        rate = max((s2 - s1) / max(t2 - t1, 1e-9), s1 / t1)
        s2 = min(total_steps, max(s2 * 2, s1 + int(rate * MIN_MEASURE_S * 1.5)))
        t2 = timed(s2)
        if t2 - t1 >= MIN_MEASURE_S or s2 >= total_steps:
            break
    sps = (s2 - s1) / max(t2 - t1, 1e-9)
    result = {
        "metric": metric,
        "value": round(sps, 2),
        "unit": "env-steps/sec",
        "vs_baseline": round(sps / baseline_sps, 3),
    }
    # Interaction time split from the long run (core/interact.py): where the
    # env-facing half of each step went — env stepping vs policy dispatch vs
    # action fetch (blocked on host vs ridden under other work). The overlap
    # fraction is the direct readout of the async-fetch win.
    from sheeprl_tpu.core import interact

    stats = interact.last_run_stats()
    if stats is not None:
        result["interaction"] = {
            "env_step_s": round(stats["env_step_s"], 3),
            "policy_dispatch_s": round(stats["policy_dispatch_s"], 3),
            "fetch_blocked_s": round(stats["fetch_blocked_s"], 3),
            "fetch_ride_s": round(stats["fetch_ride_s"], 3),
            "overlap_fraction": round(stats["overlap_fraction"], 4),
        }
    # Report the runtime semantics the number was measured under (mirror
    # sync mode, precision), so async/stale-weights or bf16 numbers are
    # never mistaken for tied-weights f32 ones.
    result["precision"] = precision
    for ov in extra:
        if ov.startswith("fabric."):
            k, v = ov.split("=", 1)
            result[k.split(".", 1)[1]] = v
    return result


def bench_ppo():
    # README.md:100-117 — 65,536 steps in 81.27 s
    return _timeboxed(
        "ppo_cartpole_env_steps_per_sec", "ppo_benchmarks", 65536, 65536 / 81.27,
        warmup_steps=512, start_steps=16384,
    )


def bench_a2c():
    # README.md:118-133 — 65,536 steps in 84.76 s
    return _timeboxed(
        "a2c_cartpole_env_steps_per_sec", "a2c_benchmarks", 65536, 65536 / 84.76,
        warmup_steps=512, start_steps=16384,
    )


def bench_sac(device_buffer: bool = False, pipelined: bool = False):
    # README.md:139-140 — 65,536 steps in 320.21 s. Off-policy: the player
    # never blocks on the weight mirror (fabric.player_sync=async,
    # core/player.py) — SAC trains every env step, so a blocking mirror
    # would serialize the interaction loop on the device link.
    extra = ["fabric.player_sync=async"]
    suffix = ""
    if device_buffer:
        # A/B leg: device-resident replay ring + fused K-step scan
        # (data/device_buffer.py) vs the host sample + per-call transfer
        # above. Same workload, same baseline, so vs_baseline is directly
        # comparable between the two rows.
        extra += ["buffer.device=true", "algo.fused_train_steps=8"]
        suffix = "_devbuf"
    if pipelined:
        # A/B leg: pipelined interaction (core/interact.py) — async action
        # fetch + 2 env slices software-pipelined over the 4 bench envs —
        # vs the serial per-step fetch above. Same workload and baseline.
        extra += ["fabric.async_fetch=true", "env.pipeline_slices=2"]
        suffix = "_pipe"
    result = _timeboxed(
        f"sac{suffix}_env_steps_per_sec", "sac_benchmarks", 65536, 65536 / 320.21,
        learning_starts=100, warmup_steps=1024, start_steps=4096,
        extra=tuple(extra),
    )
    if device_buffer:
        result["buffer_device"] = True
        result["fused_train_steps"] = 8
    if pipelined:
        result["pipeline_slices"] = 2
    return result


def _bench_checkpoint_save(reps: int = 5):
    """Direct cost of one atomic checkpoint save — stage + digest + fsync +
    rename (utils/checkpoint.py) — on a synthetic SAC-sized state (six
    256-wide f32 layers plus Adam moments, ~3 MB of leaves)."""
    import tempfile

    import numpy as np

    from sheeprl_tpu.utils.checkpoint import save_checkpoint

    rng = np.random.default_rng(0)

    def layer():
        return {"w": rng.standard_normal((256, 256)).astype(np.float32), "b": np.zeros(256, np.float32)}

    state = {
        "agent": {f"layer{i}": layer() for i in range(6)},
        "opt": {f"layer{i}": {"m": layer(), "v": layer()} for i in range(2)},
        "iter_num": 1,
    }
    payload_mb = sum(
        a.nbytes for g in ("agent", "opt") for a in _tree_leaves(state[g])
    ) / 2**20
    times = []
    with tempfile.TemporaryDirectory() as d:
        for r in range(reps):
            t0 = time.perf_counter()
            save_checkpoint(os.path.join(d, f"ckpt_{8 * (r + 1)}_0.ckpt"), state, keep_last=2)
            times.append(time.perf_counter() - t0)
    return {
        "median_s": round(sorted(times)[len(times) // 2], 4),
        "reps": reps,
        "payload_mb": round(payload_mb, 1),
    }


def _tree_leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


def bench_sac_resilience():
    # A/B leg: the full fault-tolerance stack armed (preemption guard, env
    # supervisor, dispatch watchdog — core/resilience.py) on the same SAC
    # workload and baseline as the plain `sac` row. The acceptance target is
    # this row's env-steps/s within 2% of `sac`'s: the guard is a flag check
    # per iteration, the supervisor a try/except per slice step, the watchdog
    # one condvar arm/disarm per dispatch.
    result = _timeboxed(
        "sac_resilience_env_steps_per_sec", "sac_benchmarks", 65536, 65536 / 320.21,
        learning_starts=100, warmup_steps=1024, start_steps=4096,
        extra=("fabric.player_sync=async", "resilience=on"),
    )
    result["resilience"] = {"preemption": True, "supervisor": True, "watchdog": True}
    result["checkpoint_save"] = _bench_checkpoint_save()
    return result


def bench_sac_health():
    # A/B leg: in-jit health probes + host-side sentinels (telemetry/health.py)
    # armed on the same SAC workload and baseline as the plain `sac` row.
    # Acceptance target: within 2% of `sac` — the probe is a handful of pure
    # reductions fused into the already-compiled train step, and its scalars
    # ride the StepTimer's existing coalesced per-interval transfer (zero
    # extra host syncs per step; graftlint-enforced).
    result = _timeboxed(
        "sac_health_env_steps_per_sec", "sac_benchmarks", 65536, 65536 / 320.21,
        learning_starts=100, warmup_steps=1024, start_steps=4096,
        extra=("fabric.player_sync=async", "health=on"),
    )
    result["health"] = {"probes": True, "sentinels": True}
    return result


def bench_sac_flight():
    # A/B leg: full tracing armed (telemetry.enabled=True -> live span ring,
    # per-iteration trace contexts, env-var carrier) on top of the always-on
    # flight recorder, on the same SAC workload and baseline as the plain
    # `sac` row. Acceptance target: within 2% of `sac` — a trace-context
    # child is two string formats, a span append one locked deque push, the
    # flight sink one GIL-atomic ring append, and worker spills rewrite one
    # small file every few seconds off the step path. Goodput accounting is
    # pinned OFF so this row keeps isolating the tracing cost (the goodput
    # A/B is its own leg, sac_goodput).
    result = _timeboxed(
        "sac_flight_env_steps_per_sec", "sac_benchmarks", 65536, 65536 / 320.21,
        learning_starts=100, warmup_steps=1024, start_steps=4096,
        extra=("fabric.player_sync=async", "telemetry.enabled=True", "telemetry.perf.enabled=False"),
    )
    result["flight"] = {"tracing": True, "recorder": True}
    return result


def bench_sac_fleet():
    # A/B leg: two supervised actor-replica processes feeding the learner
    # over pipes (core/fleet.py) vs the SAME decoupled recipe in-process
    # (fleet.replicas=1 — today's loop, byte for byte). Acceptance target:
    # fleet within 2% of in-process env-steps/s. The steady-state cost is
    # one connection.wait + one pickle per learner iteration (rows the
    # replica was building anyway); liveness piggybacks on the shipments
    # and restart/backoff machinery is entirely off the healthy path.
    # There is no stored sac_decoupled baseline row, so the leg measures
    # both arms itself and vs_baseline is fleet/in-process directly.
    #
    # Noise: single-shot differenced rates on a shared 1-core host swing
    # +-20% run to run, enough to invert the comparison entirely. The leg
    # therefore interleaves REPS (t1, t2) pairs per arm (interleaving
    # cancels slow host drift) and takes each arm's BEST rate: external
    # contention only ever slows a run down, so the max is the least-biased
    # estimate of the true arm speed.
    from sheeprl_tpu.cli import check_configs
    from sheeprl_tpu.config.loader import compose

    common = [
        "exp=sac_decoupled",
        "env=dummy",
        "env.id=continuous_dummy",
        "env.wrapper.id=continuous_dummy",
        "metric.log_level=0",
        "env.num_envs=4",
        "env.sync_env=True",
        "env.capture_video=False",
        "algo.learning_starts=128",
        "algo.per_rank_batch_size=256",
        "algo.hidden_size=256",
        "algo.run_test=False",
        "buffer.memmap=False",
        "buffer.size=16384",
        "checkpoint.every=0",
        "checkpoint.save_last=False",
        "fabric.accelerator=cpu",
        "fabric.devices=2",
        "fleet.param_sync_every=8",
    ]

    def timed(steps, replicas):
        cfg = compose(
            "config", common + [f"algo.total_steps={steps}", f"fleet.replicas={replicas}"]
        )
        check_configs(cfg)
        start = time.perf_counter()
        _run_silent(cfg)
        return time.perf_counter() - start

    s1, s2 = 1024, 4096
    REPS = 3
    arms = (("inprocess", 1), ("fleet2", 2))
    rates = {label: 0.0 for label, _ in arms}
    for _, replicas in arms:
        timed(s1, replicas)  # warm the jit caches (and the spawn import path)
    for _ in range(REPS):
        for label, replicas in arms:
            t1 = timed(s1, replicas)
            t2 = timed(s2, replicas)
            # Differencing the short and long runs cancels the fixed per-run
            # overhead — including the fleet arm's replica spawn/teardown,
            # which is a startup cost, not a steady-state one.
            rates[label] = max(rates[label], (s2 - s1) / max(t2 - t1, 1e-9))
    return {
        "metric": "sac_fleet_env_steps_per_sec",
        "value": round(rates["fleet2"], 2),
        "unit": "env-steps/sec",
        "vs_baseline": round(rates["fleet2"] / rates["inprocess"], 3),
        "fleet": {
            "replicas": 2,
            "inprocess_env_steps_per_sec": round(rates["inprocess"], 2),
        },
    }


def _goodput_snapshot():
    """(summary, breakdown) from the most recent PerfAccountant publish in
    this process — the long measured run's final log interval."""
    from sheeprl_tpu.telemetry.perf import last_published

    import jax

    gauges = last_published()
    if not gauges:
        return None, None
    shorts = ("flops_per_s", "bytes_per_s", "train_steps_per_s")
    if jax.default_backend() != "cpu":
        # Utilizations are device metrics: on the CPU the accountant's
        # ceiling is an sgemm/memcpy probe, not a device peak, and a share
        # of it is never written under a device metric's name.
        shorts = ("mfu", "hbm_bw_util") + shorts
    summary = {
        short: round(gauges[f"perf/{short}"], 6) for short in shorts if f"perf/{short}" in gauges
    }
    breakdown = {
        lane: round(gauges[f"perf/step_time_breakdown_{lane}"], 4)
        for lane in ("compute", "infeed", "host")
        if f"perf/step_time_breakdown_{lane}" in gauges
    }
    return (summary or None), (breakdown or None)


def bench_sac_goodput():
    # A/B leg: roofline goodput accounting armed (telemetry/perf.py — cost
    # specs noted per dispatch, lower/compile harvest + gauge publish at the
    # log interval) on the same SAC workload and baseline as the plain `sac`
    # row. Acceptance target: within 2% of `sac` — the dispatch-path cost is
    # one locked dict increment per train call. metric.log_level=1 (vs the
    # recipe's 0) so log_counters actually publishes; log_every stays at the
    # recipe's 70000, so the only interval is the run-final one and the
    # embedded snapshot summarizes the whole measured run.
    result = _timeboxed(
        "sac_goodput_env_steps_per_sec", "sac_benchmarks", 65536, 65536 / 320.21,
        learning_starts=100, warmup_steps=1024, start_steps=4096,
        extra=("fabric.player_sync=async", "telemetry.enabled=True", "metric.log_level=1"),
    )
    summary, breakdown = _goodput_snapshot()
    if summary:
        result["goodput"] = summary
    if breakdown:
        result["step_time_breakdown"] = breakdown
    return result


def bench_sac_mesh8():
    """Per-shard goodput leg on the virtual 8-device CPU mesh (main() injects
    XLA_FLAGS=--xla_force_host_platform_device_count=8 before the jax import).
    One telemetry-armed SAC run with the batch sharded over data=8; the
    headline value is the perf/shard_imbalance gauge (max/mean per-shard
    flops, 1.0 = perfectly even, direction=lower — the quantity `perf
    --check` gates so a layout change that skews one shard trips CI), with
    the full per-shard MFU map embedded via the record's `shards` field and
    throughput demoted to context. SHEEPRL_MESH_BENCH_STEPS shrinks the run
    for the CI smoke leg."""
    from sheeprl_tpu.cli import check_configs
    from sheeprl_tpu.config.loader import compose
    from sheeprl_tpu.telemetry.perf import last_published

    steps = int(os.environ.get("SHEEPRL_MESH_BENCH_STEPS", "2048"))
    overrides = [
        "exp=sac_benchmarks",
        "fabric.devices=8",
        "fabric.player_sync=async",
        "telemetry.enabled=True",
        "metric.log_level=1",
        "algo.learning_starts=100",
        f"algo.total_steps={steps}",
        "checkpoint.every=0",
        "checkpoint.save_last=False",
    ]
    cfg = compose("config", overrides)
    check_configs(cfg)
    t0 = time.perf_counter()
    _run_silent(cfg)
    wall = time.perf_counter() - t0
    gauges = last_published() or {}
    prefix = "perf/shard/"
    shards = {
        name[len(prefix) : -len("/mfu")]: round(float(v), 8)
        for name, v in gauges.items()
        if name.startswith(prefix) and name.endswith("/mfu")
    }
    imbalance = float(gauges.get("perf/shard_imbalance", 1.0))
    return {
        "metric": "sac_mesh8_shard_imbalance",
        "value": round(imbalance, 4),
        "unit": "max_over_mean",
        # max/mean is not a time unit, so bench_db would default this leg to
        # higher-better; pin the direction or the gate points backwards.
        "direction": "lower",
        "vs_baseline": round(1.0 / max(imbalance, 1e-9), 3),
        "shards": shards,
        "devices": 8,
        "env_steps": steps,
        "env_steps_per_sec": round(steps / max(wall, 1e-9), 2),
        "aggregate_mfu": round(float(gauges.get("perf/mfu", 0.0)), 8),
    }


def _bench_anakin_shard8(metric_prefix, exp, baseline_sps, extra=()):
    """Sharded-learner leg: a fused Anakin run on the virtual 8-device CPU
    mesh (main() injects the device-count flag before the jax import) with
    the shard_map'd superstep, the data-sharded device ring and the
    explicitly-sharded train jit all on the measured path. Headline is
    env-steps/s against the same reference wall-clock as the unsharded
    Anakin row; the record embeds the per-shard MFU map plus the
    perf/shard_imbalance gauge so a layout change that skews one shard is
    visible to `telemetry perf --check`. SHEEPRL_SHARD_BENCH_STEPS shrinks
    the run for the CI smoke leg."""
    from sheeprl_tpu.cli import check_configs
    from sheeprl_tpu.config.loader import compose
    from sheeprl_tpu.core import fused_loop
    from sheeprl_tpu.telemetry.perf import last_published

    steps = int(os.environ.get("SHEEPRL_SHARD_BENCH_STEPS", "16384"))
    overrides = [
        f"exp={exp}",
        "algo.fused_rollout=True",
        "fabric.devices=8",
        "env.num_envs=8",
        "telemetry.enabled=True",
        "metric.log_level=1",
        "metric.disable_timer=True",
        "algo.run_test=False",
        f"algo.total_steps={steps}",
        "checkpoint.every=0",
        "checkpoint.save_last=False",
        *extra,
    ]
    cfg = compose("config", overrides)
    check_configs(cfg)
    t0 = time.perf_counter()
    _run_silent(cfg)
    wall = time.perf_counter() - t0
    stats = fused_loop.last_run_stats()
    gauges = last_published() or {}
    prefix = "perf/shard/"
    shards = {
        name[len(prefix) : -len("/mfu")]: round(float(v), 8)
        for name, v in gauges.items()
        if name.startswith(prefix) and name.endswith("/mfu")
    }
    value = round(stats["env_steps"] / max(wall, 1e-9), 2)
    return {
        "metric": f"{metric_prefix}_env_steps_per_sec",
        "value": value,
        "unit": "env_steps_per_sec",
        "vs_baseline": round(value / baseline_sps, 3),
        "devices": 8,
        "shards": shards,
        "aggregate_mfu": round(float(gauges.get("perf/mfu", 0.0)), 8),
        "shard_imbalance": round(float(gauges.get("perf/shard_imbalance", 1.0)), 4),
        "fused": {
            "supersteps": stats["supersteps"],
            "jit_dispatches": stats["jit_dispatches"],
            "env_steps": stats["env_steps"],
        },
    }


def bench_sac_shard8():
    # Same reference wall-clock as the sac rows; fused_train_steps sized as
    # in bench_sac_anakin so steady-state supersteps stay 2 dispatches.
    return _bench_anakin_shard8(
        "sac_shard8", "sac_anakin", 65536 / 320.21,
        extra=("algo.learning_starts=1024", "algo.fused_train_steps=1024"),
    )


def bench_ppo_anakin_shard8():
    return _bench_anakin_shard8("ppo_anakin_shard8", "ppo_anakin", 65536 / 81.27)


def bench_serve_sac(traced: bool = False):
    """Closed-loop load test of the serving stack (sheeprl_tpu/serve): train
    a tiny SAC policy, export it to an artifact, host it in an
    InferenceEngine, then sweep concurrent in-process clients 1..max_batch.
    Each client loops synchronous act() calls (closed loop: a client's next
    request waits for its previous answer), so throughput scaling beyond 1x
    comes entirely from dynamic micro-batching — the engine riding N
    requests on one padded jitted apply. The headline value is peak
    requests/s across the sweep; vs_baseline is peak over the single-client
    rate (the batching speedup itself). Each sweep row embeds p50/p99
    latency, per-bucket mean occupancy, and shed counts from the engine's
    own histogram/telemetry.

    With ``traced=True`` (the ``serve_sac_traced`` leg) every client request
    carries its own trace context and the live span ring the HTTP server
    installs is active, so the engine's per-request/batch span emission and
    request->batch linking sit on the measured path. Acceptance target:
    peak within 2% of the plain ``serve_sac`` row."""
    import glob
    import tempfile
    import threading

    import numpy as np

    from sheeprl_tpu.cli import check_configs
    from sheeprl_tpu.config.loader import compose
    from sheeprl_tpu.serve.artifact import export_artifact
    from sheeprl_tpu.serve.engine import InferenceEngine
    from sheeprl_tpu.telemetry import flight as flight_mod
    from sheeprl_tpu.telemetry import trace_context
    from sheeprl_tpu.telemetry import tracer as tracer_mod

    tmp = tempfile.mkdtemp(prefix="bench_serve_")
    overrides = [
        "exp=sac",
        "env=dummy",
        "env.id=continuous_dummy",
        "env.wrapper.id=continuous_dummy",
        "metric.log_level=0",
        "env.num_envs=2",
        "env.sync_env=True",
        "env.capture_video=False",
        "algo.per_rank_batch_size=32",
        "algo.learning_starts=64",
        "algo.run_test=False",
        "algo.total_steps=256",
        "buffer.memmap=False",
        "buffer.checkpoint=False",
        "checkpoint.every=0",
        "checkpoint.save_last=True",
        "fabric.accelerator=cpu",
        f"root_dir={tmp}",
        "run_name=bench_serve",
    ]
    cfg = compose("config", overrides)
    check_configs(cfg)
    _run_silent(cfg)
    ckpt = sorted(glob.glob(os.path.join(tmp, "**", "ckpt_*"), recursive=True))[-1]
    artifact_path = export_artifact(ckpt)

    max_batch = 8
    engine = InferenceEngine(max_batch=max_batch, queue_capacity=512, batch_window_s=0.002)
    card = engine.load("sac", artifact_path)

    restore_tracer = None
    recorder = None
    if traced:
        restore_tracer = tracer_mod.set_current(tracer_mod.Tracer(capacity=65536, enabled=True))
        recorder = flight_mod.install(flight_mod.FlightRecorder(run_info={"role": "serve_bench"}))

    rng = np.random.default_rng(0)
    client_obs = [
        {k: rng.standard_normal(shape).astype(np.float32) for k, shape in card["obs_keys"].items()}
        for _ in range(max_batch)
    ]

    # Prime the dispatch path + service-time EWMA past the first-call jitter.
    for i in range(16):
        engine.act("sac", client_obs[i % max_batch], mode="sample", seed=i)

    window_s = float(os.environ.get("SHEEPRL_SERVE_BENCH_WINDOW_S", "4"))
    sweep = []
    for n_clients in [n for n in (1, 2, 4, 8, 16) if n <= max_batch]:
        engine.reset_stats()
        counts = [0] * n_clients
        stop_t = time.perf_counter() + window_s

        def client(i):
            obs = client_obs[i % max_batch]
            while time.perf_counter() < stop_t:
                if traced:
                    with trace_context.use(trace_context.mint()):
                        engine.act("sac", obs, mode="sample", seed=i, timeout=60)
                else:
                    engine.act("sac", obs, mode="sample", seed=i, timeout=60)
                counts[i] += 1

        threads = [threading.Thread(target=client, args=(i,)) for i in range(n_clients)]
        start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - start
        stats = engine.stats()
        lat = stats["latency"]
        sweep.append(
            {
                "clients": n_clients,
                "requests_per_sec": round(sum(counts) / elapsed, 2),
                "p50_latency_s": round(lat["p50"], 5),
                "p99_latency_s": round(lat["p99"], 5),
                "mean_occupancy_per_bucket": {
                    b: round(row["mean_occupancy"], 2) for b, row in stats["occupancy"].items()
                },
                "sheds": stats["counters"]["sheds"],
                "timeouts": stats["counters"]["timeouts"],
            }
        )
    engine.close()
    if traced:
        flight_mod.uninstall(recorder)
        tracer_mod.set_current(restore_tracer)

    single = sweep[0]["requests_per_sec"]
    peak = max(row["requests_per_sec"] for row in sweep)
    return {
        "metric": "serve_sac_traced_peak_requests_per_sec" if traced else "serve_sac_peak_requests_per_sec",
        "traced": traced,
        "value": peak,
        "unit": "requests/sec",
        # The batching speedup: peak closed-loop throughput over the
        # single-client rate. > len(sweep[0]) clients' linear share means
        # superlinear scaling from batch amortization.
        "vs_baseline": round(peak / max(single, 1e-9), 3),
        "max_batch": max_batch,
        "window_s": window_s,
        "sweep": sweep,
    }


def _bench_dreamer(
    version: str,
    baseline_seconds: float,
    device_buffer: bool = False,
    pipelined: bool = False,
    health: bool = False,
    goodput: bool = False,
):
    # Off-policy: async weight mirror (see bench_sac). Precision is the
    # recipe's own (bf16-mixed); _timeboxed records it in the result.
    extra = ["fabric.player_sync=async"]
    suffix = ""
    if device_buffer:
        # A/B leg (see bench_sac): HBM replay ring + fused K-step scan vs
        # host buffer + ReplayInfeed.
        extra += ["buffer.device=true", "algo.fused_train_steps=8"]
        suffix = "_devbuf"
    if pipelined:
        # A/B leg: async action fetch + train-dispatch-before-harvest
        # (core/interact.py). The bench recipe runs 1 env, so no slicing —
        # the win here is the fetch riding under the fused-train dispatch.
        extra += ["fabric.async_fetch=true"]
        suffix = "_pipe"
    if health:
        # A/B leg (see bench_sac_health): probes over the world-model/actor/
        # critic grad trees + the KL aux, sentinels on the host. <2% target.
        extra += ["health=on"]
        suffix = "_health"
    if goodput:
        # A/B leg (see bench_sac_goodput): roofline goodput accounting over
        # the world-model/actor/critic train jits. <2% target.
        extra += ["telemetry.enabled=True", "metric.log_level=1"]
        suffix = "_goodput"
    result = _timeboxed(
        f"dreamer_v{version}{suffix}_env_steps_per_sec",
        f"dreamer_v{version}_benchmarks",
        16384,
        16384 / baseline_seconds,
        learning_starts=1024,
        extra=tuple(extra),
    )
    if device_buffer:
        result["buffer_device"] = True
        result["fused_train_steps"] = 8
    if health:
        result["health"] = {"probes": True, "sentinels": True}
    if goodput:
        summary, breakdown = _goodput_snapshot()
        if summary:
            result["goodput"] = summary
        if breakdown:
            result["step_time_breakdown"] = breakdown
    return result


def bench_dreamer_v1():
    return _bench_dreamer("1", 2207.13)  # README.md:150-158


def bench_dreamer_v2():
    return _bench_dreamer("2", 906.42)  # README.md:159-167


def bench_dreamer_v3():
    return _bench_dreamer("3", 1589.30)  # README.md:168-176


def bench_dreamer_v3_S(batch: int = None):
    # North-star scale (BASELINE.md): DreamerV3-S at the Atari-100K recipe —
    # S model, batch 16 x sequence 64, replay_ratio 1 — vs the RTX 3080's
    # 100K frames in 14 h (README.md:44-51) = 1.98 env-steps/s. ALE is not
    # installed in this image, so the deterministic dummy pixel env stands in
    # for MsPacman (documented divergence: the emulator costs the reference
    # only a few seconds; the number is dominated by the S-size train step
    # and per-step policy latency). buffer.size capped host-side (RAM);
    # steady-state throughput is unaffected and the differencing cancels it.
    #
    # `batch` overrides per_rank_batch_size for the batch-scaling study (the
    # B=16 step was HBM-bound when last profiled — CHANGES.md, "Round-3
    # profile" — so batch growth is the MFU lever): env-steps/s drops as
    # the train step does batch/16x more
    # samples per policy step, while train-samples/s and MFU rise.
    extra = [
        "env=dummy",
        "env.id=discrete",
        "env.capture_video=False",
        "env.sync_env=True",
        "buffer.size=20000",
        "buffer.memmap=False",
        "buffer.prefetch=True",
        "fabric.player_sync=async",
        "metric.log_level=0",
        "metric.disable_timer=True",
    ]
    suffix = ""
    if batch is not None:
        extra.append(f"algo.per_rank_batch_size={batch}")
        suffix = f"_b{batch}"
    result = _timeboxed(
        f"dreamer_v3_S{suffix}_env_steps_per_sec",
        "dreamer_v3_100k_ms_pacman",
        100000,
        100000 / (14 * 3600),
        learning_starts=1024,
        warmup_steps=1280,
        start_steps=1536,
        extra=tuple(extra),
    )
    if batch is not None:
        result["per_rank_batch_size"] = batch
    return result


def _bench_anakin(
    algo: str,
    exp: str,
    total_steps: int,
    baseline_sps: float,
    *,
    learning_starts: int = 0,
    warmup_steps: int = 1536,
    start_steps: int = 2048,
    fused_extra=(),
    host_extra=(),
    common_extra=(),
):
    """Anakin head-to-head leg (howto/anakin_lane.md): the SAME pure-JAX env
    and recipe through the fused lane (rollout + train inside donated jits,
    core/fused_loop.py) and through the host lane (algo.fused_rollout=false:
    JaxToGymnasium + SyncVectorEnv + core/interact.py). Both lanes share
    every other knob, so `fused_vs_host` isolates exactly what fusing buys:
    the per-step dispatch + transfer overhead the host lane pays T*E times
    per superstep collapses to 1 (PPO) or 2 (SAC/DreamerV3) donated calls.
    The headline value/vs_baseline stay comparable with the plain gym rows
    (same step budget, same reference wall-clock); `fused` embeds the
    dispatch accounting from the fused long run
    (core/fused_loop.last_run_stats()) — dispatches_per_superstep <= 2 is
    the lane's contract."""
    from sheeprl_tpu.core import fused_loop

    common = [
        "metric.log_level=0",
        "metric.disable_timer=True",
        "algo.run_test=False",
        "env.capture_video=False",
        # In-process vector env on the host lane (matches the *_benchmarks
        # recipes): a subprocess env would re-jit the jax step per worker
        # and measure fork overhead, not the lane.
        "env.sync_env=True",
        *common_extra,
    ]
    fused = _timeboxed(
        f"{algo}_anakin_env_steps_per_sec", exp, total_steps, baseline_sps,
        learning_starts=learning_starts, warmup_steps=warmup_steps,
        start_steps=start_steps,
        extra=("algo.fused_rollout=True", *fused_extra, *common),
    )
    # interact.py never runs inside the fused lane; any split _timeboxed
    # picked up is a stale readout from an earlier leg in this process.
    fused.pop("interaction", None)
    stats = fused_loop.last_run_stats()
    host = _timeboxed(
        f"{algo}_anakin_host_env_steps_per_sec", exp, total_steps, baseline_sps,
        learning_starts=learning_starts, warmup_steps=warmup_steps,
        start_steps=start_steps,
        extra=("algo.fused_rollout=False", *host_extra, *common),
    )
    fused["fused"] = {
        "supersteps": stats["supersteps"],
        "jit_dispatches": stats["jit_dispatches"],
        "env_steps": stats["env_steps"],
        "dispatches_per_superstep": round(
            stats["jit_dispatches"] / max(stats["supersteps"], 1), 3
        ),
    }
    host_row = {
        "metric": host["metric"],
        "value": host["value"],
        "vs_baseline": host["vs_baseline"],
    }
    if "interaction" in host:
        host_row["interaction"] = host["interaction"]
    fused["host_lane"] = host_row
    fused["fused_vs_host"] = round(fused["value"] / max(host["value"], 1e-9), 3)
    return fused


def bench_ppo_anakin():
    # Same step budget and reference wall-clock as the ppo row
    # (README.md:100-117); the jax CartPole physics are bit-identical to
    # Gymnasium's (tests/test_envs/test_jax_envs.py), so the rows compare.
    # One donated dispatch covers the whole rollout scan + GAE + every
    # update epoch per superstep.
    return _bench_anakin(
        "ppo", "ppo_anakin", 65536, 65536 / 81.27,
        warmup_steps=512, start_steps=16384,
    )


def bench_sac_anakin():
    # fused_train_steps=1024 sizes the train bucket above the per-superstep
    # gradient debt (64 iters x 4 envs x replay_ratio 1.0 = 256 -> one
    # power-of-two bucket), so every steady-state training superstep is
    # exactly 1 rollout + 1 train dispatch; it also swallows the Ratio
    # controller's one-time post-prefill catch-up (~1k steps) in 3 dispatches
    # instead of 6, keeping the run-average dispatches_per_superstep <= 2.
    # Warmup runs past learning_starts so the train executables hit the
    # persistent compile cache in the measured runs.
    return _bench_anakin(
        "sac", "sac_anakin", 65536, 65536 / 320.21,
        learning_starts=1024, warmup_steps=2048, start_steps=4096,
        fused_extra=("algo.fused_train_steps=1024",),
        host_extra=("fabric.player_sync=async",),
    )


def bench_dreamer_v3_anakin():
    # Micro world model at the reference replay ratio (the
    # dreamer_v3_benchmarks sizes) so the leg runs end-to-end on CPU —
    # applied to BOTH lanes, so the head-to-head stays fair. 0.0625 x 16
    # iters x 4 envs = 4 gradient steps per superstep = exactly one
    # fused_train_steps=4 bucket: 1 rollout + 1 train dispatch.
    micro = (
        "algo.replay_ratio=0.0625",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.world_model.discrete_size=4",
        "algo.world_model.stochastic_size=4",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=8",
        "algo.world_model.transition_model.hidden_size=8",
        "algo.world_model.representation_model.hidden_size=8",
        "buffer.size=16384",
    )
    return _bench_anakin(
        "dreamer_v3", "dreamer_v3_anakin", 16384, 16384 / 1589.30,
        learning_starts=1024, common_extra=micro,
        host_extra=("fabric.player_sync=async",),
    )


def bench_graftlint_repo():
    """Analyzer wall time over the whole package: the CI lint gate's <=10 s
    CPU budget as a measured number instead of a vibe. vs_baseline is
    budget/actual, so >=1.0 means within budget. No jax import anywhere on
    this path — graftlint deliberately runs without the accelerator stack."""
    from sheeprl_tpu.analysis.runner import lint_paths_ex

    repo_root = os.path.dirname(os.path.abspath(__file__))
    t0 = time.perf_counter()
    result = lint_paths_ex([os.path.join(repo_root, "sheeprl_tpu")], root=repo_root)
    wall = time.perf_counter() - t0
    return {
        "metric": "graftlint_repo_wall_seconds",
        "value": round(wall, 3),
        "unit": "seconds",
        "vs_baseline": round(10.0 / max(wall, 1e-9), 3),
        "files_scanned": result.files_scanned,
        "findings": len(result.findings),
        "suppressed": result.suppressed,
        "parse_seconds": round(result.parse_s, 3),
        "backend": "none",
    }


def _append_history(leg: str, result: dict) -> None:
    """One schema-versioned record per finished leg into BENCH_HISTORY.jsonl
    (telemetry/bench_db.py): git sha + dirty flag, hardware fingerprint,
    value/unit, and the goodput/breakdown snapshot when the leg carried one.
    SHEEPRL_BENCH_HISTORY overrides the path; SHEEPRL_BENCH_NO_HISTORY=1
    skips the append (smoke runs with shrunk windows must not pollute the
    regression baseline). bench_db is stdlib-only — safe on the jax-free
    graftlint path too."""
    if os.environ.get("SHEEPRL_BENCH_NO_HISTORY") == "1":
        return
    repo = os.path.dirname(os.path.abspath(__file__))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from sheeprl_tpu.telemetry import bench_db

    device = str(result.get("device", ""))
    if not device:
        # Stamp the accelerator kind when a jax leg already paid the import;
        # the jax-free graftlint leg must not pull jax in just for this.
        jax_mod = sys.modules.get("jax")
        if jax_mod is not None:
            try:
                device = jax_mod.devices()[0].device_kind
            except Exception:
                device = ""
    record = bench_db.make_record(
        leg,
        float(result["value"]),
        str(result.get("unit", "")),
        backend=str(result.get("backend", "unknown")),
        device=device,
        extra={"vs_baseline": result.get("vs_baseline")},
        goodput=result.get("goodput"),
        breakdown=result.get("step_time_breakdown"),
        root=repo,
        direction=result.get("direction"),
        shards=result.get("shards"),
    )
    path = bench_db.default_history_path(repo)
    bench_db.append_record(path, record)
    print(f"bench: appended {leg} record to {path}", file=sys.stderr)


def _emit(leg: str, result: dict) -> None:
    """The bench's output contract: append the history record, then print the
    result as the LAST line on stdout — both streams flushed first, so a
    combined `2>&1` capture can always recover the record as the final line
    starting with '{' even when something (a library, a late absl warning)
    wrote noise around it."""
    _append_history(leg, result)
    sys.stderr.flush()
    sys.stdout.flush()
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()


_CPU_LEGS = (
    "ppo", "a2c", "sac", "sac_health", "sac_flight", "sac_goodput", "sac_mesh8", "sac_fleet",
    "sac_shard8", "ppo_anakin_shard8", "serve_sac", "serve_sac_traced",
)


def main() -> None:
    which = sys.argv[1] if len(sys.argv) > 1 else "dreamer_v3"
    if which == "graftlint_repo":
        # Static-analysis leg: no accelerator probe, no jax, no registry.
        _emit(which, bench_graftlint_repo())
        return
    # PPO/A2C/SAC are the reference's 4-CPU workloads and pin
    # fabric.accelerator=cpu in their exp configs, and the virtual-mesh legs
    # are CPU by construction: those twelve select the CPU platform outright.
    # Every other leg wants the accelerator and fails without one.
    if which in ("sac_mesh8", "sac_fleet", "sac_shard8", "ppo_anakin_shard8"):
        # Virtual multi-device CPU legs: the flag must be in the environment
        # before the first jax import or the CPU backend initializes with one
        # device and the mesh build fails (fleet replicas inherit it too).
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
    cpu_leg = which in _CPU_LEGS
    _setup_jax("cpu" if cpu_leg else None)
    import jax
    import sheeprl_tpu

    if not cpu_leg:
        device = jax.devices()[0]
        if device.platform == "cpu":
            raise SystemExit(
                f"bench: leg {which!r} wants an accelerator and JAX found only the CPU "
                f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); there is no CPU fallback"
            )
        # An accelerator the peak table does not know is an error, by name.
        from sheeprl_tpu.telemetry.perf import peaks_for_device_kind

        peaks_for_device_kind(device.device_kind)

    sheeprl_tpu.register_all()
    result = {
        "dreamer_v3": bench_dreamer_v3,
        "dreamer_v3_devbuf": lambda: _bench_dreamer("3", 1589.30, device_buffer=True),
        "dreamer_v3_pipe": lambda: _bench_dreamer("3", 1589.30, pipelined=True),
        "dreamer_v3_health": lambda: _bench_dreamer("3", 1589.30, health=True),
        "dreamer_v3_goodput": lambda: _bench_dreamer("3", 1589.30, goodput=True),
        "dreamer_v3_S": bench_dreamer_v3_S,
        "dreamer_v3_S_b32": lambda: bench_dreamer_v3_S(batch=32),
        "dreamer_v3_S_b64": lambda: bench_dreamer_v3_S(batch=64),
        "dreamer_v2": bench_dreamer_v2,
        "dreamer_v1": bench_dreamer_v1,
        "ppo": bench_ppo,
        "a2c": bench_a2c,
        "sac": bench_sac,
        "sac_devbuf": lambda: bench_sac(device_buffer=True),
        "sac_pipe": lambda: bench_sac(pipelined=True),
        "sac_resilience": bench_sac_resilience,
        "sac_fleet": bench_sac_fleet,
        "sac_health": bench_sac_health,
        "sac_flight": bench_sac_flight,
        "sac_goodput": bench_sac_goodput,
        "sac_mesh8": bench_sac_mesh8,
        "serve_sac": bench_serve_sac,
        "serve_sac_traced": lambda: bench_serve_sac(traced=True),
        "ppo_anakin": bench_ppo_anakin,
        "sac_anakin": bench_sac_anakin,
        "dreamer_v3_anakin": bench_dreamer_v3_anakin,
        "sac_shard8": bench_sac_shard8,
        "ppo_anakin_shard8": bench_ppo_anakin_shard8,
    }[which]()
    result["backend"] = jax.default_backend()
    _emit(which, result)


if __name__ == "__main__":
    main()
