"""End-to-end telemetry smoke: CPU dry runs of ppo and dreamer_v3 with
`telemetry.enabled=True` must write a non-empty telemetry.jsonl and a Chrome
trace containing rollout/train spans and at least one compile event — the
acceptance contract of the observability subsystem."""

import glob
import json
import os

import pytest

from sheeprl_tpu.cli import run
from sheeprl_tpu.telemetry import Telemetry
from sheeprl_tpu.telemetry.telemetry import git_stamp
from sheeprl_tpu.utils.utils import dotdict

pytestmark = pytest.mark.telemetry


@pytest.fixture(autouse=True)
def _chdir_tmp(tmp_path, monkeypatch):
    # Keep logs/ out of the repo (runs write ./logs/runs relative to cwd).
    monkeypatch.chdir(tmp_path)


def _telemetry_overrides():
    return [
        "telemetry.enabled=True",
        # Spans flow from the phase timers, so metrics must be on; log every
        # iteration so the StepTimer flushes inside the short dry run.
        "metric.log_level=1",
        "metric.log_every=1",
    ]


def _find_exports(root):
    trace = glob.glob(os.path.join(root, "logs", "runs", "**", "trace.json"), recursive=True)
    jsonl = glob.glob(os.path.join(root, "logs", "runs", "**", "telemetry.jsonl"), recursive=True)
    assert trace and jsonl, "telemetry exports missing"
    return trace[-1], jsonl[-1]


def _check_exports(root):
    trace_path, jsonl_path = _find_exports(root)
    with open(trace_path) as fp:
        doc = json.load(fp)
    events = doc["traceEvents"]
    names = {e["name"] for e in events}
    cats = {e.get("cat") for e in events}
    # Rollout + train-step spans from the phase timers / StepTimer...
    assert "Time/env_interaction_time" in names
    assert "Time/train_time" in names
    assert "train/dispatch" in names
    # ...and at least one compile event from the jax.monitoring listeners.
    assert "compile/backend" in names
    assert "compile" in cats

    lines = [json.loads(line) for line in open(jsonl_path)]
    assert lines, "telemetry.jsonl is empty"
    kinds = {rec["type"] for rec in lines}
    assert {"meta", "counters", "span"} <= kinds
    # The meta line's fields are read outside the package (the benchmark's
    # harness takes perf_epoch_s from it): none may go.
    meta = lines[0]
    assert set(meta) >= {
        "type", "time", "backend", "process_index", "profiler_window", "trace_id", "pid",
        "perf_epoch_s", "wall_epoch_s", "git", "host", "device", "device_count", "local_device_count",
    }
    assert set(meta["git"]) == {"sha", "dirty"}
    assert set(meta["host"]) == {"hostname", "machine", "system", "cpu_count", "python"}
    final_counters = [rec for rec in lines if rec["type"] == "counters"][-1]["values"]
    assert final_counters.get("compiles", 0) >= 1
    assert final_counters.get("device_get_bytes", 0) > 0


def test_ppo_smoke_writes_telemetry(tmp_path):
    run(
        [
            "exp=ppo",
            "env=dummy",
            "dry_run=True",
            "env.num_envs=2",
            "env.sync_env=True",
            "env.capture_video=False",
            "algo.rollout_steps=4",
            "algo.per_rank_batch_size=4",
            "algo.update_epochs=1",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.encoder.cnn_features_dim=16",
            "algo.encoder.mlp_features_dim=8",
            "algo.mlp_keys.encoder=[state]",
            "algo.run_test=False",
            "buffer.memmap=False",
            "checkpoint.every=0",
            "fabric.accelerator=cpu",
        ]
        + _telemetry_overrides()
    )
    _check_exports(str(tmp_path))


def test_dreamer_v3_smoke_writes_telemetry(tmp_path):
    run(
        [
            "exp=dreamer_v3",
            "env=dummy",
            "dry_run=True",
            "env.num_envs=2",
            "env.sync_env=True",
            "env.capture_video=False",
            "env.screen_size=64",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.per_rank_batch_size=2",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=8",
            "algo.world_model.representation_model.hidden_size=8",
            "algo.world_model.transition_model.hidden_size=8",
            "algo.world_model.stochastic_size=4",
            "algo.world_model.discrete_size=4",
            "algo.horizon=2",
            "algo.per_rank_sequence_length=1",
            "algo.learning_starts=0",
            "algo.run_test=False",
            "buffer.memmap=False",
            "checkpoint.every=0",
            "fabric.accelerator=cpu",
        ]
        + _telemetry_overrides()
    )
    _check_exports(str(tmp_path))
    # The Dreamer loop also exercises the replay/transfer spans.
    trace_path, _ = _find_exports(str(tmp_path))
    events = [e for e in json.load(open(trace_path))["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in events}
    assert "replay/sample" in names
    assert "fetch/player_actions" in names
    assert {"replay/add", "interaction/dispatch/slice0"} <= names
    # loop/iteration spans tile the loop: each span the loop thread emitted
    # while the loop ran lies inside exactly one of them.
    iterations = [e for e in events if e["name"] == "loop/iteration"]
    assert iterations and all(e["dur"] > 0 and "gradient_steps" in e["args"] for e in iterations)
    first, last = min(e["ts"] for e in iterations), max(e["ts"] + e["dur"] for e in iterations)
    loop_thread = iterations[0]["args"]["thread"]
    inside = [e for e in events if e["name"] != "loop/iteration" and e["args"]["thread"] == loop_thread
              and first <= e["ts"] and e["ts"] + e["dur"] <= last]
    assert {"fetch/player_actions", "train/dispatch", "replay/add"} <= {e["name"] for e in inside}
    for child in inside:
        assert sum(i["ts"] <= child["ts"] and child["ts"] + child["dur"] <= i["ts"] + i["dur"] for i in iterations) == 1


def test_git_stamp_degrades_outside_a_worktree(tmp_path):
    stamp = git_stamp(str(tmp_path))
    assert stamp["sha"] == "unknown"


def test_from_config_maps_the_telemetry_group():
    cfg = dotdict(
        {
            "telemetry": {
                "enabled": True,
                "buffer_capacity": 128,
                "warmup_iters": 7,
                "warn_on_recompile": False,
                "chrome_trace": False,
                "jsonl": True,
                "profiler": {"start_step": 10, "stop_step": 20, "trace_dir": None, "port": None},
            }
        }
    )
    tele = Telemetry.from_config(cfg)
    assert tele.enabled
    assert tele._tracer.capacity == 128
    assert tele._monitor.warmup_iters == 7
    assert not tele._monitor.warn_on_recompile
    assert not tele.chrome_trace
    assert tele._profiler.configured
    assert (tele._profiler.start_step, tele._profiler.stop_step) == (10, 20)
    # Absent group -> disabled noop.
    assert not Telemetry.from_config(dotdict({})).enabled


def test_disabled_telemetry_writes_nothing(tmp_path):
    tele = Telemetry.noop()
    tele.open(str(tmp_path), rank_zero=True)
    st = tele.step_timer("train")
    with st.step():
        pass
    st.pend(None, {"x": 1})
    assert st.flush() == [{"x": 1}]  # the fetch still works when disabled
    with tele.span("nope"):
        pass
    tele.advance(1)
    tele.log_counters(None, 1)
    tele.close()
    # The always-on flight recorder may spill its crash ring; nothing else
    # (no trace.json, no telemetry.jsonl) may appear when telemetry is off.
    leftovers = set(os.listdir(str(tmp_path))) - {"flight"}
    assert leftovers == set()
    flight_dir = tmp_path / "flight"
    if flight_dir.is_dir():
        assert all(name.startswith("proc_") for name in os.listdir(flight_dir))
