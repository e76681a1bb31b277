"""Device scopes and the tracer's clock: the lowered DreamerV3 steps carry every
phase name in their `op_name`s, the scopes change nothing that runs, and the
program's spans export the `perf_counter` second they count from and the
thread they ran on (CPU, micro widths; nothing is compiled or run)."""

import contextlib
import json
import os
import re
import threading

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sheeprl_tpu
from sheeprl_tpu.telemetry import Telemetry, scopes
from sheeprl_tpu.telemetry import tracer as tracer_mod

pytestmark = pytest.mark.telemetry

T, B = 4, 2


def _micro(continuous: bool):
    """(cfg, mesh, agent, txs, step arguments as shapes) of a micro agent:
    the builder is traced for shapes, nothing is initialised."""
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3
    from sheeprl_tpu.config.loader import compose
    from sheeprl_tpu.core import Runtime
    from sheeprl_tpu.utils.ops import init_moments

    sheeprl_tpu.register_all()
    cfg = compose("config", [
        "exp=dreamer_v3", "env=dummy", "metric.log_level=0", "env.num_envs=1", "env.screen_size=64",
        "algo.dense_units=8", "algo.mlp_layers=1", f"algo.per_rank_batch_size={B}",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=8",
        "algo.world_model.representation_model.hidden_size=8",
        "algo.world_model.transition_model.hidden_size=8",
        "algo.world_model.stochastic_size=4", "algo.world_model.discrete_size=4",
        "algo.horizon=3", f"algo.per_rank_sequence_length={T}",
        "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[state]",
        "algo.cnn_keys.decoder=[rgb]", "algo.mlp_keys.decoder=[state]",
        "fabric.accelerator=cpu", "fabric.devices=1",
    ])
    cfg.env.frame_stack = -1
    runtime = Runtime(devices=1, accelerator="cpu").launch()
    runtime.seed_everything(5)
    obs_space = gym.spaces.Dict({
        "rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8),
        "state": gym.spaces.Box(-1, 1, (5,), np.float32),
    })
    actions_dim, is_continuous = ((2,), True) if continuous else ((3,), False)
    built = {}

    def shapes():
        built["agent"], state = dv3.build_agent(runtime, actions_dim, is_continuous, cfg, obs_space)
        built["txs"] = {
            name: dv3._make_optimizer(cfg.algo[name].optimizer, cfg.algo[name].clip_gradients)
            for name in ("world_model", "actor", "critic")
        }
        return state, {name: tx.init(state[name]) for name, tx in built["txs"].items()}, init_moments()

    state, opt_states, moments = jax.eval_shape(shapes)
    like = jax.ShapeDtypeStruct
    row = {"rgb": ((64, 64, 3), jnp.uint8), "state": ((5,), jnp.float32), "actions": ((sum(actions_dim),), jnp.float32),
           "rewards": ((1,), jnp.float32), "terminated": ((1,), jnp.float32), "truncated": ((1,), jnp.float32),
           "is_first": ((1,), jnp.float32)}
    args = {"state": state, "opt_states": opt_states, "moments": moments, "row": row,
            "data": {k: like((T, B) + shape, dtype) for k, (shape, dtype) in row.items()},
            "key": like((2,), jnp.uint32)}
    return cfg, runtime.mesh, built["agent"], built["txs"], args


def _lower(kind: str, continuous: bool = False):
    """The lowered `train_step` or `fused_train_step` of a freshly built step
    function (so that it is traced now, under whatever `jax.named_scope` is)."""
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3
    from sheeprl_tpu.data.device_buffer import DeviceReplayRing

    cfg, mesh, agent, txs, a = _micro(continuous)
    if kind == "train_step":
        step = dv3.make_train_step(agent, txs, cfg, mesh)
        return step.lower(a["state"], a["opt_states"], a["moments"], a["data"], a["key"],
                          jax.ShapeDtypeStruct((), jnp.float32))
    ring = DeviceReplayRing(16, 1, cnn_keys=("rgb",), obs_keys=("rgb", "state"))
    ring.allocate(a["row"])
    sample = ring.make_sample_fn(B, sequence_length=T, time_major=True)
    step = dv3.make_fused_train_step(agent, txs, cfg, mesh, sample)
    return step.lower(a["state"], a["opt_states"], a["moments"], jax.eval_shape(lambda: ring.state), a["key"],
                      jax.ShapeDtypeStruct((2,), jnp.float32))


def _op_names(lowered) -> str:
    return "\n".join(re.findall(r'op_name="([^"]*)"', lowered.as_text(dialect="hlo", debug_info=True)))


@pytest.mark.parametrize("kind", ["train_step", "fused_train_step"])
def test_lowered_steps_carry_every_scope(kind):
    names = _op_names(_lower(kind))
    for scope in scopes.DV3_STEP:
        assert scope in names, scope
    # forward and backward of a scope are told apart by what JAX writes round it
    assert "jvp(dv3/rssm)/" in names and "transpose(jvp(dv3/rssm))" in names
    assert "jvp(dv3/imagine)/" in names
    # discrete actions: the actor's gradient is REINFORCE on stopped trajectories,
    # nothing flows back through the imagination scan
    assert "transpose(jvp(dv3/imagine))" not in names
    assert (scopes.RING_SAMPLE in names) == (kind == "fused_train_step")
    assert f"jit({kind})" in names


def test_the_imagination_scan_has_a_backward_pass_with_continuous_actions():
    names = _op_names(_lower("train_step", continuous=True))
    assert "jvp(dv3/imagine)/" in names and "transpose(jvp(dv3/imagine))" in names


def test_scopes_change_nothing_that_runs(monkeypatch):
    scoped = _lower("train_step")

    @contextlib.contextmanager
    def no_scope(name):
        yield

    monkeypatch.setattr(jax, "named_scope", no_scope)
    bare = _lower("train_step")
    assert "dv3/" in _op_names(scoped) and "dv3/" not in _op_names(bare)
    # Without its locations the lowered module is the same program, letter for
    # letter. (The StableHLO text: the HLO dialect's text names each
    # instruction after its location, so it differs in names and nothing else.)
    assert scoped.as_text() == bare.as_text()



def test_named_scope_enters_the_program_through_the_one_table():
    root = os.path.dirname(os.path.abspath(sheeprl_tpu.__file__))
    users, uses = [], ""
    for folder, _, files in os.walk(root):
        for name in files:
            if name.endswith(".py") and os.path.join(folder, name) != scopes.__file__:
                with open(os.path.join(folder, name)) as fp:
                    text = fp.read()
                if "named_scope" in text and "analysis" not in folder:
                    users.append(name)
                uses += text
    assert users == []
    for constant in ("DV3_ENCODER", "DV3_RSSM", "DV3_HEADS", "DV3_IMAGINE", "DV3_ACTOR_CRITIC", "DV3_OPTIM",
                     "DV3_ACT", "RING_SAMPLE", "RING_WRITE"):
        assert f"scopes.{constant}" in uses, constant


def test_the_ring_write_program_is_scoped():
    from sheeprl_tpu.data.device_buffer import DeviceReplayRing

    ring = DeviceReplayRing(8, 1, obs_keys=("state",))
    ring.allocate({"state": ((3,), jnp.float32)})
    rows = {"state": jax.ShapeDtypeStruct((2, 1, 3), jnp.float32)}
    lowered = ring._build_write_fn().lower(
        jax.eval_shape(lambda: ring.state["data"]), jax.ShapeDtypeStruct((1,), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32), rows, jax.ShapeDtypeStruct((2, 1), jnp.bool_),
        jax.ShapeDtypeStruct((1,), jnp.int32))
    assert scopes.RING_WRITE in _op_names(lowered)


# ---------------------------------------------------------------- the tracer
class _Annotation:
    entered = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Annotation.entered.append(self.name)
        return self

    def __exit__(self, *exc_info):
        _Annotation.entered.append("/" + self.name)


def test_a_span_is_one_emission_for_both_clocks(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    _Annotation.entered = []
    off = tracer_mod.Tracer(enabled=False)
    assert off.span("a") is off.span("b") is tracer_mod._NOOP_CTX
    with off.span("a") as span:
        span.set(hit=True)
    assert _Annotation.entered == [] and off.spans() == []
    on = tracer_mod.Tracer(enabled=True)
    with on.span("infeed/take", "transfer") as span:
        span.set(hit=True)
    assert _Annotation.entered == ["infeed/take", "/infeed/take"]
    assert [(s.name, s.args, s.thread) for s in on.spans()] == [("infeed/take", {"hit": True}, "MainThread")]


def test_exports_state_the_clock_and_the_thread(tmp_path):
    tracer = tracer_mod.Tracer(enabled=True)
    with tracer.span("on_main"):
        pass
    worker = threading.Thread(target=lambda: tracer.span("on_worker").__enter__().__exit__(None, None, None),
                              name="sheeprl-infeed_0")
    worker.start()
    worker.join(timeout=10)
    chrome = tracer.chrome_trace()
    assert chrome["metadata"]["perf_epoch_s"] == tracer.perf_epoch_s
    assert abs(chrome["metadata"]["wall_epoch_s"] - tracer.wall_epoch_s) == 0
    threads = {e["name"]: e["args"]["thread"] for e in chrome["traceEvents"] if e["ph"] == "X"}
    assert threads == {"on_main": "MainThread", "on_worker": "sheeprl-infeed_0"}
    lines = [json.loads(line) for line in tracer.iter_jsonl()]
    assert {r["name"]: r["thread"] for r in lines} == threads
    # a span's perf_counter second is recovered from the export alone
    span = tracer.spans()[0]
    assert tracer.perf_epoch_s + lines[0]["ts_us"] / 1e6 == pytest.approx(span.start_s, abs=1e-6)


def test_loop_iterations_tile_the_loop(tmp_path):
    tele = Telemetry(enabled=True, flight_enabled=False).open(str(tmp_path))
    timer = tele.step_timer("train")
    for step, fused in ((1, 0), (2, 4), (3, 0)):
        tele.advance(step)
        with tele.span("replay/add", "replay"):
            pass
        if fused:
            with timer.step(fused):
                pass
    tele.close()
    records = [json.loads(line) for line in open(tmp_path / "telemetry.jsonl")]
    assert {"perf_epoch_s", "wall_epoch_s"} <= set(records[0])
    spans = [r for r in records if r["type"] == "span"]
    iterations = sorted((r for r in spans if r["name"] == "loop/iteration"), key=lambda r: r["ts_us"])
    assert [r["args"] for r in iterations] == [
        {"step": 1, "gradient_steps": 0}, {"step": 2, "gradient_steps": 4}, {"step": 3, "gradient_steps": 0}]
    assert all(r["dur_us"] > 0 for r in iterations)
    # each ends where the next begins (within the export's rounding) ...
    for a, b in zip(iterations, iterations[1:]):
        assert a["ts_us"] + a["dur_us"] == pytest.approx(b["ts_us"], abs=0.01)
    # ... and every other span lies inside exactly one, whose trace context it carries
    for child in (r for r in spans if r["name"] != "loop/iteration"):
        holders = [i for i in iterations
                   if i["ts_us"] <= child["ts_us"] and child["ts_us"] + child["dur_us"] <= i["ts_us"] + i["dur_us"] + 0.01]
        assert len(holders) == 1 and child["parent_id"] == holders[0]["span_id"]


def test_setup_is_a_span_tree_the_first_iteration_closes(tmp_path):
    """Phases the entry point timed before the run's Telemetry existed become
    spans with their own starts; compiles from the hand-over on are recorded,
    before `open` too; the root `setup` runs from its start to the first
    iteration, and no later iteration adds to it."""
    import time

    def compiled_in_setup(x):
        return x - 1

    x = jnp.ones((3,))
    tele = Telemetry(enabled=True, flight_enabled=False)
    started = time.perf_counter()
    tele.begin_setup(started, (("setup/config", started, started + 1e-3),))
    with tele.span("setup/runtime", "setup"):
        jax.jit(compiled_in_setup)(x)
    tele.open(str(tmp_path))
    with tele.span("setup/agent", "setup"):
        pass
    for step in (1, 2):
        tele.advance(step)
    tele.close()
    records = [json.loads(line) for line in open(tmp_path / "telemetry.jsonl")]
    epoch = records[0]["perf_epoch_s"]
    spans = {r["name"]: r for r in records if r["type"] == "span" and r["name"] != "loop/iteration"}
    assert set(spans) == {"setup", "setup/config", "setup/runtime", "setup/agent", "compile/trace", "compile/lower", "compile/backend"}
    assert spans["setup/config"]["ts_us"] == pytest.approx((started - epoch) * 1e6, abs=0.01)
    assert spans["setup/config"]["dur_us"] == pytest.approx(1e3, abs=0.01)
    runtime, backend = spans["setup/runtime"], spans["compile/backend"]
    assert backend["args"]["fun"] == "jit(compiled_in_setup)"
    assert runtime["ts_us"] <= backend["ts_us"] <= backend["ts_us"] + backend["dur_us"] <= runtime["ts_us"] + runtime["dur_us"] + 100
    first = min((r for r in records if r.get("name") == "loop/iteration"), key=lambda r: r["ts_us"])
    root = spans["setup"]
    assert root["ts_us"] == spans["setup/config"]["ts_us"]
    assert root["ts_us"] + root["dur_us"] == pytest.approx(first["ts_us"], abs=0.01)
    assert all(spans[name]["cat"] == "setup" for name in spans if not name.startswith("compile/"))
