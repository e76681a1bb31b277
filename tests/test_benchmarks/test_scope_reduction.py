"""The reduction of a device trace to self time by scope and direction, and
the program's own spans on the device's clock, on a hand-made trace and
hand-written spans whose answers are known (fixtures/make_scoped_xplane.py)."""

import os
import shutil
import sys
import types

import pytest

from conftest import ROOT
from benchmarks.harness import manifest, tracing

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
sys.path.insert(0, FIXTURES)
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "layer_metrics"))
import _scopes  # noqa: E402
import make_scoped_xplane as made  # noqa: E402

US = 1e-6
CONFIG = manifest.load_json(os.path.join(ROOT, "benchmarks", "configs", "dreamer_v3_XL.json"))
SCOPES = tuple(CONFIG["program"]["step_scopes"])


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """What `runner.run_cell` hands the readers after a traced run."""
    run_dir = tmp_path_factory.mktemp("scoped_run")
    where = run_dir / "xla_trace" / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(FIXTURES, "scoped.xplane.pb"), where / "host.xplane.pb")
    reduced = tracing.reduce_planes(tracing.load_planes(str(where / "host.xplane.pb")))
    tracer = types.SimpleNamespace(first_marker_done=made.HOST_CLOCK_AT_FIRST_MARKER)
    return {
        "cell": types.SimpleNamespace(config={"program": {"train_modules": ["train_step"], "step_scopes": list(SCOPES)}}),
        "run_dir": str(run_dir),
        "trace": dict(reduced, gradient_steps=2),
        "window": types.SimpleNamespace(tracer=tracer, gradient_steps=lambda: 2),
        "spans": made.telemetry_records(),
    }


def read(name, run):
    return manifest.load_reader(name, ROOT)(run)


def test_the_wire_format_gives_each_operation_its_op_name():
    names = _scopes.op_names(os.path.join(FIXTURES, "scoped.xplane.pb"))
    assert set(names) == {"/device:TPU:0"}  # device planes only
    table = names["/device:TPU:0"]
    loop = next(name for name in table if name.startswith("%while.6 "))
    assert table[loop] == "jit(train_step)/transpose(jvp(dv3/rssm))/while"
    assert not any(name.startswith(("%add.0 ", "jit_")) for name in table)  # no stat: no entry


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train_step)/jvp(dv3/rssm)/while/body/WorldModel.dynamic/recurrent_model/rnn/dot_general:", ("dv3/rssm", "fwd")),
    ("jit(train_step)/transpose(jvp(dv3/rssm))/while/body/add_any", ("dv3/rssm", "bwd")),
    ("jit(fused_train_step)/while/body/transpose(jvp(dv3/heads))/mul", ("dv3/heads", "bwd")),
    ("jit(fused_train_step)/while/body/replay/ring_sample/gather", ("replay/ring_sample", "fwd")),
    ("jit(train_step)/dv3/optim/transpose", ("dv3/optim", "fwd")),  # optax's own transpose is no backward pass
    ("jit(train_step)/jit(_threefry_split)/slice", ("unscoped", "fwd")),
    ("", ("unscoped", "fwd")),
])
def test_scope_and_direction_of_an_op_name(op_name, scope):
    assert _scopes.scope_of(op_name, _scopes.scope_pattern(SCOPES)) == scope


def test_self_time_goes_to_the_innermost_operation():
    # a holds b holds c; d starts inside a and outlives it; e stands alone
    events = [(0, 10, "a"), (2, 4, "b"), (3, 3.5, "c"), (5, 12, "d"), (20, 21, "e")]
    got = _scopes.self_times(events)
    assert got == {"a": 3.0, "b": 1.5, "c": 0.5, "d": 7.0, "e": 1.0}
    assert sum(got.values()) == tracing.total(tracing.union([(s, e) for s, e, _ in events]))


def test_self_time_by_scope_exactly(run):
    got = _scopes.scopes_of(run)
    assert got["calls"] == 2
    assert {k: round(v / US) for k, v in got["by_scope"].items()} == {k: 2 * v for k, v in made.SELF_US.items()}
    # the player step between the two executions is no part of the train step
    assert ("dv3/act", "fwd") not in got["by_scope"]


def test_scopes_and_the_unscoped_rest_add_up_to_the_busy_union_of_the_train_modules(run):
    got = _scopes.scopes_of(run)
    planes = tracing.load_planes(tracing.newest_xplane(os.path.join(run["run_dir"], "xla_trace")))
    lines = {ln["name"]: ln["events"] for ln in planes[0]["lines"]}
    steps = [(s, s + d) for name, s, d in lines["XLA Modules"] if "train_step" in name]
    busy = sum(tracing.total(tracing.union(tracing.clip([(s, s + d) for _, s, d in lines["XLA Ops"]], lo, hi)))
               for lo, hi in steps)
    assert sum(got["by_scope"].values()) == pytest.approx(busy) == pytest.approx(3600 * US)
    # ... which is the step's device time less its own idle gaps (2 x 200 us here)
    device_ms = read("train_step.device_ms", run)
    phases = ["encoder_ms", "rssm_fwd_ms", "rssm_bwd_ms", "heads_ms", "imagine_fwd_ms", "imagine_bwd_ms",
              "actor_critic_ms", "optim_ms"]
    in_scopes = sum(read("train_step." + name, run) for name in phases)
    unscoped = made.SELF_US[("unscoped", "fwd")] / 1e3
    assert in_scopes + unscoped == pytest.approx(device_ms - 0.2)


@pytest.mark.parametrize("metric, expected", [
    ("train_step.encoder_ms", 0.2), ("train_step.rssm_fwd_ms", 0.6), ("train_step.rssm_bwd_ms", 0.5),
    ("train_step.heads_ms", 0.2), ("train_step.imagine_fwd_ms", 0.0), ("train_step.imagine_bwd_ms", 0.0),
    ("train_step.actor_critic_ms", 0.0), ("train_step.optim_ms", 0.25),
    ("train_step.unscoped_share", 100 * 50 / 1800),
    ("host.env_step_ms", 0.035), ("host.dispatch_ms", (0.18 + 0.1) / 2),
    ("host.idle_unattributed_share", 100 * (made.IDLE_US - made.IDLE_COVERED_US) / made.IDLE_US),
    ("replay.infeed_wait_ms", (0.02 + 0.01 + 0.29) / 2), ("replay.infeed_hit_share", 50.0),
])
def test_readers_on_the_scoped_trace(run, metric, expected):
    assert read(metric, run) == pytest.approx(expected)


def test_spans_land_on_the_devices_clock_by_the_marker_shift(run):
    spans = _scopes.program_spans(run)
    assert len(spans) == len(made.SPANS) - 1  # the fetch before the first marker is outside the window
    on_device = {(s["name"], round(s["start"] / US), round(s["end"] / US)) for s in spans}
    assert {(name, start, end) for name, _, start, end, _ in made.SPANS[1:]} == on_device
    assert [s["main"] for s in spans if s["name"] == "transfer/h2d_stage"] == [False]
    idle = _scopes.scopes_of(run)["idle"]
    assert tracing.total(idle) == pytest.approx(made.IDLE_US * US)


def test_a_run_of_a_program_without_scopes_or_clock_reads_nothing(run):
    """The parent of the PR that brought the scopes: its trace names no scope,
    its spans carry no thread and its meta record no `perf_epoch_s`."""
    bare = dict(run, spans=[{k: v for k, v in r.items() if k not in ("thread", "perf_epoch_s")} for r in run["spans"]])
    for metric in ("host.env_step_ms", "host.dispatch_ms", "host.idle_unattributed_share", "replay.infeed_wait_ms",
                   "replay.infeed_hit_share"):
        assert read(metric, bare) is None
    planes = tracing.load_planes(tracing.newest_xplane(os.path.join(run["run_dir"], "xla_trace")))
    got = _scopes.reduce_scopes(planes, {}, ["no_such_module"], SCOPES)
    assert got["calls"] == 0 and got["by_scope"] == {}
    unscoped = _scopes.reduce_scopes(planes, {"/device:TPU:0": {}}, ["train_step"], SCOPES)
    assert set(unscoped["by_scope"]) == {("unscoped", "fwd")}


def test_the_readers_scopes_are_the_programs():
    from sheeprl_tpu.telemetry import scopes

    assert SCOPES == scopes.DV3_STEP
    assert _scopes.scope_pattern(SCOPES).pattern == "(?:dv3|replay)/[a-z_]+"
