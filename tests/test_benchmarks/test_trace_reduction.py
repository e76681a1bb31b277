"""The reduction from a profiler trace to device busy time, idle share,
per-program device time and idle gaps by host activity, on a small recorded
trace whose answers are known (fixtures/make_xplane.py)."""

import os
import sys

import pytest

import conftest  # noqa: F401
from benchmarks.harness import tracing

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    """As `Tracer.reduce` does it: the window from the marker program's two
    executions, the host stamps shifted onto the device's clock."""
    sys.path.insert(0, os.path.dirname(FIXTURE))
    import make_xplane

    planes = tracing.load_planes(FIXTURE)
    shift = tracing.reduce_planes(planes)["first_marker_end"] - make_xplane.HOST_CLOCK_AT_FIRST_MARKER
    host = [(label, (a + shift, b + shift)) for label, a, b in make_xplane.HOST_STAMPS]
    return tracing.reduce_planes(planes, host)


def test_interval_arithmetic():
    assert tracing.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tracing.total(tracing.clip([(0, 3), (5, 6)], 1, 5.5)) == pytest.approx(2.5)
    assert tracing.gaps([(1, 3), (5, 6)], 0, 8) == [(0, 1), (3, 5), (6, 8)]
    assert tracing.overlap((0, 2), (1, 5)) == 1


@pytest.mark.parametrize("name, short", [
    ("%copy.9 = u8[3500,1,64,64,3]{3,2,4,1,0:T(8,128)(4,1)} copy(u8[3500,1,64,64,3]{0,3,4,2,1} %ring.1), sharding={replicated}",
     "copy.9 u8[3500,1,64,64,3]"),
    ("%while.40 = (s32[]{:T(128)}, f32[4096]{0:T(1024)}) while((s32[], f32[4096]) %tuple.5), body=%b", "while.40"),
    ("fusion.1", "fusion.1"),
])
def test_operation_names_are_cut_to_what_identifies_them(name, short):
    assert tracing.short_op(name) == short
    assert len(tracing.short_op("x" * 500)) <= 96


def test_busy_union_and_window(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(8000e-6)
    # fusion.1 and fusion.2 overlap by 100 us: the union counts them once
    assert reduced["busy_s"] == pytest.approx(4100e-6)
    assert 100 * (1 - reduced["busy_s"] / reduced["window_s"]) == pytest.approx(48.75)


def test_per_program_device_time(reduced):
    assert reduced["modules"]["jit_train_step(123)"] == pytest.approx(4000e-6)
    assert reduced["module_counts"]["jit_train_step(123)"] == 2
    assert reduced["modules"]["jit__player_step(7)"] == pytest.approx(100e-6)


def test_top_operations(reduced):
    ops = dict(map(tuple, reduced["device_ops"]))
    assert ops["fusion.1"] == pytest.approx(3000e-6)
    assert ops["fusion.2"] == pytest.approx(1100e-6)
    assert reduced["device_ops"][0][0] == "fusion.1" and len(reduced["device_ops"]) <= 10


def test_idle_gaps_by_host_activity(reduced):
    idle = dict(map(tuple, reduced["idle_gaps"]))
    assert idle["bench/action_fetch"] == pytest.approx(1400e-6)
    assert idle["bench/env_step"] == pytest.approx(400e-6)
    assert idle["bench/train_dispatch"] == pytest.approx(200e-6)
    assert idle["bench/other_host"] == pytest.approx((500 + 100 + 1300) * 1e-6)
    assert sum(idle.values()) == pytest.approx(reduced["window_s"] - reduced["busy_s"])


def test_readers_on_the_reduced_trace(reduced):
    import types

    from conftest import ROOT
    from benchmarks.harness import manifest

    cell = types.SimpleNamespace(config={"program": {"train_modules": ["train_step"]}})
    run = {"trace": dict(reduced, gradient_steps=2), "cell": cell}
    assert manifest.load_reader("train_step.device_ms", ROOT)(run) == pytest.approx(2.0)
    assert manifest.load_reader("device.idle_share", ROOT)(run) == pytest.approx(48.75)
    # a trace with no device operation gives no share at all, never a 0
    assert manifest.load_reader("device.idle_share", ROOT)({"trace": {"devices": 0}}) is None


def test_a_trace_without_the_marker_reads_nothing():
    planes = [p for p in tracing.load_planes(FIXTURE)]
    for plane in planes:
        for line in plane["lines"]:
            line["events"] = [e for e in line["events"] if tracing.MARKER not in e[0]]
    assert tracing.reduce_planes(planes) == {"devices": 0}
