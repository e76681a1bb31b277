"""ProfilerWindow: the [start, stop) step window drives jax.profiler.trace
exactly once, and an unconfigured window is inert."""

import os

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.telemetry.profiling import ProfilerWindow

pytestmark = pytest.mark.telemetry


def test_unconfigured_window_is_inert(tmp_path):
    w = ProfilerWindow(trace_dir=str(tmp_path / "x"))
    assert not w.configured
    w.advance(0)
    w.advance(10)
    w.close()
    assert not w.active
    assert not os.path.exists(str(tmp_path / "x"))


def test_window_traces_the_configured_steps(tmp_path):
    trace_dir = str(tmp_path / "xla_trace")
    w = ProfilerWindow(trace_dir=trace_dir, start_step=2, stop_step=4)
    assert w.configured
    w.advance(1)
    assert not w.active
    w.advance(2)
    assert w.active
    jax.jit(lambda x: x * 2)(jnp.ones((16,))).block_until_ready()
    w.advance(3)
    assert w.active  # still inside [2, 4)
    w.advance(4)
    assert not w.active
    # One-shot: re-entering the window must not restart the profiler.
    w.advance(2)
    assert not w.active
    w.close()
    # The xplane trace directory was created by the start.
    assert os.path.isdir(trace_dir)


def test_window_records_annotations_and_not_python_calls(tmp_path, monkeypatch):
    """Host tracer at level 1 (the program's spans enter TraceAnnotations),
    Python tracer off."""
    seen = {}
    monkeypatch.setattr(jax.profiler, "start_trace", lambda path, profiler_options=None: seen.update(
        path=path, host=profiler_options.host_tracer_level, python=profiler_options.python_tracer_level))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    w = ProfilerWindow(trace_dir=str(tmp_path / "xla_trace"), start_step=0, stop_step=2)
    w.advance(0)
    w.close()
    assert seen == {"path": str(tmp_path / "xla_trace"), "host": 1, "python": 0}
