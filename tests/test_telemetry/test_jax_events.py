"""jax.monitoring tests: compile counting, the compile spans (named, on the
tracer's clock and the calling thread), the recompile-after-warmup watchdog
(forced with a shape change), listeners that do not outlive telemetry, and HBM
gauges on CPU."""

import threading
import time
import warnings

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.telemetry import Telemetry
from sheeprl_tpu.telemetry import tracer as tracer_mod
from sheeprl_tpu.telemetry.jax_events import JaxEventMonitor
from sheeprl_tpu.telemetry.tracer import Tracer

pytestmark = pytest.mark.telemetry


def _fresh_jit():
    # A distinct closure per call: every test gets its own compile.
    def f(x):
        return (x * 3 + 1).sum()

    return jax.jit(f)


def test_compile_events_counted_and_spanned():
    t = Tracer()
    prev = tracer_mod.set_current(t)
    monitor = JaxEventMonitor(warmup_iters=100)
    monitor.attach()
    try:
        _fresh_jit()(jnp.ones((8,)))
        assert monitor.counters.get("compiles", 0) >= 1
        assert monitor.counters.get("compile_secs", 0) > 0
        assert monitor.counters.get("traces", 0) >= 1
        assert any(s.name == "compile/backend" and s.category == "compile" for s in t.spans())
    finally:
        monitor.detach()
        tracer_mod.set_current(prev)


def test_recompile_after_warmup_warns_and_counts():
    monitor = JaxEventMonitor(warmup_iters=2)
    monitor.attach()
    try:
        f = _fresh_jit()
        f(jnp.ones((4,)))  # warmup compile
        monitor.advance()
        monitor.advance()  # warmup watermark armed at iteration 2
        monitor.advance()  # past warmup, no new compiles: silent
        f(jnp.ones((6,)))  # shape change -> retrace -> fresh backend compile
        with pytest.warns(RuntimeWarning, match="recompile"):
            monitor.advance()
        assert monitor.counters.get("recompiles_after_warmup", 0) >= 1
    finally:
        monitor.detach()


def test_no_warning_during_warmup():
    monitor = JaxEventMonitor(warmup_iters=10)
    monitor.attach()
    try:
        f = _fresh_jit()
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            f(jnp.ones((3,)))
            monitor.advance()
            f(jnp.ones((5,)))  # recompiles, but still inside warmup
            monitor.advance()
    finally:
        monitor.detach()


def test_detached_monitor_stops_counting():
    monitor = JaxEventMonitor()
    monitor.attach()
    monitor.detach()
    before = dict(monitor.counters)
    _fresh_jit()(jnp.ones((7,)))
    assert monitor.counters == before


def test_memory_gauges_cpu_safe():
    # CPU devices expose no memory_stats (or None): must degrade to {} keys
    # being absent rather than raising.
    gauges = JaxEventMonitor.memory_gauges(jax.devices()[0])
    assert isinstance(gauges, dict)


def test_compile_events_reach_the_default_registry():
    # The bridge to MetricsRegistry: a compile observed by the monitor also
    # increments the process-wide `jax/*` counters, so Prometheus scrapes
    # (/metrics) see XLA activity without the tracer mirroring step.
    from sheeprl_tpu.telemetry.registry import default_registry

    reg = default_registry()
    before = reg.counter("jax/compiles").value
    monitor = JaxEventMonitor(warmup_iters=100)
    monitor.attach()
    try:
        _fresh_jit()(jnp.ones((9,)))
    finally:
        monitor.detach()
    assert reg.counter("jax/compiles").value >= before + 1
    assert reg.counter("jax/compile_secs").value > 0
    # Prometheus rendering sanitizes the slash.
    assert "jax_compiles_total" in reg.prometheus_text()


def _listeners():
    from jax._src import monitoring

    return monitoring.get_event_time_span_listeners(), monitoring.get_event_listeners()


def test_compile_spans_name_their_function_on_the_callers_clock_and_thread(tmp_path):
    def spanned_once(x):
        return (x * 5 - 2).sum()

    x = jnp.ones((11,))
    tele = Telemetry(enabled=True, flight_enabled=False).open(str(tmp_path))
    try:
        before = time.perf_counter()
        jax.jit(spanned_once)(x)
        after = time.perf_counter()
        spans = [s for s in tracer_mod.current().spans() if "spanned_once" in (s.args or {}).get("fun", "")]
    finally:
        tele.close()
    assert sorted(s.name for s in spans) == ["compile/backend", "compile/lower", "compile/trace"]
    for span in spans:
        # JAX's wall-clock stamps, carried onto perf_counter through the tracer's epochs
        assert before - 1e-4 <= span.start_s <= span.start_s + span.duration_s <= after + 1e-4, span.name
        assert span.category == "compile" and span.thread == threading.current_thread().name
    backend = next(s for s in spans if s.name == "compile/backend")
    assert backend.args["seen"] == 0 and backend.args["cache"] in ("hit", "miss", "off")


def test_a_second_compile_at_a_new_shape_is_seen_and_counted(tmp_path):
    def seen_twice(x):
        return x.sum()

    three, four = jnp.ones((3,)), jnp.ones((4,))
    f = jax.jit(seen_twice)
    tele = Telemetry(enabled=True, flight_enabled=False).open(str(tmp_path))
    try:
        f(three)
        f(three)  # cached: no compile
        f(four)
        backend = [s for s in tracer_mod.current().spans() if s.name == "compile/backend"]
        counters = tele.counters()
    finally:
        tele.close()
    assert [(s.args["fun"], s.args["seen"]) for s in backend] == [("jit(seen_twice)", 0), ("jit(seen_twice)", 1)]
    assert counters["compile/recompiles"] == 1 and counters["compiles"] == 2


def test_disabled_telemetry_records_no_span_and_leaves_no_listener(tmp_path):
    before = _listeners()
    tele = Telemetry(enabled=False, flight_enabled=False)
    tele.begin_setup(time.perf_counter())
    tele.open(str(tmp_path))
    assert _listeners() == before
    _fresh_jit()(jnp.ones((13,)))
    assert tracer_mod.current().spans() == [] and tele.counters() == {}
    tele.close()
    assert _listeners() == before
    # an enabled one listens from the hand-over of set-up (or from open) until close, opened or not
    on = Telemetry(enabled=True, flight_enabled=False)
    on.begin_setup(time.perf_counter())
    assert _listeners() != before
    on.close()
    assert _listeners() == before
    on = Telemetry(enabled=True, flight_enabled=False).open(str(tmp_path / "on"))
    assert _listeners() != before
    on.close()
    assert _listeners() == before
