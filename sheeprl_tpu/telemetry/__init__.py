"""sheeprl_tpu.telemetry: first-party observability for every train loop.

Parts (see each module's docstring for the design):

- :mod:`~sheeprl_tpu.telemetry.tracer` — span ring buffer, Chrome-trace /
  JSONL exporters, the process-wide current tracer;
- :mod:`~sheeprl_tpu.telemetry.step_timer` — async-dispatch-aware step
  timing with the coalesced per-interval metric fetch (the productized
  donated-chain pattern: N chained dispatches bounded by one fetch);
- :mod:`~sheeprl_tpu.telemetry.histogram` — streaming geometric-bucket
  latency histogram (p50/p95/p99) used by StepTimer and the serving engine;
- :mod:`~sheeprl_tpu.telemetry.jax_events` — compile spans and
  compile/retrace/cache counters via jax.monitoring, HBM gauges,
  recompile-after-warmup watchdog;
- :mod:`~sheeprl_tpu.telemetry.profiling` — config-driven jax.profiler
  step-window traces and live profiler server;
- :mod:`~sheeprl_tpu.telemetry.registry` — the unified counters/gauges/
  histograms :class:`MetricsRegistry` with Prometheus text exposition and
  the ``GET /metrics`` exporter;
- :mod:`~sheeprl_tpu.telemetry.health` — in-jit :func:`health_probe`
  reducers and the host-side :class:`HealthMonitor` sentinels
  (warn|preempt|abort, wired into the resilience trip path);
- :mod:`~sheeprl_tpu.telemetry.trace_context` — W3C-traceparent-style
  :class:`TraceContext` (trace_id/span_id/parent_id): contextvar
  propagation in-process, an env-var carrier across process boundaries,
  explicit ``ctx=`` handoff across threads;
- :mod:`~sheeprl_tpu.telemetry.flight` — the always-on
  :class:`FlightRecorder` crash ring (last N spans/events per process,
  spilled per-process, merged into a Perfetto-loadable ``flight_*.json``
  on watchdog/health/preemption/overload/crash trips) and the
  cross-process trace aggregator;
- :mod:`~sheeprl_tpu.telemetry.perf` — roofline goodput accounting: XLA
  ``cost_analysis`` harvest from the donated jits, per-backend peak table
  (CPU fallback: calibrated micro-kernel probe), and the
  :class:`PerfAccountant` that publishes ``perf/mfu``,
  ``perf/hbm_bw_util`` and the compute/infeed/host step-time breakdown;
- :mod:`~sheeprl_tpu.telemetry.telemetry` — the :class:`Telemetry` facade
  the Runtime carries and the algorithms thread through their loops.

``python -m sheeprl_tpu.telemetry tail <logdir>`` renders a live run's
current health and throughput from its ``telemetry.jsonl``;
``python -m sheeprl_tpu.telemetry flight <logdir>`` lists and inspects
flight dumps (``--merge`` writes the cross-process aggregated trace).
"""

from sheeprl_tpu.telemetry import flight, trace_context, tracer
from sheeprl_tpu.telemetry.flight import FlightRecorder, aggregate_traces
from sheeprl_tpu.telemetry.health import HealthEvent, HealthMonitor, health_probe, probes_enabled
from sheeprl_tpu.telemetry.histogram import Histogram, geometric_bounds
from sheeprl_tpu.telemetry.jax_events import JaxEventMonitor
from sheeprl_tpu.telemetry.perf import PerfAccountant, jit_cost, resolve_peaks
from sheeprl_tpu.telemetry.profiling import ProfilerWindow
from sheeprl_tpu.telemetry.registry import Counter, Gauge, MetricsExporter, MetricsRegistry, default_registry
from sheeprl_tpu.telemetry.step_timer import StepTimer
from sheeprl_tpu.telemetry.telemetry import CHROME_TRACE_FILENAME, JSONL_FILENAME, Telemetry
from sheeprl_tpu.telemetry.trace_context import TraceContext
from sheeprl_tpu.telemetry.tracer import Span, Tracer

__all__ = [
    "CHROME_TRACE_FILENAME",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "HealthEvent",
    "HealthMonitor",
    "Histogram",
    "JSONL_FILENAME",
    "JaxEventMonitor",
    "MetricsExporter",
    "MetricsRegistry",
    "TraceContext",
    "PerfAccountant",
    "aggregate_traces",
    "default_registry",
    "flight",
    "geometric_bounds",
    "health_probe",
    "jit_cost",
    "probes_enabled",
    "ProfilerWindow",
    "resolve_peaks",
    "Span",
    "StepTimer",
    "Telemetry",
    "trace_context",
    "Tracer",
    "tracer",
]
