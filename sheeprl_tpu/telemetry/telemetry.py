"""The `Telemetry` facade: one object per run, hung off the Runtime.

Composition of the observability subsystem's parts:

- a :class:`~sheeprl_tpu.telemetry.tracer.Tracer` (span ring buffer),
  installed as the process-wide current tracer while the run is open so
  low-level emitters (utils/timer, core/rollout, data/infeed) need no
  plumbing;
- :class:`~sheeprl_tpu.telemetry.jax_events.JaxEventMonitor` compile
  spans (``compile/trace``, ``compile/lower``, ``compile/backend``) and
  compile/retrace/cache counters plus HBM gauges;
- a :class:`~sheeprl_tpu.telemetry.profiling.ProfilerWindow` for the
  config-driven XLA trace window and live profiler server;
- :class:`~sheeprl_tpu.telemetry.step_timer.StepTimer` instances for the
  train loops (always functional — they carry the coalesced metric fetch —
  whether or not telemetry is enabled).

Set-up is a span tree: the root ``setup`` runs from the entry point's first
line (``cli.run``) to the loop's first :meth:`Telemetry.advance`. The entry
point builds this object first and hands set-up over
(:meth:`Telemetry.begin_setup`: the root's start, ``setup/config`` timed
before; from then on set-up's compiles are recorded), times ``setup/runtime``
with :meth:`Telemetry.span`, and the mains place ``setup/envs``,
``setup/agent``, ``setup/replay`` and ``setup/player`` around their own
set-up code.

Exports (rank zero, on :meth:`close`): ``trace.json`` (Chrome trace-event
JSON) and ``telemetry.jsonl`` (a meta line at open, one counters line per
log interval, every span + final counters at close) in the run's log dir.

Every recording path short-circuits when disabled; a disabled Telemetry is
safe to thread through any loop.
"""

from __future__ import annotations

import json
import os
import platform
import socket
import subprocess
import time
import warnings
from typing import Any, Dict, Optional

from sheeprl_tpu.telemetry import flight as flight_mod
from sheeprl_tpu.telemetry import trace_context
from sheeprl_tpu.telemetry import tracer as tracer_mod
from sheeprl_tpu.telemetry.jax_events import JaxEventMonitor
from sheeprl_tpu.telemetry.profiling import ProfilerWindow
from sheeprl_tpu.telemetry.step_timer import StepTimer
from sheeprl_tpu.telemetry.tracer import Tracer

CHROME_TRACE_FILENAME = "trace.json"
JSONL_FILENAME = "telemetry.jsonl"
FLIGHT_DIRNAME = "flight"


def git_stamp(root: Optional[str] = None) -> Dict[str, Any]:
    """``{"sha", "dirty"}`` of the checkout at ``root`` (cwd default); both
    degrade gracefully (sha ``"unknown"``) outside a git work tree."""
    cwd = root or os.getcwd()
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=cwd, capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        sha = "unknown"
    dirty = False
    try:
        status = subprocess.run(
            ["git", "status", "--porcelain"],
            cwd=cwd, capture_output=True, text=True, timeout=10,
        )
        dirty = status.returncode == 0 and bool(status.stdout.strip())
    except Exception:
        pass
    return {"sha": sha, "dirty": dirty}


def host_fingerprint() -> Dict[str, Any]:
    """Hardware/host identity coarse enough to be stable across runs on the
    same box, fine enough to separate baselines from different machines."""
    return {
        "hostname": socket.gethostname(),
        "machine": platform.machine(),
        "system": platform.system(),
        "cpu_count": os.cpu_count() or 0,
        "python": platform.python_version(),
    }


class Telemetry:
    def __init__(
        self,
        enabled: bool = False,
        buffer_capacity: int = 65536,
        warmup_iters: int = 3,
        warn_on_recompile: bool = True,
        chrome_trace: bool = True,
        jsonl: bool = True,
        profiler_start_step: int = -1,
        profiler_stop_step: int = -1,
        profiler_trace_dir: Optional[str] = None,
        profiler_port: Optional[int] = None,
        metrics_port: Optional[int] = None,
        flight_enabled: bool = True,
        flight_capacity: int = 4096,
        flight_spill_interval_s: float = 5.0,
        flight_min_dump_interval_s: float = 30.0,
        perf_enabled: Optional[bool] = None,
        perf_probe: bool = True,
        perf_peak_flops: Optional[float] = None,
        perf_peak_hbm_gbps: Optional[float] = None,
        perf_per_shard: bool = True,
        federate_metrics: bool = True,
    ) -> None:
        self.enabled = bool(enabled)
        self.chrome_trace = bool(chrome_trace)
        self.jsonl = bool(jsonl)
        self.metrics_port = int(metrics_port) if metrics_port is not None else None
        self.federate_metrics = bool(federate_metrics)
        # Flight recorder knobs: deliberately independent of `enabled` — the
        # crash ring is always-on unless explicitly switched off.
        self.flight_enabled = bool(flight_enabled)
        self.flight_capacity = int(flight_capacity)
        self.flight_spill_interval_s = float(flight_spill_interval_s)
        self.flight_min_dump_interval_s = float(flight_min_dump_interval_s)
        self._tracer = Tracer(capacity=buffer_capacity, enabled=self.enabled)
        self._monitor = JaxEventMonitor(
            warmup_iters=warmup_iters, warn_on_recompile=warn_on_recompile, tracer=self._tracer
        )
        self._profiler = ProfilerWindow(
            trace_dir=profiler_trace_dir,
            start_step=profiler_start_step,
            stop_step=profiler_stop_step,
            port=profiler_port,
        )
        # Goodput accounting follows `enabled` unless the perf group pins it.
        from sheeprl_tpu.telemetry.perf import PerfAccountant

        self._perf = PerfAccountant(
            enabled=self.enabled if perf_enabled is None else bool(perf_enabled),
            probe=bool(perf_probe),
            peak_flops=perf_peak_flops,
            peak_hbm_gbps=perf_peak_hbm_gbps,
            per_shard=bool(perf_per_shard),
        )
        self._step_timers: Dict[str, StepTimer] = {}
        self._log_dir: Optional[str] = None
        self._rank_zero = True
        self._device: Any = None
        self._opened = False
        self._previous_tracer: Optional[Tracer] = None
        self._exporter: Any = None
        # Per-interval rate state (log_counters): previous snapshot + time.
        self._prev_counters: Optional[Dict[str, float]] = None
        self._prev_counters_t = 0.0
        # Trace + flight state (always-on layer, managed by open/close).
        self._tracing_open = False
        self._trace_root: Optional[trace_context.TraceContext] = None
        self._trace_token: Any = None
        self._iteration: Optional[tuple] = None  # the open loop/iteration: (ctx, start, step, gradient steps before)
        self._setup_start: Optional[float] = None  # the `setup` root's start, until advance() closes it
        self._carrier_prev: Optional[tuple] = None
        self._flight: Optional[flight_mod.FlightRecorder] = None
        self._flight_tracer: Optional[Tracer] = None
        # Federated metric source over sibling flight spills (mesh_obs).
        self._federation: Any = None

    # ------------------------------------------------------------- config
    @classmethod
    def from_config(cls, cfg: Any) -> "Telemetry":
        """Build from the composed run config's ``telemetry`` group (absent
        or empty group -> disabled)."""
        tele = cfg.get("telemetry") if hasattr(cfg, "get") else None
        if not tele:
            return cls(enabled=False)
        prof = tele.get("profiler") or {}
        fl = tele.get("flight") or {}
        perf = tele.get("perf") or {}
        perf_enabled = perf.get("enabled")
        return cls(
            perf_enabled=None if perf_enabled is None else bool(perf_enabled),
            perf_probe=bool(perf.get("probe", True)),
            perf_peak_flops=perf.get("peak_flops"),
            perf_peak_hbm_gbps=perf.get("peak_hbm_gbps"),
            perf_per_shard=bool(perf.get("per_shard", True)),
            federate_metrics=bool(tele.get("federate_metrics", True)),
            flight_enabled=bool(fl.get("enabled", True)),
            flight_capacity=int(fl.get("capacity", 4096)),
            flight_spill_interval_s=float(fl.get("spill_interval_s", 5.0)),
            flight_min_dump_interval_s=float(fl.get("min_dump_interval_s", 30.0)),
            enabled=bool(tele.get("enabled", False)),
            buffer_capacity=int(tele.get("buffer_capacity", 65536)),
            warmup_iters=int(tele.get("warmup_iters", 3)),
            warn_on_recompile=bool(tele.get("warn_on_recompile", True)),
            chrome_trace=bool(tele.get("chrome_trace", True)),
            jsonl=bool(tele.get("jsonl", True)),
            profiler_start_step=int(prof.get("start_step", -1)),
            profiler_stop_step=int(prof.get("stop_step", -1)),
            profiler_trace_dir=prof.get("trace_dir"),
            profiler_port=prof.get("port"),
            metrics_port=tele.get("metrics_port"),
        )

    @classmethod
    def noop(cls) -> "Telemetry":
        return cls(enabled=False)

    # ---------------------------------------------------------- lifecycle
    def begin_setup(self, started: float, phases: tuple = ()) -> None:
        """The entry point hands set-up over: the ``setup`` root starts at
        ``started`` and ``phases`` are ``(name, start, end)`` timed before this
        object existed (``perf_counter`` seconds). From here on the run's
        compiles are recorded, so those of set-up before :meth:`open` are too."""
        if not self.enabled:
            return
        for name, start, end in phases:
            self._tracer.add_span(name, "setup", start, end - start)
        self._setup_start = started
        self._monitor.attach()

    def open(self, log_dir: Optional[str], rank_zero: bool = True, device: Any = None) -> "Telemetry":
        """Bind the run's log dir and go live: install the tracer as the
        process-wide current one, attach the jax.monitoring counters, start
        the profiler server if configured. Idempotent; returns self."""
        self._log_dir = log_dir
        self._rank_zero = bool(rank_zero)
        self._device = device
        self._open_tracing(log_dir)
        if not self.enabled or self._opened:
            return self
        self._opened = True
        self._previous_tracer = tracer_mod.set_current(self._tracer)
        self._monitor.attach()
        if self._profiler.trace_dir is None and log_dir is not None:
            self._profiler.trace_dir = os.path.join(log_dir, "xla_trace")
        self._profiler.start_server()
        if self.metrics_port is not None and self._rank_zero:
            from sheeprl_tpu.telemetry.registry import MetricsExporter, default_registry

            def _metric_sources() -> list:
                # Resolved per scrape: the default registry is re-fetched (it
                # may be reset) and the federated spill source — created by
                # _open_tracing, possibly after the exporter — appears as
                # soon as it exists. This is the ONE merged endpoint covering
                # the trainer plus every spilling sibling process.
                sources: list = [default_registry()]
                if self._federation is not None:
                    sources.append(self._federation)
                return sources

            try:
                self._exporter = MetricsExporter(self.metrics_port, _metric_sources)
            except OSError as err:
                warnings.warn(f"telemetry.metrics_port={self.metrics_port} unavailable ({err}); exporter disabled")
        if self._jsonl_path() is not None:
            import jax

            self._append_jsonl(
                {
                    "type": "meta",
                    "time": time.time(),
                    "backend": jax.default_backend(),
                    "process_index": jax.process_index(),
                    "profiler_window": [self._profiler.start_step, self._profiler.stop_step],
                    "trace_id": self._trace_root.trace_id if self._trace_root else None,
                    "pid": os.getpid(),
                    # span timestamps (ts_us) count from these two readings of one instant
                    "perf_epoch_s": self._tracer.perf_epoch_s,
                    "wall_epoch_s": self._tracer.wall_epoch_s,
                    # Provenance stamps: which code on which hardware produced
                    # this run. Stamp the PACKAGE checkout, not the run cwd:
                    # runs launch from throwaway dirs outside the repo.
                    "git": git_stamp(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
                    "host": host_fingerprint(),
                    "device": getattr(jax.devices()[0], "device_kind", ""),
                    "device_count": jax.device_count(),
                    "local_device_count": jax.local_device_count(),
                },
                mode="w",
            )
        return self

    def _open_tracing(self, log_dir: Optional[str]) -> None:
        """The always-on layer: mint (or adopt) the run's root trace context,
        publish the env-var carrier BEFORE env worker processes fork, and
        install the flight recorder. Runs whether or not telemetry is
        enabled — crash forensics must not depend on someone having turned
        the profiler on."""
        if self._tracing_open:
            return
        self._tracing_open = True
        # A valid carrier in the environment means this process is itself a
        # child of a traced run (a restarted trainer, a spawned peer): join
        # that trace instead of starting a new one.
        self._trace_root = trace_context.mint(trace_context.extract_env_carrier())
        self._trace_token = trace_context.set_current(self._trace_root)
        trace_dir = os.path.join(log_dir, FLIGHT_DIRNAME) if log_dir else None
        self._carrier_prev = (
            os.environ.get(trace_context.TRACEPARENT_ENV),
            os.environ.get(trace_context.TRACE_DIR_ENV),
        )
        trace_context.inject_env_carrier(self._trace_root, trace_dir)
        if self.flight_enabled:
            self._flight = flight_mod.FlightRecorder(
                capacity=self.flight_capacity,
                trace_dir=trace_dir,
                spill_interval_s=self.flight_spill_interval_s,
                min_dump_interval_s=self.flight_min_dump_interval_s,
                run_info={"role": "trainer"},
            )
            flight_mod.install(self._flight)
            if self.federate_metrics and trace_dir is not None:
                from sheeprl_tpu.telemetry import mesh_obs

                self._federation = mesh_obs.SpillMetricsSource(
                    trace_dir, exclude_pids=(os.getpid(),)
                )
            if not self.enabled:
                # Telemetry off still means a populated crash ring: give the
                # process a live tracer feeding the flight sink.
                self._flight_tracer = flight_mod.ensure_live_tracer(
                    capacity=min(self.flight_capacity, 8192)
                )

    def _close_tracing(self) -> None:
        if not self._tracing_open:
            return
        self._tracing_open = False
        if self._flight is not None:
            flight_mod.uninstall(self._flight)
            self._flight = None
        self._federation = None
        if self._flight_tracer is not None:
            if tracer_mod.current() is self._flight_tracer:
                tracer_mod.set_current(None)
            self._flight_tracer = None
        if self._carrier_prev is not None:
            for key, prev in zip(
                (trace_context.TRACEPARENT_ENV, trace_context.TRACE_DIR_ENV), self._carrier_prev
            ):
                if prev is None:
                    os.environ.pop(key, None)
                else:
                    os.environ[key] = prev
            self._carrier_prev = None
        if self._trace_token is not None:
            try:
                trace_context.reset(self._trace_token)
            except ValueError:  # closed from a different thread than open
                trace_context.set_current(None)
            self._trace_token = None
        self._trace_root = None

    def close(self) -> None:
        """Stop profiling, detach counters, export trace.json/telemetry.jsonl
        (rank zero), and restore the previously-installed tracer."""
        self._end_iteration(time.perf_counter())
        for st in self._step_timers.values():
            st.flush()
        self._monitor.detach()
        self._setup_start = None
        if self._opened:
            if self._exporter is not None:
                self._exporter.close()
                self._exporter = None
            self._profiler.close()
            self._export()
            tracer_mod.set_current(self._previous_tracer)
            self._previous_tracer = None
            self._opened = False
        self._close_tracing()

    # ------------------------------------------------------------ hot path
    def span(self, name: str, category: str = "host", **args: Any):
        return self._tracer.span(name, category, **args)

    def fetch(self, tree: Any, label: str = "fetch") -> Any:
        """``jax.device_get`` with the transfer accounted: a fetch span plus
        the device->host byte counter. This is the audited home for
        structurally-necessary per-step syncs (actions feeding env.step)."""
        import jax

        start = time.perf_counter()
        out = jax.device_get(tree)
        if self.enabled:
            elapsed = time.perf_counter() - start
            nbytes = tracer_mod.tree_bytes(out)
            self._tracer.add_span(f"fetch/{label}", "fetch", start, elapsed, {"bytes": nbytes})
            self._tracer.count("device_get_calls", 1)
            self._tracer.count("device_get_bytes", nbytes)
        return out

    @property
    def perf(self) -> Any:
        """The run's goodput accountant (a safe no-op when disabled):
        ``perf.note(key, fn, args)`` before each jit dispatch,
        ``with perf.infeed():`` around env interaction / data infeed."""
        return self._perf

    def step_timer(self, name: str = "train", timer_key: Optional[str] = None) -> StepTimer:
        st = self._step_timers.get(name)
        if st is None:
            st = StepTimer(name=name, timer_key=timer_key)
            self._step_timers[name] = st
        return st

    def advance(self, step: int) -> None:
        """Once per train iteration: drives the profiler window and the
        recompile-after-warmup watchdog, closes the previous ``loop/iteration``
        span and rolls the active trace context to a fresh per-iteration child
        of the run root (so every span this iteration emits — dispatch, fetch,
        ship, env restarts — parents to one iteration span)."""
        if self._trace_root is not None:
            now = time.perf_counter()
            if self._setup_start is not None:  # the first iteration ends set-up
                self._tracer.add_span("setup", "setup", self._setup_start, now - self._setup_start)
                self._setup_start = None
            self._end_iteration(now)
            ctx = self._trace_root.child()
            trace_context.set_current(ctx)
            self._iteration = (ctx, now, int(step), self._gradient_steps())
        if not self.enabled:
            return
        self._profiler.advance(step)
        self._monitor.advance()

    def _gradient_steps(self) -> int:
        train = self._step_timers.get("train")
        return train.gradient_steps if train is not None else 0

    def _end_iteration(self, now: float) -> None:
        """``loop/iteration`` is the loop's request: it runs from one
        :meth:`advance` to the next (or to :meth:`close`), every span the
        iteration emits carries its trace context, and its args say how many
        gradient steps it dispatched."""
        if self._iteration is None:
            return
        ctx, start, step, steps_before = self._iteration
        self._iteration = None
        tracer_mod.current().add_span(
            "loop/iteration",
            "loop",
            start,
            now - start,
            {"step": step, "gradient_steps": self._gradient_steps() - steps_before},
            ctx=ctx,
        )

    # ------------------------------------------------------------ counters
    def counters(self) -> Dict[str, float]:
        merged = self._tracer.counters()
        merged.update(self._monitor.counters)
        if self._device is not None:
            merged.update(self._monitor.memory_gauges(self._device))
        if self._tracer.dropped:
            merged["spans_dropped"] = float(self._tracer.dropped)
        return merged

    def log_counters(self, logger: Any, step: int) -> Dict[str, float]:
        """Per-log-interval export: every counter through the experiment
        logger (TensorBoard/MLflow `log` surface) and one counters line in
        telemetry.jsonl — plus host-computed per-interval ``*_per_s`` rates
        for the monotonic counters, so throughput is readable live (the
        ``tail`` inspector, dashboards) without differencing the JSONL
        after the fact."""
        if not self.enabled:
            return {}
        # Publish goodput first: the gauges go through the tracer, so the
        # counters snapshot below (and hence this interval's JSONL record,
        # logger export, and /metrics mirror) carries perf/mfu and friends.
        self._perf.publish(self._step_timers.get("train"), self._tracer)
        counters = self.counters()
        now = time.perf_counter()
        rates = self._interval_rates(counters, now)
        if logger is not None:
            for name in sorted(counters):
                logger.log(f"Telemetry/{name}", counters[name], step)
            for name in sorted(rates):
                logger.log(f"Telemetry/{name}", rates[name], step)
            st = self._step_timers.get("train")
            if st is not None and st.steps:
                logger.log("Telemetry/train_step_ms", st.seconds_per_step * 1e3, step)
        if self._jsonl_path() is not None:
            record: Dict[str, Any] = {"type": "counters", "step": step, "time": time.time(), "values": counters}
            if rates:
                record["rates"] = rates
            self._append_jsonl(record)
        # Mirror the interval snapshot into the process metrics registry so a
        # /metrics scrape (serve server or the metrics_port exporter) reports
        # the same values the logger and the JSONL do.
        from sheeprl_tpu.telemetry.registry import default_registry

        registry = default_registry()
        registry.set_gauges(counters)
        registry.set_gauges(rates)
        return counters

    def _interval_rates(self, counters: Dict[str, float], now: float) -> Dict[str, float]:
        """``(cur - prev) / dt`` for every monotonic counter (gauges — HBM
        levels, health probes, queue depths — are excluded by name via the
        tracer's gauge registry; monitor memory gauges by their prefix)."""
        rates: Dict[str, float] = {}
        prev, prev_t = self._prev_counters, self._prev_counters_t
        self._prev_counters = dict(counters)
        self._prev_counters_t = now
        if prev is None:
            return rates
        dt = now - prev_t
        if dt <= 0.0:
            return rates
        gauges = self._tracer.gauge_names()
        for name, cur in counters.items():
            if name in gauges or name.startswith("hbm_"):
                continue
            last = prev.get(name)
            if last is None:
                continue
            delta = float(cur) - float(last)
            if delta < 0.0:
                continue
            rates[name + "_per_s"] = delta / dt
        return rates

    def record_event(self, record: Dict[str, Any]) -> None:
        """Append a structured event record (e.g. a health sentinel event)
        to telemetry.jsonl (no-op when disabled or not rank zero) and to the
        flight ring (always, so trips see recent health events)."""
        flight_mod.record_event(dict(record))
        self._append_jsonl(dict(record))

    # ------------------------------------------------------------- tracing
    @property
    def trace_root(self) -> Optional[trace_context.TraceContext]:
        """The run's root trace context (None before open)."""
        return self._trace_root

    @property
    def flight(self) -> Optional[flight_mod.FlightRecorder]:
        return self._flight

    def set_run_info(self, **info: Any) -> None:
        """Annotate this process in flight dumps (algo name, rank, role)."""
        if self._flight is not None:
            self._flight.run_info.update(info)

    def set_mesh(self, mesh: Any) -> None:
        """Attach the run's device mesh: arms the accountant's per-shard
        goodput split, stamps the axis sizes into flight ``run_info``, and
        appends a serialized ``{"type": "mesh"}`` topology record to
        telemetry.jsonl for the ``telemetry mesh`` inspector. Call once the
        mesh exists (after :meth:`open`); safe no-op on ``mesh=None``."""
        if mesh is None:
            return
        self._perf.set_mesh(mesh)
        try:
            from sheeprl_tpu.telemetry import mesh_obs

            topo = mesh_obs.mesh_topology(mesh)
        except Exception:  # noqa: BLE001 - inspector data, never run-fatal
            return
        self.set_run_info(mesh=topo["axis_sizes"])
        if self.enabled:
            self.record_event({"type": "mesh", "time": time.time(), "topology": topo})

    def record_param_layouts(self, tree: Any, max_leaves: int = 24) -> None:
        """Serialize the sharding layout of up to ``max_leaves`` param leaves
        into telemetry.jsonl (``{"type": "param_layouts"}``) — the data the
        ``telemetry mesh`` inspector renders as per-param ASCII grids."""
        if not self.enabled:
            return
        try:
            from sheeprl_tpu.telemetry import mesh_obs

            layouts = mesh_obs.param_layouts(tree, max_leaves=max_leaves)
        except Exception:  # noqa: BLE001
            return
        if layouts:
            self.record_event({"type": "param_layouts", "time": time.time(), "layouts": layouts})

    # ------------------------------------------------------------- export
    def _jsonl_path(self) -> Optional[str]:
        if self.enabled and self.jsonl and self._rank_zero and self._log_dir:
            return os.path.join(self._log_dir, JSONL_FILENAME)
        return None

    def _append_jsonl(self, record: Dict[str, Any], mode: str = "a") -> None:
        path = self._jsonl_path()
        if path is None:
            return
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, mode) as fp:
            fp.write(json.dumps(record) + "\n")

    def _export(self) -> None:
        if not (self._rank_zero and self._log_dir):
            return
        if self.chrome_trace:
            self._tracer.export_chrome(os.path.join(self._log_dir, CHROME_TRACE_FILENAME))
        path = self._jsonl_path()
        if path is not None:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "a") as fp:
                for line in self._tracer.iter_jsonl():
                    fp.write(line + "\n")
                fp.write(
                    json.dumps({"type": "counters", "step": -1, "values": self.counters()}) + "\n"
                )
