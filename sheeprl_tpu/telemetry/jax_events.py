"""Compile/retrace/transfer counters wired to ``jax.monitoring``.

JAX instruments its own compiler pipeline with named monitoring events;
registering listeners is the zero-overhead way to count compiles — no
wrapping of ``jax.jit``, no log scraping. The events this module consumes
(names as of jax 0.4.x):

- ``/jax/core/compile/backend_compile_duration`` — one per real XLA
  backend compile (the expensive thing; a retrace that hits the executable
  cache does NOT fire it);
- ``/jax/core/compile/jaxpr_trace_duration`` — one per trace of a jitted
  function (fires on every retrace, cached or not);
- ``/jax/compilation_cache/cache_hits`` / ``cache_misses`` — persistent
  compile-cache traffic.

``jax.monitoring`` has no public unregister, and test suites construct many
telemetry stacks per process, so ONE module-level listener pair is
registered lazily and fans out to the currently-attached monitors — attach/
detach is list membership, not listener churn.

Retrace detection: hand-run profiling has to exclude the "hidden recompile"
(the second call after compilation recompiles once for the donated-layout
change). :meth:`JaxEventMonitor.advance` is called once per train
iteration; compiles observed after ``warmup_iters`` iterations are counted
as ``recompiles_after_warmup`` and warned about — the silent
recompile-storm trap made loud.
"""

from __future__ import annotations

import time
import warnings
from typing import Any, Dict, List, Optional

from sheeprl_tpu.telemetry import tracer as tracer_mod

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_COUNT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile_cache_hits",
    "/jax/compilation_cache/cache_misses": "compile_cache_misses",
}
#: Substrings that mark a monitoring event as a device transfer. jax 0.4.37
#: emits no transfer events yet (only the compile pipeline is instrumented),
#: but the name family is reserved upstream — matching by substring means
#: the runtime transfer ledger (core/mesh.py accounted puts) gains the
#: runtime's own numbers the day the installed jax starts emitting them,
#: with no code change here.
_TRANSFER_NAME_PARTS = ("transfer", "device_put", "copy_to_host")

_ACTIVE: List["JaxEventMonitor"] = []
_LISTENERS_INSTALLED = False


def _registry_count(name: str, amount: float = 1.0) -> None:
    """Mirror a compiler event into the process default MetricsRegistry.

    The ``jax/`` prefix keeps these distinct from the *gauge* mirrors that
    ``Telemetry.log_counters`` derives from monitor counters (``compiles``
    etc.) — a registry name can hold one kind only. This is the bridge that
    puts compile/retrace/cache traffic on ``/metrics`` and the telemetry
    tail for EVERY process with the listeners installed (serve included),
    monitor attached or not.
    """
    try:
        from sheeprl_tpu.telemetry.registry import default_registry

        default_registry().counter(name).inc(amount)
    except Exception:  # noqa: BLE001 - metrics must never break a compile
        pass


def _transfer_key(event: str) -> Optional[str]:
    """Counter stem for a transfer-family monitoring event, else None."""
    lowered = event.lower()
    if not any(part in lowered for part in _TRANSFER_NAME_PARTS):
        return None
    stem = lowered.rsplit("/", 1)[-1] or "transfer"
    return f"transfer_event_{stem}"


def _on_event(event: str, **kwargs: Any) -> None:
    key = _CACHE_COUNT_EVENTS.get(event)
    if key is None:
        tkey = _transfer_key(event)
        if tkey is None:
            return
        _registry_count(f"jax/{tkey}")
        for monitor in list(_ACTIVE):
            monitor.counters[tkey] = monitor.counters.get(tkey, 0.0) + 1.0
        return
    _registry_count(f"jax/{key}")
    for monitor in list(_ACTIVE):
        monitor.counters[key] = monitor.counters.get(key, 0.0) + 1.0


def _on_event_duration(event: str, duration_secs: float, **kwargs: Any) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        _registry_count("jax/compiles")
        _registry_count("jax/compile_secs", float(duration_secs))
        for monitor in list(_ACTIVE):
            monitor._record_compile(duration_secs)
    elif event == _TRACE_EVENT:
        _registry_count("jax/traces")
        _registry_count("jax/trace_secs", float(duration_secs))
        for monitor in list(_ACTIVE):
            monitor.counters["traces"] = monitor.counters.get("traces", 0.0) + 1.0
            monitor.counters["trace_secs"] = monitor.counters.get("trace_secs", 0.0) + float(
                duration_secs
            )
    else:
        tkey = _transfer_key(event)
        if tkey is not None:
            _registry_count(f"jax/{tkey}_calls")
            _registry_count(f"jax/{tkey}_secs", float(duration_secs))
            for monitor in list(_ACTIVE):
                monitor.counters[f"{tkey}_secs"] = monitor.counters.get(
                    f"{tkey}_secs", 0.0
                ) + float(duration_secs)


def _ensure_listeners() -> None:
    global _LISTENERS_INSTALLED
    if _LISTENERS_INSTALLED:
        return
    from jax import monitoring

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_event_duration)
    _LISTENERS_INSTALLED = True


def install_listeners() -> None:
    """Public, idempotent listener install for processes that never build a
    :class:`JaxEventMonitor` — the serve engine calls this so inference
    processes still expose ``jax/*`` compile counters on ``/metrics``."""
    _ensure_listeners()


class JaxEventMonitor:
    """Per-run compile/transfer counter set fed by the module listeners."""

    def __init__(self, warmup_iters: int = 3, warn_on_recompile: bool = True) -> None:
        self.warmup_iters = int(warmup_iters)
        self.warn_on_recompile = bool(warn_on_recompile)
        self.counters: Dict[str, float] = {}
        self.iters = 0
        self._compiles_at_warmup: Optional[float] = None

    # ----------------------------------------------------------- lifecycle
    def attach(self) -> None:
        _ensure_listeners()
        if self not in _ACTIVE:
            _ACTIVE.append(self)

    def detach(self) -> None:
        try:
            _ACTIVE.remove(self)
        except ValueError:
            pass

    # ------------------------------------------------------------- events
    def _record_compile(self, duration_secs: float) -> None:
        self.counters["compiles"] = self.counters.get("compiles", 0.0) + 1.0
        self.counters["compile_secs"] = self.counters.get("compile_secs", 0.0) + float(
            duration_secs
        )
        # A compile span on the timeline: ends now, lasted duration_secs.
        now = time.perf_counter()
        tracer_mod.current().add_span("xla_compile", "compile", now - duration_secs, duration_secs)

    # -------------------------------------------------------------- steps
    def advance(self) -> None:
        """Called once per train iteration: arms the warmup watermark, then
        warns on (and counts) any compile past it."""
        self.iters += 1
        compiles = self.counters.get("compiles", 0.0)
        if self.iters <= self.warmup_iters:
            # Still warming up: every compile so far is expected (initial
            # lowering + the donated-layout recompile on the second call).
            self._compiles_at_warmup = compiles
            return
        if self._compiles_at_warmup is None:
            self._compiles_at_warmup = compiles
            return
        fresh = compiles - self._compiles_at_warmup
        if fresh > 0:
            self._compiles_at_warmup = compiles
            self.counters["recompiles_after_warmup"] = (
                self.counters.get("recompiles_after_warmup", 0.0) + fresh
            )
            if self.warn_on_recompile:
                warnings.warn(
                    f"{int(fresh)} XLA recompile(s) after warmup "
                    f"(iteration {self.iters}): a traced shape/dtype/static-arg "
                    "is changing per iteration. Check for weak-type promotion, "
                    "python-scalar arguments, or shape-dependent branches "
                    "(graftlint GL004 finds the static patterns).",
                    RuntimeWarning,
                    stacklevel=2,
                )

    # ------------------------------------------------------------- gauges
    @staticmethod
    def memory_gauges(device: Any) -> Dict[str, float]:
        """HBM gauges from ``device.memory_stats()`` (absent on CPU -> {})."""
        stats = None
        try:
            stats = device.memory_stats()
        except Exception:
            return {}
        if not stats:
            return {}
        gauges: Dict[str, float] = {}
        for key, name in (
            ("bytes_in_use", "hbm_bytes_in_use"),
            ("peak_bytes_in_use", "hbm_peak_bytes_in_use"),
            ("bytes_limit", "hbm_bytes_limit"),
        ):
            if key in stats:
                gauges[name] = float(stats[key])
        return gauges
