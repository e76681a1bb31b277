"""Compile spans and counters from ``jax.monitoring``.

JAX instruments its own compiler pipeline: each stage of a jitted function's
first call is reported as a time span (wall-clock start and end, the
function's name in ``fun_name``), and the persistent compile cache reports its
traffic as events. Listening is the zero-overhead way to see compiles — no
wrapping of ``jax.jit``, no log scraping. A :class:`JaxEventMonitor`
registers its listeners on :meth:`~JaxEventMonitor.attach` and removes them on
:meth:`~JaxEventMonitor.detach` (``Telemetry.begin_setup`` or ``open`` /
``close``: with telemetry off none is registered). It records one span per
stage, into the run's tracer (the current one where it was given none),
on the tracer's ``perf_counter`` clock (JAX's wall-clock stamps are carried
over through the tracer's ``perf_epoch_s`` / ``wall_epoch_s`` pair) and on the
thread that compiled, which is the thread that called the jit:

- ``compile/trace`` — ``/jax/core/compile/jaxpr_trace_duration``, one per
  trace of a function (an inner jit's trace nests inside its caller's);
- ``compile/lower`` — ``/jax/core/compile/jaxpr_to_mlir_module_duration``;
- ``compile/backend`` — ``/jax/core/compile/backend_compile_duration``, the
  XLA compile, or the persistent cache's load in its place.

Every span carries ``fun``. ``compile/backend`` also carries ``cache``
(``hit``: loaded from the persistent cache; ``miss``: looked up there and
compiled; ``off``: not looked up) and ``seen``: how many earlier
``compile/backend`` spans of the same ``fun`` the monitor recorded. The second
call of a jit that donates its inputs compiles once more for the donated
layout; that compile reads ``seen`` 1.

Counters: ``compiles`` and ``compile_secs`` (backend), ``traces`` and
``trace_secs``, ``compile_cache_hits`` / ``compile_cache_misses`` (a miss is
counted when the cache is written), ``compile/recompiles`` (backend compiles
with ``seen`` > 0) and ``recompiles_after_warmup``:
:meth:`JaxEventMonitor.advance` is called once per train iteration, and
compiles observed after ``warmup_iters`` iterations are counted there and
warned about — the silent recompile-storm trap made loud.
"""

from __future__ import annotations

import threading
import warnings
from typing import Any, Dict, Optional

from sheeprl_tpu.telemetry import tracer as tracer_mod

#: jax.monitoring time-span event -> span name
SPAN_NAMES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
}
_CACHE_LOOKUP = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_COUNT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile_cache_hits",
    "/jax/compilation_cache/cache_misses": "compile_cache_misses",
}

_MIRROR_INSTALLED = False


def _registry_count(name: str, amount: float = 1.0) -> None:
    """Mirror a compiler event into the process default MetricsRegistry.

    The ``jax/`` prefix keeps these distinct from the *gauge* mirrors that
    ``Telemetry.log_counters`` derives from monitor counters (``compiles``
    etc.) — a registry name can hold one kind only. This is the bridge that
    puts compile/retrace/cache traffic on ``/metrics`` and the telemetry tail.
    """
    try:
        from sheeprl_tpu.telemetry.registry import default_registry

        default_registry().counter(name).inc(amount)
    except Exception:  # noqa: BLE001 - metrics must never break a compile
        pass


def _mirror_span(event: str, start: float, end: float, **kwargs: Any) -> None:
    name = SPAN_NAMES.get(event)
    if name == "compile/backend":
        _registry_count("jax/compiles")
        _registry_count("jax/compile_secs", end - start)
    elif name == "compile/trace":
        _registry_count("jax/traces")
        _registry_count("jax/trace_secs", end - start)


def _mirror_event(event: str, **kwargs: Any) -> None:
    key = _CACHE_COUNT_EVENTS.get(event)
    if key is not None:
        _registry_count(f"jax/{key}")


def install_listeners() -> None:
    """Mirror compile traffic into the default registry for the rest of the
    process, for processes that never attach a :class:`JaxEventMonitor` —
    the serve engine calls this so inference processes still expose ``jax/*``
    compile counters on ``/metrics``. Idempotent; attached monitors stop
    mirroring once it has run, so nothing is counted twice."""
    global _MIRROR_INSTALLED
    if _MIRROR_INSTALLED:
        return
    from jax import monitoring

    monitoring.register_event_time_span_listener(_mirror_span)
    monitoring.register_event_listener(_mirror_event)
    _MIRROR_INSTALLED = True


class JaxEventMonitor:
    """Per-run compile spans and counters, fed by its own listeners while
    attached; the spans go to ``tracer`` (the current tracer where None)."""

    def __init__(
        self, warmup_iters: int = 3, warn_on_recompile: bool = True, tracer: Optional[tracer_mod.Tracer] = None
    ) -> None:
        self.warmup_iters = int(warmup_iters)
        self.warn_on_recompile = bool(warn_on_recompile)
        self._tracer = tracer
        self.counters: Dict[str, float] = {}
        self.iters = 0
        self._compiles_at_warmup: Optional[float] = None
        self._seen: Dict[str, int] = {}
        self._lock = threading.Lock()  # jits compile on whichever thread calls them
        self._local = threading.local()  # .cache: what the cache said of the backend compile running on this thread
        self._attached = False

    # ----------------------------------------------------------- lifecycle
    def attach(self) -> None:
        if self._attached:
            return
        from jax import monitoring

        monitoring.register_event_time_span_listener(self._on_span)
        monitoring.register_event_listener(self._on_event)
        self._attached = True

    def detach(self) -> None:
        if not self._attached:
            return
        from jax import monitoring

        monitoring.unregister_event_time_span_listener(self._on_span)
        monitoring.unregister_event_listener(self._on_event)
        self._attached = False

    # ------------------------------------------------------------- events
    def _count(self, name: str, amount: float = 1.0) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0.0) + float(amount)

    def _on_event(self, event: str, **kwargs: Any) -> None:
        key = _CACHE_COUNT_EVENTS.get(event)
        if key is not None:
            self._count(key)
            self._local.cache = "hit" if key == "compile_cache_hits" else "miss"
            if not _MIRROR_INSTALLED:
                _mirror_event(event)
        elif event == _CACHE_LOOKUP and getattr(self._local, "cache", "off") == "off":
            self._local.cache = "miss"  # a hit, if one follows, overrides it

    def _on_span(self, event: str, start: float, end: float, **kwargs: Any) -> None:
        name = SPAN_NAMES.get(event)
        if name is None:
            return
        fun = str(kwargs.get("fun_name", ""))
        seconds = end - start
        args: Dict[str, Any] = {"fun": fun}
        if name == "compile/backend":
            with self._lock:
                seen = self._seen.get(fun, 0)
                self._seen[fun] = seen + 1
            args["cache"] = getattr(self._local, "cache", "off")
            args["seen"] = seen
            self._local.cache = "off"
            self._count("compiles")
            self._count("compile_secs", seconds)
            if seen:
                self._count("compile/recompiles")
        elif name == "compile/trace":
            self._count("traces")
            self._count("trace_secs", seconds)
        if not _MIRROR_INSTALLED:
            _mirror_span(event, start, end)
        tracer = self._tracer or tracer_mod.current()
        tracer.add_span(name, "compile", tracer.perf_epoch_s + (start - tracer.wall_epoch_s), seconds, args)

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
