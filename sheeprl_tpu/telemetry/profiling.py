"""Config-driven ``jax.profiler`` integration: step-window traces + server.

Profiling is a run feature: configure ``telemetry.profiler.start_step`` /
``stop_step`` and the run traces exactly that policy-step window
``[start, stop)`` into an XLA/xplane trace directory, viewable with
Perfetto / TensorBoard's profile plugin. Optionally a live profiler server
(``telemetry.profiler.port``) allows on-demand capture from a running
training job without any window configured up front.

Profiler failures must never kill a training run — every jax.profiler call
is wrapped and degrades to a warning.
"""

from __future__ import annotations

import os
import warnings
from typing import Optional

from sheeprl_tpu.telemetry import tracer as tracer_mod


class ProfilerWindow:
    def __init__(
        self,
        trace_dir: Optional[str] = None,
        start_step: int = -1,
        stop_step: int = -1,
        port: Optional[int] = None,
    ) -> None:
        self.trace_dir = trace_dir
        self.start_step = int(start_step)
        self.stop_step = int(stop_step)
        self.port = int(port) if port else None
        self.active = False
        self._done = False
        self._server = None

    @property
    def configured(self) -> bool:
        return self.start_step >= 0 and self.stop_step > self.start_step

    # ----------------------------------------------------------- lifecycle
    def start_server(self) -> None:
        """Start the live-capture profiler server (idempotent)."""
        if self.port is None or self._server is not None:
            return
        import jax

        try:
            self._server = jax.profiler.start_server(self.port)
        except Exception as e:  # pragma: no cover - backend-dependent
            warnings.warn(f"jax.profiler.start_server({self.port}) failed: {e}")
            self.port = None

    def advance(self, step: int) -> None:
        """Drive the `[start_step, stop_step)` window from the train loop's
        policy-step counter. Steps advance by num_envs*world_size per
        iteration, so boundaries are >= comparisons, not equality."""
        if not self.configured or self._done:
            return
        if not self.active and self.start_step <= step < self.stop_step:
            self._start()
        elif self.active and step >= self.stop_step:
            self._stop()

    def close(self) -> None:
        if self.active:
            self._stop()

    # ------------------------------------------------------------ plumbing
    def _start(self) -> None:
        import jax

        assert self.trace_dir, "ProfilerWindow needs trace_dir before starting"
        try:
            os.makedirs(self.trace_dir, exist_ok=True)
            # Host tracer at level 1: the lowest that records TraceAnnotations
            # (the program's own spans, tracer.py); Python tracer off. The TPU
            # runtime's own level-1 annotations come with it and slow a host
            # loop that copies pixel batches several times over (PERF.md, PR
            # 25): device operation times stay true, idle gaps do not.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        except Exception as e:  # pragma: no cover - backend-dependent
            warnings.warn(f"jax.profiler.start_trace({self.trace_dir}) failed: {e}")
            self._done = True
            return
        self.active = True
        tracer_mod.current().count("profiler_windows", 1)

    def _stop(self) -> None:
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception as e:  # pragma: no cover - backend-dependent
            warnings.warn(f"jax.profiler.stop_trace() failed: {e}")
        self.active = False
        self._done = True
