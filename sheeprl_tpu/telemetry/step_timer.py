"""Async-dispatch-aware step timing with a coalesced metric fetch.

XLA dispatch is asynchronous: the wall-clock around a jitted train call
measures the *enqueue*, not the step — and the obvious fix (block every
step) serializes the pipeline and is exactly the per-iteration host sync
graftlint's GL002 exists to kill. The hand-rolled answer of the round-3
profiling was the donated-chain pattern: time N chained dispatches and bound
the chain with a single host fetch at the end. :class:`StepTimer`
productizes it:

- :meth:`step` wraps each dispatch and accumulates the enqueue wall-clock
  (cheap, async, never blocks);
- :meth:`pend` stashes the step's device-resident metric tree plus a
  bounding token (any output of the dispatch chain — donated chains make
  the last output transitively wait on every step);
- :meth:`flush` — called ONCE per log interval — does ONE
  ``jax.block_until_ready`` on the bounding token and ONE
  ``jax.device_get`` for every pending metric tree, credits the block time
  back to the phase timer (``timer.add``), and returns the host metrics.

So per-interval wall-clock never lies (the final block trues it up), and
the loop contains zero in-loop syncs: both sync calls below live outside
any loop, which is what makes this module GL002-clean by construction.

StepTimer is always functional — it is how train loops fetch their losses —
even when telemetry is disabled; only the span/counter emission follows the
installed tracer.
"""

from __future__ import annotations

import time
from collections import deque
from contextlib import contextmanager
from typing import Any, List, Optional

from sheeprl_tpu.telemetry import tracer as tracer_mod
from sheeprl_tpu.telemetry.histogram import Histogram
from sheeprl_tpu.utils.timer import timer


class StepTimer:
    def __init__(
        self,
        name: str = "train",
        timer_key: Optional[str] = None,
        max_pending: int = 8192,
    ) -> None:
        self.name = name
        # Phase-timer key credited with the interval-bounding block time
        # (e.g. "Time/train_time"), so timer.compute() stays truthful even
        # though the per-step region only measured the enqueue.
        self.timer_key = timer_key
        self._pending: deque = deque(maxlen=int(max_pending))
        self._token: Any = None
        self.steps = 0
        self.gradient_steps = 0
        self.dispatch_s = 0.0
        self.bound_s = 0.0
        self.flushes = 0
        self.dropped_metrics = 0
        # Per-dispatch enqueue-latency distribution: a mean hides the
        # retrace/compile outliers that make a training step stall, so every
        # dispatch wall-clock is histogrammed and flush() publishes the
        # p50/p95/p99 as gauges.
        self.dispatch_hist = Histogram()

    # ------------------------------------------------------------- dispatch
    @contextmanager
    def step(self, gradient_steps: int = 1):
        """Wrap ONE jitted dispatch; accumulates enqueue wall-clock and emits
        a dispatch span. ``gradient_steps`` is what the dispatch holds (K for
        a fused K-step call): ``loop/iteration`` reports it."""
        trc = tracer_mod.current()
        start = time.perf_counter()
        with trc.span(f"{self.name}/dispatch", "dispatch"):
            yield
        elapsed = time.perf_counter() - start
        self.steps += 1
        self.gradient_steps += int(gradient_steps)
        self.dispatch_s += elapsed
        self.dispatch_hist.record(elapsed)
        # Dispatch-count counter: fused K-step trains show up as one
        # dispatch, which is the whole point — the counter is how the A/B
        # proves it.
        trc.count(f"{self.name}_dispatches", 1)

    def pend(self, token: Any, metrics: Any = None) -> None:
        """Stash the step's bounding token (always replaces: with donated
        chains the newest output transitively bounds the whole chain) and
        optionally its device-resident metric tree for the coalesced fetch."""
        self._token = token
        if metrics is not None:
            if len(self._pending) == self._pending.maxlen:
                self.dropped_metrics += 1
            self._pending.append(metrics)

    # ---------------------------------------------------------------- flush
    def flush(self) -> List[Any]:
        """Bound the interval and fetch every pending metric tree.

        ONE ``block_until_ready`` + ONE ``device_get`` per call — call it
        once per log interval. Returns the pending metrics as host values
        (numpy leaves), oldest first; the pending queue is cleared.
        """
        import jax

        token, self._token = self._token, None
        if token is not None:
            start = time.perf_counter()
            jax.block_until_ready(token)
            elapsed = time.perf_counter() - start
            self.bound_s += elapsed
            tracer_mod.current().add_span(f"{self.name}/bound", "dispatch", start, elapsed)
            if self.timer_key is not None:
                timer.add(self.timer_key, elapsed)
        fetched: List[Any] = []
        if self._pending:
            pending = list(self._pending)
            self._pending.clear()
            start = time.perf_counter()
            fetched = jax.device_get(pending)
            elapsed = time.perf_counter() - start
            trc = tracer_mod.current()
            if trc.enabled:
                nbytes = tracer_mod.tree_bytes(fetched)
                trc.add_span(
                    f"{self.name}/metric_fetch",
                    "fetch",
                    start,
                    elapsed,
                    {"trees": len(fetched), "bytes": nbytes},
                )
                trc.count("device_get_calls", 1)
                trc.count("device_get_bytes", nbytes)
        trc = tracer_mod.current()
        if trc.enabled and self.dispatch_hist.count:
            for pct in (50.0, 95.0, 99.0):
                trc.set_gauge(
                    f"{self.name}/dispatch_p{pct:.0f}_s", self.dispatch_hist.percentile(pct)
                )
        self.flushes += 1
        return fetched

    # ---------------------------------------------------------------- stats
    @property
    def interval_seconds(self) -> float:
        """Total step time accounted so far: enqueue walls + bounding blocks
        (the donated-chain total)."""
        return self.dispatch_s + self.bound_s

    @property
    def seconds_per_step(self) -> float:
        return self.interval_seconds / self.steps if self.steps else 0.0
