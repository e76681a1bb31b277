"""One run of one cell: set-up, the measured window, the readings.

In a traced run the window is the traced window (`TRACE_SECONDS`): the
profiler is started before the first edge and stopped after the last.

The window drives the program's normal training entry point in this process.
The harness observes iteration boundaries from outside. Set-up ends, and the
window begins, at the first boundary at which the program has made its first
gradient steps (first call, donated-layout recompile, one steady) and the
traffic's warm-up has passed (the adapter's `warm_policy_steps`); both edges wait for the device (everything the
program has enqueued) before the clock is read. The run is ended the way a
preemption ends one: SIGTERM to this process, the loop leaves at its next
iteration boundary and saves nothing.
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import signal
import statistics
import time
from typing import Any, Callable, Dict, List, Optional


#: A traced run measures this much: enough iterations to read every layer,
#: and a trace the reduction gets through inside the run's time limit.
TRACE_SECONDS = 5.0


class Window:
    """The state machine behind the iteration hook."""

    def __init__(
        self,
        seconds: float,
        warm_policy_steps: int,
        warm_train_calls: int,
        record: Any,
        stop: Callable[[], None],
        tracer: Optional[Any] = None,
    ) -> None:
        self.seconds = float(seconds)
        self.warm_policy_steps = int(warm_policy_steps)
        self.warm_train_calls = int(warm_train_calls)
        self.record = record
        self.stop = stop
        self.tracer = tracer
        self.phase = "setup"
        self.edges: List[float] = []  # boundary times, first and last are the window's edges
        self.policy_steps: List[int] = []
        self.train_steps: List[int] = []
        self.compiles_at_open = 0

    def on_iteration(self, policy_step: int) -> None:
        if self.phase == "closed":
            return
        if self.phase == "setup":
            if self.record.calls < self.warm_train_calls or policy_step < self.warm_policy_steps:
                return
            if self.tracer is not None:
                self.tracer.start()  # takes tens of seconds on a TPU: before the edge, so it is set-up
            self.record.sync()
            self.phase = "window"
            self._mark(policy_step)
            return
        now = time.perf_counter()
        if now - self.edges[0] >= self.seconds:
            self.record.sync()
            self._mark(policy_step)
            self.phase = "closed"
            if self.tracer is not None:
                self.tracer.close()
            self.stop()
            return
        self._mark(policy_step, now)

    def _mark(self, policy_step: int, now: Optional[float] = None) -> None:
        if not self.edges:
            self.wall_at_open = time.time()
        self.edges.append(time.perf_counter() if now is None else now)
        self.policy_steps.append(policy_step)
        self.train_steps.append(self.record.steps)

    # ------------------------------------------------------------- readings
    @property
    def elapsed(self) -> float:
        return self.edges[-1] - self.edges[0]

    def iteration_ms(self) -> List[float]:
        return [(b - a) * 1e3 for a, b in zip(self.edges, self.edges[1:])]

    def env_steps(self) -> int:
        return self.policy_steps[-1] - self.policy_steps[0]

    def gradient_steps(self) -> int:
        return self.train_steps[-1] - self.train_steps[0]


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile by the nearest-rank rule on the sorted values."""
    ordered = sorted(values)
    rank = max(int(-(-q * len(ordered) // 100)), 1)
    return ordered[rank - 1]


class CompileCounter:
    """Backend compiles, with the time each ended (jax.monitoring)."""

    def __init__(self) -> None:
        self.events: List[tuple] = []

    def install(self) -> None:
        import jax

        def listener(event: str, duration: float, **_: Any) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.events.append((time.perf_counter(), float(duration)))

        jax.monitoring.register_event_duration_secs_listener(listener)

    def between(self, start: float, end: float) -> int:
        return sum(1 for t, _ in self.events if start <= t <= end)

    def seconds_before(self, end: float) -> float:
        return sum(d for t, d in self.events if t <= end)


def self_sigterm() -> None:
    os.kill(os.getpid(), signal.SIGTERM)


def run_cell(cell: Any, seed: int, seconds: float, trace: bool, started: float, run_dir: str, say: Callable[[str], None]) -> Dict[str, Any]:
    """Everything between the chip check and the result line."""
    from benchmarks.harness import compare, device, tracing

    adapter = compare.load_adapter(cell.config)
    traffic = cell.traffic
    program_seed = int(seed) % (2**31 - 1)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir, exist_ok=True)
    compiles = CompileCounter()
    compiles.install()
    record = adapter.Record(program_seed, traffic)
    tracer = None
    if trace:
        tracer = tracing.Tracer(os.path.join(run_dir, "xla_trace"), TRACE_SECONDS, record)
        seconds = min(seconds, tracer.seconds)  # a traced run measures the traced window and no more
    # set-up lasts at least through the gradient steps that the comparison follows
    window = Window(seconds, adapter.warm_policy_steps(traffic), adapter.StepProbe.CAPTURED, record, self_sigterm, tracer)
    args = adapter.overrides(cell.config, traffic, program_seed, run_dir, trace)
    say(f"program: python -m sheeprl_tpu {' '.join(args)}")
    record.mark("program called")
    annotated = tracer.annotated(adapter.annotation_targets()) if tracer else contextlib.nullcontext()
    with adapter.installed(record, window.on_iteration), annotated:
        adapter.run_program(args)
    if window.phase != "closed":
        raise SystemExit(f"benchmark: the program ended before the window closed (phase {window.phase})")
    fell_back = record.fell_back() if hasattr(record, "fell_back") else None
    if fell_back:
        raise SystemExit(f"benchmark: {fell_back}")
    for root, _, files in os.walk(run_dir):
        if any(name.endswith(".ckpt") for name in files):
            raise SystemExit(f"benchmark: the run saved a checkpoint under {root}")

    peak = device.memory_peak_bytes()
    iters = window.iteration_ms()
    opened = window.edges[0]
    setup_s = opened - started
    in_window = compiles.between(opened, window.edges[-1])
    compile_s = compiles.seconds_before(opened)
    say(
        f"window: {window.elapsed:.3f} s, {len(iters)} iterations, {window.env_steps()} policy steps, "
        f"{window.gradient_steps()} gradient steps, {in_window} compile(s) inside; "
        f"iteration ms median {statistics.median(iters):.3f} p95 {percentile(iters, 95):.3f} max {max(iters):.3f} "
        f"(n={len(iters)}); set-up {setup_s:.2f} s of which {compile_s:.1f} s compiling"
    )
    marks = [*record.marks, ("window open", opened)]
    say("set-up, seconds from the start: " + ", ".join(f"{what} {at - started:.1f}" for what, at in marks))
    spans, span_epoch = tracing.read_spans(run_dir) if trace else ([], None)
    run = {
        "cell": cell,
        "window": window,
        "record": record,
        "run_dir": run_dir,
        "compiles_in_window": in_window,
        "compile_seconds_setup": compile_s,
        "trace": tracer.reduce() if tracer else None,
        "spans": spans,
        "span_epoch_wall": span_epoch,
        "wall_at_open": window.wall_at_open,
        "readings": {
            "env_steps_per_s": window.env_steps() / window.elapsed,
            "iter_p95_ms": percentile(iters, 95),
            "peak_hbm_gib": peak / 2**30,
            "setup_s": setup_s,
        },
    }
    with open(os.path.join(run_dir, "iterations.json"), "w") as fp:
        json.dump({"ms": iters, "policy_steps": window.policy_steps, "train_steps": window.train_steps,
                   "trace": run["trace"]}, fp)

    # -- correct: after the peak was read and the program's state is gone.
    captured, acted = record.captured, record.acted()
    record.release()
    t_ref = time.perf_counter()
    program = adapter.program_numbers(captured, acted, record.sensitivity())
    reference = compare.reference_run(cell.config, captured, program_seed)
    reference["acting"] = compare.acting_steps(cell.config, reference["initial"], acted)
    worst: Dict[str, str] = {}
    values = compare.numbers(adapter, program, reference, worst)
    values["ratio_steps"] = abs(window.gradient_steps() - adapter.gradient_steps_owed(traffic, window.env_steps()))
    correct, shown = compare.judge(values, cell.limits)
    say(f"reference: the step asked again and three steps in {time.perf_counter() - t_ref:.1f} s")
    say("leaves read (worst, or median for direction and moved): " + json.dumps(worst))
    say("losses, program then reference, step by step: " + json.dumps([program["losses"], reference["losses"]]))
    run.update(program=program, acted=acted, reference=reference, correct=correct, compared=shown)
    return run
