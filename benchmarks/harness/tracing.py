"""The traced run: a few seconds of the window under `jax.profiler`, host
annotations put around the program's layer boundaries from outside, and the
reduction of the `.xplane.pb` to device busy time, per-program device time,
the top device operations and the idle gaps by what the host was doing.
"""

from __future__ import annotations

import contextlib
import glob
import json
import os
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

MARKER = "bench_marker"  # the jitted program that delimits the traced window on the device
Interval = Tuple[float, float]  # start, end in seconds on the trace's clock


# ------------------------------------------------------------------ arithmetic
def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], end))
        else:
            merged.append((start, end))
    return merged


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def short_op(name: str, limit: int = 96) -> str:
    """A device operation's event name is its whole HLO instruction: keep the
    instruction's own name (which starts with its opcode) and, where the
    result is one array, its shape."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:limit]
    shape = "" if rest.startswith("(") else rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head.lstrip('%')} {shape}".strip()[:limit]


# ------------------------------------------------------------------ reading a trace
def load_planes(path: str) -> List[Dict[str, Any]]:
    """The trace as plain data: planes -> lines -> (name, start_s, duration_s)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and name.split(":")[-1].strip().isdigit()


def reduce_planes(
    planes: List[Dict[str, Any]],
    host_spans: Optional[List[Tuple[str, Interval]]] = None,
    marker: str = MARKER,
    ops_line: str = "XLA Ops",
    modules_line: str = "XLA Modules",
) -> Dict[str, Any]:
    """Device busy union, idle share, per-program and per-operation device time
    inside the traced window, averaged over the device planes.

    The window is what lies between the first and the last execution of the
    marker program on the device (the harness runs it, and waits for it, right
    after the profiler starts and right before it stops), so it is read on the
    device's own clock. ``host_spans`` are (label, (start, end)) on that clock
    too (the harness shifts its host-clock stamps by the first marker's end)."""
    host_spans = host_spans or []
    devices = [p for p in planes if is_device_plane(p["name"])]
    if not devices:
        return {"devices": 0}
    busy_s, window_s, ops, modules, module_counts = [], [], {}, {}, {}
    idle_by_host: Dict[str, float] = {}
    for plane in devices:
        by_line = {ln["name"]: ln["events"] for ln in plane["lines"]}
        marks = sorted((s, s + d) for name, s, d in by_line.get(modules_line, []) if marker in name)
        if len(marks) < 2:
            return {"devices": 0}
        lo, hi = marks[0][1], marks[-1][0]
        window = (lo, hi)
        window_s.append(hi - lo)
        op_events = by_line.get(ops_line, [])
        busy = union(clip([(s, s + d) for _, s, d in op_events], lo, hi))
        busy_s.append(total(busy))
        for name, s, d in op_events:
            got = overlap((s, s + d), window)
            if got > 0:
                op = short_op(name)
                ops[op] = ops.get(op, 0.0) + got / len(devices)
        for name, s, d in by_line.get(modules_line, []):
            if s >= lo and s + d <= hi:  # whole executions only
                modules[name] = modules.get(name, 0.0) + d / len(devices)
                module_counts[name] = module_counts.get(name, 0) + 1
        for gap in gaps(busy, lo, hi):
            covered = 0.0
            for label, span in host_spans:
                got = overlap(gap, span)
                if got > 0:
                    idle_by_host[label] = idle_by_host.get(label, 0.0) + got / len(devices)
                    covered += got
            rest = (gap[1] - gap[0]) - covered
            if rest > 0:
                idle_by_host["bench/other_host"] = idle_by_host.get("bench/other_host", 0.0) + rest / len(devices)
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]  # noqa: E731
    return {
        "devices": len(devices),
        "window_s": sum(window_s) / len(window_s),
        "busy_s": sum(busy_s) / len(busy_s),
        "first_marker_end": lo,
        "modules": modules,
        "module_counts": {k: v // len(devices) for k, v in module_counts.items()},
        "device_ops": top(ops),
        "idle_gaps": top(idle_by_host),
    }


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")), key=os.path.getmtime)
    return found[-1] if found else None


def read_spans(run_dir: str) -> Tuple[List[Dict[str, Any]], Optional[float]]:
    """The program's own telemetry records of the run (meta, spans, counters)
    and the wall-clock second its span timestamps count from."""
    found = glob.glob(os.path.join(run_dir, "**", "telemetry.jsonl"), recursive=True)
    if not found:
        return [], None
    with open(found[0]) as fp:
        records = [json.loads(line) for line in fp if line.strip()]
    epoch = None
    chrome = os.path.join(os.path.dirname(found[0]), "trace.json")
    if os.path.exists(chrome):
        with open(chrome) as fp:
            epoch = json.load(fp).get("metadata", {}).get("wall_epoch_s")
    return records, epoch


# ------------------------------------------------------------------ the tracer
class Tracer:
    """`jax.profiler` over the measured window of a traced run, with the
    host tracer off: on this machine it slows the program's host side several
    times over at the largest widths (PERF.md, PR 24), and a perturbed trace
    reads the wrong idle share. Host activity is stamped by the harness's own
    wrappers on the host clock instead."""

    def __init__(self, trace_dir: str, seconds: float, record: Any = None) -> None:
        self.trace_dir = trace_dir
        self.seconds = seconds
        self.record = record  # the adapter's record: what is enqueued is waited for through it
        self.tracing = False
        self.host_spans: List[Tuple[str, float, float]] = []
        self.first_marker_done: Optional[float] = None
        self.steps_at_start = 0
        self.gradient_steps = 0
        self._marker = None

    def _mark_device(self) -> float:
        import jax
        import jax.numpy as jnp

        if self._marker is None:

            def bench_marker(x):
                return x + 1

            self._marker = jax.jit(bench_marker)
        self._marker(jnp.zeros((8, 128), jnp.float32)).block_until_ready()
        return time.perf_counter()

    def start(self) -> None:
        """Called at the window's first edge, before it is marked: everything
        enqueued is waited for, the profiler starts, the marker program runs."""
        import jax

        self._mark_device()  # compiles the marker outside the trace
        if self.record is not None:
            self.record.sync()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.tracing = True
        self.first_marker_done = self._mark_device()
        self.steps_at_start = self.record.steps if self.record is not None else 0

    def close(self) -> None:
        """Called after the window's last edge was marked (the device is idle)."""
        import jax

        if not self.tracing:
            return
        self.gradient_steps = (self.record.steps if self.record is not None else 0) - self.steps_at_start
        self._mark_device()
        jax.profiler.stop_trace()
        self.tracing = False

    @contextlib.contextmanager
    def annotated(self, targets: List[Tuple[Any, str, str]]) -> Iterator[None]:
        """Host-clock stamps around the layer boundaries the adapter names
        (owner, attribute, label), wrapped from outside for this run only."""
        saved = []
        spans = self.host_spans
        for owner, name, label in targets:
            raw = getattr(owner, name)
            saved.append((owner, name, raw))

            def wrapped(*args, _raw=raw, _label=label, **kwargs):
                begun = time.perf_counter()
                try:
                    return _raw(*args, **kwargs)
                finally:
                    spans.append((_label, begun, time.perf_counter()))

            setattr(owner, name, wrapped)
        try:
            yield
        finally:
            for owner, name, raw in saved:
                setattr(owner, name, raw)

    def reduce(self) -> Optional[Dict[str, Any]]:
        path = newest_xplane(self.trace_dir)
        if path is None:
            return None
        planes = load_planes(path)
        probe = reduce_planes(planes)
        if not probe.get("devices"):
            return probe
        # host stamps onto the device's clock: the first marker ended on the
        # device when the host saw it done (less a device-to-host latency of
        # well under a millisecond)
        shift = probe["first_marker_end"] - self.first_marker_done
        host = [(label, (a + shift, b + shift)) for label, a, b in self.host_spans]
        reduced = reduce_planes(planes, host)
        reduced["gradient_steps"] = self.gradient_steps
        reduced["layout"] = {p["name"]: {ln["name"]: len(ln["events"]) for ln in p["lines"]} for p in planes}
        return reduced
