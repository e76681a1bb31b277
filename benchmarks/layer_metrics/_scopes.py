"""What the readers of the phase metrics and of the host-lane metrics share:
the device trace of a traced run reduced to self time by scope, and the
program's own spans on the device's clock. Imported by the reader files the
way `_spans.py` is, so one run's trace is parsed once for all of them.

**Scopes.** The program wraps the phases of its jitted steps in
`jax.named_scope` (`sheeprl_tpu/telemetry/scopes.py`), which puts the scope
into every instruction's `op_name`: `jit(train_step)/jvp(<scope>)/while/body/...`
forward, `.../transpose(jvp(<scope>))/...` backward. The phases of a
configuration's gradient step are the scopes its file lists
(`program.step_scopes`, `<family>/<phase>` each; a test holds the list to the
program's own table). On the TPU every event
of a device plane's "XLA Ops" line names its HLO instruction, and the `op_name`
is a stat (`tf_op`) of the event's metadata, which `jax.profiler.ProfileData`
does not show: `op_names` reads the metadata tables from the file's wire format
(no protobuf package, no tensorflow beside JAX) and keys them by event name.
Each instant inside an execution of a train module (the configuration's
`train_modules`) inside the marker window belongs to the innermost operation
running then (`self_times`: a `while` keeps what its body's operations do not
cover), so scopes and the unscoped rest add up to the modules' busy union.

**The clock shift.** The program's spans are stamped with `time.perf_counter()`
and exported as microseconds from `perf_epoch_s` (the meta record of
`telemetry.jsonl`). The harness holds the `perf_counter` second at which the
first marker program ended (`run["window"].tracer.first_marker_done`) and the
device-clock second of the same event (`run["trace"]["first_marker_end"]`):
their difference carries a span onto the device's clock (`program_spans`).

A run without a trace, without scopes in it, or of a program that exports no
`perf_epoch_s` or no thread (the parent of the PR that brought these) reads as
nothing: every function here returns None then, and so does the reader.
"""

from __future__ import annotations

import os
import re
from typing import Any, Dict, Iterator, List, Optional, Tuple

from benchmarks.harness import tracing

UNSCOPED = "unscoped"
#: Scope families every configuration's programs may pass through beside its
#: own (the in-jit replay ring's sampler and writer, `replay/*`).
SHARED_FAMILIES = ("replay",)
OP_NAME_STAT = "tf_op"  # the stat of an event's metadata that holds its `op_name`

_parsed: Dict[Tuple[str, Tuple[str, ...]], Optional[Dict[str, Any]]] = {}  # (xplane path, step scopes) -> reduce_scopes(...)


def step_scopes(run: Dict[str, Any]) -> Tuple[str, ...]:
    """The phases of the gradient step, as the configuration's file lists them."""
    return tuple(run["cell"].config["program"].get("step_scopes", ()))


def scope_pattern(scopes: Tuple[str, ...]) -> "re.Pattern[str]":
    """Matches every scope of the listed scopes' families and of the shared ones."""
    families = sorted({s.split("/", 1)[0] for s in scopes} | set(SHARED_FAMILIES))
    return re.compile("(?:" + "|".join(map(re.escape, families)) + ")/[a-z_]+")


# ------------------------------------------------------------------ the file's wire format
def _varint(buf: bytes, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf: bytes, at: int, end: int) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is its (start, end) in ``buf``."""
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 1:
            value, at = buf[at:at + 8], at + 8
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = (at, at + size), at + size
        elif wire == 5:
            value, at = buf[at:at + 4], at + 4
        else:
            raise ValueError(f"xplane: wire type {wire}")
        yield number, wire, value


def op_names(path: str) -> Dict[str, Dict[str, str]]:
    """plane name -> {event name: op_name}, from the `tf_op` stat of each
    XEventMetadata (XSpace.planes=1; XPlane.name=2, event_metadata=4,
    stat_metadata=5; map entries key=1 value=2; XEventMetadata.name=2, stats=5;
    XStat.metadata_id=1, str_value=5, ref_value=7; XStatMetadata.name=2)."""
    with open(path, "rb") as fp:
        buf = fp.read()
    text = lambda span: buf[span[0]:span[1]].decode("utf-8", "replace")  # noqa: E731
    out: Dict[str, Dict[str, str]] = {}
    for number, _, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for field, _, value in _fields(buf, *plane):
            if field == 2:
                name = text(value)
            elif field in (4, 5):
                entry = {k: v for k, _, v in _fields(buf, *value)}
                if 2 not in entry:
                    continue
                if field == 4:
                    events.append(entry[2])
                else:
                    stat_names[entry.get(1, 0)] = next((text(v) for k, _, v in _fields(buf, *entry[2]) if k == 2), "")
        if not tracing.is_device_plane(name):
            continue
        wanted = {i for i, n in stat_names.items() if n == OP_NAME_STAT}
        names: Dict[str, str] = {}
        for span in events:
            event_name, found = "", None
            for field, _, value in _fields(buf, *span):
                if field == 2:
                    event_name = text(value)
                elif field == 5 and found is None:
                    stat = {k: v for k, _, v in _fields(buf, *value)}
                    if stat.get(1) in wanted:
                        found = text(stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
            if found:
                names[event_name] = found
        out[name] = names
    return out


# ------------------------------------------------------------------ self time
def self_times(events: List[Tuple[float, float, Any]]) -> Dict[Any, float]:
    """Seconds by key of (start, end, key) events, each instant given to the
    innermost event running then (of those running, the one that started
    last): a parent keeps its duration less what its children cover, and the
    values add up to the union of the intervals."""
    out: Dict[Any, float] = {}
    stack: List[Tuple[float, Any]] = []
    cursor = 0.0
    for start, end, key in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            done, was = stack.pop()
            if done > cursor:
                out[was] = out.get(was, 0.0) + done - cursor
                cursor = done
        if stack and start > cursor:
            out[stack[-1][1]] = out.get(stack[-1][1], 0.0) + start - cursor
        cursor = max(cursor, start)
        stack.append((end, key))
    while stack:
        done, was = stack.pop()
        if done > cursor:
            out[was] = out.get(was, 0.0) + done - cursor
            cursor = done
    return out


def scope_of(op_name: str, pattern: "re.Pattern[str]") -> Tuple[str, str]:
    """(scope, direction) of one instruction: the innermost scope (a match of
    ``pattern``, as `scope_pattern` builds it) its `op_name` passes through,
    backward where a `transpose(` precedes it."""
    found = None
    for found in pattern.finditer(op_name):
        pass
    if found is None:
        return UNSCOPED, "fwd"
    return found.group(0), "bwd" if "transpose(" in op_name[:found.start()] else "fwd"


def reduce_scopes(
    planes: List[Dict[str, Any]], names: Dict[str, Dict[str, str]], train_modules: List[str], scopes: Tuple[str, ...]
) -> Optional[Dict[str, Any]]:
    """``planes`` as `tracing.load_planes` gives them, ``names`` as `op_names`
    does, ``scopes`` the configuration's step scopes. Per device, inside the marker window: self time by (scope, direction)
    of the operations that ran inside whole executions of the train modules,
    the number of those executions, and the device's idle gaps; times and
    counts averaged over the devices, the gaps those of the first."""
    devices = [p for p in planes if tracing.is_device_plane(p["name"])]
    by_scope: Dict[Tuple[str, str], float] = {}
    calls, idle = 0.0, None
    pattern = scope_pattern(scopes)
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        modules = lines.get("XLA Modules", [])
        marks = sorted((s, s + d) for name, s, d in modules if tracing.MARKER in name)
        if len(marks) < 2:
            return None
        lo, hi = marks[0][1], marks[-1][0]
        table = names.get(plane["name"], {})
        runs = sorted((s, s + d) for name, s, d in modules
                      if s >= lo and s + d <= hi and any(w in name for w in train_modules))
        ops = sorted((s, s + d, name) for name, s, d in lines.get("XLA Ops", []))
        inside, at = [], 0
        for start, end, name in ops:
            while at < len(runs) and runs[at][1] < end:
                at += 1
            if at < len(runs) and runs[at][0] <= start:
                inside.append((start, end, name))
        for name, seconds in self_times(inside).items():
            key = scope_of(table.get(name, ""), pattern)
            by_scope[key] = by_scope.get(key, 0.0) + seconds / len(devices)
        calls += len(runs) / len(devices)
        if idle is None:
            busy = tracing.union(tracing.clip([(s, e) for s, e, _ in ops], lo, hi))
            idle = tracing.gaps(busy, lo, hi)
    if not devices:
        return None
    return {"by_scope": by_scope, "calls": calls, "idle": idle}


def scopes_of(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """`reduce_scopes` of the run's trace, read once per run."""
    trace_dir = os.path.join(run.get("run_dir") or "", "xla_trace")
    path = tracing.newest_xplane(trace_dir) if run.get("trace") else None
    if path is None:
        return None
    scopes = step_scopes(run)
    if (path, scopes) not in _parsed:
        modules = run["cell"].config["program"]["train_modules"]
        _parsed[path, scopes] = reduce_scopes(tracing.load_planes(path), op_names(path), modules, scopes)
    return _parsed[path, scopes]


def _step_times(run: Dict[str, Any]) -> Optional[Tuple[Dict[str, Any], Tuple[str, ...]]]:
    """`scopes_of` the run with its step scopes; None where the trace names none of them."""
    got = scopes_of(run)
    if not got:
        return None
    scopes = step_scopes(run)
    return (got, scopes) if any(s in scopes for s, _ in got["by_scope"]) else None


def phase_ms(run: Dict[str, Any], scope: str, direction: Optional[str] = None) -> Optional[float]:
    """Self time under ``scope`` per execution of the train module, in ms;
    None where the trace names no scope of the step at all."""
    found = _step_times(run)
    if not found or not found[0]["calls"]:
        return None
    got = found[0]
    seconds = sum(v for (s, d), v in got["by_scope"].items() if s == scope and direction in (None, d))
    return seconds * 1e3 / got["calls"]


def unscoped_share(run: Dict[str, Any]) -> Optional[float]:
    """Share of the train modules' self time under none of the step's scopes."""
    found = _step_times(run)
    if not found:
        return None
    got, scopes = found
    total = sum(got["by_scope"].values())
    scoped = sum(v for (s, _), v in got["by_scope"].items() if s in scopes)
    return 100.0 * (total - scoped) / total if total else None


# ------------------------------------------------------------------ the program's spans
def program_spans(run: Dict[str, Any], prefix: str = "") -> Optional[List[Dict[str, Any]]]:
    """The program's spans that started inside the traced window, on the
    device's clock: {name, thread, start, end, args}, ``main`` true on the
    thread that runs the loop (the one `loop/iteration` is stamped on)."""
    trace = run.get("trace")
    meta = next((r for r in run.get("spans", []) if r.get("type") == "meta"), {})
    epoch = meta.get("perf_epoch_s")
    if not trace or epoch is None or trace.get("first_marker_end") is None:
        return None
    shift = trace["first_marker_end"] - run["window"].tracer.first_marker_done
    lo = trace["first_marker_end"]
    hi = lo + trace["window_s"]
    spans = [r for r in run["spans"] if r.get("type") == "span" and "thread" in r]
    loop = next((r["thread"] for r in spans if r["name"] == "loop/iteration"), None)
    out = []
    for r in spans:
        start = epoch + r["ts_us"] / 1e6 + shift
        if r["name"].startswith(prefix) and lo <= start <= hi:
            out.append({"name": r["name"], "start": start, "end": start + r["dur_us"] / 1e6,
                        "main": r["thread"] == loop, "args": r.get("args") or {}})
    return out


def span_ms(run: Dict[str, Any], *prefixes: str) -> Optional[List[float]]:
    """Durations in ms of the loop thread's spans under the prefixes; None
    where the first prefix has none (the program does not emit it)."""
    found = [program_spans(run, p) for p in prefixes]
    if not found[0]:
        return None
    return [(s["end"] - s["start"]) * 1e3 for spans in found for s in spans or [] if s["main"]]


def per_gradient_step(run: Dict[str, Any], values: Optional[List[float]]) -> Optional[float]:
    steps = run["window"].gradient_steps() if values is not None else 0
    return sum(values) / steps if steps else None


def idle_unattributed_share(run: Dict[str, Any]) -> Optional[float]:
    """Of the device's idle time in the traced window, the share under no
    leaf span of the loop thread (a span that holds no other of them)."""
    got, spans = scopes_of(run), program_spans(run)
    if not got or not got["idle"] or not spans:
        return None
    main = sorted(((s["start"], s["end"]) for s in spans if s["main"]), key=lambda s: (s[0], -s[1]))
    leaves = [a for i, a in enumerate(main) if not (i + 1 < len(main) and main[i + 1][1] <= a[1])]
    cover = tracing.union(leaves)
    idle = tracing.total(got["idle"])
    covered = sum(tracing.total(tracing.clip(cover, lo, hi)) for lo, hi in got["idle"])
    return 100.0 * (idle - covered) / idle if idle else None
