"""What the set-up readers share: set-up as the program's own spans saw it.

**Set-up's interval** runs from the harness's origin (`window.edges[0]` less
`readings["setup_s"]`: the process's first `perf_counter` reading) to the
start of the `loop/iteration` span in which the window opened. That iteration
holds the profiler's start in a traced run, which no untraced run pays, so it
is left out. The program stamps its spans with `perf_counter` and exports them
as microseconds from `perf_epoch_s` (the meta record of `telemetry.jsonl`), the
clock the harness reads too: nothing is shifted.

**The program's part** starts with its root span `setup` (the entry point's
first line) and ends with set-up. Inside the root: the phases `setup/config`,
`setup/runtime`, `setup/envs`, `setup/agent`, `setup/replay`, `setup/player`;
after it, warm `loop/iteration` spans; inside either, the compile spans
`compile/trace`, `compile/lower` and `compile/backend` (args `fun`; `cache` and
`seen` on the backend's). The loop thread is the one `loop/iteration` is
stamped on (as `_scopes.program_spans` takes it, without its window filter).

A run without the program's telemetry, or of a program that emits no `setup`
root (the parent of the PR that brought it), reads as nothing: every function
here returns None then.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from _scopes import self_times

from benchmarks.harness import tracing

ROOT = "setup"
ITERATION = "loop/iteration"
COMPILE = ("compile/trace", "compile/lower", "compile/backend")


def setup_spans(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """{origin, root, end, loop, spans}: set-up's interval, the root span, the
    loop thread's name, and every span of the run as {name, start, end,
    thread, args} on the `perf_counter` clock."""
    meta = next((r for r in run.get("spans", []) if r.get("type") == "meta"), {})
    epoch = meta.get("perf_epoch_s")
    if epoch is None:
        return None
    spans = [{"name": r["name"], "start": epoch + r["ts_us"] / 1e6, "end": epoch + (r["ts_us"] + r["dur_us"]) / 1e6,
              "thread": r.get("thread"), "args": r.get("args") or {}} for r in run["spans"] if r.get("type") == "span"]
    opened = run["window"].edges[0]
    root = next((s for s in spans if s["name"] == ROOT), None)
    holder = next((s for s in spans if s["name"] == ITERATION and s["start"] <= opened <= s["end"]), None)
    if root is None or holder is None:
        return None
    return {"origin": opened - run["readings"]["setup_s"], "root": root, "end": holder["start"], "loop": holder["thread"],
            "spans": spans}


def inside(got: Dict[str, Any], *names: str) -> List[Dict[str, Any]]:
    """Spans called one of ``names`` that started inside set-up."""
    return [s for s in got["spans"] if s["name"] in names and got["origin"] <= s["start"] < got["end"]]


def seconds_under(spans: List[Dict[str, Any]], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] under at least one of ``spans`` (nested spans count once)."""
    return tracing.total(tracing.union(tracing.clip([(s["start"], s["end"]) for s in spans], lo, hi)))


def uncovered(got: Dict[str, Any]) -> List[Tuple[float, float]]:
    """The intervals of the program's part of set-up under no span of the loop
    thread but the root and the iterations, which only hold the others."""
    lo, hi = got["root"]["start"], got["end"]
    cover = tracing.union(tracing.clip([(s["start"], s["end"]) for s in got["spans"] if s["thread"] == got["loop"]
                                        and s["name"] not in (ROOT, ITERATION)], lo, hi))
    return tracing.gaps(cover, lo, hi)


def table(run: Dict[str, Any], longest: float = 1.0) -> Optional[Dict[str, Any]]:
    """Set-up in rows that add up to `setup_s`: each instant of the loop thread
    goes to the innermost of the root, the iterations, the `setup/*` phases and
    the compile spans running then (`"<stage> <fun> cache=.. seen=.."` for a
    compile), the time before the root to `before the entry`, and the opening
    iteration's part before the window's edge to `opening iteration`; with
    each uncovered interval longer than ``longest`` seconds and the names of
    the loop thread's spans that end and start beside it."""
    got = setup_spans(run)
    if got is None:
        return None
    origin, root, end = got["origin"], got["root"], got["end"]
    events = [(origin, root["start"], "before the entry"), (root["start"], root["end"], "unattributed")]
    for s in got["spans"]:
        if s["thread"] != got["loop"] or s["end"] <= origin or s["start"] >= end:
            continue
        if s["name"] == ITERATION:
            key = "warm iterations"
        elif s["name"].startswith("setup/"):
            key = s["name"]
        elif s["name"] in COMPILE:
            key = f"{s['name']} {s['args'].get('fun', '')}"
            if s["name"] == "compile/backend":
                key += f" cache={s['args'].get('cache')} seen={s['args'].get('seen')}"
        else:
            continue
        events.append((max(s["start"], origin), min(s["end"], end), key))
    rows = self_times(events)
    rows["opening iteration"] = run["window"].edges[0] - end
    gaps = []
    main = [s for s in got["spans"] if s["thread"] == got["loop"] and s["name"] not in (ROOT, ITERATION)]
    for lo, hi in uncovered(got):
        if hi - lo > longest:
            before = max((s for s in main if s["end"] <= lo + 1e-6), key=lambda s: s["end"], default=None)
            after = min((s for s in main if s["start"] >= hi - 1e-6), key=lambda s: s["start"], default=None)
            gaps.append({"from": lo - origin, "seconds": hi - lo, "after": before and before["name"], "before": after and after["name"]})
    return {"rows": rows, "gaps": gaps}
