"""Helpers the per-layer readers share: spans of the program's own telemetry
inside the measured window. The program's span timestamps count from a wall
clock second that its trace export states; the window's first edge is read on
the wall clock too."""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional


def spans_in_window(run: Dict[str, Any], prefix: str) -> List[float]:
    """Durations in ms of every span whose name starts with ``prefix`` and
    that started inside the window."""
    epoch = run.get("span_epoch_wall")
    if epoch is None:
        return []
    lo = run["wall_at_open"]
    hi = lo + run["window"].elapsed
    return [
        r["dur_us"] / 1e3
        for r in run["spans"]
        if r.get("type") == "span" and r.get("name", "").startswith(prefix) and lo <= epoch + r["ts_us"] / 1e6 <= hi
    ]


def median_or_none(values: List[float]) -> Optional[float]:
    return statistics.median(values) if values else None
