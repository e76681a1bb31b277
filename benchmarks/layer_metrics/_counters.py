"""What the readers of the program's counters share: the totals the program
exports when it closes its telemetry (the `counter` records of
`telemetry.jsonl`: one per name, summed over the run). A run without
telemetry, or of a program that counts no such thing, reads as nothing."""

from __future__ import annotations

from typing import Any, Dict, Optional


def counter(run: Dict[str, Any], name: str) -> Optional[float]:
    for record in run.get("spans") or []:
        if record.get("type") == "counter" and record.get("name") == name:
            return float(record["value"])
    return None


def ratio(run: Dict[str, Any], over: str, under: str, scale: float = 100.0) -> Optional[float]:
    top, bottom = counter(run, over), counter(run, under)
    return scale * top / bottom if top is not None and bottom else None
