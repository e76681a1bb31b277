"""BENCHMARK.json and the data files it names: loading and validation.

Everything that belongs to one configuration, one traffic mix, one cell or one
per-layer metric is a file of its own, found from the entry's name:

- ``benchmarks/configs/<configuration>.json`` (the entry's ``file``)
- ``benchmarks/traffic/<traffic>.json``
- ``benchmarks/cells/<cell>.json`` (correctness limits, notes)
- ``benchmarks/layer_metrics/<metric>.py``
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmarks")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as fp:
        return json.load(fp)


def load_manifest(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with its configuration, traffic and limits."""

    def __init__(self, manifest: Dict[str, Any], name: str, root: str = ROOT) -> None:
        entries = {w["name"]: w for w in manifest["workloads"]}
        if name not in entries:
            raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json (have {sorted(entries)})")
        self.manifest = manifest
        self.entry = entries[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        config_entry = next(c for c in manifest["configs"] if c["name"] == self.entry["config"])
        self.config = load_json(os.path.join(root, config_entry["file"]))
        self.traffic = load_json(os.path.join(root, "benchmarks", "traffic", self.entry["traffic"] + ".json"))
        cell_file = os.path.join(root, "benchmarks", "cells", name + ".json")
        self.limits = load_json(cell_file).get("limits", {}) if os.path.exists(cell_file) else {}

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.manifest["end_to_end"] if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[Dict[str, Any]]:
        return [m for m in self.manifest["per_layer"] if self.name in m.get("workloads", [self.name])]


def load_reader(metric_name: str, root: str = ROOT) -> Callable[..., Optional[float]]:
    """The ``read(run) -> number | None`` of one per-layer metric's own file."""
    path = os.path.join(root, "benchmarks", "layer_metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric_" + re.sub(r"\W", "_", metric_name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def validate(manifest: Dict[str, Any], root: str = ROOT) -> List[str]:
    """Every breach of the benchmark's contract that can be seen without a
    run; an empty list means the manifest and its data files hold together."""
    errors: List[str] = []

    def name_ok(value: Any, what: str) -> None:
        if not isinstance(value, str) or not NAME_RE.match(value):
            errors.append(f"{what}: {value!r} is not a name")

    def line_ok(value: Any, what: str) -> None:
        if not isinstance(value, str) or not 1 <= len(value) <= 200 or "\n" in value or "\t" in value:
            errors.append(f"{what}: not one line of 1..200 characters")

    expected = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    if set(manifest) != expected:
        errors.append(f"top-level keys {sorted(manifest)} != {sorted(expected)}")
        return errors
    if not (isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51):
        errors.append("run_seconds must be a whole number in 1..51")
    paths = manifest["paths"]
    for word in manifest["command"]:
        line_ok(word, "command word")
    config_names = set()
    files = set()
    for cfg in manifest["configs"]:
        if set(cfg) != {"name", "source", "file", "reduced", "why"}:
            errors.append(f"config {cfg.get('name')}: keys {sorted(cfg)}")
            continue
        name_ok(cfg["name"], "config name")
        line_ok(cfg["source"], "config source")
        line_ok(cfg["why"], "config why")
        if cfg["name"] in config_names or cfg["file"] in files:
            errors.append(f"config {cfg['name']}: duplicate name or file")
        config_names.add(cfg["name"])
        files.add(cfg["file"])
        if not any(cfg["file"].startswith(p.rstrip("/") + "/") for p in paths):
            errors.append(f"config {cfg['name']}: file {cfg['file']} is not under paths")
        elif not os.path.exists(os.path.join(root, cfg["file"])):
            errors.append(f"config {cfg['name']}: file {cfg['file']} does not exist")
        else:
            body = load_json(os.path.join(root, cfg["file"]))
            if sorted(body.get("reduced", [])) != sorted(cfg["reduced"]):
                errors.append(f"config {cfg['name']}: reduced differs between BENCHMARK.json and its file")
        if len(cfg["reduced"]) > 16:
            errors.append(f"config {cfg['name']}: more than 16 reduced keys")
        for key in cfg["reduced"]:
            name_ok(key, f"config {cfg['name']} reduced key")
    cells: Dict[str, Dict[str, Any]] = {}
    pairs = set()
    for cell in manifest["workloads"]:
        if set(cell) != {"name", "config", "traffic", "chips", "why"}:
            errors.append(f"workload {cell.get('name')}: keys {sorted(cell)}")
            continue
        name_ok(cell["name"], "workload name")
        name_ok(cell["traffic"], "traffic name")
        line_ok(cell["why"], f"workload {cell['name']} why")
        if cell["config"] not in config_names:
            errors.append(f"workload {cell['name']}: unknown config {cell['config']}")
        if cell["chips"] not in (1, 4):
            errors.append(f"workload {cell['name']}: chips must be 1 or 4")
        if cell["name"] in cells or (cell["config"], cell["traffic"]) in pairs:
            errors.append(f"workload {cell['name']}: duplicate name or (config, traffic) pair")
        cells[cell["name"]] = cell
        pairs.add((cell["config"], cell["traffic"]))
        traffic = os.path.join(root, "benchmarks", "traffic", cell["traffic"] + ".json")
        if not os.path.exists(traffic):
            errors.append(f"workload {cell['name']}: no traffic file {traffic}")
    if sum(c["chips"] == 4 for c in cells.values()) > max(1, len(cells) // 4):
        errors.append("too many four-chip cells")
    for used in config_names - {c["config"] for c in cells.values()}:
        errors.append(f"config {used} is used by no cell")
    metric_names = set()
    e2e: Dict[str, Dict[str, Any]] = {}
    for metric in manifest["end_to_end"]:
        if not {"name", "unit", "better", "bound", "source"} <= set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}:
            errors.append(f"end_to_end {metric.get('name')}: keys {sorted(metric)}")
            continue
        name_ok(metric["name"], "metric name")
        if not UNIT_RE.match(metric["unit"]):
            errors.append(f"end_to_end {metric['name']}: unit {metric['unit']!r}")
        if metric["better"] not in ("lower", "higher"):
            errors.append(f"end_to_end {metric['name']}: better")
        if metric["source"] not in ("host_clock", "device_trace"):
            errors.append(f"end_to_end {metric['name']}: source {metric['source']}")
        if not 0.01 <= metric["bound"] <= 0.1:
            errors.append(f"end_to_end {metric['name']}: bound {metric['bound']} outside 1%..10%")
        if metric["name"] in metric_names:
            errors.append(f"duplicate metric {metric['name']}")
        metric_names.add(metric["name"])
        e2e[metric["name"]] = metric
    if "setup_s" not in e2e:
        errors.append("no setup_s among end_to_end")
    for metric in manifest["per_layer"]:
        if not {"name", "unit", "better", "source", "layer", "moves"} <= set(metric) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}:
            errors.append(f"per_layer {metric.get('name')}: keys {sorted(metric)}")
            continue
        name_ok(metric["name"], "metric name")
        line_ok(metric["layer"], f"per_layer {metric['name']} layer")
        if not UNIT_RE.match(metric["unit"]):
            errors.append(f"per_layer {metric['name']}: unit {metric['unit']!r}")
        if metric["better"] not in ("lower", "higher") or metric["source"] not in SOURCES:
            errors.append(f"per_layer {metric['name']}: better/source")
        if metric["name"] in metric_names:
            errors.append(f"duplicate metric {metric['name']}")
        metric_names.add(metric["name"])
        if metric["moves"] not in e2e:
            errors.append(f"per_layer {metric['name']}: moves {metric['moves']!r}, not an end-to-end metric")
            continue
        moved = e2e[metric["moves"]]
        for cell_name in metric.get("workloads", list(cells)):
            if cell_name not in cells:
                errors.append(f"per_layer {metric['name']}: unknown workload {cell_name}")
            elif cell_name not in moved.get("workloads", list(cells)):
                errors.append(f"per_layer {metric['name']}: {cell_name} does not report {metric['moves']}")
        if not os.path.exists(os.path.join(root, "benchmarks", "layer_metrics", metric["name"] + ".py")):
            errors.append(f"per_layer {metric['name']}: no reader file")
    for cell_name in cells:
        if not any(cell_name in m.get("workloads", [cell_name]) for m in manifest["per_layer"]):
            errors.append(f"workload {cell_name}: reports no per-layer metric")
    if len(json.dumps(manifest)) > 64 * 1024:
        errors.append("BENCHMARK.json over 64 KiB")
    return errors
