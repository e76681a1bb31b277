"""Orchestration: walk paths, run per-file rules, then project rules.

Two passes per scan:

1. **file pass** — every ``.py`` is parsed into a LintContext and the
   per-file rules run against it. Files are independent, so this pass fans
   out over a thread pool (``jobs``); parsing and AST walking release enough
   of the interpreter between files that the full-repo scan stays in
   single-digit seconds.
2. **project pass** — the parsed contexts are assembled into one
   :class:`~sheeprl_tpu.analysis.project.AnalysisContext` (module graph +
   symbol table + call edges + jit closure) and each ProjectRule runs once
   over the whole program.

Per-rule wall time is accumulated into ``LintResult.rule_timings`` so an
analyzer perf regression is visible (``--stats``), not felt.
"""

from __future__ import annotations

import ast
import os
import subprocess
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

from sheeprl_tpu.analysis.context import LintContext
from sheeprl_tpu.analysis.finding import Finding
from sheeprl_tpu.analysis.project import AnalysisContext
from sheeprl_tpu.analysis.registry import ProjectRule, all_rules

_SKIP_DIRS = {"__pycache__", ".git", ".venv", "node_modules", "build", "dist"}


@dataclass
class LintResult:
    findings: List[Finding] = field(default_factory=list)
    files_scanned: int = 0
    suppressed: int = 0
    rule_timings: Dict[str, float] = field(default_factory=dict)
    parse_s: float = 0.0
    total_s: float = 0.0


def _parse_finding(path: str, exc: SyntaxError) -> Finding:
    return Finding(
        rule="GL000",
        path=path,
        line=exc.lineno or 1,
        col=(exc.offset or 0) + 1,
        message=f"syntax error: {exc.msg}",
        snippet=(exc.text or "").strip(),
    )


def lint_source(
    source: str, path: str = "<string>", rules: Optional[Iterable[str]] = None
) -> Tuple[List[Finding], int]:
    """Lint one source blob (single-module project). Returns
    (findings, suppressed count).

    A syntax error surfaces as a GL000 parse finding rather than an
    exception: the linter must be able to report on a broken tree-in-progress
    without taking CI down with a traceback.
    """
    try:
        tree = ast.parse(source)
    except SyntaxError as exc:
        return [_parse_finding(path, exc)], 0
    ctx = LintContext(path=path, source=source, tree=tree)
    result = _run_rules([ctx], rules)
    return result.findings, result.suppressed


def lint_file(
    path: str, display_path: Optional[str] = None, rules: Optional[Iterable[str]] = None
) -> Tuple[List[Finding], int]:
    with open(path, "r", encoding="utf-8") as fh:
        source = fh.read()
    return lint_source(source, path=display_path or path, rules=rules)


def iter_python_files(paths: Iterable[str]) -> List[str]:
    out: List[str] = []
    for path in paths:
        if os.path.isfile(path):
            if path.endswith(".py"):
                out.append(path)
            continue
        for root, dirnames, filenames in os.walk(path):
            dirnames[:] = sorted(d for d in dirnames if d not in _SKIP_DIRS)
            for name in sorted(filenames):
                if name.endswith(".py"):
                    out.append(os.path.join(root, name))
    return out


def _display_path(abs_path: str, root: str) -> str:
    try:
        display = os.path.relpath(abs_path, root)
    except ValueError:  # different drive (windows)
        display = abs_path
    if display.startswith(".."):
        display = abs_path
    return display.replace(os.sep, "/")


def _run_rules(
    contexts: List[LintContext],
    rules: Optional[Iterable[str]],
    jobs: int = 1,
    timings: Optional[Dict[str, float]] = None,
) -> LintResult:
    """File pass (parallel over contexts) then project pass (once)."""
    selected = set(rules) if rules is not None else None
    timings = timings if timings is not None else {}
    file_rules = [
        r
        for r in all_rules()
        if not isinstance(r, ProjectRule) and (selected is None or r.id in selected)
    ]
    proj_rules = [
        r
        for r in all_rules()
        if isinstance(r, ProjectRule) and (selected is None or r.id in selected)
    ]

    def run_file(ctx: LintContext) -> Dict[str, float]:
        local: Dict[str, float] = {}
        for rule in file_rules:
            t0 = time.perf_counter()
            rule.check(ctx)
            local[rule.id] = local.get(rule.id, 0.0) + (time.perf_counter() - t0)
        return local

    if jobs > 1 and len(contexts) > 1:
        with ThreadPoolExecutor(max_workers=jobs) as pool:
            per_file = list(pool.map(run_file, contexts))
    else:
        per_file = [run_file(ctx) for ctx in contexts]
    for local in per_file:
        for rule_id, dt in local.items():
            timings[rule_id] = timings.get(rule_id, 0.0) + dt

    result = LintResult()
    if proj_rules:
        actx = AnalysisContext(contexts)
        for rule in proj_rules:
            t0 = time.perf_counter()
            rule.check_project(actx)
            dt = time.perf_counter() - t0
            timings[rule.id] = timings.get(rule.id, 0.0) + dt
        result.findings.extend(actx.external_findings)
        result.suppressed += actx.external_suppressed

    for ctx in contexts:
        result.findings.extend(ctx.findings)
        result.suppressed += ctx.suppressed_count
    result.findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    result.rule_timings = timings
    return result


def default_jobs() -> int:
    return min(8, os.cpu_count() or 1)


def lint_paths_ex(
    paths: Iterable[str],
    root: Optional[str] = None,
    rules: Optional[Iterable[str]] = None,
    jobs: Optional[int] = None,
) -> LintResult:
    """Lint every .py under `paths`. Finding paths are made relative to
    `root` (default: cwd) so they are stable across machines."""
    t_start = time.perf_counter()
    root = os.path.abspath(root or os.getcwd())
    files = iter_python_files(paths)
    jobs = default_jobs() if jobs is None else max(1, jobs)
    timings: Dict[str, float] = {}

    parse_findings: List[Finding] = []
    contexts: List[LintContext] = []

    def load(file_path: str) -> Optional[LintContext]:
        abs_path = os.path.abspath(file_path)
        display = _display_path(abs_path, root)
        with open(abs_path, "r", encoding="utf-8") as fh:
            source = fh.read()
        try:
            tree = ast.parse(source)
        except SyntaxError as exc:
            parse_findings.append(_parse_finding(display, exc))
            return None
        return LintContext(path=display, source=source, tree=tree)

    t0 = time.perf_counter()
    if jobs > 1 and len(files) > 1:
        with ThreadPoolExecutor(max_workers=jobs) as pool:
            loaded = list(pool.map(load, files))
    else:
        loaded = [load(f) for f in files]
    contexts = [c for c in loaded if c is not None]
    parse_s = time.perf_counter() - t0

    result = _run_rules(contexts, rules, jobs=jobs, timings=timings)
    result.findings.extend(parse_findings)
    result.findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    result.files_scanned = len(files)
    result.parse_s = parse_s
    result.total_s = time.perf_counter() - t_start
    return result


def lint_paths(
    paths: Iterable[str],
    root: Optional[str] = None,
    rules: Optional[Iterable[str]] = None,
) -> Tuple[List[Finding], int, int]:
    """Compatibility wrapper: (findings, files scanned, suppressed)."""
    result = lint_paths_ex(paths, root=root, rules=rules)
    return result.findings, result.files_scanned, result.suppressed


def changed_files(ref: str, cwd: Optional[str] = None) -> Optional[List[str]]:
    """Paths changed vs `ref` per git (committed + staged + worktree), or
    None when git/ref is unavailable — callers fall back to a full scan."""
    try:
        proc = subprocess.run(
            ["git", "diff", "--name-only", ref, "--"],
            capture_output=True,
            text=True,
            cwd=cwd,
            timeout=30,
        )
    except (OSError, subprocess.TimeoutExpired):
        return None
    if proc.returncode != 0:
        return None
    return [line.strip() for line in proc.stdout.splitlines() if line.strip()]
