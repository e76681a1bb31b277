"""`run.py` gives no result without the cell's chips."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def run_cell(cwd, name):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", name, "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def has_result_line(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]][:1])
def test_no_result_on_the_cpu(name):
    done = run_cell(ROOT, name)
    assert done.returncode != 0
    assert not has_result_line(done.stdout)
    assert "needs a TPU" in done.stderr


def test_no_result_from_a_bare_directory(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path, ignore=shutil.ignore_patterns(".runs", "__pycache__"))
    done = run_cell(str(tmp_path), BENCH["workloads"][0]["name"])
    assert done.returncode != 0
    assert not has_result_line(done.stdout)


def test_unknown_workload_is_refused():
    done = run_cell(ROOT, "no_such_cell")
    assert done.returncode != 0 and not has_result_line(done.stdout)
