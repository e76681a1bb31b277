"""The set-up readers on traced micro runs of the three families through the
harness on the CPU: every reader reads a number, set-up's tree and its compile
spans are there, the compile spans agree with the harness's own listener, the
set-up table adds up to `setup_s`, and a program without the `setup` root (the
parent of the change that brought it) reads as nothing, never as a 0."""

import os
import sys
import time

import pytest

from conftest import ROOT, micro_cell
from benchmarks.harness import manifest, runner

sys.path.insert(0, os.path.join(ROOT, "benchmarks", "layer_metrics"))
import _setup  # noqa: E402

METRICS = ("setup.trace_lower_s", "setup.agent_s", "setup.recompiles", "setup.unattributed_share")
FAMILIES = ("dreamer_v3", "ppo_lm", "ppo_lm_hybrid")


def family_cell(family):
    if family == "dreamer_v3":
        return micro_cell("dreamer_v3_XL", "crafter_host")
    if family == "ppo_lm":
        from test_token_family import micro_cell as token_cell

        return token_cell()
    from test_hybrid_family import micro_cell as hybrid_cell

    return hybrid_cell()


@pytest.fixture(scope="module", params=FAMILIES)
def traced(request, tmp_path_factory):
    log = []
    run = runner.run_cell(family_cell(request.param), 2**31 + 38, 1.0, True, time.perf_counter(),
                          str(tmp_path_factory.mktemp("setup_run")), log.append)
    run["family"] = request.param
    return run


def read(name, run):
    return manifest.load_reader(name, ROOT)(run)


def test_every_set_up_reader_reads_a_number(traced):
    values = {name: read(name, traced) for name in METRICS}
    assert all(isinstance(v, (int, float)) for v in values.values()), values
    assert 0 <= values["setup.unattributed_share"] <= 100
    assert values["setup.recompiles"] == int(values["setup.recompiles"]) >= 0  # the donated-layout compile may not happen on the CPU
    assert 0 < values["setup.agent_s"] < traced["readings"]["setup_s"]
    assert 0 < values["setup.trace_lower_s"] < traced["readings"]["setup_s"]
    spans = {r["name"] for r in traced["spans"] if r.get("type") == "span"}
    tree = {"setup", "setup/config", "setup/runtime", "setup/envs", "setup/agent", "setup/player"}
    assert tree | {"compile/trace", "compile/lower", "compile/backend"} <= spans
    assert ("setup/replay" in spans) == (traced["family"] == "dreamer_v3")


def test_the_compile_spans_agree_with_the_harness_listener(traced):
    got = _setup.setup_spans(traced)
    backend = _setup.inside(got, "compile/backend")
    # the harness's listener also times the marker program, which compiles in the window's opening iteration
    assert sum(s["end"] - s["start"] for s in backend) == pytest.approx(traced["compile_seconds_setup"], abs=0.2)
    window = traced["window"]
    in_window = [s for s in got["spans"] if s["name"] == "compile/backend" and window.edges[0] <= s["end"] <= window.edges[-1]]
    assert len(in_window) == traced["compiles_in_window"]
    assert all({"fun", "cache", "seen"} <= set(s["args"]) for s in backend)


def test_the_set_up_table_adds_up_to_setup_s(traced):
    table = _setup.table(traced)
    rows = table["rows"]
    assert sum(rows.values()) == pytest.approx(traced["readings"]["setup_s"], abs=1e-6)
    assert rows["setup/agent"] > 0 and rows["before the entry"] > 0
    assert any(key.startswith("compile/backend jit(") for key in rows)


def test_a_program_without_the_setup_root_reads_as_nothing(traced):
    parent = dict(traced, spans=[r for r in traced["spans"] if r.get("name") != "setup"])
    assert [read(name, parent) for name in METRICS] == [None] * len(METRICS)
    untraced = dict(traced, spans=[])
    assert [read(name, untraced) for name in METRICS] == [None] * len(METRICS)
