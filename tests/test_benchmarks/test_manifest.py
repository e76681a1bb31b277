"""BENCHMARK.json and every data file it names hold together."""

import json
import os

import pytest

from conftest import ROOT
from benchmarks.harness import manifest

BENCH = manifest.load_manifest(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["per_layer"]]


def test_manifest_validates():
    assert manifest.validate(BENCH, ROOT) == []


@pytest.mark.parametrize("breach", ["unit", "moves", "source", "bound", "name"])
def test_validation_catches(breach):
    bad = json.loads(json.dumps(BENCH))
    if breach == "unit":
        bad["per_layer"][0]["unit"] = "seconds per iteration"
    elif breach == "moves":
        bad["per_layer"][0]["moves"] = "no_such_metric"
    elif breach == "source":
        bad["end_to_end"][0]["source"] = "program_span"
    elif breach == "bound":
        bad["end_to_end"][0]["bound"] = 0.5
    else:
        bad["workloads"][0]["name"] = "a name with spaces"
    assert manifest.validate(bad, ROOT) != []


@pytest.mark.parametrize("name", CELLS)
def test_cell_files(name):
    cell = manifest.Cell(BENCH, name, ROOT)
    assert cell.chips in (1, 4)
    assert {"overrides", "env", "why"} <= set(cell.traffic)
    assert {"model", "program", "adapter", "reference", "reduced", "assumed", "source"} <= set(cell.config)
    assert "setup_s" in {m["name"] for m in cell.end_to_end()}
    assert len(cell.end_to_end()) >= 2 and len(cell.per_layer()) >= 1
    # every limit of the cell is a number the comparison produces, by the names the adapter gives them
    from benchmarks.harness import compare

    adapter = compare.load_adapter(cell.config)
    known = {f"{k}.{group}" for k in ("grad", "change", "direction") for group in adapter.GROUPS}
    known |= {"ratio_steps"} | {f"loss.{name}" for name in adapter.LOSSES}
    known |= {getattr(adapter, name) for name in ("MOVED", "ACTING") if hasattr(adapter, name)}
    assert set(cell.limits) <= known
    # the files the configuration names are there
    for kind in ("reference", "flops"):
        assert os.path.exists(os.path.join(ROOT, "benchmarks", kind, cell.config[kind] + ".py"))
    assert len(cell.entry["why"]) <= 200


@pytest.mark.parametrize("name", METRICS)
def test_reader_exists(name):
    read = manifest.load_reader(name, ROOT)
    assert callable(read)
    # a reader that finds nothing to read returns nothing
    empty = {"spans": [], "trace": None, "span_epoch_wall": None, "compiles_in_window": 0, "compile_seconds_setup": 0.0}
    if name.split(".")[0] not in ("setup", "window"):
        assert read(empty) is None


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_configuration_is_the_recipe(config):
    """The sizes in the configuration's file are the composed recipe's own, as
    the configuration's adapter reads them from the recipe (`recipe_sizes`)."""
    import sheeprl_tpu
    from benchmarks.harness import compare
    from sheeprl_tpu.config.loader import compose

    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    body = manifest.load_json(os.path.join(ROOT, entry["file"]))
    sheeprl_tpu.register_all()
    cfg = compose("config", [f"exp={body['program']['exp']}", "env=dummy"])
    sizes = compare.load_adapter(body).recipe_sizes(cfg)
    assert sizes, "the adapter compares no size"
    for key, recipe in sizes.items():
        stated = body["model"][key]
        if isinstance(recipe, list):  # names only: the file's dict gives each a width the recipe does not state
            assert sorted(stated) == recipe, key
        elif isinstance(recipe, float):
            assert abs(stated - recipe) < 1e-12, key
        else:
            assert stated == recipe, key
