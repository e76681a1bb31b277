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
    # every limit of the cell is a number the comparison produces
    from benchmarks.harness import compare

    known = {f"{k}.{o}" for k in ("grad", "change", "direction") for o in compare.OPTIMIZERS}
    known |= {"ratio_steps", "player.recurrent", "moved.world_model"} | {
        f"loss.{name}" for name in ("world_model", "policy", "value", "observation", "reward", "continue", "state", "kl")
    }
    assert set(cell.limits) <= known
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
    """The sizes in the configuration's file are the composed recipe's own."""
    import sheeprl_tpu
    from sheeprl_tpu.config.loader import compose

    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    body = manifest.load_json(os.path.join(ROOT, entry["file"]))
    sheeprl_tpu.register_all()
    cfg = compose("config", [f"exp={body['program']['exp']}", "env=dummy"])
    m, wm = body["model"], cfg.algo.world_model
    assert m["recurrent"] == wm.recurrent_model.recurrent_state_size
    assert m["dense"] == cfg.algo.dense_units and m["mlp_layers"] == cfg.algo.mlp_layers
    assert m["hidden"] == wm.transition_model.hidden_size == wm.representation_model.hidden_size
    assert m["cnn_mult"] == wm.encoder.cnn_channels_multiplier
    assert (m["stoch"], m["discrete"]) == (wm.stochastic_size, wm.discrete_size)
    assert (m["batch"], m["sequence"], m["horizon"]) == (
        cfg.algo.per_rank_batch_size, cfg.algo.per_rank_sequence_length, cfg.algo.horizon)
    assert m["bins"] == wm.reward_model.bins == cfg.algo.critic.bins
    assert abs(m["gamma"] - cfg.algo.gamma) < 1e-12 and m["lmbda"] == cfg.algo.lmbda
    assert str(cfg.fabric.precision) == "bf16-mixed" and m["compute_dtype"] == "bfloat16"
    for name, opt in (("world_model", cfg.algo.world_model), ("actor", cfg.algo.actor), ("critic", cfg.algo.critic)):
        assert m["optim"][name] == {"lr": opt.optimizer.lr, "eps": opt.optimizer.eps, "clip": opt.clip_gradients}
    assert sorted(m["mlp_keys"]) == sorted(cfg.algo.mlp_keys.encoder)
