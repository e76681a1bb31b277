"""Shared pieces of the benchmark's own tests: the repo root on the path, and
a micro-width cell built from a real cell's files (control flow and the
comparison at a size the CPU holds; the chip always runs the published widths)."""

import copy
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

MICRO_MODEL = dict(sequence=8, batch=4, horizon=3, stoch=4, discrete=4, recurrent=8, dense=8, hidden=8, mlp_layers=1, cnn_mult=2)
MICRO_OVERRIDES = {
    "algo.dense_units": 8,
    "algo.mlp_layers": 1,
    "algo.horizon": 3,
    "algo.world_model.encoder.cnn_channels_multiplier": 2,
    "algo.world_model.recurrent_model.recurrent_state_size": 8,
    "algo.world_model.representation_model.hidden_size": 8,
    "algo.world_model.transition_model.hidden_size": 8,
    "algo.world_model.discrete_size": 4,
    "algo.world_model.stochastic_size": 4,
    "algo.per_rank_batch_size": 4,
    "algo.per_rank_sequence_length": 8,
}
#: float32 readings at this size are 1e-5 or less (conftest's own runs); a
#: bfloat16 run of the program and a bfloat16 reference both read 1e-3 or more.
MICRO_LIMITS = {
    name: 2e-4
    for name in (
        "loss.world_model", "loss.policy", "loss.value", "grad.world_model", "grad.actor", "grad.critic",
        "change.world_model", "change.actor", "change.critic", "player.recurrent",
        "direction.world_model", "direction.actor", "direction.critic", "moved.world_model",
    )
}
MICRO_LIMITS["ratio_steps"] = 1.0


def micro_cell(config: str, traffic: str, precision: str = "32-true", ring: bool = False, **traffic_overrides):
    """``ring``: the same mix with the replay on the device and sampled inside
    the train jit (no cell of the benchmark has one yet; the harness's ring
    path and its fall-back check are held by these tests)."""
    from benchmarks.harness import manifest

    cfg = manifest.load_json(os.path.join(ROOT, "benchmarks", "configs", config + ".json"))
    tr = manifest.load_json(os.path.join(ROOT, "benchmarks", "traffic", traffic + ".json"))
    cfg["model"].update(MICRO_MODEL, compute_dtype="float32" if precision == "32-true" else "bfloat16")
    cfg["program"]["overrides"].update(MICRO_OVERRIDES)
    cfg["program"]["overrides"]["fabric.precision"] = precision
    tr["overrides"].update({"algo.learning_starts": 16, "buffer.size": 512})
    if ring:
        tr["overrides"].update({"buffer.device": True, "algo.replay_ratio": 1})
        tr["ring"] = True
    tr["overrides"].update(traffic_overrides)
    tr["env"].update(warm_lengths=[6, 14], length_low=20, length_high=40)
    limits = dict(MICRO_LIMITS)
    if ring:  # the step samples its own batch and trains from the first policy step on: nothing to ask again, no acting step before it
        del limits["moved.world_model"], limits["player.recurrent"]
    return types.SimpleNamespace(name="micro", chips=1, config=cfg, traffic=tr, limits=limits)


@pytest.fixture(scope="session")
def run_micro(tmp_path_factory):
    """``run(cell, seed=..., fault=None, trace=False)`` -> the harness's run
    record, with the chip requirement out of the way (the CPU stands in)."""
    import time

    from benchmarks.harness import runner
    from benchmarks.harness.adapters import dreamer_v3 as adapter

    def run(cell, seed=7, fault=None, seconds=1.0, trace=False):
        run_dir = str(tmp_path_factory.mktemp("bench_run"))
        log = []
        real = adapter.Record

        def record(*args, **kwargs):
            rec = real(*args, **kwargs)
            rec.fault = fault
            return rec

        adapter.Record = record
        try:
            out = runner.run_cell(cell, seed, seconds, trace, time.perf_counter(), run_dir, log.append)
        finally:
            adapter.Record = real
        out["log"] = log
        return out

    return run
