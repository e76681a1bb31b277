"""Sharded-learner acceptance tests (ISSUE 20 tentpole).

On the virtual 8-device CPU mesh (tests/conftest.py) the fused Anakin lane
runs the SAME shard_map'd superstep program as on a single device — per-env
PRNG streams are keyed by global env ids and ring sampling draws global
uniform indices under ``jax_threefry_partitionable`` — so an 8-shard run must
reproduce the 1-device run: progress counters exactly, trained params within
the float tolerance documented below.

Tolerance: the train jits are GSPMD data-parallel, so gradient reductions
split across shards and float summation order differs from the single-device
schedule. Low-bit deltas compound over gradient steps; the short budgets here
keep them within rtol=2e-4 / atol=1e-5 (howto/sharded_training.md).
"""

import glob
import json
import os
import sys

import jax
import numpy as np
import pytest

from sheeprl_tpu.cli import run
from sheeprl_tpu.core import fused_loop
from sheeprl_tpu.utils.checkpoint import load_checkpoint

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

# The recipes and the tolerance live with chip_smoke.py, which makes the same
# 1 <-> N comparison on four real chips (`python chip_smoke.py --chips 4`).
import chip_smoke  # noqa: E402

NEEDS_8 = pytest.mark.skipif(jax.device_count() < 8, reason="needs the 8-device CPU platform")

RTOL = chip_smoke.PARITY_RTOL
ATOL = chip_smoke.PARITY_ATOL


@pytest.fixture(autouse=True)
def _chdir_tmp(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)


def find_checkpoints(root):
    ckpts = []
    for r, dirs, _files in os.walk(root):
        for d in dirs:
            if d.startswith("ckpt_") and d.endswith(".ckpt"):
                ckpts.append(os.path.join(r, d))
    return sorted(ckpts)


def sac_shard_overrides(devices, **extra):
    return chip_smoke.sac_shard_overrides(devices, "cpu", **extra)


def ppo_shard_overrides(devices, **extra):
    return chip_smoke.ppo_shard_overrides(devices, "cpu", **extra)


def _assert_tree_close(a, b, rtol=RTOL, atol=ATOL):
    leaves_a, treedef_a = jax.tree_util.tree_flatten(a)
    leaves_b, treedef_b = jax.tree_util.tree_flatten(b)
    assert treedef_a == treedef_b
    for x, y in zip(leaves_a, leaves_b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=rtol, atol=atol)


def _run_and_snapshot(tmp_path, overrides, seen_ckpts):
    run(overrides)
    stats = fused_loop.last_run_stats()
    ckpts = [c for c in find_checkpoints(tmp_path / "logs") if c not in seen_ckpts]
    assert ckpts, "run wrote no checkpoint"
    seen_ckpts.update(ckpts)
    return stats, load_checkpoint(ckpts[-1])


@NEEDS_8
class TestShardedBitTolerance:
    def test_sac_anakin_8_shards_match_single_device(self, tmp_path):
        seen = set()
        stats1, state1 = _run_and_snapshot(tmp_path, sac_shard_overrides(1), seen)
        stats8, state8 = _run_and_snapshot(tmp_path, sac_shard_overrides(8), seen)
        # Counters are schedule facts: they must match EXACTLY.
        assert stats1 == stats8
        assert state1["iter_num"] == state8["iter_num"]
        assert state1["batch_size"] == state8["batch_size"]
        assert state1["ratio"] == state8["ratio"]
        _assert_tree_close(state1["agent"], state8["agent"])

    def test_ppo_anakin_8_shards_match_single_device(self, tmp_path):
        seen = set()
        stats1, state1 = _run_and_snapshot(tmp_path, ppo_shard_overrides(1), seen)
        stats8, state8 = _run_and_snapshot(tmp_path, ppo_shard_overrides(8), seen)
        assert stats1 == stats8
        assert state1["iter_num"] == state8["iter_num"]
        assert state1["batch_size"] == state8["batch_size"]
        _assert_tree_close(state1["agent"], state8["agent"])

    def test_sac_indivisible_envs_fall_back_to_replicated(self, tmp_path):
        """6 envs on 8 shards can't split: the lane must warn and finish on
        the replicated path with the same counters contract."""
        with pytest.warns(UserWarning, match="not divisible"):
            run(
                sac_shard_overrides(
                    8,
                    **{
                        "env.num_envs": 6,
                        "algo.total_steps": 72,
                        "algo.learning_starts": 24,
                        "algo.per_rank_batch_size": 6,
                        "checkpoint.save_last": False,
                    },
                )
            )
        stats = fused_loop.last_run_stats()
        assert stats["env_steps"] == 72


@NEEDS_8
class TestShardedGoodput:
    def test_sac_anakin_shard8_publishes_per_shard_mfu(self, tmp_path):
        run(
            sac_shard_overrides(
                8,
                **{
                    "checkpoint.save_last": False,
                    "telemetry.enabled": True,
                    "metric.log_level": 1,
                    "metric.log_every": 1,
                },
            )
        )
        jsonl = glob.glob(
            os.path.join(str(tmp_path), "logs", "runs", "**", "telemetry.jsonl"), recursive=True
        )
        assert jsonl, "telemetry.jsonl missing"
        lines = [json.loads(line) for line in open(jsonl[-1])]
        counters = [rec["values"] for rec in lines if rec["type"] == "counters"]
        with_shard = [c for c in counters if any("/shard/" in k for k in c)]
        assert with_shard, f"no perf/shard gauges; keys={sorted(counters[-1]) if counters else []}"
        gauges = with_shard[-1]
        shard = {k: v for k, v in gauges.items() if "/shard/" in k and k.endswith("/mfu")}
        assert len(shard) == 8
        assert all(k.startswith("perf/shard/data=") for k in shard)
        # Acceptance: per-shard MFUs sum to the aggregate.
        assert sum(shard.values()) == pytest.approx(gauges["perf/mfu"], abs=1e-6)
        assert any(rec["type"] == "mesh" for rec in lines)
        assert any(rec["type"] == "param_layouts" for rec in lines)
