"""Roofline goodput accounting (telemetry/perf.py): the cost harvest, the
hardware-ceiling resolution, the accountant's interval math, and the e2e
acceptance contract — perf/mfu + the compute/infeed/host breakdown (summing
to ~1) in telemetry.jsonl AND /metrics for sac + dreamer_v3, host and fused
lanes."""

import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.cli import run
from sheeprl_tpu.telemetry import Telemetry
from sheeprl_tpu.telemetry import tracer as tracer_mod
from sheeprl_tpu.telemetry.perf import (
    PEAK_TABLE,
    PerfAccountant,
    jit_cost,
    peaks_for_device_kind,
    resolve_peaks,
)
from sheeprl_tpu.telemetry.registry import MetricsRegistry, default_registry
from sheeprl_tpu.telemetry.tracer import Tracer

pytestmark = pytest.mark.telemetry


# ------------------------------------------------------------------ ceilings
class TestResolvePeaks:
    def test_explicit_override_wins(self):
        peaks = resolve_peaks(peak_flops=1e12, peak_bytes_per_s=2e11, probe=False)
        assert peaks == {"flops": 1e12, "bytes_per_s": 2e11, "source": "override"}

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("SHEEPRL_PERF_PEAK_FLOPS", "3e12")
        monkeypatch.setenv("SHEEPRL_PERF_PEAK_BW_GBPS", "100")
        peaks = resolve_peaks(probe=False)
        assert peaks["source"] == "override"
        assert peaks["flops"] == pytest.approx(3e12)
        assert peaks["bytes_per_s"] == pytest.approx(100e9)

    def test_table_match_on_device_kind(self):
        peaks = resolve_peaks(backend="tpu", device_kind="TPU v4", probe=False)
        assert peaks["source"] == "table"
        row = next(r for r in PEAK_TABLE if r[0] == "v4")
        assert peaks["flops"] == row[1]
        assert peaks["bytes_per_s"] == row[2]

    def test_cpu_probe_measures_a_positive_ceiling(self):
        peaks = resolve_peaks(backend="cpu", device_kind="generic-cpu", probe=True)
        assert peaks["source"] == "probe"
        assert peaks["flops"] > 0.0
        assert peaks["bytes_per_s"] > 0.0
        # Cached: the second resolve must not re-run the ~100ms micro-kernels.
        t0 = time.perf_counter()
        again = resolve_peaks(backend="cpu", device_kind="generic-cpu", probe=True)
        assert time.perf_counter() - t0 < 0.05
        assert again["flops"] == peaks["flops"]

    def test_cpu_without_probe_resolves_nothing(self):
        peaks = resolve_peaks(backend="cpu", device_kind="generic-cpu", probe=False)
        assert peaks == {"flops": 0.0, "bytes_per_s": 0.0, "source": "none"}

    def test_v5e_row_matches_the_kind_the_chip_reports(self):
        # A TPU v5e's device_kind is "TPU v5 lite" (chip_smoke.py prints it).
        peaks = resolve_peaks(backend="tpu", device_kind="TPU v5 lite", probe=False)
        assert peaks == {"flops": 197e12, "bytes_per_s": 819e9, "source": "table"}
        assert peaks_for_device_kind("TPU v5 lite") == peaks_for_device_kind("TPU v5e") == (197e12, 819e9)

    def test_unknown_accelerator_kind_is_reported_by_name(self):
        # Strict callers (chip_smoke.py) get an error naming the
        # kind; a training run gets a warning naming it — never silent zeros.
        with pytest.raises(LookupError, match="mystery-9000"):
            peaks_for_device_kind("mystery-9000")
        with pytest.warns(UserWarning, match="mystery-9000"):
            peaks = resolve_peaks(backend="rocm", device_kind="mystery-9000", probe=False)
        assert peaks == {"flops": 0.0, "bytes_per_s": 0.0, "source": "none"}


# ------------------------------------------------------------------- harvest
class TestJitCost:
    def test_matmul_flops_match_the_textbook_count(self):
        f = jax.jit(lambda a, b: a @ b)
        a = jnp.ones((64, 64))
        b = jnp.ones((64, 64))
        f(a, b)
        cost = jit_cost(f, (a, b))
        assert cost is not None
        assert cost["flops"] == pytest.approx(2 * 64**3, rel=0.05)
        assert cost["bytes"] > 0.0

    def test_spec_harvest_survives_donation(self):
        # The real loops donate their buffers: the harvest must work from
        # ShapeDtypeStructs captured BEFORE dispatch, never the live arrays.
        f = jax.jit(lambda x: x * 2.0, donate_argnums=0)
        x = jnp.ones((128,))
        acc = PerfAccountant(enabled=True, registry=MetricsRegistry(), probe=False)
        acc.note("train/step", f, (x,))
        f(x)  # x is donated and dead now
        costs = acc.costs()
        assert "train/step" in costs
        assert costs["train/step"]["flops"] > 0.0

    def test_non_jit_callable_degrades_to_none(self):
        assert jit_cost(lambda x: x, (1,)) is None


# ---------------------------------------------------------------- accountant
class TestPerfAccountant:
    def test_disabled_is_a_total_noop(self):
        acc = PerfAccountant(enabled=False)
        acc.note("k", jax.jit(lambda x: x), (jnp.ones(2),))
        with acc.infeed():
            pass
        acc.add_compute(1.0)
        assert acc.publish() == {}
        assert acc.costs() == {}

    def test_publish_emits_breakdown_summing_to_one(self):
        reg = MetricsRegistry()
        acc = PerfAccountant(enabled=True, registry=reg, probe=False, peak_flops=1e12, peak_hbm_gbps=100.0)
        f = jax.jit(lambda a, b: a @ b)
        a = jnp.ones((32, 32))
        b = jnp.ones((32, 32))
        f(a, b)
        live = Tracer()
        for _ in range(3):
            acc.note("train/step", f, (a, b))
            with acc.infeed():
                time.sleep(0.01)
            f(a, b).block_until_ready()
        acc.add_compute(0.005)
        gauges = acc.publish(tracer=live)
        fractions = [gauges[f"perf/step_time_breakdown_{lane}"] for lane in ("compute", "infeed", "host")]
        assert sum(fractions) == pytest.approx(1.0, abs=1e-6)
        assert all(0.0 <= frac <= 1.0 for frac in fractions)
        assert gauges["perf/step_time_breakdown_infeed"] > 0.0
        assert gauges["perf/step_time_breakdown_compute"] > 0.0
        assert gauges["perf/mfu"] > 0.0
        assert gauges["perf/hbm_bw_util"] > 0.0
        assert gauges["perf/peak_flops"] == pytest.approx(1e12)
        # Published to the tracer (telemetry.jsonl path) ...
        assert "perf/mfu" in live.counters()
        # ... and the registry (/metrics path).
        assert reg.gauge("perf/mfu").value == pytest.approx(gauges["perf/mfu"])
        assert acc.last_gauges == gauges

    def test_interval_is_differenced_not_cumulative(self):
        acc = PerfAccountant(enabled=True, registry=MetricsRegistry(), probe=False, peak_flops=1e12, peak_hbm_gbps=1.0)
        f = jax.jit(lambda a, b: a @ b)
        a = jnp.ones((32, 32))
        b = jnp.ones((32, 32))
        f(a, b)
        acc.note("k", f, (a, b), steps=4.0)
        first = acc.publish()
        assert first["perf/flops_per_s"] > 0.0
        # No new dispatches: the second interval must read ~zero work, not
        # re-bill the first interval's FLOPs.
        time.sleep(0.01)
        second = acc.publish()
        assert second["perf/flops_per_s"] == 0.0
        assert second["perf/train_steps_per_s"] == 0.0

    def test_harvest_cap_bounds_lower_compile_work(self):
        acc = PerfAccountant(enabled=True, registry=MetricsRegistry(), probe=False, max_harvests=2)
        f = jax.jit(lambda x: x + 1)
        x = jnp.ones((4,))
        f(x)
        for i in range(5):
            acc.note(f"k{i}", f, (x,))
        assert len(acc.costs()) == 2

    def test_note_without_fn_only_counts(self):
        acc = PerfAccountant(enabled=True, registry=MetricsRegistry(), probe=False)
        acc.note("k", steps=2.0)
        acc.note("k", steps=2.0)
        gauges = acc.publish()
        assert gauges["perf/train_steps_per_s"] > 0.0
        assert acc.costs() == {}


def test_telemetry_facade_threads_the_accountant():
    cfg = {
        "telemetry": {
            "enabled": True,
            "perf": {"enabled": True, "probe": False, "peak_flops": 1e12, "peak_hbm_gbps": 50.0},
        }
    }
    tele = Telemetry.from_config(cfg)
    assert tele.perf.enabled
    assert tele.perf.peaks()["source"] == "override"
    # Pinned off decouples from telemetry.enabled.
    cfg["telemetry"]["perf"]["enabled"] = False
    assert not Telemetry.from_config(cfg).perf.enabled
    # Unpinned (null) follows telemetry.enabled.
    cfg["telemetry"]["perf"]["enabled"] = None
    assert Telemetry.from_config(cfg).perf.enabled


# ------------------------------------------------------------- e2e contract
def _tiny_sac(**extra):
    args = [
        "exp=sac",
        "env=dummy",
        "env.id=continuous_dummy",
        "env.wrapper.id=continuous_dummy",
        "env.num_envs=2",
        "env.sync_env=True",
        "env.capture_video=False",
        "algo.per_rank_batch_size=4",
        "algo.learning_starts=4",
        "algo.hidden_size=8",
        "algo.run_test=False",
        "algo.total_steps=32",
        "buffer.memmap=False",
        "buffer.size=64",
        "checkpoint.every=0",
        "fabric.accelerator=cpu",
        "telemetry.enabled=True",
        "metric.log_level=1",
        "metric.log_every=1",
    ]
    for k, v in extra.items():
        args.append(f"{k}={v}")
    return args


def _tiny_dreamer_v3(**extra):
    args = [
        "exp=dreamer_v3",
        "env=dummy",
        "dry_run=True",
        "env.num_envs=2",
        "env.sync_env=True",
        "env.capture_video=False",
        "env.screen_size=64",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.per_rank_batch_size=2",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=8",
        "algo.world_model.representation_model.hidden_size=8",
        "algo.world_model.transition_model.hidden_size=8",
        "algo.world_model.stochastic_size=4",
        "algo.world_model.discrete_size=4",
        "algo.horizon=2",
        "algo.per_rank_sequence_length=1",
        "algo.learning_starts=0",
        "algo.run_test=False",
        "buffer.memmap=False",
        "checkpoint.every=0",
        "fabric.accelerator=cpu",
        "telemetry.enabled=True",
        "metric.log_level=1",
        "metric.log_every=1",
    ]
    for k, v in extra.items():
        args.append(f"{k}={v}")
    return args


def _sac_anakin(**extra):
    args = [
        "exp=sac_anakin",
        "env.num_envs=2",
        "env.sync_env=True",
        "algo.fused_superstep_steps=8",
        "algo.fused_train_steps=4",
        "algo.total_steps=96",
        "algo.learning_starts=32",
        "algo.per_rank_batch_size=4",
        "algo.hidden_size=8",
        "algo.run_test=False",
        "algo.fused_rollout=True",
        "buffer.size=256",
        "buffer.memmap=False",
        "checkpoint.every=0",
        "fabric.accelerator=cpu",
        "fabric.devices=1",
        "telemetry.enabled=True",
        "metric.log_level=1",
        "metric.log_every=1",
    ]
    for k, v in extra.items():
        args.append(f"{k}={v}")
    return args


def _dreamer_v3_anakin(**extra):
    args = [
        "exp=dreamer_v3_anakin",
        "env.num_envs=2",
        "algo.fused_superstep_steps=8",
        "algo.fused_train_steps=2",
        "algo.total_steps=48",
        "algo.learning_starts=16",
        "algo.per_rank_batch_size=2",
        "algo.per_rank_sequence_length=8",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=8",
        "algo.world_model.representation_model.hidden_size=8",
        "algo.world_model.transition_model.hidden_size=8",
        "algo.world_model.stochastic_size=4",
        "algo.world_model.discrete_size=4",
        "algo.horizon=2",
        "algo.run_test=False",
        "buffer.size=256",
        "buffer.memmap=False",
        "checkpoint.every=0",
        "fabric.accelerator=cpu",
        "fabric.devices=1",
        "telemetry.enabled=True",
        "metric.log_level=1",
        "metric.log_every=1",
    ]
    for k, v in extra.items():
        args.append(f"{k}={v}")
    return args


def _perf_gauges_from_jsonl(root):
    jsonl = glob.glob(os.path.join(root, "logs", "runs", "**", "telemetry.jsonl"), recursive=True)
    assert jsonl, "telemetry.jsonl missing"
    lines = [json.loads(line) for line in open(jsonl[-1])]
    counters = [rec["values"] for rec in lines if rec["type"] == "counters"]
    assert counters, "no counters records"
    with_perf = [c for c in counters if "perf/mfu" in c]
    assert with_perf, f"no perf/mfu in any counters record; keys={sorted(counters[-1])}"
    meta = next(rec for rec in lines if rec["type"] == "meta")
    return with_perf[-1], meta


def _assert_perf_contract(root):
    """The PR's acceptance criterion, applied to one finished run: perf/mfu
    and the step-time breakdown in telemetry.jsonl with fractions summing to
    ~1, the same gauges scrape-able from the /metrics registry, and the meta
    line carrying the git + host provenance stamps."""
    gauges, meta = _perf_gauges_from_jsonl(root)
    assert gauges["perf/mfu"] > 0.0
    fractions = [gauges[f"perf/step_time_breakdown_{lane}"] for lane in ("compute", "infeed", "host")]
    assert sum(fractions) == pytest.approx(1.0, abs=1e-6)
    assert all(0.0 <= frac <= 1.0 for frac in fractions)
    # /metrics: the default registry carries the same gauge family, and the
    # Prometheus rendering exposes it under the sanitized name.
    text = default_registry().prometheus_text()
    assert "perf_mfu" in text
    assert "perf_step_time_breakdown_compute" in text
    # Provenance stamps (satellite): git sha + dirty flag + host fingerprint.
    assert set(meta["git"]) == {"sha", "dirty"}
    assert meta["host"]["hostname"]
    return gauges


@pytest.fixture(autouse=True)
def _chdir_tmp(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)


class TestGoodputEndToEnd:
    def test_sac_host_lane_emits_goodput(self, tmp_path):
        run(_tiny_sac())
        gauges = _assert_perf_contract(str(tmp_path))
        # The host lane wraps env interaction in perf.infeed().
        assert gauges["perf/step_time_breakdown_infeed"] > 0.0

    def test_sac_fused_lane_emits_goodput(self, tmp_path):
        run(_sac_anakin())
        _assert_perf_contract(str(tmp_path))

    def test_dreamer_v3_host_lane_emits_goodput(self, tmp_path):
        run(_tiny_dreamer_v3())
        gauges = _assert_perf_contract(str(tmp_path))
        assert gauges["perf/step_time_breakdown_infeed"] > 0.0

    def test_dreamer_v3_fused_lane_emits_goodput(self, tmp_path):
        run(_dreamer_v3_anakin())
        _assert_perf_contract(str(tmp_path))

    def test_perf_disable_keeps_jsonl_clean(self, tmp_path):
        run(_tiny_sac(**{"telemetry.perf.enabled": "False"}))
        jsonl = glob.glob(
            os.path.join(str(tmp_path), "logs", "runs", "**", "telemetry.jsonl"), recursive=True
        )
        lines = [json.loads(line) for line in open(jsonl[-1])]
        counters = [rec["values"] for rec in lines if rec["type"] == "counters"]
        assert counters and all("perf/mfu" not in c for c in counters)
