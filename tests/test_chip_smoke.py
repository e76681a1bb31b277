"""chip_smoke.py rehearsed on the CPU: control flow only.

The chip check itself runs on a TPU (``python chip_smoke.py``). What can be
held here: the device gate refuses a CPU and never prints the result line, a
phase that raises keeps the script from exiting 0, and the ``--chips 4``
placement check tells a sharded ring from one that sits whole on the first
device. The three phases at micro size — half a minute of compiles — are in
tests/test_utils/test_chip_smoke_phases.py, at the end of the collection
order: tier-1 is cut at its time limit today (ROADMAP D10), and a second
spent early in the order costs two quick tests at the cut.
"""

import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from sheeprl_tpu.core import mesh as mesh_lib  # noqa: E402
from sheeprl_tpu.data.device_buffer import DeviceReplayRing  # noqa: E402

OK_LINE = '"ok": true'


def test_device_gate_rejects_a_cpu_and_prints_no_result(capsys):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as exit_info:
        chip_smoke.main([])
    # A message or a non-zero number: either way the process exits non-zero.
    assert exit_info.value.code not in (0, None)
    assert "needs a TPU" in str(exit_info.value.code)
    assert OK_LINE not in capsys.readouterr().out


def test_a_phase_that_raises_keeps_the_script_from_exiting_zero(monkeypatch, tmp_path, capsys):
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path / "out"))
    monkeypatch.setattr(
        chip_smoke, "require_tpu", lambda chips: {"platform": "tpu", "kind": "TPU v5 lite", "count": chips}
    )
    monkeypatch.setattr(chip_smoke, "phase_trainer", lambda *a: "unused.ckpt")
    monkeypatch.setattr(chip_smoke, "time_agent_init_on_device", lambda *a: None)

    def broken_phase(*args):
        raise RuntimeError("phase B broke")

    monkeypatch.setattr(chip_smoke, "phase_fused_lane", broken_phase)
    # Uncaught, so the interpreter exits 1; the later phase never runs.
    monkeypatch.setattr(chip_smoke, "phase_server", lambda *a: pytest.fail("ran past a failed phase"))
    with pytest.raises(RuntimeError, match="phase B broke"):
        chip_smoke.main([])
    assert OK_LINE not in capsys.readouterr().out


@pytest.mark.skipif(jax.device_count() < 4, reason="needs 4 virtual CPU devices")
@pytest.mark.parametrize("sharded", [True, False], ids=["ring_on_the_mesh", "ring_whole_on_first_device"])
def test_four_chip_placement_check(sharded):
    devices = jax.devices()[:4]
    mesh = mesh_lib.build_mesh(devices=devices)
    ring = DeviceReplayRing(8, 4, obs_keys=("obs",), device=devices[0], mesh=mesh if sharded else None)
    ring.allocate({"obs": ((3,), np.float32), "rewards": ((1,), np.float32)})
    arrays = chip_smoke.ring_fields(ring)
    if sharded:
        for name, array in arrays.items():
            chip_smoke.assert_one_shard_per_device(array, devices, name)
    else:
        for name, array in arrays.items():
            with pytest.raises(AssertionError, match="expected one on each of 4 devices"):
                chip_smoke.assert_one_shard_per_device(array, devices, name)
