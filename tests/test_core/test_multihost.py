"""Real 2-process jax.distributed test of the multi-host primitives the
training loops rely on: `process_allgather` (PPO's share_data path) and the
logger's log-dir string broadcast. The analog of the reference's 2-process
gloo-group tests (their torch.distributed strategy), here two CPU processes
coordinated over localhost."""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = '''
import os, sys
proc_id = int(sys.argv[1]); num_procs = int(sys.argv[2]); port = sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=num_procs, process_id=proc_id
)
import numpy as np
from jax.experimental import multihost_utils

assert jax.process_count() == num_procs, jax.process_count()

# --- process_allgather over DCN (ppo.py share_data path)
local = np.full((2, 3), proc_id, np.float32)
gathered = multihost_utils.process_allgather(local)
assert gathered.shape == (num_procs, 2, 3), gathered.shape
for p in range(num_procs):
    assert (gathered[p] == p).all()

# --- rank-0 string broadcast (logger log-dir sharing)
sys.path.insert(0, {repo!r})
from sheeprl_tpu.utils.logger import _broadcast_str

s = _broadcast_str("run-dir-from-rank0" if proc_id == 0 else "")
assert s == "run-dir-from-rank0", s

# --- sync_on_compute cross-rank metric reduction (utils/metric.py)
from sheeprl_tpu.utils.metric import MaxMetric, MeanMetric, SumMetric

mean = MeanMetric(sync_on_compute=True)
mean.update([1.0, 2.0] if proc_id == 0 else [6.0])  # global mean = 9/3
assert abs(mean.compute() - 3.0) < 1e-9, mean.compute()
local_mean = MeanMetric(sync_on_compute=False)
local_mean.update([1.0, 2.0] if proc_id == 0 else [6.0])
assert abs(local_mean.compute() - (1.5 if proc_id == 0 else 6.0)) < 1e-9
total = SumMetric(sync_on_compute=True)
total.update(float(proc_id + 1))
assert abs(total.compute() - 3.0) < 1e-9, total.compute()
peak = MaxMetric(sync_on_compute=True)
peak.update(float(proc_id))
assert peak.compute() == 1.0, peak.compute()
print(f"proc {proc_id} OK")
'''


def test_two_process_allgather_and_log_dir_broadcast(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    worker = tmp_path / "mh_worker.py"
    worker.write_text(_WORKER.replace("{repo!r}", repr(repo)))

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()

    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
    )
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(i), "2", str(port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            env=env,
        )
        for i in range(2)
    ]
    try:
        outs = [p.communicate(timeout=220)[0].decode() for p in procs]
    finally:
        # A hung worker must not outlive the test (it holds the coordinator
        # port and would collide with a re-run).
        for p in procs:
            if p.poll() is None:
                p.kill()
    # Capability gate: some jaxlib builds simply do not implement
    # multi-process coordination on the CPU backend. That is an environment
    # limitation, not a regression in the primitives under test.
    _CPU_BACKEND_UNSUPPORTED = "Multiprocess computations aren't implemented on the CPU backend"
    if any(p.returncode != 0 and _CPU_BACKEND_UNSUPPORTED in out for p, out in zip(procs, outs)):
        pytest.skip(f"jaxlib capability: {_CPU_BACKEND_UNSUPPORTED}")
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out}"
        assert f"proc {i} OK" in out
