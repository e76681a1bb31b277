"""Unit tests for the async host->device infeed (data/infeed.py)."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.data.infeed import AsyncInfeed


def _put(host_batch):
    return {k: jnp.asarray(v) for k, v in host_batch.items()}


class TestAsyncInfeed:
    def test_take_without_stage_is_none(self):
        infeed = AsyncInfeed(_put)
        assert infeed.take(2) is None
        assert infeed.misses == 1
        infeed.close()

    def test_stage_then_take_returns_device_batches(self):
        infeed = AsyncInfeed(_put)
        host = [{"x": np.full((2, 2), float(i))} for i in range(3)]
        infeed.stage(host)
        out = infeed.take(3)
        assert out is not None and len(out) == 3
        for i, b in enumerate(out):
            np.testing.assert_array_equal(np.asarray(b["x"]), np.full((2, 2), float(i)))
        assert infeed.hits == 1
        infeed.close()

    def test_count_mismatch_falls_back(self):
        infeed = AsyncInfeed(_put)
        infeed.stage([{"x": np.zeros((1,))}])
        assert infeed.take(2) is None
        assert infeed.misses == 1
        infeed.close()

    def test_take_consumes_the_stage(self):
        infeed = AsyncInfeed(_put)
        infeed.stage([{"x": np.zeros((1,))}])
        assert infeed.take(1) is not None
        assert infeed.take(1) is None
        infeed.close()

    def test_restaging_drops_previous(self):
        infeed = AsyncInfeed(_put)
        infeed.stage([{"x": np.zeros((1,))}])
        infeed.stage([{"x": np.ones((1,))}, {"x": np.ones((1,))}])
        out = infeed.take(2)
        assert out is not None and len(out) == 2
        infeed.close()

    def test_worker_copies_by_value_not_by_reference(self):
        # Mutating the source after stage() must not corrupt staged batches:
        # the worker may still be copying. stage() must snapshot-safe the
        # list, and the put_fn's jnp.asarray copies the data.
        infeed = AsyncInfeed(_put)
        src = np.zeros((64, 64))
        infeed.stage([{"x": src}])
        time.sleep(0.05)  # let the worker finish its device_put
        src[:] = 1.0
        out = infeed.take(1)
        np.testing.assert_array_equal(np.asarray(out[0]["x"]), np.zeros((64, 64)))
        infeed.close()


def test_take_is_a_span_of_the_callers_thread_that_says_whether_it_hit():
    """`infeed/take` holds the wait on the worker's future and carries `hit`
    (never, with the infeed off); on a miss `replay/sample` and
    `transfer/h2d_sync` follow it, side by side; the worker's
    `transfer/h2d_stage` is on a thread of its own."""
    from sheeprl_tpu.data.buffers import SequentialReplayBuffer
    from sheeprl_tpu.data.infeed import ReplayInfeed
    from sheeprl_tpu.telemetry import tracer as tracer_mod

    rb = SequentialReplayBuffer(16, n_envs=1)
    rb.add({"x": np.zeros((8, 1, 2), np.float32), "terminated": np.zeros((8, 1, 1), np.float32),
            "truncated": np.zeros((8, 1, 1), np.float32)})
    tracer = tracer_mod.Tracer(enabled=True)
    previous = tracer_mod.set_current(tracer)
    try:
        for enabled, hits in ((True, [False, True]), (False, [False, False])):
            tracer.clear()
            infeed = ReplayInfeed(rb, 2, 4, cnn_keys=(), enabled=enabled)
            assert len(infeed.take_or_sample(1)) == 1
            infeed.stage(1)
            assert len(infeed.take_or_sample(1)) == 1
            infeed.close()
            spans = tracer.spans()
            assert [s.args["hit"] for s in spans if s.name == "infeed/take"] == hits
            threads = {s.name: s.thread for s in spans}
            assert threads["infeed/take"] == threads["transfer/h2d_sync"] == threads["replay/sample"] == "MainThread"
            assert ("transfer/h2d_stage" in threads) == enabled
            if enabled:
                assert threads["transfer/h2d_stage"].startswith("sheeprl-infeed")
            # the copy's span no longer holds the sampling: siblings, not parent and child
            sync = next(s for s in spans if s.name == "transfer/h2d_sync")
            sample = next(s for s in spans if s.name == "replay/sample")
            assert sample.start_s + sample.duration_s <= sync.start_s
    finally:
        tracer_mod.set_current(previous)
