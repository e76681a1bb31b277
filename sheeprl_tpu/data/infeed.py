"""Asynchronous host→device infeed for replay-sampled training batches.

The off-policy loops (SAC, all Dreamers, P2E) alternate between env stepping
(host-bound) and a train call whose batches must first be copied host→device.
Synchronously, that copy serializes with everything else: for a Dreamer
recipe the per-call batch is ~13 MB of uint8 pixels, tens of milliseconds of
host time that the chip spends idle.

`AsyncInfeed` overlaps the copy with env stepping (SURVEY §7.1 step 3,
"sample on host threads → double-buffered device_put"):

- `stage(host_batches)` is called right after a train call is dispatched,
  with batches ALREADY SAMPLED on the caller's thread — sampling stays on
  the main thread, between buffer writes, so the replay buffer needs no
  locking. A worker thread then runs the host→device transfers while the
  caller returns to stepping envs (numpy slicing and `jax.device_put`
  release the GIL).
- `take()` at the next train call returns the staged device batches if the
  expected shape matches, or `None` (caller falls back to the synchronous
  path — e.g. the Ratio controller asked for a different gradient-step
  count, or nothing was staged).

The worker only ever touches host arrays handed to it by value; it never
reads the replay buffer, so there is no concurrent-mutation hazard.
"""

from __future__ import annotations

import concurrent.futures
from typing import Any, Callable, List, Optional

from sheeprl_tpu.telemetry.tracer import current as _current_tracer


class AsyncInfeed:
    """Double-buffered device staging of pre-sampled host batches."""

    def __init__(self, put_fn: Callable[[Any], Any]) -> None:
        """``put_fn(host_batch) -> device_batch`` runs on the worker thread."""
        self._put_fn = put_fn
        self._executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="sheeprl-infeed"
        )
        self._future: Optional[concurrent.futures.Future] = None
        self._staged_count: Optional[int] = None
        self.hits = 0
        self.misses = 0

    def stage(self, host_batches: List[Any]) -> None:
        """Hand sampled host batches to the worker for device transfer.

        Any previously staged result that was never taken is dropped (its
        transfers were already enqueued; the arrays are simply released).
        """
        batches = list(host_batches)

        def work():
            # Worker thread: the tracer is thread-safe, and the span makes the
            # overlapped H2D staging visible on its own trace track.
            with _current_tracer().span("transfer/h2d_stage", "transfer", batches=len(batches)):
                return [self._put_fn(b) for b in batches]

        self._staged_count = len(batches)
        self._future = self._executor.submit(work)

    def take(self, expected_count: int) -> Optional[List[Any]]:
        """Return `expected_count` staged device batches, or None.

        A larger stage serves its first `expected_count` batches (the Ratio
        controller's step count can drift by one between calls); a smaller
        stage is a miss and the caller falls back to synchronous sampling.
        """
        future, count = self._future, self._staged_count
        self._future = None
        self._staged_count = None
        if future is None or count < expected_count:
            if future is not None:
                future.cancel()
            self.misses += 1
            return None
        self.hits += 1
        return future.result()[:expected_count]

    def close(self) -> None:
        self._executor.shutdown(wait=False, cancel_futures=True)


class ReplayInfeed:
    """The sample→stage→take protocol shared by every sequence-replay loop.

    Bundles an :class:`AsyncInfeed` with the Dreamer-family batch recipe:
    host batches come from ``rb.sample_tensors`` (always on the caller's
    thread — no concurrent buffer access), CNN-keyed entries stay in their
    storage dtype (uint8 pixels; normalized inside jit) and everything else
    is converted to float32 on the way to the device.
    """

    def __init__(self, rb, batch_size: int, sequence_length: int, cnn_keys, *, enabled: bool = True) -> None:
        import jax.numpy as jnp
        import numpy as np

        self._rb = rb
        self._batch_size = int(batch_size)
        self._sequence_length = int(sequence_length)
        cnn_key_set = set(cnn_keys)

        def device_batch(host_batch):
            # Count the host->device traffic before conversion (the host
            # array's nbytes is what actually crosses the PCIe/ICI link);
            # the tracer is thread-safe, so this is fine on the worker.
            trc = _current_tracer()
            if trc.enabled:
                nbytes = sum(int(getattr(v, "nbytes", 0)) for v in host_batch.values())
                trc.count("host_to_device_calls", 1)
                trc.count("host_to_device_bytes", nbytes)
            return {
                k: jnp.asarray(v, jnp.float32) if k not in cnn_key_set else jnp.asarray(v)
                for k, v in host_batch.items()
            }

        self._device_batch = device_batch
        self._np = np
        self._infeed = AsyncInfeed(device_batch) if enabled else None

    def _sample_host(self, n: int) -> List[Any]:
        data = self._rb.sample_tensors(
            self._batch_size, sequence_length=self._sequence_length, n_samples=n
        )
        np = self._np
        return [{k: np.asarray(v[i]) for k, v in data.items()} for i in range(n)]

    def take_or_sample(self, n: int) -> List[Any]:
        """Staged device batches if available, else sample+copy synchronously.

        ``infeed/take`` holds the wait on the worker's future (what the loop
        waited for, where ``transfer/h2d_stage`` is what the worker hid) and
        says whether the batches were staged: never, with the infeed off. On
        a miss ``replay/sample`` and then ``transfer/h2d_sync``, the copy
        alone, follow on the caller's thread."""
        tracer = _current_tracer()
        with tracer.span("infeed/take", "transfer") as take:
            batches = self._infeed.take(n) if self._infeed is not None else None
            take.set(hit=batches is not None)
        if batches is None:
            host_batches = self._sample_host(n)
            with tracer.span("transfer/h2d_sync", "transfer", batches=n):
                batches = [self._device_batch(b) for b in host_batches]
        return batches

    def stage(self, n: int) -> None:
        """Sample the next call's batches now (caller's thread) and hand the
        device copies to the worker to overlap the env-step phase."""
        if self._infeed is not None:
            self._infeed.stage(self._sample_host(n))

    def close(self) -> None:
        if self._infeed is not None:
            self._infeed.close()
