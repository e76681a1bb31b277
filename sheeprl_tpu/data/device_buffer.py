"""Device-resident replay ring: the replay buffer as a pytree of HBM arrays.

The round-3 roofline (CHANGES.md, "Round-3 profile") left the host-side
data path as the last measured overhead in the DV3 step: a memcpy-bound numpy gather plus a
~12 MB host→device transfer *per gradient step*. The T5X-style answer is to
keep the ring on-device and sample it inside the train jit, so the host
never touches the hot path:

- :class:`DeviceReplayRing` mirrors the host replay ring as a dict of
  ``(capacity, n_envs, *feature)`` arrays living in HBM. Rollout rows are
  *staged* on the host (cheap numpy copies) and shipped once per train
  interval by :meth:`flush` — a single donated jitted scatter, not one
  transfer per gradient step.
- :meth:`make_sample_fn` returns a **pure function** ``sample(state, key)``
  that draws uniform sequence starts with the JAX PRNG entirely inside the
  caller's jit, reproducing ``SequentialReplayBuffer``'s valid-start
  semantics (the write head never appears inside a sampled window).
- Capacity accounting up front: when the ring would not fit the HBM budget
  the ring deactivates itself and the train loop falls back to the existing
  host buffer + ``ReplayInfeed`` path.

The host replay buffer stays authoritative for checkpointing — ring writes
are additive, so resume just replays the host ring into HBM via
:meth:`load_host_buffer`. Nothing here is pickled.

Valid-start math (shared by the in-jit sampler and the tests): with
per-env write position ``pos``, per-env total rows written ``added``,
ring ``capacity`` and window ``span``::

    full    = added >= capacity
    n_valid = full ? capacity - span + 1 : max(added - span + 1, 1)
    offset  = full ? pos : 0
    start   = (offset + uniform_int(0, n_valid)) % capacity

which enumerates exactly the starts ``SequentialReplayBuffer.sample``
allows: the oldest valid start is the write head itself once the ring has
wrapped (the head is the oldest row), and windows never straddle the seam
between the newest and the oldest row.
"""

from __future__ import annotations

import warnings
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from sheeprl_tpu.core import mesh as mesh_lib
from sheeprl_tpu.telemetry import scopes
from sheeprl_tpu.telemetry import tracer as tracer_mod

__all__ = ["DeviceReplayRing", "next_power_of_two"]


def next_power_of_two(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    return 1 << (max(int(n), 1) - 1).bit_length()


def _feature_shape(shape: Sequence[int]) -> Tuple[int, ...]:
    """Feature dims of a ``[T, E, *feature]`` rollout array."""
    return tuple(int(s) for s in shape[2:])


class DeviceReplayRing:
    """A replay ring held in device memory as ``{key: (capacity, n_envs, *f)}``.

    Host-side staging + one donated jitted write per :meth:`flush`; sampling
    is a pure function over :attr:`state` built by :meth:`make_sample_fn`
    and meant to be closed over by the caller's train jit.

    The ring is *additive*: the host buffer keeps receiving the same rows
    and remains the checkpoint source of truth. ``capacity`` is the per-env
    ring length (matching the host per-env sub-buffer size).

    With ``mesh`` given (and ``n_envs`` divisible by its `data` axis) the
    ring is **sharded across the mesh**: storage lives as
    ``[capacity, n_envs/data, *f]`` per shard (env columns split over
    `data`, no full-ring replication), :meth:`flush` stages rows onto the
    shard that owns those envs, and the in-jit writer/sampler run SPMD.
    Sampling keeps *global* uniform semantics — indices are computed from
    replicated pos/added and the same PRNG bits on every topology (under
    ``jax_threefry_partitionable``), so a sharded ring draws the identical
    batch a single-device ring would; the sampled batch is then constrained
    back onto the `data` axis so each shard trains on the rows it owns.
    """

    def __init__(
        self,
        capacity: int,
        n_envs: int,
        cnn_keys: Sequence[str] = (),
        obs_keys: Sequence[str] = ("observations",),
        hbm_fraction: float = 0.4,
        hbm_budget_bytes: Optional[int] = None,
        device: Any = None,
        mesh: Any = None,
    ) -> None:
        if capacity < 1:
            raise ValueError(f"DeviceReplayRing capacity must be >= 1, got {capacity}")
        if n_envs < 1:
            raise ValueError(f"DeviceReplayRing n_envs must be >= 1, got {n_envs}")
        self.capacity = int(capacity)
        self.n_envs = int(n_envs)
        self.cnn_keys = tuple(cnn_keys)
        self.obs_keys = tuple(obs_keys)
        self.hbm_fraction = float(hbm_fraction)
        self.hbm_budget_bytes = hbm_budget_bytes if hbm_budget_bytes is None else int(hbm_budget_bytes)
        self._device = device
        self._mesh = None
        if mesh is not None:
            data_size = int(mesh.shape[mesh_lib.DATA_AXIS])
            if self.n_envs % data_size == 0:
                self._mesh = mesh
            else:
                warnings.warn(
                    f"DeviceReplayRing: n_envs {self.n_envs} not divisible by the "
                    f"`{mesh_lib.DATA_AXIS}` mesh axis ({data_size}); the ring stays "
                    "unsharded (single-device placement)."
                )
        # Ring state (allocated lazily on the first add, when key shapes and
        # dtypes are known).
        self._specs: Optional[Dict[str, Tuple[Tuple[int, ...], np.dtype]]] = None
        self._data: Optional[Dict[str, jax.Array]] = None
        self._pos: Optional[jax.Array] = None
        self._added: Optional[jax.Array] = None
        # Host-side mirrors of pos/added so readiness checks never touch the
        # device (GL002: no per-iteration host sync).
        self._host_pos = np.zeros(self.n_envs, dtype=np.int64)
        self._host_added = np.zeros(self.n_envs, dtype=np.int64)
        # Staged rows awaiting flush: parallel lists of (mask[E], {k: row[E,*f]}).
        self._staged_masks: List[np.ndarray] = []
        self._staged_rows: List[Dict[str, np.ndarray]] = []
        self._write_fn = None
        # active=False -> the ring declined its allocation (HBM budget) and
        # every method is a no-op; callers use the host path instead.
        self.active = True
        self.inactive_reason: Optional[str] = None

    # ------------------------------------------------------------ capacity
    def _budget_bytes(self) -> Optional[int]:
        """The HBM byte budget, or None when unknown (no accounting)."""
        if self.hbm_budget_bytes is not None:
            return self.hbm_budget_bytes
        device = self._device
        if device is None:
            devices = jax.local_devices()
            device = devices[0] if devices else None
        if device is None:
            return None
        stats = getattr(device, "memory_stats", None)
        if stats is None:
            return None
        try:
            limit = (stats() or {}).get("bytes_limit")
        except Exception:  # memory_stats unsupported on this backend
            return None
        if limit is None:
            return None
        return int(int(limit) * self.hbm_fraction)

    def ring_nbytes(self) -> int:
        """Total ring bytes for the recorded key specs (0 before first add)."""
        if self._specs is None:
            return 0
        total = 0
        for feature, dtype in self._specs.values():
            total += self.capacity * self.n_envs * int(np.prod(feature, dtype=np.int64)) * dtype.itemsize
        return total

    def _deactivate(self, reason: str) -> None:
        self.active = False
        self.inactive_reason = reason
        self._staged_masks.clear()
        self._staged_rows.clear()
        self._data = None
        warnings.warn(f"DeviceReplayRing disabled, falling back to the host buffer path: {reason}")

    def _allocate(self) -> None:
        needed = self.ring_nbytes()
        budget = self._budget_bytes()
        if budget is not None and needed > budget:
            self._deactivate(
                f"ring needs {needed / 2**20:.1f} MiB but the HBM budget is {budget / 2**20:.1f} MiB"
            )
            return
        shardings = self.state_shardings()
        data: Dict[str, jax.Array] = {}
        for key, (feature, dtype) in self._specs.items():
            shape = (self.capacity, self.n_envs) + feature
            if shardings is not None:
                # Sharded allocation: each shard materializes only its own
                # env columns — no full-ring replication across the mesh.
                data[key] = jnp.zeros(shape, dtype=dtype, device=shardings["data"])
            else:
                data[key] = jnp.zeros(shape, dtype=dtype)
        self._data = data
        env_sharding = None if shardings is None else shardings["pos"]
        if env_sharding is not None:
            self._pos = jnp.zeros(self.n_envs, dtype=jnp.int32, device=env_sharding)
            self._added = jnp.zeros(self.n_envs, dtype=jnp.int32, device=env_sharding)
        else:
            self._pos = jnp.zeros(self.n_envs, dtype=jnp.int32)
            self._added = jnp.zeros(self.n_envs, dtype=jnp.int32)
        tracer_mod.current().set_gauge("replay_ring_bytes", float(needed))

    # ------------------------------------------------------------- staging
    def add(self, data: Dict[str, Any], env_idxes: Optional[Sequence[int]] = None) -> None:
        """Stage ``[T, E', *f]`` rows for the given env columns (all when
        ``env_idxes`` is None). Values are **copied** — callers are free to
        mutate ``data`` in place afterwards (the train loops do)."""
        if not self.active:
            return
        if env_idxes is None:
            env_idxes = range(self.n_envs)
        env_idxes = [int(e) for e in env_idxes]
        arrays = {key: np.asarray(value) for key, value in data.items()}
        n_steps = int(next(iter(arrays.values())).shape[0])
        if self._specs is None:
            # First add fixes the key set, feature shapes and dtypes; the
            # HBM budget check happens here so a too-big ring deactivates
            # before any staging cost is paid.
            self._specs = {
                key: (_feature_shape(value.shape), np.dtype(value.dtype))
                for key, value in arrays.items()
            }
            needed = self.ring_nbytes()
            budget = self._budget_bytes()
            if budget is not None and needed > budget:
                self._deactivate(
                    f"ring needs {needed / 2**20:.1f} MiB but the HBM budget is {budget / 2**20:.1f} MiB"
                )
                return
        for t in range(n_steps):
            mask = np.zeros(self.n_envs, dtype=bool)
            mask[env_idxes] = True
            row: Dict[str, np.ndarray] = {}
            for key, (feature, dtype) in self._specs.items():
                full_row = np.zeros((self.n_envs,) + feature, dtype=dtype)
                value = arrays.get(key)
                if value is not None:
                    # Keys absent from this add (e.g. sparse reset rows)
                    # keep their natural zero, matching what the loops put
                    # in reset rows explicitly.
                    full_row[env_idxes] = value[t]
                row[key] = full_row
            self._staged_masks.append(mask)
            self._staged_rows.append(row)
        self._host_pos[env_idxes] = (self._host_pos[env_idxes] + n_steps) % self.capacity
        self._host_added[env_idxes] = np.minimum(self._host_added[env_idxes] + n_steps, self.capacity)

    def amend_last(self, env_idx: int, values: Dict[str, Any]) -> None:
        """Patch the newest row written for one env (staged when possible,
        an eager device update otherwise). Used by the restart-on-exception
        path to flip terminal flags on the already-added row."""
        if not self.active:
            return
        env_idx = int(env_idx)
        for mask, row in zip(reversed(self._staged_masks), reversed(self._staged_rows)):
            if mask[env_idx]:
                for key, value in values.items():
                    if key in row:
                        row[key][env_idx] = np.asarray(value).reshape(row[key][env_idx].shape)
                return
        if self._data is None or self._host_added[env_idx] == 0:
            return
        t = int((self._host_pos[env_idx] - 1) % self.capacity)
        for key, value in values.items():
            if key in self._data:
                patch = jnp.asarray(np.asarray(value).reshape(self._data[key].shape[2:]))
                self._data[key] = self._data[key].at[t, env_idx].set(patch.astype(self._data[key].dtype))

    # ----------------------------------------------------------- sharding
    @property
    def mesh(self) -> Any:
        """The mesh the ring is sharded over, or None when unsharded."""
        return self._mesh

    def state_shardings(self) -> Optional[Dict[str, Any]]:
        """Sharding pytree-prefix matching :attr:`state` when the ring is
        mesh-sharded (None otherwise): ring storage is ``P(None, data)``
        (env columns over `data`), pos/added ``P(data)``. The ``data`` entry
        is a single sharding applied to every ring key (jit prefix
        semantics), so this works before the specs are known too — feed it
        to the fused train jit's ``in_shardings``/``out_shardings`` so the
        carried ring state keeps its layout across supersteps."""
        if self._mesh is None:
            return None
        row = NamedSharding(self._mesh, P(None, mesh_lib.DATA_AXIS))
        env = NamedSharding(self._mesh, P(mesh_lib.DATA_AXIS))
        return {"data": row, "pos": env, "added": env}

    # --------------------------------------------------------------- write
    def _build_write_fn(self):
        capacity = self.capacity
        n_envs = self.n_envs
        env_ids = jnp.arange(n_envs)

        @partial(jax.jit, donate_argnums=(0,))
        @scopes.scope(scopes.RING_WRITE)
        def write(data, pos, added, rows, mask, shift):
            # mask: [S, E] bool; rows: {k: [S, E, *f]}. Per-env cumulative
            # write count turns the staged order into ring targets; masked-out
            # slots are sent out of bounds and dropped by the scatter.
            # shift: [E] rows the host dropped when trimming an oversized
            # flush — they still advance the write head, keeping the device
            # pos in lockstep with the host mirror.
            pos = (pos + shift) % capacity
            counts = jnp.cumsum(mask.astype(jnp.int32), axis=0)  # [S, E]
            t_idx = jnp.where(mask, (pos[None, :] + counts - 1) % capacity, capacity)
            e_idx = jnp.broadcast_to(env_ids[None, :], t_idx.shape)
            new_data = {
                key: value.at[t_idx, e_idx].set(rows[key].astype(value.dtype), mode="drop")
                for key, value in data.items()
            }
            new_pos = (pos + counts[-1]) % capacity
            new_added = jnp.minimum(added + shift + counts[-1], capacity)
            return new_data, new_pos, new_added

        return write

    def flush(self) -> bool:
        """Ship every staged row to the device in ONE donated jitted write.

        Returns True when a write happened. The staged step count is padded
        to the next power of two (extra rows fully masked out) so the write
        kernel recompiles at most log2(max_steps) times.
        """
        if not self.active or not self._staged_rows:
            return False
        if self._data is None:
            self._allocate()
            if not self.active:
                return False
        n_staged = len(self._staged_rows)
        shift = np.zeros(self.n_envs, dtype=np.int32)
        if n_staged > self.capacity:
            # Only the last `capacity` masked rows per env can survive; drop
            # older ones on the host so ring targets stay collision-free.
            # The dropped rows still advance the write head (shift), keeping
            # the device pos equal to the host mirror's.
            masks = np.stack(self._staged_masks, axis=0)
            seen_from_end = np.cumsum(masks[::-1].astype(np.int64), axis=0)[::-1]
            keep = masks & (seen_from_end <= self.capacity)
            shift = (masks.sum(axis=0) - keep.sum(axis=0)).astype(np.int32)
            self._staged_masks = [keep[t] for t in range(n_staged)]
        padded = next_power_of_two(n_staged)
        mask = np.zeros((padded, self.n_envs), dtype=bool)
        mask[:n_staged] = np.stack(self._staged_masks, axis=0)
        rows: Dict[str, np.ndarray] = {}
        for key in self._staged_rows[0]:
            stacked = np.stack([row[key] for row in self._staged_rows], axis=0)
            if padded > n_staged:
                pad = np.zeros((padded - n_staged,) + stacked.shape[1:], dtype=stacked.dtype)
                stacked = np.concatenate([stacked, pad], axis=0)
            rows[key] = stacked
        self._staged_masks.clear()
        self._staged_rows.clear()
        if self._write_fn is None:
            self._write_fn = self._build_write_fn()
        nbytes = int(sum(value.nbytes for value in rows.values()) + mask.nbytes)
        trc = tracer_mod.current()
        if self._mesh is not None:
            # Per-shard staging: each staged row lands directly on the shard
            # that owns its env columns (env dim 1 split over `data`), so the
            # donated SPMD write scatters locally — no full-row replication.
            rows = mesh_lib.shard_batch(rows, self._mesh, axis=1)
            mask = mesh_lib.shard_batch(mask, self._mesh, axis=1)
            shift = mesh_lib.shard_batch(shift, self._mesh, axis=0)
        with trc.span("transfer/ring_write", "transfer", steps=n_staged, bytes=nbytes):
            self._data, self._pos, self._added = self._write_fn(
                self._data, self._pos, self._added, rows, mask, shift
            )
        trc.count("host_to_device_calls", 1)
        trc.count("host_to_device_bytes", nbytes)
        trc.count("ring_write_rows", int(mask.sum()))
        return True

    # ------------------------------------------------- fused-lane interface
    def allocate(self, specs: Dict[str, Tuple[Sequence[int], Any]]) -> None:
        """Eagerly allocate the ring from explicit per-key feature specs.

        The host-interaction lane allocates lazily on the first ``add`` (the
        staged row fixes shapes/dtypes); the fused lane writes rows *inside*
        the superstep jit and never stages, so the ring must exist — with
        the HBM budget check already passed — before the first dispatch.
        ``specs`` maps key -> (feature_shape, dtype). No-op when already
        allocated with identical specs; mismatched re-allocation raises.
        """
        if not self.active:
            return
        normalized = {
            key: (tuple(int(s) for s in feature), np.dtype(dtype))
            for key, (feature, dtype) in specs.items()
        }
        if self._specs is not None:
            if self._specs != normalized:
                raise ValueError(
                    f"DeviceReplayRing.allocate specs mismatch: ring holds {self._specs}, "
                    f"caller wants {normalized}"
                )
            if self._data is not None:
                return
        self._specs = normalized
        self._allocate()

    def make_step_write_fn(self) -> Callable[[Dict[str, Any], Dict[str, jax.Array], jax.Array], Dict[str, Any]]:
        """Build the pure in-jit per-step writer ``write(state, row, mask)``.

        The fused rollout scan appends one ``[E, *f]`` row per env step
        directly into the ring pytree carried through the scan — zero host
        staging, zero transfers. ``mask`` ([E] bool) gates which env
        columns advance (dreamer's sparse reset rows); masked-out columns
        are scattered out of bounds and dropped. Semantics match one
        staged ``add`` + ``flush`` per masked column, so the host mirror
        stays in lockstep via :meth:`advance_host`.

        The writer derives its env width from the traced ``state`` (not the
        ring's global ``n_envs``), so the same function works unchanged
        inside a ``shard_map`` over `data`, where each shard carries only
        its own ``n_envs/data`` env columns.
        """
        capacity = self.capacity

        @scopes.scope(scopes.RING_WRITE)
        def write(state: Dict[str, Any], row: Dict[str, jax.Array], mask: jax.Array) -> Dict[str, Any]:
            pos = state["pos"]
            added = state["added"]
            env_ids = jnp.arange(pos.shape[0])  # local width under shard_map
            inc = mask.astype(jnp.int32)
            t_idx = jnp.where(mask, pos, capacity)  # out-of-bounds -> dropped
            data = {
                key: value.at[t_idx, env_ids].set(
                    row[key].astype(value.dtype), mode="drop"
                )
                for key, value in state["data"].items()
            }
            return {
                "data": data,
                "pos": (pos + inc) % capacity,
                "added": jnp.minimum(added + inc, capacity),
            }

        return write

    def adopt_state(self, state: Dict[str, Any], steps_written: Any = 0) -> None:
        """Adopt the ring pytree a fused superstep returned (donated in, new
        buffers out) and advance the host pos/added mirrors by the rows the
        superstep wrote per env — pure host arithmetic, no device sync."""
        if not self.active:
            return
        self._data = state["data"]
        self._pos = state["pos"]
        self._added = state["added"]
        steps = np.asarray(steps_written, dtype=np.int64)
        self._host_pos = (self._host_pos + steps) % self.capacity
        self._host_added = np.minimum(self._host_added + steps, self.capacity)

    # ------------------------------------------------------------ sampling
    @property
    def state(self) -> Dict[str, Any]:
        """The device-resident ring as a pytree: pass this into the train
        jit; :meth:`make_sample_fn`'s pure function consumes it."""
        if self._data is None:
            raise RuntimeError("DeviceReplayRing.state read before the first flush allocated the ring")
        return {"data": self._data, "pos": self._pos, "added": self._added}

    def ready(self, span: int) -> bool:
        """True when every env column has at least ``span`` rows *flushed*,
        so the in-jit sampler cannot window into unwritten rows. Pure host
        arithmetic — no device sync."""
        if not self.active or self._data is None:
            return False
        return bool(self._host_added.min() >= max(int(span), 1)) and span <= self.capacity

    def make_sample_fn(
        self,
        batch_size: int,
        sequence_length: int = 1,
        sample_next_obs: bool = False,
        time_major: bool = False,
    ) -> Callable[[Dict[str, Any], jax.Array], Dict[str, jax.Array]]:
        """Build the pure in-jit sampler ``sample(state, key) -> batch``.

        Uniform env choice then uniform valid sequence start per sample —
        ``SequentialReplayBuffer`` semantics (one env per sequence, windows
        never cross the write head). Output is ``[B, *f]`` when
        ``sequence_length == 1`` and ``time_major`` is False, else
        ``[L, B, *f]`` (time-major) or ``[B, L, *f]``. Non-CNN keys are cast
        to float32 in-jit (the CNN keys keep their storage dtype for the
        train step's own ``/255`` normalisation). With ``sample_next_obs``
        the window is one longer and each obs key ``k`` gains ``next_k``.
        """
        capacity = self.capacity
        cnn_keys = frozenset(self.cnn_keys)
        obs_keys = tuple(self.obs_keys)
        span = int(sequence_length) + int(bool(sample_next_obs))
        if span > capacity:
            raise ValueError(
                f"sequence window {span} exceeds DeviceReplayRing capacity {capacity}"
            )
        batch_size = int(batch_size)
        sequence_length = int(sequence_length)
        batch_constraint = None
        if self._mesh is not None and int(self._mesh.shape[mesh_lib.DATA_AXIS]) > 1:
            if batch_size % int(self._mesh.shape[mesh_lib.DATA_AXIS]) == 0:
                # Sampled rows re-land on the shard that trains on them: the
                # batch dim splits over `data` (dim 1 when time-major).
                spec = P(None, mesh_lib.DATA_AXIS) if time_major else P(mesh_lib.DATA_AXIS)
                batch_constraint = NamedSharding(self._mesh, spec)

        def _cast(key: str, value: jax.Array) -> jax.Array:
            return value if key in cnn_keys else value.astype(jnp.float32)

        def _shape(value: jax.Array) -> jax.Array:
            # value: [B, L(+1) sliced to L, *f] -> requested layout.
            if sequence_length == 1 and not time_major:
                return value[:, 0]
            if time_major:
                return jnp.swapaxes(value, 0, 1)
            return value

        @scopes.scope(scopes.RING_SAMPLE)
        def sample(state: Dict[str, Any], key: jax.Array) -> Dict[str, jax.Array]:
            pos = state["pos"]
            added = state["added"]
            # Env width from the traced state, not the ring's global n_envs:
            # the sampler stays correct if the caller hands it a sub-ring.
            num_envs = pos.shape[0]
            k_env, k_start = jax.random.split(key)
            env_idx = jax.random.randint(k_env, (batch_size,), 0, num_envs)
            full = added >= capacity
            n_valid = jnp.where(
                full,
                capacity - span + 1,
                jnp.maximum(added - span + 1, 1),
            )
            offset = jnp.where(full, pos, 0)
            r = jax.random.randint(k_start, (batch_size,), 0, n_valid[env_idx])
            start = (offset[env_idx] + r) % capacity
            t_idx = (start[:, None] + jnp.arange(span)) % capacity  # [B, span]
            batch: Dict[str, jax.Array] = {}
            for name, ring in state["data"].items():
                window = ring[t_idx, env_idx[:, None]]  # [B, span, *f]
                batch[name] = _shape(_cast(name, window[:, :sequence_length]))
                if sample_next_obs and name in obs_keys:
                    batch[f"next_{name}"] = _shape(_cast(name, window[:, 1:]))
            if batch_constraint is not None:
                batch = {
                    name: jax.lax.with_sharding_constraint(value, batch_constraint)
                    for name, value in batch.items()
                }
            return batch

        return sample

    # ------------------------------------------------------------- resume
    def load_host_buffer(self, rb: Any) -> None:
        """Stage the host buffer's current contents chronologically (oldest first)
        so a resumed run samples its checkpointed history on-device.

        Understands ``EnvIndependentReplayBuffer`` (per-env sub-buffers) and
        flat ``ReplayBuffer``/``SequentialReplayBuffer``; anything else
        (episode buffers) deactivates the ring with a warning.
        """
        if not self.active:
            return
        sub_buffers = getattr(rb, "buffer", None)
        if sub_buffers is not None and isinstance(sub_buffers, (list, tuple)):
            for env_idx, sub in enumerate(sub_buffers):
                self._load_flat(sub, [env_idx])
            return
        if hasattr(rb, "_pos") and hasattr(rb, "full"):
            self._load_flat(rb, list(range(self.n_envs)))
            return
        self._deactivate(f"cannot mirror a {type(rb).__name__} into the device ring")

    def _load_flat(self, rb: Any, env_idxes: List[int]) -> None:
        if getattr(rb, "empty", True):
            return
        size = int(rb.buffer_size)
        pos = int(rb._pos)
        if getattr(rb, "full", False):
            order = np.concatenate([np.arange(pos, size), np.arange(0, pos)])
        else:
            order = np.arange(pos)
        if order.size == 0:
            return
        data = {key: np.asarray(rb[key])[order] for key in rb.buffer.keys()}
        self.add(data, env_idxes)
