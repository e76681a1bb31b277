"""Latency-aware placement of the per-env-step player.

Training on the mesh is throughput-bound: big batched matmuls that want the
MXU. The per-env-step policy forward is the opposite regime — a tiny
computation whose wall-clock cost is dominated by dispatch + fetch latency
between the host (where the env lives) and the accelerator. On an attached
TPU v5e that round trip measures ~1 ms (chip_smoke.py prints it) against
~50 us on the host CPU backend; where it grows past a couple of milliseconds
a tiny policy is better served from the host while the chip trains.

This module makes the placement explicit and configurable
(``fabric.player_device``):

- ``mesh``  — player runs on the first mesh device (classic coupled layout;
  the analog of the reference's single-device player fabric,
  sheeprl/utils/fabric.py:8-35).
- ``host``  — player runs on the host CPU backend; a :class:`ParamMirror`
  keeps a copy of the training parameters on the host, refreshed after every
  optimizer step (the analog of the reference's decoupled mode, where the
  trainer broadcasts a flattened parameter vector back to the player,
  sheeprl/algos/sac/sac_decoupled.py:260-263 — here it is a device-to-host
  array copy, no flatten/unflatten dance).
- ``auto``  — measure the mesh dispatch latency once and pick ``host`` when
  the round trip is slower than :data:`AUTO_LATENCY_THRESHOLD_S` (and the
  player parameters are small enough for the copy to be cheap).

Parameter-sync semantics (``fabric.player_sync``):

- ``fresh`` — the mirror copy is enqueued immediately after each update and
  the player's next step waits for it: the player always acts with the
  current weights, matching the reference's coupled tied-weights behavior.
- ``async`` — the copy is enqueued but never waited on; the player keeps
  acting with the newest snapshot that has *finished* transferring. Under
  transfer backpressure intermediate snapshots are skipped (newest wins), so
  the interaction loop never blocks on the weight copy. On-policy algorithms
  (PPO/A2C) ignore this setting: their update happens between rollouts, and
  correctness requires the rollout to run on the post-update weights.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp

from sheeprl_tpu.telemetry import tracer as tracer_mod

AUTO_LATENCY_THRESHOLD_S = 2e-3
# Above this the host copy of the player parameters costs more than the
# dispatch latency it saves (and compiles slowly on CPU): stay on the mesh.
AUTO_MAX_PARAM_BYTES = 64 * 1024 * 1024
# How long an `auto` placement trusts its latency probe before re-measuring:
# a dispatch latency that changes MID-RUN (a contended host) would otherwise
# keep the stale placement until restart.
AUTO_REPROBE_TTL_S = float(os.environ.get("SHEEPRL_PLAYER_REPROBE_TTL_S", "300"))

_latency_cache: dict[Any, tuple[float, float]] = {}  # device -> (seconds, measured_at)

# On the CPU platform host and mesh are the same silicon, so `auto` skips the
# probe entirely; tests flip this to exercise the placement switch with a
# monkeypatched probe.
_PROBE_CPU_MESH = False


def host_device() -> jax.Device:
    """The host CPU backend device. JAX enables it beside the accelerator
    unless ``JAX_PLATFORMS`` names the accelerator alone."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError as err:
        raise RuntimeError(
            "fabric.player_device needs the host CPU backend, which is not enabled "
            f"(JAX_PLATFORMS={jax.config.jax_platforms!r}): add it, e.g. JAX_PLATFORMS=tpu,cpu, "
            "or set fabric.player_device=mesh"
        ) from err


def dispatch_latency(device: jax.Device, *, samples: int = 5, max_age_s: Optional[float] = None) -> float:
    """Median round-trip seconds of a tiny jitted call on ``device``.

    Measures dispatch + completion + host fetch — the fixed cost every
    per-env-step player call pays regardless of model size. The measurement
    is cached; ``max_age_s`` bounds how stale a cached value may be
    (None = any age, the one-shot resolve path).
    """
    now = time.monotonic()
    hit = _latency_cache.get(device)
    if hit is not None and (max_age_s is None or now - hit[1] < max_age_s):
        return hit[0]
    f = jax.jit(lambda x: x + 1.0)
    x = jax.device_put(jnp.zeros((8,), jnp.float32), device)
    # Measuring device round-trip latency IS the point here; the sync is
    # the measurement, not an accident.
    jax.device_get(f(x))  # compile + warm path  # graftlint: disable=GL002
    times = []
    for _ in range(samples):
        t0 = time.perf_counter()
        jax.device_get(f(x))  # graftlint: disable=GL002
        times.append(time.perf_counter() - t0)
    lat = sorted(times)[len(times) // 2]
    _latency_cache[device] = (lat, time.monotonic())
    return lat


def param_bytes(tree: Any) -> int:
    """Total bytes of all array leaves in a pytree."""
    return sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "dtype")
    )


def resolve_player_device(
    mode: str,
    mesh_device: jax.Device,
    *,
    params: Any = None,
    probe_max_age_s: Optional[float] = None,
) -> jax.Device:
    """Pick the device the player runs on. ``mode``: auto | host | mesh.

    ``probe_max_age_s`` bounds the latency-probe cache age (None = reuse any
    cached measurement; 0.0 = force a fresh probe — the TTL re-probe path).
    """
    mode = str(mode).lower()
    if mode not in ("auto", "host", "mesh"):
        raise ValueError(f"fabric.player_device must be one of auto|host|mesh, got {mode!r}")
    if mode == "host":
        return host_device()
    if mode == "mesh" or (mesh_device.platform == "cpu" and not _PROBE_CPU_MESH):
        # On the CPU platform (tests, multichip dry runs) host and mesh are
        # the same silicon — nothing to win.
        return mesh_device
    if params is not None and param_bytes(params) > AUTO_MAX_PARAM_BYTES:
        return mesh_device
    # Probe a device THIS process can address: on a multi-host mesh the
    # global first device may belong to another process, and device_put onto
    # a non-addressable device raises.
    probe = next(
        (d for d in jax.local_devices() if d.platform == mesh_device.platform), None
    )
    if probe is None:
        return mesh_device
    lat = dispatch_latency(probe, max_age_s=probe_max_age_s)
    return host_device() if lat > AUTO_LATENCY_THRESHOLD_S else mesh_device


def _all_ready(tree: Any) -> bool:
    for leaf in jax.tree_util.tree_leaves(tree):
        ready = getattr(leaf, "is_ready", None)
        if ready is not None and not ready():
            return False
    return True


class ParamMirror:
    """Keeps the player's copy of the training parameters on one device.

    ``push(params)`` is called after every optimizer step with the freshly
    updated (mesh-resident) parameters; ``get()`` is what the player reads.
    When the player device *is* the training device, both are pass-throughs.

    The copy travels PACKED: a jitted packer concatenates every leaf into one
    contiguous vector per dtype on the training device, so the device-to-host
    hop is one transfer instead of one per leaf — a per-leaf ``device_put``
    pays the dispatch round trip ~#leaves times. (This is
    the role of the reference's ``parameters_to_vector`` broadcast,
    sac_decoupled.py:260-263.)

    The transfer leg runs on a worker thread: ``jax.device_put`` across
    devices blocks its calling thread for the whole copy, so the main
    thread only packs (an async on-device dispatch) and hands the packed
    vectors over. In ``async`` mode at most one transfer is in flight with
    the NEWEST snapshot parked behind it (older waiting snapshots are the
    ones dropped); ``fresh`` mode submits every push and the next ``get()``
    waits for the last — tied-weights semantics, with the copy overlapping
    whatever the host does between update and next action.

    The pack runs immediately at push — never stashing the source arrays —
    because train steps donate their inputs: holding a reference for a
    deferred copy would read a deleted buffer. The worker only ever touches
    packed vectors, which nothing donates.
    """

    def __init__(self, device: Optional[jax.Device], *, sync: str = "fresh") -> None:
        sync = str(sync).lower()
        if sync not in ("fresh", "async"):
            raise ValueError(f"fabric.player_sync must be fresh|async, got {sync!r}")
        self.device = device
        self.sync = sync
        self._current: Any = None
        self._transfer = None  # Future of the in-flight D2H copy
        # Newest packed snapshot waiting behind an in-flight transfer
        # (async backpressure): at most one transfer in flight plus one
        # waiting snapshot, and the waiting slot always holds the NEWEST.
        self._next_packed: Any = None
        self._executor = None
        self._treedef = None
        self._shapes: Any = None
        self._dtypes: Any = None
        self._pack_fn = None
        self._unpack_fn = None
        self.pushes = 0
        self.skipped = 0

    # ------------------------------------------------------------- packing
    def _build_codec(self, params: Any) -> None:
        leaves, self._treedef = jax.tree_util.tree_flatten(params)
        self._shapes = [l.shape for l in leaves]
        self._dtypes = [jnp.dtype(l.dtype) for l in leaves]
        dtype_order = sorted({d.name for d in self._dtypes})

        def pack(tree):
            ls = jax.tree_util.tree_leaves(tree)
            out = {}
            for dname in dtype_order:
                out[dname] = jnp.concatenate(
                    [l.ravel() for l, d in zip(ls, self._dtypes) if d.name == dname]
                )
            return out

        def unpack(packed):
            offsets = {dname: 0 for dname in dtype_order}
            ls = []
            for shape, d in zip(self._shapes, self._dtypes):
                n = 1
                for dim in shape:
                    n *= int(dim)
                start = offsets[d.name]
                ls.append(packed[d.name][start : start + n].reshape(shape))
                offsets[d.name] = start + n
            return jax.tree_util.tree_unflatten(self._treedef, ls)

        self._pack_fn = jax.jit(pack)
        self._unpack_fn = jax.jit(unpack)

    def _unpack_on_device(self, packed: Any) -> Any:
        with jax.default_device(self.device):
            return self._unpack_fn(packed)

    # -------------------------------------------------------------- public
    def _submit(self, packed: Any):
        import concurrent.futures

        if self._executor is None:
            self._executor = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="sheeprl-mirror"
            )
        return self._executor.submit(jax.device_put, packed, self.device)

    def _promote(self, wait: bool = False) -> None:
        """Advance the pipeline: finished transfer -> current; waiting
        snapshot -> in-flight."""
        if self._transfer is not None and (
            wait or self._current is None or self._transfer.done()
        ):
            self._current = self._unpack_on_device(self._transfer.result())
            self._transfer = None
        if self._transfer is None and self._next_packed is not None:
            self._transfer = self._submit(self._next_packed)
            self._next_packed = None

    def push(self, params: Any) -> None:
        self.pushes += 1
        if self.device is None:  # player on the training device: share arrays
            self._current = params
            return
        # The trainer->player weight hop is the decoupled seam a distributed
        # trace needs visible: the span parents to the iteration that
        # produced these weights.
        with tracer_mod.current().span("player/mirror_push", "transfer", sync=self.sync):
            if self._pack_fn is None:
                self._build_codec(params)
            packed = self._pack_fn(params)
            if self.sync == "fresh" or self._transfer is None:
                # FIFO worker: in fresh mode every push transfers and get()
                # waits for the newest; replacing the Future reference keeps
                # exactly it.
                self._transfer = self._submit(packed)
                self._next_packed = None
                return
            if not self._transfer.done():
                # Backpressure: keep the in-flight transfer, park THIS
                # (newest) snapshot in the waiting slot — older waiting
                # snapshots are the ones dropped, so the newest always lands
                # eventually.
                if self._next_packed is not None:
                    self.skipped += 1
                self._next_packed = packed
                return
            self._promote()
            self._transfer = self._submit(packed)

    def get(self) -> Any:
        if self.device is not None:
            self._promote(wait=self.sync == "fresh")
        return self._current

    def flush(self) -> Any:
        """Block until the newest pushed snapshot is the served one.

        Call before final evaluation/checkpointing in async mode so results
        are reported for the trained weights, not a stale mirror.
        """
        if self.device is not None:
            with tracer_mod.current().span("player/mirror_flush", "transfer"):
                while self._transfer is not None or self._next_packed is not None:
                    self._promote(wait=True)
        return self._current

    def close(self) -> None:
        """Retire this mirror: drop any in-flight transfer and stop the
        worker thread. The served snapshot stays readable."""
        self._transfer = None
        self._next_packed = None
        if self._executor is not None:
            self._executor.shutdown(wait=False)
            self._executor = None


class PlayerPlacement:
    """Bundle of (player device, parameter mirror, default-device context).

    Usage in an algorithm loop::

        placement = PlayerPlacement.resolve(cfg, mesh_device, params=actor_params)
        placement.push(actor_params)                  # initial mirror
        ...
        with placement.ctx():                         # per env step
            obs = prepare_obs(...)                    # arrays land player-side
            key, sub = jax.random.split(key)
            out = player_step_fn(placement.params(), obs, sub)
        ...
        placement.push(new_params)                    # after each train step
    """

    def __init__(self, device: jax.Device, mesh_device: jax.Device, sync: str, mode: str = "mesh") -> None:
        self.device = device
        self.on_mesh = device == mesh_device
        self.mirror = ParamMirror(None if self.on_mesh else device, sync=sync)
        self._mode = str(mode).lower()
        self._sync = sync
        self._mesh_device = mesh_device
        self._next_reprobe = time.monotonic() + AUTO_REPROBE_TTL_S
        self.placement_switches = 0

    @classmethod
    def resolve(
        cls,
        cfg: Any,
        mesh_device: jax.Device,
        *,
        params: Any = None,
        force_fresh: bool = False,
    ) -> "PlayerPlacement":
        fabric = cfg.get("fabric") if hasattr(cfg, "get") else getattr(cfg, "fabric", None)
        mode = (fabric.get("player_device") or "auto") if fabric is not None else "auto"
        sync = (fabric.get("player_sync") or "fresh") if fabric is not None else "fresh"
        if force_fresh:
            sync = "fresh"
        device = resolve_player_device(mode, mesh_device, params=params)
        return cls(device, mesh_device, sync, mode=mode)

    def _maybe_reprobe(self, params: Any = None) -> bool:
        """TTL'd re-evaluation of an `auto` placement: a dispatch latency
        that changes mid-run flips the verdict at the next push past the TTL
        instead of persisting until restart. ``params`` (the tree about to
        be pushed) keeps the AUTO_MAX_PARAM_BYTES guard in force — an
        oversized player must stay on-mesh however slow dispatch gets.
        Returns True on a switch."""
        if self._mode != "auto" or (self._mesh_device.platform == "cpu" and not _PROBE_CPU_MESH):
            return False
        now = time.monotonic()
        if now < self._next_reprobe:
            return False
        self._next_reprobe = now + AUTO_REPROBE_TTL_S
        new_device = resolve_player_device(
            "auto", self._mesh_device, params=params, probe_max_age_s=0.0
        )
        if new_device == self.device:
            return False
        self.device = new_device
        self.on_mesh = new_device == self._mesh_device
        # A fresh mirror (old in-flight transfers target the old device); the
        # caller's push right after this lands the current weights on it.
        self.mirror.close()
        self.mirror = ParamMirror(None if self.on_mesh else new_device, sync=self._sync)
        self.placement_switches += 1
        return True

    def ctx(self):
        """Context manager placing new arrays (obs, PRNG keys) player-side.

        On-mesh this is a no-op: inputs stay uncommitted so jit resolves
        their placement from the (possibly multi-device) parameter sharding.
        """
        if self.on_mesh:
            return contextlib.nullcontext()
        return jax.default_device(self.device)

    def put(self, tree: Any) -> Any:
        """Commit a pytree (e.g. the rollout PRNG key) to the player device."""
        if self.on_mesh:
            return tree
        return jax.device_put(tree, self.device)

    def push(self, params: Any) -> None:
        # Re-probe BEFORE the push so a switch never strands these (newest)
        # weights in a mirror about to be replaced.
        self._maybe_reprobe(params)
        self.mirror.push(params)

    def params(self) -> Any:
        return self.mirror.get()
