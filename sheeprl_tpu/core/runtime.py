"""The Runtime: the framework's substrate object, replacing Lightning Fabric.

Where the reference passes a ``fabric`` into every algorithm ``main(fabric,
cfg)`` (sheeprl/cli.py:199), this framework passes a :class:`Runtime`. It
owns:

- accelerator/device selection (cpu | tpu | auto),
- multi-host initialization (jax.distributed; DCN between hosts, ICI within),
- the device :class:`~jax.sharding.Mesh` (data × model axes),
- the precision policy,
- seeding and the root PRNG key,
- rank-zero-gated printing/logging helpers.

Unlike Fabric there is no module wrapping / DDP setup: parallelism is sharding
metadata on jitted functions, so "setup_module" has no equivalent — algorithms
jit their train steps with shardings derived from `runtime.mesh`.
"""

from __future__ import annotations

import contextlib
import os
import re
from typing import Any, Optional, Sequence

import jax
import numpy as np

from sheeprl_tpu.core import mesh as mesh_lib
from sheeprl_tpu.core.precision import Precision, resolve_precision
from sheeprl_tpu.core.prng import seed_everything
from sheeprl_tpu.telemetry import Telemetry

#: The checkout (the directory that holds the ``sheeprl_tpu`` package), resolved
#: from this file and never from the cwd: runs start from throwaway cwds.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def force_cpu_platform(num_devices: Optional[int] = None) -> None:
    """Select the CPU platform for this process, with at least ``num_devices``
    (virtual) CPU devices: what tests and ``fabric.accelerator=cpu`` runs use.

    JAX reads ``JAX_PLATFORMS`` once, at import, so the config option is
    updated too; the environment variable is set as well so that spawned
    children inherit the selection. The CPU client is sized when it is first
    built: before that, ``num_devices`` raises the size (it is a MINIMUM — a
    platform the environment already sized larger through ``XLA_FLAGS
    --xla_force_host_platform_device_count`` is never shrunk); afterwards the
    platform keeps the size it has, every live array stays valid, and a
    shortfall is reported by whoever asks for the devices.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    if num_devices is None:
        return
    flag = re.search(r"--xla_force_host_platform_device_count=(\d+)", os.environ.get("XLA_FLAGS", ""))
    wanted = max(int(num_devices), int(flag.group(1)) if flag else 1, jax.config.jax_num_cpu_devices)
    if wanted != jax.config.jax_num_cpu_devices:
        try:
            jax.config.update("jax_num_cpu_devices", wanted)
        except RuntimeError:
            pass  # JAX refuses the option once the backends exist: the size stays


def configure_compilation_cache() -> Optional[str]:
    """The one owner of the persistent XLA compile cache's location; returns
    the directory in effect (None when the environment turned it off).

    Placed from outside when ``JAX_COMPILATION_CACHE_DIR`` is in the
    environment: JAX reads that itself and nothing here overrides it. Unset,
    the cache is ``<checkout>/.jax_cache`` — a fixed path (the path is part
    of what makes an entry findable again) inside the tree a run was started
    from, with every compile persisted so that repeated short runs and the
    test suite reuse each other's executables.
    """
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        return os.environ["JAX_COMPILATION_CACHE_DIR"] or None
    cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


class DispatchThrottle:
    """Bound the number of in-flight async train dispatches.

    XLA dispatch is asynchronous: an off-policy loop with metrics disabled
    and `fabric.player_sync=async` never fetches anything, so the host can
    enqueue train calls (each pinning its sampled device batch — ~13 MB at
    the DreamerV3-S 100K shape) far ahead of the device, growing host
    memory without bound until the client stalls. `add(token)` keeps a
    window of ``depth`` dispatched outputs and blocks on the oldest when
    the window is full — a full window costs no throughput (the device is
    `depth` steps behind at most), an unbounded one took a bench host to
    38 GB RSS before deadlocking.
    """

    def __init__(self, depth: int = 4) -> None:
        from collections import deque

        self._depth = int(depth)
        self._queue = deque()

    def add(self, token: Any) -> None:
        self._queue.append(token)
        while len(self._queue) > self._depth:
            # Deliberate backpressure: blocking on the OLDEST in-flight step is
            # what bounds device queue depth (async dispatch would otherwise
            # run away); the current step keeps riding.
            jax.block_until_ready(self._queue.popleft())  # graftlint: disable=GL002

    def drain(self) -> None:
        while self._queue:
            # End-of-run barrier: draining the pipeline is an explicit sync point.
            jax.block_until_ready(self._queue.popleft())  # graftlint: disable=GL002


def enable_xla_determinism() -> None:
    """Process-wide determinism knob (``cfg.xla_deterministic``).

    Reference semantics: the ``reproducible()`` wrapper
    (sheeprl/cli.py:187-197) sets the CUBLAS workspace config,
    ``cudnn.deterministic`` and ``torch.use_deterministic_algorithms``
    before the entrypoint runs. The XLA analog, applied before the first
    backend touch:

    - **TPU/CPU**: XLA executables are deterministic by construction for a
      fixed program (reductions are compiled tree-reductions, not atomics),
      so the contract here is PRNG discipline — one root key, fold_in-only
      streams (core/prng.py), which ``Runtime.seed_everything`` enforces —
      plus stable compilation inputs (static shapes; no autotune lottery).
    - **GPU** (JAX-on-CUDA completeness): ``--xla_gpu_deterministic_ops``
      forces deterministic reductions/scatters and
      ``--xla_gpu_autotune_level=0`` pins kernel selection. XLA_FLAGS is
      read at backend construction, so this must run before any jax op;
      appended here if absent.
    - ``jax_threefry_partitionable`` makes random bits invariant to
      sharding, so the same seed draws the same values whether a tensor
      lives on 1 or 8 devices — determinism across mesh shapes, not just
      across runs.
    """
    # Drop any pre-existing settings of these two flags (whatever their
    # value — "=false" must not survive a determinism request), then append
    # the deterministic ones.
    kept = [
        tok
        for tok in os.environ.get("XLA_FLAGS", "").split()
        if not tok.startswith(("--xla_gpu_deterministic_ops", "--xla_gpu_autotune_level"))
    ]
    kept += ["--xla_gpu_deterministic_ops=true", "--xla_gpu_autotune_level=0"]
    os.environ["XLA_FLAGS"] = " ".join(kept)
    jax.config.update("jax_threefry_partitionable", True)


class Runtime:
    def __init__(
        self,
        devices: int | str = 1,
        num_nodes: int = 1,
        strategy: str = "auto",
        accelerator: str = "auto",
        precision: str = "32-true",
        model_axis: int = 1,
        player_device: str = "auto",
        player_sync: str = "fresh",
        shard_superstep: bool = True,
        async_fetch: bool = False,
    ) -> None:
        self.requested_devices = devices
        self.num_nodes = num_nodes
        self.strategy = strategy
        self.accelerator = accelerator
        self.precision: Precision = resolve_precision(precision)
        self.model_axis = int(model_axis)
        # Consumed by PlayerPlacement.resolve via cfg.fabric (core/player.py)
        # and InteractionPipeline.from_config via cfg.fabric (core/interact.py);
        # mirrored here so `instantiate(cfg.fabric)` accepts the keys.
        self.player_device = str(player_device)
        self.player_sync = str(player_sync)
        # Consumed by the fused Anakin lane via cfg.fabric (core/fused_loop.py).
        self.shard_superstep = bool(shard_superstep)
        self.async_fetch = bool(async_fetch)
        self._mesh: Optional[mesh_lib.Mesh] = None
        self._launched = False
        self.seed: Optional[int] = None
        self.root_key: Optional[jax.Array] = None
        # The run's observability surface (sheeprl_tpu/telemetry): the CLI
        # replaces this with Telemetry.from_config(cfg); the default no-op
        # keeps direct Runtime construction (tests, scripts) zero-cost.
        self.telemetry: Telemetry = Telemetry.noop()
        # The run's fault-tolerance surface (sheeprl_tpu/core/resilience):
        # same contract as telemetry — the CLI installs Resilience.from_config
        # and the no-op default keeps bare Runtime construction untouched.
        from sheeprl_tpu.core.resilience import Resilience

        self.resilience: Resilience = Resilience.noop()
        # The run's training-health sentinels (sheeprl_tpu/telemetry/health):
        # the CLI installs HealthMonitor.from_config; the no-op default keeps
        # bare Runtime construction untouched.
        from sheeprl_tpu.telemetry.health import HealthMonitor

        self.health: HealthMonitor = HealthMonitor.noop()

    # ------------------------------------------------------------ lifecycle
    def launch(self) -> "Runtime":
        """Initialize multi-host (if configured) and build the mesh."""
        if self._launched:
            return self
        if self.accelerator == "cpu" or os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
            # A CPU-selected run (fabric.accelerator=cpu, or JAX_PLATFORMS=cpu
            # in the environment): thread the requested device count through
            # so a multi-device CPU run (fabric.devices=N) gets its virtual
            # N-device platform instead of failing on the 1-device client.
            n = None
            if self.requested_devices not in ("auto", -1, None):
                n = int(self.requested_devices) * self.model_axis
            force_cpu_platform(num_devices=n)
        if self.num_nodes > 1 and not jax.distributed.is_initialized():
            # On TPU pods jax.distributed.initialize() auto-detects the
            # coordinator from platform metadata; no env var is required.
            # Failure must be loud — silently training per-host with a halved
            # world is worse than crashing.
            jax.distributed.initialize()
        configure_compilation_cache()
        self._mesh = mesh_lib.build_mesh(
            devices=self._select_devices(),
            data_axis_size=None,
            model_axis_size=self.model_axis,
        )
        self._launched = True
        return self

    def _select_devices(self) -> Sequence[jax.Device]:
        if self.accelerator == "cpu":
            devs = jax.devices("cpu")
        elif self.accelerator == "tpu":
            devs = [d for d in jax.devices() if d.platform == "tpu"]
            if not devs:
                raise RuntimeError("accelerator=tpu requested but no TPU devices are visible")
        else:  # auto
            devs = jax.devices()
        if self.requested_devices in ("auto", -1, None):
            return devs
        n = int(self.requested_devices) * self.model_axis
        if n > len(devs):
            raise RuntimeError(
                f"Requested {n} devices (devices={self.requested_devices} x model_axis={self.model_axis}) "
                f"but only {len(devs)} are visible"
            )
        return devs[:n]

    # ------------------------------------------------------------ properties
    @property
    def mesh(self) -> mesh_lib.Mesh:
        if self._mesh is None:
            self.launch()
        return self._mesh

    @property
    def device(self) -> jax.Device:
        return self.mesh.devices.flat[0]

    @property
    def world_size(self) -> int:
        """Number of data-parallel workers (devices on the data axis).

        Plays the role of the reference's world_size: per_rank_* config values
        are per data-parallel shard.
        """
        return int(self.mesh.shape[mesh_lib.DATA_AXIS])

    @property
    def global_rank(self) -> int:
        return jax.process_index()

    @property
    def node_rank(self) -> int:
        return jax.process_index()

    @property
    def is_global_zero(self) -> bool:
        return jax.process_index() == 0

    # ------------------------------------------------------------ utilities
    def seed_everything(self, seed: int) -> jax.Array:
        # Different hosts must draw different env seeds but identical model
        # init: algorithms use root_key (identical) for params and
        # fold_in(rank) streams for env/sampling.
        self.seed = seed
        # Post-launch the backend exists, so the rank is known here; before
        # launch() single-process semantics apply (core/prng.py).
        rank = jax.process_index() if self._launched else 0
        self.root_key = seed_everything(seed, rank=rank)
        return self.root_key

    def print(self, *args: Any, **kwargs: Any) -> None:
        if self.is_global_zero:
            print(*args, **kwargs)

    def shard_batch(self, tree: Any, axis: int = 0) -> Any:
        return mesh_lib.shard_batch(tree, self.mesh, axis=axis)

    def replicate(self, tree: Any) -> Any:
        return mesh_lib.replicate(tree, self.mesh)

    def host_init(self):
        """Context manager: run eager parameter/optimizer initialization on
        the host CPU backend.

        Flax ``.init`` and optax ``.init`` dispatch eagerly, one primitive at
        a time, and on an accelerator each of those tiny dispatches pays a
        compile plus a host-device round trip. Initialize host-side, then
        move the finished pytrees to the mesh in one pass with
        :meth:`shard_params` (host-to-device transfers are bulk and cheap).
        Where the CPU backend is not enabled (``JAX_PLATFORMS`` names the
        accelerator only) the init simply runs on the mesh device.
        """
        try:
            cpu = jax.devices("cpu")[0]
        except RuntimeError:
            return contextlib.nullcontext()
        return jax.default_device(cpu)

    def shard_params(self, tree: Any, min_dim: int = 1024) -> Any:
        """Place params/opt-state on the mesh: wide leaves tensor-parallel over
        the `model` axis (when model_axis > 1), the rest replicated."""
        return mesh_lib.shard_wide_params(tree, self.mesh, min_dim=min_dim)

    def to_host(self, tree: Any) -> Any:
        return jax.tree_util.tree_map(np.asarray, tree)

    def local_batch_size(self, global_batch: int) -> int:
        return mesh_lib.local_batch_size(global_batch, self.mesh)

    def __repr__(self) -> str:  # pragma: no cover
        # repr must not initialize the JAX backend as a side effect (that
        # would lock in the platform before launch()).
        if self._mesh is None:
            return f"Runtime(accelerator={self.accelerator}, precision={self.precision.name}, unlaunched)"
        return (
            f"Runtime(accelerator={self.accelerator}, precision={self.precision.name}, "
            f"mesh={dict(self.mesh.shape)}, processes={jax.process_count()})"
        )


def get_single_device_runtime(runtime: Runtime) -> Runtime:
    """A single-device view of an existing runtime, for the *player*.

    Parity with `get_single_device_fabric` (sheeprl/utils/fabric.py:8-35): env
    interaction must never synchronize across the mesh. In JAX terms the
    player just runs jitted forwards on device 0 with replicated params — no
    collective ops are traced, so a separate strategy object is unnecessary;
    this helper exists to make that intent explicit at call sites.
    """
    view = Runtime(
        devices=1,
        num_nodes=1,
        strategy="single_device",
        accelerator=runtime.accelerator,
        precision=runtime.precision.name,
        model_axis=1,
    )
    # The player must live on a device *this process* can address: the global
    # mesh's first device belongs to process 0, which is remote on other hosts.
    local = [d for d in runtime.mesh.devices.flat if d.process_index == jax.process_index()]
    player_device = local[0] if local else jax.local_devices()[0]
    view._mesh = mesh_lib.build_mesh(devices=[player_device], model_axis_size=1)
    view._launched = True
    view.seed = runtime.seed
    view.root_key = runtime.root_key
    view.telemetry = runtime.telemetry
    view.resilience = runtime.resilience
    view.health = runtime.health
    return view
