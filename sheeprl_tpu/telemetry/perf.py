"""Roofline goodput accounting: how far from the hardware ceiling a run is.

The ROADMAP's north star is "as fast as the hardware allows", and Podracer
(arXiv:2104.06272) makes that a measurable quantity: the fraction of the
device's peak FLOPs/bandwidth the program actually uses. EnvPool
(arXiv:2206.10558) adds the complementary lesson that RL throughput is a
pipeline property — a single env-steps/s number cannot say *which* lane
(compute, infeed, host) regressed. This module productizes both readouts:

- :func:`jit_cost` harvests ``lower().compile().cost_analysis()`` (FLOPs,
  bytes accessed) from an already-warm donated jit using shape specs
  captured BEFORE dispatch, so donation never turns the harvest into a
  use-after-donate;
- :func:`resolve_peaks` supplies the per-backend hardware ceiling: a device
  table for TPU/GPU kinds, a calibrated micro-kernel probe on the CPU
  fallback (BLAS sgemm for FLOPs, a large memcpy for bandwidth), env/config
  overrides for both;
- :class:`PerfAccountant` combines harvested costs with the StepTimer's
  measured dispatch+bound time and wall-clock interval anchors to publish
  ``perf/mfu``, ``perf/hbm_bw_util``, and the
  ``perf/step_time_breakdown_{compute,infeed,host}`` fractions (summing to
  ~1) as gauges through the tracer (-> telemetry.jsonl) and the
  :class:`~sheeprl_tpu.telemetry.registry.MetricsRegistry` (-> /metrics).

Hot-path discipline: :meth:`PerfAccountant.note` on the dispatch path is a
dict increment after the first sighting of a key (shape specs are captured
once, the expensive lower/compile harvest is deferred to the log-interval
:meth:`publish`), and every method short-circuits when disabled — the
accountant rides the same <2% A/B budget as health probes and tracing.

jax is imported lazily inside functions only: the module itself stays
importable from the jax-free ``python -m sheeprl_tpu.telemetry`` CLI paths.
"""

from __future__ import annotations

import os
import threading
import time
import warnings
from contextlib import contextmanager
from typing import Any, Dict, Optional, Tuple

__all__ = [
    "PerfAccountant",
    "jit_cost",
    "resolve_peaks",
    "peaks_for_device_kind",
    "GAUGE_PREFIX",
]

GAUGE_PREFIX = "perf"

#: Peak dense-math FLOP/s and HBM bandwidth (bytes/s) per accelerator kind,
#: matched by substring against ``device.device_kind.lower()``. Sources: the
#: public TPU/GPU datasheets (bf16/fp16 peak for accelerators — the recipe
#: precision on those backends). First match wins. A TPU v5e reports
#: ``device_kind == "TPU v5 lite"``, so its peaks (Google Cloud "TPU v5e":
#: 197 TFLOP/s bf16, 819 GB/s HBM) are listed under both spellings.
PEAK_TABLE: Tuple[Tuple[str, float, float], ...] = (
    ("v5p", 459e12, 2.765e12),
    ("v5 lite", 197e12, 819e9),
    ("v5e", 197e12, 819e9),
    ("v4", 275e12, 1.23e12),
    ("v3", 123e12, 0.90e12),
    ("v2", 45e12, 0.70e12),
    ("h100", 989e12, 3.35e12),
    ("a100", 312e12, 1.94e12),
    ("v100", 125e12, 0.90e12),
    ("rtx 3080", 59.5e12, 0.76e12),
)


def peaks_for_device_kind(device_kind: str) -> Tuple[float, float]:
    """``(peak FLOP/s, peak bytes/s)`` of an accelerator from
    :data:`PEAK_TABLE`. A kind the table does not hold is an error that
    names it — a missing row must never read as a zero ceiling."""
    kind = (device_kind or "").lower()
    for needle, flops, bw in PEAK_TABLE:
        if needle in kind:
            return flops, bw
    raise LookupError(
        f"device kind {device_kind!r} is not in sheeprl_tpu.telemetry.perf.PEAK_TABLE: add its "
        "datasheet peaks there (or set SHEEPRL_PERF_PEAK_FLOPS and SHEEPRL_PERF_PEAK_BW_GBPS)"
    )


# ------------------------------------------------------------------ ceilings
_probe_lock = threading.Lock()
_probe_cache: Dict[str, Tuple[float, float]] = {}  # graftlint: guarded-by(_probe_lock)


def _probe_cpu_peaks(reps: int = 3, n: int = 256, copy_mb: int = 32) -> Tuple[float, float]:
    """Calibrated micro-kernel probe for the CPU fallback: there is no
    datasheet number for "whatever this container is throttled to", so the
    achievable ceiling is measured — best-of-``reps`` BLAS sgemm for FLOP/s
    (numpy, not jnp: an XLA compile would time the compiler) and a
    best-of-``reps`` large ``copyto`` for memory bandwidth. ~100 ms once per
    process; the verdict is cached by :func:`resolve_peaks`."""
    import numpy as np

    rng = np.random.default_rng(0)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    a @ b  # BLAS thread-pool warmup
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        a @ b
        best = min(best, time.perf_counter() - t0)
    peak_flops = (2.0 * n * n * n) / max(best, 1e-9)

    words = (copy_mb << 20) // 4
    src = np.zeros(words, np.float32)
    dst = np.empty_like(src)
    np.copyto(dst, src)  # page-fault warmup
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np.copyto(dst, src)
        best = min(best, time.perf_counter() - t0)
    # One read + one write stream.
    peak_bw = (2.0 * src.nbytes) / max(best, 1e-9)
    return peak_flops, peak_bw


def resolve_peaks(
    backend: Optional[str] = None,
    device_kind: Optional[str] = None,
    *,
    peak_flops: Optional[float] = None,
    peak_bytes_per_s: Optional[float] = None,
    probe: bool = True,
) -> Dict[str, Any]:
    """The hardware ceiling for roofline accounting, resolved in priority
    order: explicit/config values, ``SHEEPRL_PERF_PEAK_FLOPS`` /
    ``SHEEPRL_PERF_PEAK_BW_GBPS`` env overrides, the :data:`PEAK_TABLE`
    device-kind match on an accelerator, the CPU micro-kernel probe on the
    CPU backend. Returns ``{"flops", "bytes_per_s", "source"}``; an
    accelerator kind the table does not hold is warned about by name and
    resolves to zeros (gauges depending on the ceiling are then omitted,
    never wrong)."""
    env_flops = os.environ.get("SHEEPRL_PERF_PEAK_FLOPS")
    env_bw = os.environ.get("SHEEPRL_PERF_PEAK_BW_GBPS")
    try:
        if peak_flops is None and env_flops:
            peak_flops = float(env_flops)
        if peak_bytes_per_s is None and env_bw:
            peak_bytes_per_s = float(env_bw) * 1e9
    except ValueError:
        pass
    if peak_flops is not None and peak_bytes_per_s is not None:
        return {"flops": float(peak_flops), "bytes_per_s": float(peak_bytes_per_s), "source": "override"}

    if backend is None or device_kind is None:
        try:
            import jax

            device = jax.devices()[0]
            backend = backend or jax.default_backend()
            device_kind = device_kind or getattr(device, "device_kind", "")
        except Exception:
            backend = backend or "unknown"
            device_kind = device_kind or ""

    flops, bw, source = 0.0, 0.0, "none"
    if backend != "cpu":
        try:
            flops, bw = peaks_for_device_kind(device_kind or "")
            source = "table"
        except LookupError as err:
            # A training run goes on without its utilization gauges, but
            # says which device it could not account for; chip_smoke.py
            # calls peaks_for_device_kind itself and fails.
            warnings.warn(f"{err}; perf/mfu and perf/hbm_bw_util are not published")
    elif probe:
        with _probe_lock:
            cached = _probe_cache.get("cpu")
            if cached is None:
                cached = _probe_cpu_peaks()
                _probe_cache["cpu"] = cached
        flops, bw = cached
        source = "probe"
    return {
        "flops": float(peak_flops if peak_flops is not None else flops),
        "bytes_per_s": float(peak_bytes_per_s if peak_bytes_per_s is not None else bw),
        "source": source,
    }


# ------------------------------------------------------------------- harvest
def _arg_specs(tree: Any) -> Any:
    """Shape/dtype specs for a pytree of (possibly soon-donated) arrays.
    Array-likes become ``jax.ShapeDtypeStruct``; everything else (python
    scalars, None) passes through verbatim so weak-typing matches the real
    call and ``lower`` resolves to the SAME executable the loop compiled.
    The leaf's sharding rides along when present — without it the deferred
    lowering sees single-device inputs and the per-shard attribution
    (mesh_obs.shares_from_aot) would pile every flop onto device 0."""
    import jax

    def spec(leaf: Any) -> Any:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            sharding = getattr(leaf, "sharding", None)
            # An uncommitted array (fresh host transfer on the default
            # device) is movable: the real dispatch lets jit place it next
            # to the committed args, so pinning its SingleDeviceSharding
            # here would lower a different — mixed-device, hence invalid —
            # program when the other args live on a multi-device mesh.
            if sharding is not None and not getattr(leaf, "_committed", True):
                sharding = None
            try:
                return jax.ShapeDtypeStruct(tuple(leaf.shape), leaf.dtype, sharding=sharding)
            except TypeError:
                return jax.ShapeDtypeStruct(tuple(leaf.shape), leaf.dtype)
        return leaf

    return jax.tree_util.tree_map(spec, tree)


def _cost_from_compiled(compiled: Any) -> Optional[Dict[str, float]]:
    """FLOPs + bytes accessed from an already-compiled executable's
    ``cost_analysis()``; None when the backend exposes no cost model."""
    analysis = compiled.cost_analysis()
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    if not isinstance(analysis, dict):
        return None
    flops = float(analysis.get("flops", 0.0))
    bytes_accessed = float(analysis.get("bytes accessed", 0.0))
    if flops <= 0.0 and bytes_accessed <= 0.0:
        return None
    return {"flops": max(flops, 0.0), "bytes": max(bytes_accessed, 0.0)}


def jit_cost(fn: Any, args: Tuple[Any, ...] = (), kwargs: Optional[Dict[str, Any]] = None) -> Optional[Dict[str, float]]:
    """FLOPs + bytes accessed of one dispatch of ``fn(*args, **kwargs)`` from
    XLA's own cost model (``Compiled.cost_analysis``). ``args`` may be live
    arrays or the specs :func:`_arg_specs` captured before donation. Returns
    None when the backend/jax version exposes no cost model — callers degrade
    to time-only accounting, never crash a train loop over a metric."""
    try:
        lowered = fn.lower(*args, **(kwargs or {}))
        return _cost_from_compiled(lowered.compile())
    except Exception:
        return None


# ---------------------------------------------------------------- accountant
class PerfAccountant:
    """Per-run goodput accountant: note() on the dispatch path, publish() at
    the log interval. A disabled accountant is a safe no-op on every method
    (one attribute check), so loops thread it unconditionally."""

    def __init__(
        self,
        enabled: bool = False,
        prefix: str = GAUGE_PREFIX,
        registry: Optional[Any] = None,
        peaks: Optional[Dict[str, Any]] = None,
        peak_flops: Optional[float] = None,
        peak_hbm_gbps: Optional[float] = None,
        probe: bool = True,
        max_harvests: int = 16,
        per_shard: bool = True,
    ) -> None:
        self.enabled = bool(enabled)
        self.prefix = prefix
        self._registry = registry
        self._peaks = peaks
        self._peak_flops_cfg = peak_flops
        self._peak_bw_cfg = peak_hbm_gbps * 1e9 if peak_hbm_gbps else None
        self._probe = bool(probe)
        self._max_harvests = int(max_harvests)
        self._per_shard = bool(per_shard)
        self._lock = threading.Lock()
        self._specs: Dict[str, Tuple[Any, Any, Any]] = {}  # graftlint: guarded-by(self._lock)
        self._costs: Dict[str, Dict[str, float]] = {}  # graftlint: guarded-by(self._lock)
        self._counts: Dict[str, int] = {}  # graftlint: guarded-by(self._lock)
        self._steps: Dict[str, float] = {}  # graftlint: guarded-by(self._lock)
        self._infeed_s = 0.0  # graftlint: guarded-by(self._lock)
        self._compute_s = 0.0  # graftlint: guarded-by(self._lock)
        self.harvest_failures = 0
        # Mesh attribution state: the live mesh (set_mesh), per-key device
        # shares from the AOT shardings, and the per-device running totals
        # the interval differencing anchors against.
        self._mesh: Optional[Any] = None  # graftlint: guarded-by(self._lock)
        self._shard_shares: Dict[str, Dict[int, float]] = {}  # graftlint: guarded-by(self._lock)
        self._prev_shard: Dict[int, float] = {}  # graftlint: guarded-by(self._lock)
        self._dev_labels: Optional[Dict[int, str]] = None  # graftlint: guarded-by(self._lock)
        # Interval state: wall anchor starts at first recorded activity so
        # the first published interval measures the loop, not agent init.
        self._anchor: Optional[float] = None
        self._prev: Dict[str, float] = {"flops": 0.0, "bytes": 0.0, "steps": 0.0, "compute_s": 0.0, "infeed_s": 0.0, "timer_s": 0.0}
        self.last_gauges: Dict[str, float] = {}

    def set_mesh(self, mesh: Any) -> None:
        """Attach the live device mesh so publish() also splits the flop
        totals per shard (``perf/shard/<label>/mfu``, HBM occupancy, and the
        max/mean imbalance gauge). Safe to call more than once; a mesh swap
        resets the per-device differencing anchors."""
        if not self.enabled or mesh is None:
            return
        with self._lock:
            self._mesh = mesh
            self._prev_shard = {}
            self._dev_labels = None

    # ------------------------------------------------------------- hot path
    def note(self, key: str, fn: Any = None, args: Tuple[Any, ...] = (), kwargs: Optional[Dict[str, Any]] = None, steps: float = 1.0) -> None:
        """Account one dispatch of the jit behind ``key``. Call BEFORE the
        dispatch so arg shapes are captured pre-donation; after the first
        sighting of a key this is a locked dict increment. The lower/compile
        harvest itself is deferred to publish() — off the step path."""
        if not self.enabled:
            return
        now = time.perf_counter()
        with self._lock:
            if self._anchor is None:
                self._anchor = now
            self._counts[key] = self._counts.get(key, 0) + 1
            self._steps[key] = self._steps.get(key, 0.0) + float(steps)
            if fn is None or key in self._costs or key in self._specs:
                return
            if len(self._costs) + len(self._specs) >= self._max_harvests:
                return
            try:
                specs = _arg_specs(tuple(args))
            except Exception:
                self.harvest_failures += 1
                return
            self._specs[key] = (fn, specs, dict(kwargs) if kwargs else None)

    @contextmanager
    def infeed(self):
        """Wrap the env-interaction / data-infeed phase of an iteration; the
        accumulated seconds become the ``infeed`` share of the breakdown."""
        if not self.enabled:
            yield
            return
        start = time.perf_counter()
        try:
            yield
        finally:
            elapsed = time.perf_counter() - start
            with self._lock:
                if self._anchor is None:
                    self._anchor = start
                self._infeed_s += elapsed

    def add_compute(self, seconds: float) -> None:
        """Credit measured device-compute seconds directly (the serve engine
        times each batch apply itself instead of carrying a StepTimer)."""
        if not self.enabled:
            return
        with self._lock:
            if self._anchor is None:
                self._anchor = time.perf_counter()
            self._compute_s += float(seconds)

    # -------------------------------------------------------------- publish
    def _resolve_peaks_locked(self) -> Dict[str, Any]:
        if self._peaks is None:
            self._peaks = resolve_peaks(
                peak_flops=self._peak_flops_cfg,
                peak_bytes_per_s=self._peak_bw_cfg,
                probe=self._probe,
            )
        return self._peaks

    def _harvest_pending(self) -> None:
        """Resolve every deferred cost harvest. Runs at publish time (log
        interval), never on the dispatch path; a failed harvest is recorded
        and not retried (the key degrades to count-only accounting). One
        lower/compile serves both the cost total and — when a mesh is
        attached — the per-device shares from the executable's shardings."""
        with self._lock:
            pending = list(self._specs.items())
            self._specs.clear()
            want_shares = self._per_shard and self._mesh is not None
        for key, (fn, specs, kwargs) in pending:
            cost = None
            shares = None
            try:
                lowered = fn.lower(*specs, **(kwargs or {}))
                compiled = lowered.compile()
                cost = _cost_from_compiled(compiled)
                if want_shares and cost is not None:
                    from sheeprl_tpu.telemetry import mesh_obs

                    shares = mesh_obs.shares_from_aot(lowered, compiled)
            except Exception:  # noqa: BLE001 - degrade, never crash the loop
                cost = None
            with self._lock:
                if cost is None:
                    self.harvest_failures += 1
                    self._costs[key] = {"flops": 0.0, "bytes": 0.0}
                else:
                    self._costs[key] = cost
                if shares:
                    self._shard_shares[key] = shares

    def _shard_interval_locked(self) -> Tuple[Optional[Dict[int, float]], Dict[int, str], Dict[int, Any]]:
        """Per-device flop deltas for this interval (caller holds the lock).

        Every mesh device starts at 0.0 so idle shards still weigh into the
        imbalance denominator; keys without harvested shares split uniformly
        across the mesh, preserving Σ(shard flops) == aggregate flops — the
        invariant that makes the per-shard MFU gauges sum to ``perf/mfu``.
        Returns ``(deltas, labels, devices)`` or ``(None, {}, {})`` when no
        mesh is attached."""
        if not self._per_shard or self._mesh is None:
            return None, {}, {}
        from sheeprl_tpu.telemetry import mesh_obs

        if self._dev_labels is None:
            self._dev_labels = mesh_obs.device_labels(self._mesh)
        mesh_devices = {int(d.id): d for d in self._mesh.devices.flat}
        totals: Dict[int, float] = {dev_id: 0.0 for dev_id in mesh_devices}
        for key, cost in self._costs.items():
            count = self._counts.get(key, 0)
            flops = cost.get("flops", 0.0)
            if count <= 0 or flops <= 0.0:
                continue
            shares = self._shard_shares.get(key) or mesh_obs.uniform_shares(mesh_devices)
            for dev_id, share in shares.items():
                totals[dev_id] = totals.get(dev_id, 0.0) + count * flops * share
        deltas = {dev_id: max(total - self._prev_shard.get(dev_id, 0.0), 0.0) for dev_id, total in totals.items()}
        self._prev_shard = totals
        return deltas, dict(self._dev_labels), mesh_devices

    def publish(self, step_timer: Any = None, tracer: Any = None, registry: Any = None) -> Dict[str, float]:
        """Compute the interval's goodput gauges and push them to the tracer
        (telemetry.jsonl) and metrics registry (/metrics). Call once per log
        interval, AFTER the StepTimer flush trued up the interval's bound
        time. Returns the gauge dict (also kept in :attr:`last_gauges`)."""
        if not self.enabled:
            return {}
        self._harvest_pending()
        now = time.perf_counter()
        with self._lock:
            anchor = self._anchor
            if anchor is None:
                return {}
            self._anchor = now
            flops_total = sum(self._counts.get(k, 0) * c["flops"] for k, c in self._costs.items())
            bytes_total = sum(self._counts.get(k, 0) * c["bytes"] for k, c in self._costs.items())
            steps_total = sum(self._steps.values())
            infeed_total = self._infeed_s
            compute_direct_total = self._compute_s
            prev = self._prev
            timer_total = float(step_timer.interval_seconds) if step_timer is not None else 0.0
            wall = max(now - anchor, 1e-9)
            flops_d = max(flops_total - prev["flops"], 0.0)
            bytes_d = max(bytes_total - prev["bytes"], 0.0)
            steps_d = max(steps_total - prev["steps"], 0.0)
            infeed_d = max(infeed_total - prev["infeed_s"], 0.0)
            compute_d = max(compute_direct_total - prev["compute_s"], 0.0) + max(
                timer_total - prev["timer_s"], 0.0
            )
            self._prev = {
                "flops": flops_total,
                "bytes": bytes_total,
                "steps": steps_total,
                "compute_s": compute_direct_total,
                "infeed_s": infeed_total,
                "timer_s": timer_total,
            }
            peaks = self._resolve_peaks_locked()
            shard_d, shard_labels, mesh_devices = self._shard_interval_locked()

        # Breakdown fractions: compute + infeed measured on the loop thread,
        # host is the remainder. Pipelined overlap can push the measured sum
        # past the wall by at most the (tiny) enqueue share — normalize so
        # the three fractions always sum to ~1.
        total = compute_d + infeed_d
        if total > wall:
            compute_d *= wall / total
            infeed_d *= wall / total
        host_d = max(wall - compute_d - infeed_d, 0.0)

        p = self.prefix
        gauges: Dict[str, float] = {
            f"{p}/flops_per_s": flops_d / wall,
            f"{p}/bytes_per_s": bytes_d / wall,
            f"{p}/step_time_breakdown_compute": compute_d / wall,
            f"{p}/step_time_breakdown_infeed": infeed_d / wall,
            f"{p}/step_time_breakdown_host": host_d / wall,
            f"{p}/train_steps_per_s": steps_d / wall,
        }
        if peaks["flops"] > 0.0:
            gauges[f"{p}/mfu"] = flops_d / (wall * peaks["flops"])
            gauges[f"{p}/peak_flops"] = peaks["flops"]
        if peaks["bytes_per_s"] > 0.0:
            gauges[f"{p}/hbm_bw_util"] = bytes_d / (wall * peaks["bytes_per_s"])
            gauges[f"{p}/peak_hbm_bytes_per_s"] = peaks["bytes_per_s"]

        if shard_d is not None:
            from sheeprl_tpu.telemetry import mesh_obs

            if peaks["flops"] > 0.0:
                for dev_id in sorted(shard_d):
                    label = shard_labels.get(dev_id, f"device={dev_id}")
                    gauges[f"{p}/{mesh_obs.SHARD_NS}/{label}/mfu"] = shard_d[dev_id] / (wall * peaks["flops"])
            gauges[f"{p}/shard_imbalance"] = mesh_obs.imbalance(shard_d.values())
            for dev_id, dev in mesh_devices.items():
                try:
                    stats = dev.memory_stats()
                except Exception:  # noqa: BLE001 - optional per-backend API
                    stats = None
                if isinstance(stats, dict) and "bytes_in_use" in stats:
                    label = shard_labels.get(dev_id, f"device={dev_id}")
                    gauges[f"{p}/{mesh_obs.SHARD_NS}/{label}/hbm_bytes_in_use"] = float(stats["bytes_in_use"])

        if tracer is not None:
            for name, value in gauges.items():
                tracer.set_gauge(name, value)
        reg = registry if registry is not None else self._registry
        if reg is None:
            from sheeprl_tpu.telemetry.registry import default_registry

            reg = default_registry()
        reg.set_gauges(gauges)
        self.last_gauges = dict(gauges)
        return gauges

    # ------------------------------------------------------------ snapshots
    def costs(self) -> Dict[str, Dict[str, float]]:
        """Harvested per-key costs."""
        self._harvest_pending()
        with self._lock:
            return {k: dict(v) for k, v in self._costs.items()}

    def peaks(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._resolve_peaks_locked())
