"""Dynamic micro-batching inference engine.

EnvPool's lesson (arXiv:2206.10558) applies on the serving side too: the
wins come from batching the request-facing half, not faster kernels. The
engine turns a stream of single-observation requests into batched, compiled
policy applies:

- requests land in a bounded FIFO; a dispatcher thread drains the head run
  of same-(model, mode) requests into one batch (at most one request per
  recurrent session), optionally lingering ``batch_window_s`` to fill it;
- batches are padded to power-of-two buckets, exactly the trick
  ``algo.fused_train_steps`` uses — the compiled-graph population is bounded
  at log2(max_batch)+1 variants per (model, mode), all warmed up at load so
  no request ever pays a compile;
- each batch is ONE jitted apply (session state donated for recurrent
  policies) followed by ONE coalesced ``device_get`` for the actions — the
  dispatcher body holds no other host syncs;
- actions are stochastic-by-seed (``jax.random.PRNGKey(seed)`` per row, the
  same derivation the evaluate paths use) or greedy; both are deterministic
  functions of (artifact, obs, seed) so responses are replayable;
- multiple artifacts are hosted concurrently with LRU eviction past
  ``max_models``.

Telemetry: every engine metric lives in a
:class:`~sheeprl_tpu.telemetry.MetricsRegistry` (one per engine, or an
injected shared one): request latency is a registry histogram (p50/p95/p99
via ``stats()``), queue depth and batch occupancy are registry gauges, and
sheds/timeouts/errors/evictions are registry counters. ``stats()``, the
server's ``GET /metrics`` Prometheus rendering, and the tracer mirrors in
``telemetry.jsonl`` all read the same objects, so the three surfaces can
never disagree.
"""

from __future__ import annotations

import threading
import time
import uuid
from collections import OrderedDict, deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from sheeprl_tpu.serve.artifact import PolicyArtifact, load_artifact, make_policy
from sheeprl_tpu.telemetry import flight as flight_mod
from sheeprl_tpu.telemetry import trace_context
from sheeprl_tpu.telemetry import tracer as tracer_mod
from sheeprl_tpu.telemetry.registry import MetricsRegistry

MODES = ("greedy", "sample")

#: Engine counter short names; registered as ``serve/<name>`` in the registry.
COUNTER_KEYS = ("requests", "batches", "sheds", "timeouts", "errors", "evictions")


class EngineClosed(RuntimeError):
    """The engine is shut down (requests are not accepted)."""


class EngineOverloaded(RuntimeError):
    """Backpressure signal: queue full, or the estimated wait exceeds the
    request deadline. Carries ``retry_after_s`` for the server's 429."""

    def __init__(self, message: str, retry_after_s: float = 1.0) -> None:
        super().__init__(message)
        self.retry_after_s = float(retry_after_s)


class RequestExpired(TimeoutError):
    """The request's deadline passed while it waited in the queue."""


def next_pow2(n: int) -> int:
    return 1 << (max(int(n), 1) - 1).bit_length()


@dataclass
class _Request:
    model: str
    mode: str
    obs: Any
    seed: int
    session: Optional[str]
    deadline_t: Optional[float]  # absolute monotonic deadline, None = no deadline
    future: Future
    t_submit: float
    # Causality: the trace context active on the SUBMITTING thread (contextvars
    # do not cross into the dispatcher thread, so it rides on the request) plus
    # the caller-facing request id for the access log.
    ctx: Optional[trace_context.TraceContext] = None
    request_id: Optional[str] = None


@dataclass
class _HostedModel:
    name: str
    artifact: Optional[PolicyArtifact]
    adapter: Any
    applies: Dict[str, Any] = field(default_factory=dict)
    sessions: "OrderedDict[str, Any]" = field(default_factory=OrderedDict)
    dummy_session: Any = None


class InferenceEngine:
    def __init__(
        self,
        *,
        max_batch: int = 8,
        queue_capacity: int = 64,
        batch_window_s: float = 0.002,
        max_models: int = 4,
        max_sessions: int = 256,
        autostart: bool = True,
        registry: Optional[MetricsRegistry] = None,
        goodput: bool = True,
    ) -> None:
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.max_batch = next_pow2(max_batch)
        self.buckets = [1 << i for i in range((self.max_batch).bit_length())]
        self.buckets = [b for b in self.buckets if b <= self.max_batch]
        self.queue_capacity = int(queue_capacity)
        self.batch_window_s = float(batch_window_s)
        self.max_models = int(max_models)
        self.max_sessions = int(max_sessions)

        self._models: "OrderedDict[str, _HostedModel]" = OrderedDict()  # graftlint: guarded-by(self._cv)
        self._queue: deque = deque()  # graftlint: guarded-by(self._cv)
        self._cv = threading.Condition()
        self._stop = False  # graftlint: guarded-by(self._cv)
        self._drain_on_close = True  # graftlint: guarded-by(self._cv)
        self._thread: Optional[threading.Thread] = None

        # Registry-backed metrics: ``stats()`` and the server's ``/metrics``
        # rendering read these same objects. A private registry per engine by
        # default so concurrent engines (tests, multi-tenant) don't mix.
        self.registry = registry if registry is not None else MetricsRegistry()
        self.latency = self.registry.histogram("serve/latency_s")
        self._counters = {key: self.registry.counter(f"serve/{key}") for key in COUNTER_KEYS}
        self._queue_depth_gauge = self.registry.gauge("serve/queue_depth")
        self._occupancy_gauge = self.registry.gauge("serve/batch_occupancy")
        # Roofline goodput accounting over the serve jits: cost specs noted at
        # warm-up/dispatch, published into this engine's registry by stats().
        from sheeprl_tpu.telemetry.perf import PerfAccountant

        self.perf = PerfAccountant(enabled=bool(goodput), registry=self.registry)
        # Device provenance gauges: which hardware this engine serves on,
        # scrape-visible so a fleet dashboard can group replicas by backend
        # (the serve-side mirror of the trainer's telemetry meta stamps).
        try:
            from sheeprl_tpu.telemetry.mesh_obs import device_provenance

            provenance = device_provenance()
            if provenance.get("device_count"):
                self.registry.gauge("serve/device_count").set(float(provenance["device_count"]))
                self.registry.gauge("serve/process_index").set(float(provenance.get("process_index", 0)))
        except Exception:  # noqa: BLE001 - metrics bridge must not block serving
            pass
        # bucket -> [requests_served, batches] for mean-occupancy reporting.
        # Written by the dispatcher thread, cleared by reset_stats() from
        # another thread — both sides must hold the condition's lock.
        self._occupancy: Dict[int, List[int]] = {}  # graftlint: guarded-by(self._cv)
        self._ewma_service_s: Optional[float] = None  # graftlint: guarded-by(self._cv)
        # Serve processes have no JaxEventMonitor; the module listeners still
        # mirror compile/retrace/cache traffic into the default registry so
        # ``/metrics`` shows the jax/* counters (warm-up compiles included).
        try:
            from sheeprl_tpu.telemetry import jax_events

            jax_events.install_listeners()
        except Exception:  # noqa: BLE001 - metrics bridge must not block serving
            pass
        if autostart:
            self.start()

    @property
    def counters(self) -> Dict[str, int]:
        """Point-in-time integer view of the registry-backed engine counters."""
        return {key: int(counter.value) for key, counter in self._counters.items()}

    def _count(self, key: str, amount: int = 1) -> None:
        self._counters[key].inc(amount)

    # ------------------------------------------------------------- lifecycle
    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._run, name="serve-dispatcher", daemon=True)
        self._thread.start()

    def close(self, drain: bool = True) -> None:
        """Stop the dispatcher. ``drain=True`` (the SIGTERM path) serves every
        queued request first; ``drain=False`` fails them with EngineClosed."""
        with self._cv:
            self._stop = True
            self._drain_on_close = bool(drain)
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        leftovers: List[_Request] = []
        with self._cv:
            while self._queue:
                leftovers.append(self._queue.popleft())
        for req in leftovers:
            req.future.set_exception(EngineClosed("engine closed before the request was served"))

    # --------------------------------------------------------- model hosting
    def load(self, name: str, path: str, *, warmup: bool = True) -> Dict[str, Any]:
        """Load an artifact under ``name``, compile every (mode, bucket)
        variant, and evict the least-recently-used model past ``max_models``."""
        artifact = load_artifact(path)
        return self.host(name, make_policy(artifact), artifact=artifact, warmup=warmup)

    def host(
        self,
        name: str,
        adapter: Any,
        *,
        artifact: Optional[PolicyArtifact] = None,
        warmup: bool = True,
    ) -> Dict[str, Any]:
        """Mount an already-constructed adapter (the in-process path ``load``
        goes through after reading an artifact from disk)."""
        import jax

        model = _HostedModel(name=name, artifact=artifact, adapter=adapter)
        for mode in MODES:
            donate = (3,) if adapter.stateful else ()
            model.applies[mode] = jax.jit(
                adapter.make_apply(greedy=(mode == "greedy")), donate_argnums=donate
            )
        if adapter.stateful:
            model.dummy_session = adapter.new_session(0)
        if warmup:
            self._warmup(model)
        evicted: List[str] = []
        with self._cv:
            self._models[name] = model
            self._models.move_to_end(name)
            while len(self._models) > self.max_models:
                victim, _ = self._models.popitem(last=False)
                evicted.append(victim)
                self._count("evictions")
        trc = tracer_mod.current()
        trc.count("serve_models_loaded", 1)
        for victim in evicted:
            trc.count("serve_models_evicted", 1)
        return adapter.describe()

    def _warmup(self, model: _HostedModel) -> None:
        """Populate the jit cache for every (mode, bucket) so no live request
        pays a compile. Dispatch-only (no block): compilation happens at
        trace time; execution of the zero batches can overlap freely."""
        start = time.perf_counter()
        for mode in MODES:
            for bucket in self.buckets:
                obs = model.adapter.pack_rows([], bucket)
                seeds = np.zeros((bucket,), np.uint32)
                state = self._stack_sessions(model, [model.dummy_session] * bucket) if model.adapter.stateful else None
                # steps=0: warm-up captures the cost specs without crediting
                # served work; live dispatches count via _dispatch_batch.
                self.perf.note(
                    f"serve/{mode}_b{bucket}", model.applies[mode],
                    (model.adapter.params, obs, seeds, state), steps=0,
                )
                model.applies[mode](model.adapter.params, obs, seeds, state)
        tracer_mod.current().add_span(
            "serve/warmup",
            "serve",
            start,
            time.perf_counter() - start,
            {"model": model.name, "buckets": list(self.buckets)},
        )

    def unload(self, name: str) -> None:
        with self._cv:
            self._models.pop(name, None)

    def models(self) -> Dict[str, Dict[str, Any]]:
        with self._cv:
            hosted = list(self._models.items())
        return {name: model.adapter.describe() for name, model in hosted}

    # --------------------------------------------------------------- ingress
    def estimated_wait_s(self) -> float:
        """Queue depth x EWMA per-request service time: the admission
        estimate the deadline shed compares against."""
        ewma = self._ewma_service_s or 0.0
        return (len(self._queue) + 1) * ewma

    def submit(
        self,
        model: str,
        obs: Any,
        *,
        mode: str = "greedy",
        seed: int = 0,
        session: Optional[str] = None,
        deadline_s: Optional[float] = None,
        request_id: Optional[str] = None,
    ) -> Future:
        """Enqueue one observation; returns a Future resolving to the action
        row (numpy). Raises KeyError (unknown model), ValueError (bad mode /
        malformed obs / missing session), EngineOverloaded (shed), or
        EngineClosed."""
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        with self._cv:
            if self._stop:
                raise EngineClosed("engine is shutting down")
            hosted = self._models.get(model)
        if hosted is None:
            raise KeyError(f"No model named {model!r} is loaded. Loaded: {sorted(self.models())}")
        if hosted.adapter.stateful and session is None:
            raise ValueError(
                f"model {model!r} is recurrent: requests must carry a session id "
                "(any stable string; state is kept per session)"
            )
        row = hosted.adapter.normalize_row(obs)

        if deadline_s is not None and self.estimated_wait_s() > float(deadline_s):
            self._count("sheds")
            tracer_mod.current().count("serve_sheds", 1)
            flight_mod.dump_on_trip(
                "engine_overload",
                message=f"deadline shed: estimated wait {self.estimated_wait_s():.3f}s",
                args={
                    "queue_depth": len(self._queue),
                    "capacity": self.queue_capacity,
                    "request_id": request_id,
                },
            )
            raise EngineOverloaded(
                f"estimated wait {self.estimated_wait_s():.3f}s exceeds the request "
                f"deadline {float(deadline_s):.3f}s",
                retry_after_s=max(self.estimated_wait_s(), 0.05),
            )
        fut: Future = Future()
        req = _Request(
            model=model,
            mode=mode,
            obs=row,
            seed=int(seed),
            session=session,
            deadline_t=(time.monotonic() + float(deadline_s)) if deadline_s is not None else None,
            future=fut,
            t_submit=time.perf_counter(),
            ctx=trace_context.current(),
            request_id=request_id,
        )
        overloaded: Optional[EngineOverloaded] = None
        with self._cv:
            if self._stop:
                raise EngineClosed("engine is shutting down")
            if len(self._queue) >= self.queue_capacity:
                self._count("sheds")
                tracer_mod.current().count("serve_sheds", 1)
                overloaded = EngineOverloaded(
                    f"request queue is full ({self.queue_capacity})",
                    retry_after_s=max(self.estimated_wait_s(), 0.05),
                )
            else:
                self._queue.append(req)
                self._count("requests")
                self._queue_depth_gauge.set(float(len(self._queue)))
                self._cv.notify_all()
        if overloaded is not None:
            # Flight dump OUTSIDE the lock: the recorder merges spill files on
            # a trip, which must not stall the dispatcher or other submitters.
            flight_mod.dump_on_trip(
                "engine_overload",
                message=f"queue-full shed ({self.queue_capacity} queued)",
                args={
                    "queue_depth": self.queue_capacity,
                    "capacity": self.queue_capacity,
                    "request_id": request_id,
                },
            )
            raise overloaded
        return fut

    def act(
        self,
        model: str,
        obs: Any,
        *,
        mode: str = "greedy",
        seed: int = 0,
        session: Optional[str] = None,
        deadline_s: Optional[float] = None,
        timeout: Optional[float] = 30.0,
    ) -> np.ndarray:
        """Synchronous submit + wait (the in-process client path)."""
        return self.submit(
            model, obs, mode=mode, seed=seed, session=session, deadline_s=deadline_s
        ).result(timeout=timeout)

    def act_with_info(
        self,
        model: str,
        obs: Any,
        *,
        mode: str = "greedy",
        seed: int = 0,
        session: Optional[str] = None,
        deadline_s: Optional[float] = None,
        timeout: Optional[float] = 30.0,
        request_id: Optional[str] = None,
    ) -> "tuple[np.ndarray, Dict[str, Any]]":
        """``act`` plus the per-request dispatch info (bucket, queue-wait,
        trace ids) the server's access log wants. The info dict is stamped on
        the future by the dispatcher before the result is set."""
        fut = self.submit(
            model,
            obs,
            mode=mode,
            seed=seed,
            session=session,
            deadline_s=deadline_s,
            request_id=request_id,
        )
        action = fut.result(timeout=timeout)
        info = dict(getattr(fut, "request_info", None) or {})
        return action, info

    def new_session_id(self) -> str:
        return uuid.uuid4().hex

    def end_session(self, model: str, session: str) -> None:
        with self._cv:
            hosted = self._models.get(model)
        if hosted is not None:
            hosted.sessions.pop(session, None)

    # ------------------------------------------------------------ dispatcher
    def _run(self) -> None:
        while True:
            batch = self._next_batch()
            if batch is None:
                return
            self._dispatch_batch(batch)

    def _next_batch(self) -> Optional[List[_Request]]:
        """Block for the next head-of-line run of batchable requests; None
        means the dispatcher should exit (stopped and nothing left to drain)."""
        with self._cv:
            while True:
                if self._queue:
                    break
                if self._stop:
                    return None
                self._cv.wait(timeout=0.1)
            if not self._stop and self.batch_window_s > 0 and len(self._queue) < self.max_batch:
                # Linger briefly to let the batch fill — bounded, and skipped
                # entirely during drain.
                deadline = time.monotonic() + self.batch_window_s
                while len(self._queue) < self.max_batch and not self._stop:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cv.wait(timeout=remaining)
            batch = [self._queue.popleft()]
            sessions = {batch[0].session}
            while self._queue and len(batch) < self.max_batch:
                head: _Request = self._queue[0]
                same_group = head.model == batch[0].model and head.mode == batch[0].mode
                # One request per recurrent session per batch: a session's
                # state advances once per apply.
                session_free = head.session is None or head.session not in sessions
                if not (same_group and session_free):
                    break
                batch.append(self._queue.popleft())
                sessions.add(head.session)
            return batch

    def _get_session(self, model: _HostedModel, req: _Request) -> Any:
        state = model.sessions.get(req.session)
        if state is None:
            state = model.adapter.new_session(req.seed)
            model.sessions[req.session] = state
            while len(model.sessions) > self.max_sessions:
                model.sessions.popitem(last=False)
        model.sessions.move_to_end(req.session)
        return state

    @staticmethod
    def _stack_sessions(model: _HostedModel, rows: List[Any]) -> Any:
        import jax
        import jax.numpy as jnp

        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *rows)

    def _dispatch_batch(self, batch: List[_Request]) -> None:
        import jax

        t_dispatch = time.perf_counter()  # queue-wait ends here for every row
        now = time.monotonic()
        live: List[_Request] = []
        for req in batch:
            if req.deadline_t is not None and now > req.deadline_t:
                self._count("timeouts")
                tracer_mod.current().count("serve_timeouts", 1)
                req.future.set_exception(
                    RequestExpired("deadline passed while the request waited in the queue")
                )
            else:
                live.append(req)
        if not live:
            return
        with self._cv:
            model = self._models.get(live[0].model)
            if model is not None:
                self._models.move_to_end(live[0].model)
        if model is None:
            for req in live:
                req.future.set_exception(KeyError(f"model {live[0].model!r} was evicted"))
            return

        mode = live[0].mode
        bucket = min(next_pow2(len(live)), self.max_batch)
        obs = model.adapter.pack_rows([r.obs for r in live], bucket)
        seeds = np.zeros((bucket,), np.uint32)
        for i, req in enumerate(live):
            seeds[i] = np.uint32(req.seed)
        state = None
        if model.adapter.stateful:
            rows = [self._get_session(model, req) for req in live]
            rows.extend([model.dummy_session] * (bucket - len(live)))
            state = self._stack_sessions(model, rows)

        # Goodput accounting BEFORE the apply (stateful adapters donate the
        # session state): one key per (mode, bucket) program variant.
        self.perf.note(
            f"serve/{mode}_b{bucket}", model.applies[mode],
            (model.adapter.params, obs, seeds, state), steps=len(live),
        )
        start = time.perf_counter()
        try:
            actions, new_state = model.applies[mode](model.adapter.params, obs, seeds, state)
            t_apply = time.perf_counter()
            # ONE coalesced host transfer per batch: the action rows. Session
            # states stay on device (sliced lazily below).
            host_actions = np.asarray(jax.device_get(actions))
        except Exception as err:  # noqa: BLE001 - any apply failure fails the batch
            self._count("errors")
            tracer_mod.current().count("serve_errors", 1)
            for req in live:
                req.future.set_exception(err)
            return
        elapsed = time.perf_counter() - start
        device_s = t_apply - start  # dispatch + (sync backends) execute
        harvest_s = elapsed - device_s  # device_get: where async backends block
        # Apply + harvest is the batch's device-bound share for the goodput
        # breakdown (the engine carries no StepTimer).
        self.perf.add_compute(elapsed)
        if model.adapter.stateful:
            for i, req in enumerate(live):
                model.sessions[req.session] = jax.tree_util.tree_map(lambda x: x[i], new_state)

        per_request = elapsed / len(live)
        with self._cv:
            # reset_stats() clears the occupancy table from another thread
            # mid-run; unlocked setdefault here would resurrect a dead bucket
            # row and double-count against the post-reset window.
            prev = self._ewma_service_s
            self._ewma_service_s = per_request if prev is None else 0.2 * per_request + 0.8 * prev
            occ = self._occupancy.setdefault(bucket, [0, 0])
            occ[0] += len(live)
            occ[1] += 1
        self._count("batches")

        # Causality: every request span is a child of ITS caller's trace (the
        # context captured at submit — contextvars don't reach this thread),
        # and the batch span carries ``links`` naming each request it padded
        # in, so a request id resolves to the exact batch that served it.
        req_ctxs: List[Optional[trace_context.TraceContext]] = [
            req.ctx.child() if req.ctx is not None else None for req in live
        ]
        batch_parent = next((c for c in req_ctxs if c is not None), None)
        batch_ctx = trace_context.mint(batch_parent)
        links = [
            {
                "request_id": req.request_id,
                "trace_id": rctx.trace_id if rctx is not None else None,
                "span_id": rctx.span_id if rctx is not None else None,
            }
            for req, rctx in zip(live, req_ctxs)
        ]

        trc = tracer_mod.current()
        trc.add_span(
            "serve/batch",
            "serve",
            start,
            elapsed,
            {
                "model": model.name,
                "mode": mode,
                "bucket": bucket,
                "occupancy": len(live),
                "links": links,
            },
            ctx=batch_ctx,
        )
        trc.count("serve_batches", 1)
        trc.count("serve_requests_served", len(live))
        queue_depth = float(len(self._queue))
        occupancy_frac = float(len(live)) / float(bucket)
        self._queue_depth_gauge.set(queue_depth)
        self._occupancy_gauge.set(occupancy_frac)
        trc.set_gauge("serve/queue_depth", queue_depth)
        trc.set_gauge("serve/batch_occupancy", occupancy_frac)

        done = time.perf_counter()
        for i, req in enumerate(live):
            self.latency.record(done - req.t_submit)
            queue_wait_s = max(t_dispatch - req.t_submit, 0.0)
            info = {
                "request_id": req.request_id,
                "bucket": bucket,
                "queue_wait_s": queue_wait_s,
                "device_s": device_s,
                "harvest_s": harvest_s,
                "batch_span": batch_ctx.span_id,
                "batch_trace": batch_ctx.trace_id,
            }
            trc.add_span(
                "serve/request",
                "serve",
                req.t_submit,
                done - req.t_submit,
                dict(info),
                ctx=req_ctxs[i],
            )
            # Stamped BEFORE set_result so act_with_info sees it on wake.
            req.future.request_info = info  # type: ignore[attr-defined]
            req.future.set_result(host_actions[i])

    # ----------------------------------------------------------------- stats
    def reset_stats(self) -> None:
        """Zero the latency histogram, occupancy table, and counters (for a
        caller that measures one window at a time); the service-time EWMA is
        kept."""
        with self._cv:
            self.latency.reset()
            self._occupancy.clear()
            for counter in self._counters.values():
                counter.reset()

    def stats(self) -> Dict[str, Any]:
        # Publish the goodput interval into the engine registry so a stats
        # poll and a /metrics scrape report the same perf/* gauges.
        goodput = self.perf.publish()
        occupancy = {
            str(bucket): {
                "batches": int(batches),
                "mean_occupancy": (served / batches) if batches else 0.0,
            }
            for bucket, (served, batches) in sorted(self._occupancy.items())
        }
        return {
            "queue_depth": len(self._queue),
            "counters": dict(self.counters),
            "latency": self.latency.summary(),
            "ewma_service_s": self._ewma_service_s,
            "occupancy": occupancy,
            "models": sorted(self._models),
            "buckets": list(self.buckets),
            "goodput": goodput,
        }
