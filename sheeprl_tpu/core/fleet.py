"""sheeprl_tpu.core.fleet — process-level supervision of actor replicas.

PR 8's EnvSupervisor restarts env *slices inside* the controller process;
this module promotes the same contract one level up, to the Podracer/Sebulba
actor fleet (arXiv:2104.06272): N actor-replica *processes* step environments
and ship rollout rows to the one learner process, which trains and broadcasts
params back. An actor death must read as a throughput dip, not a dead run.

Supervision contract (mirrors EnvSupervisor, plus the process-boundary
concerns that do not exist in-process):

- **Liveness** is a monotonic deadline fed by heartbeats piggybacked on every
  rollout shipment, with an idle-ping fallback for replicas that go long
  between shipments (PPO collecting a rollout segment, SAC waiting for first
  params). A SIGKILL'd replica is usually detected faster than the deadline:
  its pipe EOF surfaces on the very next poll.
- **Restart** of a dead replica uses exponential backoff with jitter and
  deterministic ``SeedSequence([seed, replica, restart])`` reseeding — the
  restarted process explores fresh trajectories instead of replaying the
  pre-crash ones, and a given (seed, replica, restart) triple is
  reproducible across runs.
- **Replay continuity**: transport is one private ``mp.Pipe`` pair per
  replica, so a replica killed mid-``send`` corrupts only its own stream.
  Complete-but-unread messages from a dead replica are drained WITHOUT
  ingestion and accounted on ``fleet/rows_dropped`` — rows either fully
  reach the replay buffer or are counted as lost, never half-ingested.
- **Quorum circuit breaker**: the learner keeps training as replicas
  permanently die (graceful degradation) until fewer than ``quorum`` can
  ever ship again, at which point :class:`FleetQuorumError` hard-errors the
  run — silent single-replica "fleets" are how throughput regressions hide.
- **Drain**: on learner preemption, :meth:`FleetSupervisor.drain_and_stop`
  delivers stop to every replica, waits for their byes, and only then does
  the learner commit its final (topology-elastic, see utils/checkpoint.py)
  sharded checkpoint and exit.

Every parent→child message (params broadcast AND stop) is serialized by the
replica's dedicated pump thread: two writers interleaving pickles on one
pipe is stream corruption, and a pump blocked on a hung replica dies with
the pipe instead of wedging the learner.

Observability: ``fleet/replicas_live``, ``fleet/replica_restarts``,
``fleet/heartbeat_age_s`` and ``fleet/rows_dropped`` live in the process
MetricsRegistry; spawn/restart/drain are tracer spans under the ``fleet``
category; every replica death is a flight-recorder trip recording who died,
why, and at which generation.
"""

from __future__ import annotations

import importlib
import multiprocessing as mp
import os
import threading
import time
import traceback
from collections import deque
from dataclasses import dataclass, field
from multiprocessing import connection as mp_connection
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from sheeprl_tpu.core import chaos

__all__ = [
    "FleetQuorumError",
    "FleetSupervisor",
    "ReplicaContext",
    "Shipment",
    "fleet_active",
    "replica_seed",
]

_LIVENESS_TICK_S = 0.1


class FleetQuorumError(RuntimeError):
    """Fewer replicas can ever ship again than ``fleet.quorum`` requires."""


def fleet_active(cfg: Any) -> bool:
    """True iff this run distributes acting over supervised replica
    processes. ``fleet.enabled`` null/absent means auto: active exactly when
    ``fleet.replicas > 1`` — the default single-replica config preserves
    today's in-process player loop bit for bit."""
    fleet_cfg = cfg.get("fleet") if hasattr(cfg, "get") else None
    if not fleet_cfg:
        return False
    enabled = fleet_cfg.get("enabled", None)
    replicas = int(fleet_cfg.get("replicas", 1) or 1)
    if enabled is None:
        return replicas > 1
    return bool(enabled)


def replica_seed(seed: int, replica: int, restart: int) -> int:
    """Deterministic per-(replica, restart) seed — same spawn-key derivation
    as EnvSupervisor.restart_seed, one level up: restart k of replica r is
    reproducible across runs yet never replays the pre-crash stream."""
    return int(np.random.SeedSequence([int(seed), int(replica), int(restart)]).generate_state(1)[0] % (2**31 - 1))


@dataclass
class Shipment:
    """One admitted actor→learner message, ready to ingest."""

    replica: int
    generation: int
    seq: int
    kind: str  # "rows" (per-step off-policy rows) | "rollout" (on-policy segment)
    rows: Dict[str, Any]
    env_steps: int
    episodes: List[Tuple[float, float]] = field(default_factory=list)
    meta: Dict[str, Any] = field(default_factory=dict)


# --------------------------------------------------------------- child side
class _StopRequested(Exception):
    """Raised inside a replica when the supervisor delivered stop mid-wait."""


class ReplicaContext:
    """The actor loop's handle on the fleet, inside the replica process.

    Owns the replica's half of both pipes, the per-replica chaos monkey
    (``kill9`` / ``drop_shipment`` specs targeting this replica index fire
    from :meth:`ship`), heartbeat bookkeeping, and the latest-params cache.
    """

    def __init__(
        self,
        cfg: Any,
        replica: int,
        restart: int,
        seed: int,
        log_dir: str,
        rows_conn: Any,
        ctrl_conn: Any,
        ping_interval_s: float,
        max_inflight: int = 0,
    ) -> None:
        self.cfg = cfg
        self.replica = int(replica)
        self.restart = int(restart)
        self.seed = int(seed)
        self.log_dir = log_dir
        self._rows_conn = rows_conn
        self._ctrl_conn = ctrl_conn
        self._ping_interval_s = float(ping_interval_s)
        # Credit-based backpressure (0 = unlimited): the supervisor returns
        # one credit per INGESTED shipment, so a replica can run at most
        # max_inflight shipments ahead of the learner — bounded pipe memory,
        # and on shared hardware the actor stops stealing cycles the train
        # step needs.
        self._max_inflight = int(max_inflight)
        self._credits = self._max_inflight
        self._seq = 0
        self._env_steps = 0
        self._last_sent = time.monotonic()
        self._stop = False
        self._params: Any = None
        self._version = 0
        self._monkey: Optional[chaos.ChaosMonkey] = None
        res = cfg.get("resilience") if hasattr(cfg, "get") else None
        chaos_cfg = res.get("chaos") if res else None
        if chaos_cfg and chaos_cfg.get("enabled", False) and self.restart == 0:
            # Generation 0 only: chaos's fired-injector registry is process
            # global, and a restarted replica is a NEW process — without this
            # gate a replica-scoped kill9 would re-fire every generation and
            # grind the slot into its max_restarts limit. One configured
            # fault is one fault (the chaos module's own contract).
            self._monkey = chaos.ChaosMonkey(chaos_cfg.get("injectors"), replica=self.replica)

    # ------------------------------------------------------------ shipping
    def ship(
        self,
        rows: Dict[str, Any],
        env_steps: int,
        episodes: Sequence[Tuple[float, float]] = (),
        kind: str = "rows",
        meta: Optional[Dict[str, Any]] = None,
    ) -> bool:
        """Ship rollout rows to the learner; the send doubles as this
        replica's heartbeat. False when a ``drop_shipment`` injector
        swallowed the message (the env steps still happened — the learner
        must survive the gap on idle pings)."""
        self._env_steps += int(env_steps)
        if self._monkey is not None:
            # kill9 lands here: mid-shipping, no drain — the realistic spot.
            self._monkey.on_step(self._env_steps)
        self._drain_ctrl()
        if chaos.maybe_drop("fleet.ship"):
            return False  # the credit is kept: nothing reached the wire
        if self._max_inflight > 0:
            while self._credits <= 0:
                if self._stop:
                    return False  # draining: don't queue rows nobody will read
                self.maybe_ping()  # liveness must not depend on throughput
                self._ctrl_conn.poll(0.05)
                self._drain_ctrl()
            self._credits -= 1
        self._send(kind, {
            "rows": rows,
            "env_steps": int(env_steps),
            "episodes": list(episodes),
            "meta": dict(meta or {}),
        })
        return True

    def _send(self, kind: str, payload: Any) -> None:
        self._seq += 1
        self._rows_conn.send((kind, self.restart, self._seq, payload))
        self._last_sent = time.monotonic()

    def maybe_ping(self) -> None:
        """Idle-ping fallback: call from any loop that can go longer than
        the ping interval without shipping (PPO's rollout collection, SAC's
        wait for first params) so liveness does not depend on throughput."""
        if time.monotonic() - self._last_sent >= self._ping_interval_s:
            self._send("ping", None)

    # -------------------------------------------------------------- params
    def _drain_ctrl(self) -> None:
        while self._ctrl_conn.poll(0):
            msg = self._ctrl_conn.recv()
            if msg[0] == "params":
                version = int(msg[1])
                if version > self._version:
                    self._version, self._params = version, msg[2]
            elif msg[0] == "credit":
                self._credits += int(msg[1])
            elif msg[0] == "stop":
                self._stop = True

    def poll_params(self) -> Optional[Tuple[int, Any]]:
        """Latest (version, host params) broadcast so far, or None."""
        self._drain_ctrl()
        return (self._version, self._params) if self._params is not None else None

    def wait_params(
        self, min_version: int = 1, timeout: Optional[float] = None, poll_s: float = 0.05
    ) -> Optional[Tuple[int, Any]]:
        """Block (with idle pings) until params of at least ``min_version``
        arrive; None on timeout or when the supervisor delivered stop
        mid-wait (callers check :meth:`should_stop` next)."""
        deadline = None if timeout is None else time.monotonic() + float(timeout)
        while True:
            self._drain_ctrl()
            if self._stop:
                return None
            if self._params is not None and self._version >= int(min_version):
                return self._version, self._params
            if deadline is not None and time.monotonic() >= deadline:
                return None
            self.maybe_ping()
            self._ctrl_conn.poll(poll_s)

    def should_stop(self) -> bool:
        self._drain_ctrl()
        return self._stop


def _resolve_actor(spec: str) -> Callable[[ReplicaContext], None]:
    """``"pkg.module:function"`` → callable. A dotted spec (not a pickled
    closure) is what makes the spawn start method viable: the child imports
    the module fresh, so the actor fn never drags the learner's state over."""
    module_name, _, fn_name = spec.partition(":")
    if not module_name or not fn_name:
        raise ValueError(f"actor spec must look like 'pkg.module:function', got {spec!r}")
    module = importlib.import_module(module_name)
    fn = getattr(module, fn_name, None)
    if not callable(fn):
        raise TypeError(f"actor spec {spec!r} does not name a callable")
    return fn


def _replica_entry(
    actor_spec: str,
    cfg: Any,
    log_dir: str,
    replica: int,
    restart: int,
    seed: int,
    ping_interval_s: float,
    max_inflight: int,
    sys_path: List[str],
    rows_conn: Any,
    ctrl_conn: Any,
) -> None:
    """Replica process main. Runs the actor loop until it returns (complete),
    the supervisor says stop, or something dies — always tries to tell the
    learner why via a final ``bye`` (a SIGKILL of course never reaches it;
    that is what pipe-EOF death evidence is for)."""
    import sys

    # Actors are host processes by design: the learner holds the chip, and a
    # second process that initialised the accelerator would fail or hang.
    # Select the CPU platform before the actor module's first JAX touch.
    from sheeprl_tpu.core.runtime import force_cpu_platform

    force_cpu_platform()
    for entry in sys_path:  # spawn children must see the test/driver modules
        if entry not in sys.path:
            sys.path.insert(0, entry)
    ctx = ReplicaContext(
        cfg, replica, restart, seed, log_dir, rows_conn, ctrl_conn, ping_interval_s,
        max_inflight=max_inflight,
    )
    try:
        ctx._send("hello", {"pid": os.getpid()})
        actor = _resolve_actor(actor_spec)
        actor(ctx)
        ctx._send("bye", {"reason": "stop" if ctx.should_stop() else "complete"})
    except (BrokenPipeError, EOFError, OSError):
        # Learner side went away: nothing to report to, nobody to restart us.
        os._exit(1)
    except _StopRequested:
        try:
            ctx._send("bye", {"reason": "stop"})
        except Exception:  # noqa: BLE001
            pass
    except BaseException as exc:  # noqa: BLE001 - crash evidence beats silence
        traceback.print_exc()
        try:
            ctx._send("bye", {"reason": f"crash: {type(exc).__name__}: {exc}"})
        except Exception:  # noqa: BLE001
            pass
        os._exit(1)


# -------------------------------------------------------------- parent side
class _ParamPump(threading.Thread):
    """Single-writer serializer for one replica's control pipe.

    The learner thread never blocks on a slow/dead replica: it drops the
    newest params into the latest-wins slot and moves on; this daemon thread
    does the (potentially blocking) pickling+send and simply dies with the
    pipe when the replica does.
    """

    def __init__(self, conn: Any, name: str) -> None:
        super().__init__(name=name, daemon=True)
        self._conn = conn
        self._cond = threading.Condition()
        self._params: Optional[Tuple[int, Any]] = None  # graftlint: guarded-by(self._cond)
        self._credits = 0  # graftlint: guarded-by(self._cond)
        self._stop = False  # graftlint: guarded-by(self._cond)
        self._closed = False  # graftlint: guarded-by(self._cond)

    def offer_params(self, version: int, tree: Any) -> None:
        with self._cond:
            self._params = (int(version), tree)
            self._cond.notify()

    def grant(self, n: int = 1) -> None:
        """Flow-control credits: one per shipment the learner ingested."""
        with self._cond:
            self._credits += int(n)
            self._cond.notify()

    def offer_stop(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify()

    def close(self) -> None:
        """Abandon without sending (the replica is already dead)."""
        with self._cond:
            self._closed = True
            self._cond.notify()

    def run(self) -> None:
        try:
            while True:
                with self._cond:
                    while not (
                        self._params is not None or self._credits or self._stop or self._closed
                    ):
                        self._cond.wait()
                    if self._closed:
                        return
                    params, self._params = self._params, None
                    credits, self._credits = self._credits, 0
                    stop = self._stop
                try:
                    if credits:
                        self._conn.send(("credit", credits, None))
                    if params is not None:
                        self._conn.send(("params", params[0], params[1]))
                    if stop:
                        self._conn.send(("stop", None, None))
                        return
                except (OSError, ValueError, BrokenPipeError):
                    return  # pipe died with the replica; supervisor handles it
        finally:
            try:
                self._conn.close()
            except OSError:
                pass


@dataclass
class _ReplicaSlot:
    index: int
    proc: Optional[Any] = None
    conn: Optional[Any] = None  # rows pipe, parent (read) end
    pump: Optional[_ParamPump] = None
    generation: int = 0  # == restart count of the live process
    restarts: int = 0  # total deaths observed
    last_seen: float = 0.0  # monotonic; fed by any message on the rows pipe
    dead: bool = False  # exhausted max_restarts — never coming back
    finished: bool = False  # actor loop returned cleanly — not a failure

    @property
    def live(self) -> bool:
        return self.proc is not None and not self.dead and not self.finished


class FleetSupervisor:
    """Runs and supervises N actor-replica processes feeding one learner.

    Lifecycle: ``start()`` → interleaved ``recv()`` / ``push_params()`` from
    the train loop → ``drain_and_stop()`` on preemption or ``close()``
    unconditionally. All methods are for the learner's main thread; the only
    internal thread is the per-replica param pump.
    """

    def __init__(
        self,
        actor_spec: str,
        cfg: Any,
        *,
        replicas: int,
        seed: int,
        log_dir: str = "",
        heartbeat_timeout_s: float = 30.0,
        ping_interval_s: float = 2.0,
        max_restarts: int = 8,
        backoff_base_s: float = 0.25,
        backoff_max_s: float = 5.0,
        backoff_jitter: float = 0.2,
        quorum: int = 1,
        start_method: str = "spawn",
        daemon_replicas: bool = True,
        drain_timeout_s: float = 10.0,
        max_inflight: int = 4,
    ) -> None:
        if replicas < 1:
            raise ValueError(f"fleet needs at least 1 replica, got {replicas}")
        if not (1 <= quorum <= replicas):
            raise ValueError(f"fleet.quorum must be in [1, replicas={replicas}], got {quorum}")
        self._actor_spec = actor_spec
        self._cfg = cfg
        self._replicas = int(replicas)
        self._seed = int(seed)
        self._log_dir = log_dir
        self._heartbeat_timeout_s = float(heartbeat_timeout_s)
        self._ping_interval_s = float(ping_interval_s)
        self._max_restarts = int(max_restarts)
        self._backoff_base_s = float(backoff_base_s)
        self._backoff_max_s = float(backoff_max_s)
        self._backoff_jitter = float(backoff_jitter)
        self._quorum = int(quorum)
        self._daemon = bool(daemon_replicas)
        self._drain_timeout_s = float(drain_timeout_s)
        # Credit-based flow control: each replica may run at most max_inflight
        # shipments ahead of the learner's ingestion (0 = unbounded). Bounds
        # pipe memory AND stops replicas stealing CPU from the learner on
        # shared cores.
        self._max_inflight = int(max_inflight)
        self._mp = mp.get_context(start_method)
        self._slots: List[_ReplicaSlot] = [_ReplicaSlot(index=i) for i in range(self._replicas)]
        self._pending: "deque[Shipment]" = deque()
        self._latest_params: Optional[Tuple[int, Any]] = None
        self._started = False
        self._stopped = False
        self._rows_dropped = 0
        self._restarts_total = 0

    @classmethod
    def from_config(cls, cfg: Any, actor_spec: str, *, seed: int, log_dir: str) -> "FleetSupervisor":
        f = cfg.fleet
        return cls(
            actor_spec,
            cfg,
            replicas=int(f.replicas),
            seed=int(seed),
            log_dir=log_dir,
            heartbeat_timeout_s=float(f.heartbeat_timeout_s),
            ping_interval_s=float(f.ping_interval_s),
            max_restarts=int(f.max_restarts),
            backoff_base_s=float(f.backoff_base_s),
            backoff_max_s=float(f.backoff_max_s),
            backoff_jitter=float(f.backoff_jitter),
            quorum=int(f.quorum),
            start_method=str(f.start_method),
            daemon_replicas=bool(f.daemon_replicas),
            drain_timeout_s=float(f.drain_timeout_s),
            max_inflight=int(f.max_inflight),
        )

    # ---------------------------------------------------------- observability
    def _registry(self):
        from sheeprl_tpu.telemetry.registry import default_registry

        return default_registry()

    def _tracer(self):
        from sheeprl_tpu.telemetry import tracer as tracer_mod

        return tracer_mod.current()

    def _publish_live(self) -> None:
        self._registry().gauge("fleet/replicas_live").set(float(self.live_replicas))

    def _publish_heartbeat_age(self, now: float) -> None:
        ages = [now - s.last_seen for s in self._slots if s.live and s.last_seen > 0.0]
        if ages:
            self._registry().gauge("fleet/heartbeat_age_s").set(max(0.0, max(ages)))

    # --------------------------------------------------------------- queries
    @property
    def replicas(self) -> int:
        return self._replicas

    @property
    def live_replicas(self) -> int:
        return sum(1 for s in self._slots if s.live)

    @property
    def restarts_total(self) -> int:
        return self._restarts_total

    @property
    def rows_dropped(self) -> int:
        return self._rows_dropped

    def replica_generation(self, index: int) -> int:
        return self._slots[index].generation

    # -------------------------------------------------------------- lifecycle
    def start(self) -> None:
        import sys

        if self._started:
            raise RuntimeError("FleetSupervisor.start() called twice")
        self._started = True
        self._sys_path = list(sys.path)
        start = time.perf_counter()
        for slot in self._slots:
            self._spawn(slot)
        self._publish_live()
        self._tracer().add_span(
            "fleet/spawn", "fleet", start, time.perf_counter() - start,
            {"replicas": self._replicas},
        )

    def _spawn(self, slot: _ReplicaSlot) -> None:
        """(Re)start one replica at its current generation."""
        # duplex=False pipes: (reader, writer). Rows flow child->parent, ctrl
        # flows parent->child — each process closes its copy of the far end
        # so a death reads as EOF instead of a forever-open pipe.
        rows_parent, rows_child = self._mp.Pipe(duplex=False)
        ctrl_child, ctrl_parent = self._mp.Pipe(duplex=False)
        seed = replica_seed(self._seed, slot.index, slot.generation)
        proc = self._mp.Process(
            target=_replica_entry,
            name=f"fleet-replica-{slot.index}-g{slot.generation}",
            args=(
                self._actor_spec,
                self._cfg,
                self._log_dir,
                slot.index,
                slot.generation,
                seed,
                self._ping_interval_s,
                self._max_inflight,
                self._sys_path,
                rows_child,
                ctrl_child,
            ),
            daemon=self._daemon,
        )
        proc.start()
        # The parent's copies of the child ends must close, or a dead child
        # never reads as EOF on the rows pipe.
        rows_child.close()
        ctrl_child.close()
        slot.proc = proc
        slot.conn = rows_parent
        slot.pump = _ParamPump(ctrl_parent, name=f"fleet-pump-{slot.index}-g{slot.generation}")
        slot.pump.start()
        slot.last_seen = time.monotonic()
        if self._latest_params is not None:
            # A restarted replica must not wait a full sync interval for
            # weights the learner already broadcast.
            slot.pump.offer_params(*self._latest_params)

    def push_params(self, params: Any, version: int) -> None:
        """Broadcast host params to every live replica (latest-wins per
        replica; a restarted replica is re-offered the newest broadcast)."""
        self._latest_params = (int(version), params)
        for slot in self._slots:
            if slot.live and slot.pump is not None:
                slot.pump.offer_params(int(version), params)

    # ------------------------------------------------------------------ recv
    def recv(self, timeout: Optional[float] = None) -> Optional[Shipment]:
        """Next admitted shipment, or None on timeout / fully-drained fleet.

        Liveness checks, restarts, heartbeat accounting, and quorum
        enforcement all run from inside this poll loop — the learner calling
        ``recv`` IS the supervisor's event loop; there is no hidden thread
        that could race the replay-buffer ingest.
        """
        if not self._started or self._stopped:
            return None
        deadline = None if timeout is None else time.monotonic() + float(timeout)
        while True:
            self._check_quorum()
            if self._pending:
                return self._hand_to_learner(self._pending.popleft())
            live = [s for s in self._slots if s.live and s.conn is not None]
            if not live:
                return None  # every replica finished or is permanently dead (>= quorum finished)
            now = time.monotonic()
            wait_s = _LIVENESS_TICK_S
            if deadline is not None:
                wait_s = min(wait_s, max(0.0, deadline - now))
            ready = mp_connection.wait([s.conn for s in live], wait_s)
            by_conn = {id(s.conn): s for s in live}
            # Pump EVERY ready conn one message before returning anything: a
            # replica that ships faster than the learner ingests keeps its
            # pipe permanently ready, and returning its rows first each time
            # would starve a dead sibling's EOF forever.
            for conn in ready:
                slot = by_conn[id(conn)]
                shipment = self._pump_conn(slot)
                if shipment is not None:
                    self._pending.append(shipment)
            self._liveness_pass(time.monotonic())
            self._publish_heartbeat_age(time.monotonic())
            if self._pending:
                return self._hand_to_learner(self._pending.popleft())
            if deadline is not None and time.monotonic() >= deadline:
                return None

    def _hand_to_learner(self, shipment: Shipment) -> Shipment:
        """A shipment leaving the supervisor for ingestion refunds its flow
        credit — the sender may now run one shipment further ahead. Granting
        at hand-off (not at pipe read) keeps the credit bound honest: rows
        parked in ``_pending`` still count against the sender."""
        slot = self._slots[shipment.replica]
        if slot.live and slot.pump is not None:
            slot.pump.grant(1)
        return shipment

    def _pump_conn(self, slot: _ReplicaSlot) -> Optional[Shipment]:
        """Read one message from a ready rows pipe; death evidence (EOF,
        torn pickle) routes into the restart path."""
        try:
            msg = slot.conn.recv()
        except Exception as exc:  # noqa: BLE001 - EOF/torn msg == death evidence
            self._on_death(slot, f"rows pipe broke: {type(exc).__name__}")
            return None
        slot.last_seen = time.monotonic()
        kind, generation, seq, payload = msg
        if kind in ("hello", "ping"):
            return None
        if kind == "bye":
            reason = str((payload or {}).get("reason", "unknown"))
            if reason in ("stop", "complete"):
                self._on_finished(slot)
            else:
                self._on_death(slot, reason)
            return None
        # rows / rollout
        if int(generation) != slot.generation:
            # A pre-restart straggler: the replay-continuity contract says
            # drop-and-account, never half-ingest.
            self._account_dropped(int(payload.get("env_steps", 0)))
            return None
        self._tracer().count("fleet/shipments")
        return Shipment(
            replica=slot.index,
            generation=int(generation),
            seq=int(seq),
            kind=str(kind),
            rows=payload["rows"],
            env_steps=int(payload["env_steps"]),
            episodes=list(payload.get("episodes", [])),
            meta=dict(payload.get("meta", {})),
        )

    def _liveness_pass(self, now: float) -> None:
        for slot in self._slots:
            if not slot.live:
                continue
            if not slot.proc.is_alive():
                try:
                    pending = slot.conn is not None and slot.conn.poll(0)
                except OSError:
                    pending = False
                if pending:
                    # The process is gone but complete messages (possibly its
                    # clean bye) are still queued: read those first, or a
                    # cleanly-finished replica gets "restarted" by this race.
                    continue
                self._on_death(slot, f"process exited (code {slot.proc.exitcode})")
            elif now - slot.last_seen > self._heartbeat_timeout_s:
                # Hung, not dead: reap it ourselves, then restart. SIGKILL —
                # a process that stopped heartbeating cannot be trusted to
                # honor SIGTERM either.
                slot.proc.kill()
                slot.proc.join(timeout=5.0)
                self._on_death(slot, f"heartbeat timeout ({self._heartbeat_timeout_s:.1f}s)")

    # ------------------------------------------------------------ death path
    def _account_dropped(self, env_steps: int) -> None:
        if env_steps > 0:
            self._rows_dropped += int(env_steps)
            self._registry().counter("fleet/rows_dropped").inc(int(env_steps))

    def _drain_conn_dropping(self, slot: _ReplicaSlot) -> None:
        """Swallow whatever complete messages the dead replica managed to
        queue, accounting their rows as dropped — they were in flight when
        it died and the buffer never saw them."""
        try:
            while slot.conn.poll(0):
                msg = slot.conn.recv()
                if msg[0] in ("rows", "rollout"):
                    self._account_dropped(int(msg[3].get("env_steps", 0)))
        except Exception:  # noqa: BLE001 - the torn tail of the stream
            pass

    def _close_slot_transport(self, slot: _ReplicaSlot) -> None:
        if slot.pump is not None:
            slot.pump.close()
            slot.pump = None
        if slot.conn is not None:
            try:
                slot.conn.close()
            except OSError:
                pass
            slot.conn = None

    def _on_finished(self, slot: _ReplicaSlot) -> None:
        slot.finished = True
        if slot.proc is not None:
            slot.proc.join(timeout=5.0)
        self._close_slot_transport(slot)
        self._publish_live()

    def _on_death(self, slot: _ReplicaSlot, reason: str) -> None:
        start = time.perf_counter()
        self._drain_conn_dropping(slot)
        self._close_slot_transport(slot)
        if slot.proc is not None and slot.proc.is_alive():
            slot.proc.kill()
        if slot.proc is not None:
            slot.proc.join(timeout=5.0)
        slot.restarts += 1
        from sheeprl_tpu.telemetry import flight

        flight.dump_on_trip(
            "fleet/replica_death",
            message=f"replica {slot.index} (generation {slot.generation}) died: {reason}",
            args={
                "replica": slot.index,
                "generation": slot.generation,
                "restarts": slot.restarts,
                "reason": reason,
            },
        )
        if slot.restarts > self._max_restarts:
            slot.dead = True
            slot.proc = None
            self._publish_live()
            self._check_quorum()
            return
        # Exponential backoff with deterministic jitter: [seed, replica,
        # restart] keys the jitter stream too, so a flaky test cannot hide
        # behind restart timing.
        backoff = min(
            self._backoff_base_s * (2 ** max(0, slot.restarts - 1)), self._backoff_max_s
        )
        jitter_rng = np.random.default_rng(
            np.random.SeedSequence([self._seed, slot.index, slot.restarts, 0xB0FF])
        )
        time.sleep(backoff * (1.0 + self._backoff_jitter * float(jitter_rng.random())))
        slot.generation = slot.restarts
        self._spawn(slot)
        self._restarts_total += 1
        self._registry().counter("fleet/replica_restarts").inc()
        self._publish_live()
        self._tracer().add_span(
            "fleet/restart", "fleet", start, time.perf_counter() - start,
            {"replica": slot.index, "generation": slot.generation, "reason": reason},
        )

    def _check_quorum(self) -> None:
        can_ship = sum(1 for s in self._slots if not s.dead)
        if can_ship < self._quorum:
            raise FleetQuorumError(
                f"only {can_ship} of {self._replicas} replicas can still ship "
                f"(quorum {self._quorum}); refusing to limp along on a fleet "
                "that no longer exists"
            )

    # ------------------------------------------------------------------ stop
    def drain_and_stop(self, timeout: Optional[float] = None) -> None:
        """Coordinated whole-fleet drain: deliver stop, collect byes (rows
        arriving after the learner stopped ingesting are accounted dropped),
        reap everything. The caller commits its final checkpoint AFTER this
        returns — fleet first, save second, exit third."""
        if not self._started or self._stopped:
            return
        start = time.perf_counter()
        timeout = self._drain_timeout_s if timeout is None else float(timeout)
        # Shipments pumped off the wire but never handed to the learner are
        # dropped whole, same as rows still in flight.
        while self._pending:
            self._account_dropped(int(self._pending.popleft().env_steps))
        for slot in self._slots:
            if slot.live and slot.pump is not None:
                slot.pump.offer_stop()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            pending = [s for s in self._slots if s.live and s.conn is not None]
            if not pending:
                break
            ready = mp_connection.wait(
                [s.conn for s in pending], min(0.25, max(0.0, deadline - time.monotonic()))
            )
            by_conn = {id(s.conn): s for s in pending}
            for conn in ready:
                slot = by_conn[id(conn)]
                try:
                    msg = slot.conn.recv()
                except Exception:  # noqa: BLE001 - died mid-drain; reap below
                    self._drain_conn_dropping(slot)
                    self._close_slot_transport(slot)
                    slot.finished = True
                    continue
                if msg[0] == "bye":
                    self._on_finished(slot)
                elif msg[0] in ("rows", "rollout"):
                    self._account_dropped(int(msg[3].get("env_steps", 0)))
        self._stop_all(graceful_joined=True)
        self._tracer().add_span(
            "fleet/drain", "fleet", start, time.perf_counter() - start,
            {"rows_dropped": self._rows_dropped},
        )

    def close(self) -> None:
        """Unconditional teardown (idempotent): terminate whatever still
        runs. Use :meth:`drain_and_stop` first when replay accounting and
        clean byes matter."""
        if not self._started or self._stopped:
            self._stopped = self._started or self._stopped
            return
        self._stop_all(graceful_joined=False)

    def _stop_all(self, graceful_joined: bool) -> None:
        for slot in self._slots:
            proc = slot.proc
            if proc is not None and proc.is_alive():
                if not graceful_joined:
                    proc.terminate()
                proc.join(timeout=5.0)
                if proc.is_alive():
                    proc.kill()
                    proc.join(timeout=5.0)
            self._close_slot_transport(slot)
            slot.proc = None
        self._stopped = True
        self._publish_live()
