"""Stdlib-only HTTP front end for the inference engine.

One ``ThreadingHTTPServer`` (no third-party web stack — the serving path has
the same zero-new-dependencies rule as the rest of the repo) exposing:

- ``POST /v1/act`` — body ``{"model", "obs", "mode"?, "seed"?, "session"?,
  "deadline_s"?}``; responds ``{"action": [...], "session": ...}``;
- ``GET /v1/models`` — model cards for every hosted artifact plus engine
  stats (latency percentiles, occupancy, counters);
- ``GET /healthz`` — liveness + queue depth (load balancers poll this);
- ``GET /metrics`` — Prometheus text exposition (0.0.4) of the engine's
  :class:`~sheeprl_tpu.telemetry.MetricsRegistry` merged with the process
  default registry, so a scraper sees serving and training/telemetry
  metrics from one endpoint.

Engine exceptions map onto transport semantics: unknown model → 404, bad
request rows → 400, :class:`EngineOverloaded` → 429 with ``Retry-After``
(deadline-based shedding — the engine refuses work it cannot finish in
time rather than queueing it to die), :class:`RequestExpired` → 504, and a
draining engine → 503.

Causality: every request gets an ``X-Request-Id`` (the client's, or a
minted one) and a W3C ``traceparent`` context (a child of the client's, or
a fresh root). Both come back as response headers on EVERY reply —
including 4xx/5xx error paths — so a client can always correlate its call
with the server-side trace, and the engine's per-request spans join the
caller's trace across the wire. The access log is one structured
``key=value`` line per request (request_id, route, status, latency_ms,
batch bucket) on the ``sheeprl_tpu.serve.access`` logger; shed/drain
errors log at WARNING with the same ``Retry-After`` value the response
carries, which also lands them in the flight recorder's ring.

Shutdown reuses the resilience discipline: ``serve_forever`` installs a
:class:`~sheeprl_tpu.core.resilience.PreemptionGuard` (pointer writes off —
nothing to checkpoint) and on SIGTERM stops accepting connections, drains
the queue through ``engine.close(drain=True)``, then exits 0.
"""

from __future__ import annotations

import json
import logging
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

import numpy as np

from sheeprl_tpu.serve.engine import (
    EngineClosed,
    EngineOverloaded,
    InferenceEngine,
    RequestExpired,
)
from sheeprl_tpu.telemetry import flight as flight_mod
from sheeprl_tpu.telemetry import trace_context
from sheeprl_tpu.telemetry.registry import (
    PROMETHEUS_CONTENT_TYPE,
    default_registry,
    merged_prometheus_text,
)

_ACCESS_LOG = logging.getLogger("sheeprl_tpu.serve.access")


def _json_bytes(payload: Dict[str, Any]) -> bytes:
    return json.dumps(payload).encode("utf-8")


class _Handler(BaseHTTPRequestHandler):
    # Set by PolicyServer before the server starts.
    engine: InferenceEngine

    server_version = "sheeprl-tpu-serve/1"
    protocol_version = "HTTP/1.1"

    def log_message(self, format: str, *args: Any) -> None:  # noqa: A002
        pass  # the structured access log below replaces the stdlib line

    # ------------------------------------------------------------- plumbing
    def _begin_request(self) -> None:
        """Accept-or-mint the request id and trace context; one call at the
        top of every route handler."""
        self._t_start = time.perf_counter()
        self._status: Optional[int] = None
        self._retry_after: Optional[str] = None
        self._bucket: Optional[int] = None
        rid = (self.headers.get("X-Request-Id") or "").strip()
        self._request_id = rid or uuid.uuid4().hex
        parent = trace_context.TraceContext.from_traceparent(
            self.headers.get("traceparent") or ""
        )
        self._ctx = trace_context.mint(parent)

    def _log_access(self, route: str) -> None:
        latency_ms = (time.perf_counter() - self._t_start) * 1e3
        status = self._status if self._status is not None else 0
        line = (
            f"request_id={self._request_id} route={route} status={status} "
            f"latency_ms={latency_ms:.2f} bucket={self._bucket if self._bucket is not None else '-'}"
        )
        if self._retry_after is not None:
            # Retry-After in the log matches the header byte-for-byte, so an
            # operator grepping the access log sees the same backoff a client
            # was told. WARNING level also lands it in the flight ring.
            _ACCESS_LOG.warning("%s retry_after_s=%s", line, self._retry_after)
        elif status >= 500:
            _ACCESS_LOG.warning(line)
        else:
            _ACCESS_LOG.info(line)

    def _reply_raw(
        self,
        status: int,
        body: bytes,
        content_type: str,
        headers: Optional[Dict[str, str]] = None,
    ) -> None:
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        # Correlation headers on EVERY reply, error paths included: the
        # client can always tie its call to the server-side trace.
        rid = getattr(self, "_request_id", None)
        if rid is not None:
            self.send_header("X-Request-Id", rid)
        ctx = getattr(self, "_ctx", None)
        if ctx is not None:
            self.send_header("traceparent", ctx.to_traceparent())
        for key, value in (headers or {}).items():
            self.send_header(key, value)
        self.end_headers()
        self.wfile.write(body)
        self._status = status
        self._retry_after = (headers or {}).get("Retry-After")

    def _reply(self, status: int, payload: Dict[str, Any], headers: Optional[Dict[str, str]] = None) -> None:
        self._reply_raw(status, _json_bytes(payload), "application/json", headers)

    def _error(self, status: int, message: str, headers: Optional[Dict[str, str]] = None) -> None:
        self._reply(status, {"error": message, "request_id": getattr(self, "_request_id", None)}, headers)

    # --------------------------------------------------------------- routes
    def do_GET(self) -> None:  # noqa: N802 - BaseHTTPRequestHandler API
        self._begin_request()
        if self.path == "/healthz":
            stats = self.engine.stats()
            self._reply(200, {"status": "ok", "queue_depth": stats["queue_depth"], "models": stats["models"]})
        elif self.path == "/v1/models":
            self._reply(200, {"models": self.engine.models(), "stats": self.engine.stats()})
        elif self.path.split("?")[0] == "/metrics":
            body = merged_prometheus_text([self.engine.registry, default_registry()])
            self._reply_raw(200, body.encode("utf-8"), PROMETHEUS_CONTENT_TYPE)
        else:
            self._error(404, f"no route for GET {self.path}")
        self._log_access(f"GET {self.path.split('?')[0]}")

    def do_POST(self) -> None:  # noqa: N802 - BaseHTTPRequestHandler API
        self._begin_request()
        try:
            self._do_post_inner()
        finally:
            self._log_access(f"POST {self.path.split('?')[0]}")

    def _do_post_inner(self) -> None:
        if self.path != "/v1/act":
            self._error(404, f"no route for POST {self.path}")
            return
        try:
            length = int(self.headers.get("Content-Length", "0"))
            request = json.loads(self.rfile.read(length) or b"{}")
            if not isinstance(request, dict):
                raise ValueError("request body must be a JSON object")
            model = request["model"]
            obs = request["obs"]
        except (KeyError, ValueError, json.JSONDecodeError) as err:
            self._error(400, f"malformed request: {err}")
            return
        deadline_s = request.get("deadline_s")
        try:
            # The request's context is current for the duration of the engine
            # call: the submit path captures it onto the queued request, so
            # the dispatcher's per-request span joins this client's trace.
            with trace_context.use(self._ctx):
                action, info = self.engine.act_with_info(
                    str(model),
                    obs,
                    mode=str(request.get("mode", "greedy")),
                    seed=int(request.get("seed", 0)),
                    session=request.get("session"),
                    deadline_s=float(deadline_s) if deadline_s is not None else None,
                    request_id=self._request_id,
                )
        except KeyError as err:
            self._error(404, str(err))
        except ValueError as err:
            self._error(400, str(err))
        except EngineOverloaded as err:
            self._error(429, str(err), {"Retry-After": f"{err.retry_after_s:.3f}"})
        except RequestExpired as err:
            self._error(504, str(err))
        except EngineClosed as err:
            self._error(503, str(err))
        else:
            self._bucket = info.get("bucket")
            self._reply(
                200,
                {
                    "model": str(model),
                    "action": np.asarray(action).tolist(),
                    "session": request.get("session"),
                    "request_id": self._request_id,
                },
            )


class PolicyServer:
    """Own an engine + HTTP listener pair.

    ``start()`` binds and serves on a daemon thread (tests, in-process use);
    ``serve_forever()`` is the CLI path — foreground with SIGTERM drain.
    """

    def __init__(
        self,
        engine: InferenceEngine,
        *,
        host: str = "127.0.0.1",
        port: int = 8080,
        trace_dir: Optional[str] = None,
    ) -> None:
        self.engine = engine
        # Serve processes run without the training Telemetry facade, so the
        # always-on flight recorder is installed here: overload sheds and
        # crashes produce a dump like any training trip. ``trace_dir`` gives
        # the dumps a home; without one the ring still records (and a later
        # installer can supply a directory).
        if flight_mod.current() is None:
            flight_mod.install(
                flight_mod.FlightRecorder(trace_dir=trace_dir, run_info={"role": "serve"})
            )
        flight_mod.ensure_live_tracer()
        handler = type("BoundHandler", (_Handler,), {"engine": engine})
        self._http = ThreadingHTTPServer((host, port), handler)
        self._http.daemon_threads = True
        self._thread: Optional[threading.Thread] = None

    @property
    def address(self) -> str:
        host, port = self._http.server_address[:2]
        return f"http://{host}:{port}"

    def start(self) -> "PolicyServer":
        self._thread = threading.Thread(target=self._http.serve_forever, name="serve-http", daemon=True)
        self._thread.start()
        return self

    def close(self, drain: bool = True) -> None:
        self._http.shutdown()
        self._http.server_close()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self.engine.close(drain=drain)

    def serve_forever(self, poll_s: float = 0.25) -> None:
        """Foreground serve with graceful preemption: SIGTERM (or Ctrl-C)
        flips the guard, we stop accepting connections, drain the queue,
        and return — the k8s-friendly exit the training loops already use."""
        from sheeprl_tpu.core.resilience import PreemptionGuard

        guard = PreemptionGuard(enabled=True, write_pointer=False).install()
        self.start()
        try:
            while not guard.preempted:
                time.sleep(poll_s)
        finally:
            self.close(drain=True)
            guard.close()


class ServeClient:
    """In-process client mirroring the HTTP surface (tests exercise the
    exact engine semantics without a socket in the loop)."""

    def __init__(self, engine: InferenceEngine) -> None:
        self.engine = engine

    def act(
        self,
        model: str,
        obs: Dict[str, Any],
        *,
        mode: str = "greedy",
        seed: int = 0,
        session: Optional[str] = None,
        deadline_s: Optional[float] = None,
        timeout: Optional[float] = 30.0,
    ) -> np.ndarray:
        return self.engine.act(
            model, obs, mode=mode, seed=seed, session=session, deadline_s=deadline_s, timeout=timeout
        )

    def models(self) -> Dict[str, Any]:
        return self.engine.models()

    def stats(self) -> Dict[str, Any]:
        return self.engine.stats()
