"""Ops status endpoint: a stdlib HTTP thread serving the fleet's live view.

Opt-in via one environment variable::

    COVALENT_TPU_OPS_PORT=9464 python my_workflow.py
    curl localhost:9464/metrics   # Prometheus text exposition (scrapable)
    curl localhost:9464/status    # JSON: in-flight electrons, heartbeats,
                                  #       circuit breakers, dispatches
    curl localhost:9464/events    # bounded tail of the structured stream

Port 0 binds an ephemeral port (tests); the bound port is readable from
``OpsServer.port`` and logged in the ``ops.server_started`` event.  The
server binds ``COVALENT_TPU_OPS_HOST`` (default loopback — exposing an
unauthenticated ops port beyond the host is an operator decision, not a
default) and runs entirely on daemon threads: it can never hold the
dispatcher open at exit.

``/status`` is assembled from *status providers*: components register a
zero-argument callable (``TPUExecutor`` its in-flight/breaker view, the
workflow runner its dispatch table) and the handler merges their dicts at
request time.  Providers are held weakly by convention — register a
closure over a weakref, return ``{}`` when the owner is gone — so a
forgotten executor cannot be kept alive by its ops registration.

``/events`` is fed by an in-process event listener into a bounded ring
buffer (``COVALENT_TPU_EVENTS_TAIL`` entries, default 256), so the tail
works even when no JSONL path is configured.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable
from urllib.parse import parse_qs, urlparse

from . import events as _events
from . import flightrec as _flightrec
from . import history as _history
from . import slo as _slo
from . import tracestore as _tracestore
from .heartbeat import MONITOR
from .metrics import REGISTRY

__all__ = [
    "OpsServer",
    "ensure_ops_server",
    "register_status_provider",
    "unregister_status_provider",
    "register_profile_provider",
    "unregister_profile_provider",
]

_PORT_ENV = "COVALENT_TPU_OPS_PORT"
_HOST_ENV = "COVALENT_TPU_OPS_HOST"
_TAIL_ENV = "COVALENT_TPU_EVENTS_TAIL"

_providers_lock = threading.Lock()
_providers: dict[str, Callable[[], dict]] = {}
_profile_providers: dict[str, Callable[[dict], "dict | None"]] = {}


def register_status_provider(name: str, provider: Callable[[], dict]) -> None:
    """Contribute a dict to ``/status`` under ``name`` (last write wins)."""
    with _providers_lock:
        _providers[name] = provider


def unregister_status_provider(name: str) -> None:
    with _providers_lock:
        _providers.pop(name, None)


def register_profile_provider(
    name: str, provider: Callable[[dict], "dict | None"]
) -> None:
    """Contribute a ``POST /profile`` target.

    ``provider(params)`` runs on the HTTP request thread and returns the
    capture's artifact info (path, digest, bytes), or None when its owner
    currently has no resident runtime to profile (the handler then tries
    the next provider).  Same weakref-by-convention contract as status
    providers: return None forever once the owner is gone.
    """
    with _providers_lock:
        _profile_providers[name] = provider


def unregister_profile_provider(name: str) -> None:
    with _providers_lock:
        _profile_providers.pop(name, None)


def _tail_size() -> int:
    try:
        return max(16, int(os.environ.get(_TAIL_ENV, "256")))
    except ValueError:
        return 256


class OpsServer:
    """One HTTP thread serving /metrics, /status, /events, /healthz."""

    def __init__(self, port: int, host: str | None = None) -> None:
        self.host = host or os.environ.get(_HOST_ENV) or "127.0.0.1"
        self.started_at = time.time()
        self._tail: collections.deque = collections.deque(
            maxlen=_tail_size()
        )
        self._listener = self._tail.append
        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # protocol-only stdout stays clean
                pass

            def _send(self, code: int, body: bytes, content_type: str) -> None:
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _send_json(self, payload, code: int = 200) -> None:
                self._send(
                    code,
                    json.dumps(payload, default=repr, indent=2).encode(),
                    "application/json",
                )

            def do_GET(self) -> None:  # noqa: N802 - http.server contract
                try:
                    url = urlparse(self.path)
                    route = url.path.rstrip("/") or "/"
                    params = parse_qs(url.query)
                    if route == "/metrics":
                        # OpenMetrics (exemplar-carrying) exposition on
                        # content negotiation or ?format=openmetrics; the
                        # classic 0.0.4 format cannot legally carry
                        # exemplars, so it stays the default.
                        accept = self.headers.get("Accept") or ""
                        fmt = (params.get("format") or [""])[0]
                        openmetrics = (
                            fmt == "openmetrics"
                            or "application/openmetrics-text" in accept
                        )
                        if openmetrics:
                            self._send(
                                200,
                                REGISTRY.prometheus_text(
                                    openmetrics=True
                                ).encode(),
                                "application/openmetrics-text; "
                                "version=1.0.0; charset=utf-8",
                            )
                        else:
                            self._send(
                                200, REGISTRY.prometheus_text().encode(),
                                "text/plain; version=0.0.4",
                            )
                    elif route == "/status":
                        self._send_json(server.status())
                    elif route == "/events":
                        try:
                            n = int(params.get("n", ["0"])[0])
                        except ValueError:
                            n = 0
                        self._send(
                            200, server.events_tail(n).encode(),
                            "application/x-ndjson",
                        )
                    elif route == "/history":
                        self._send_json(server.history(params))
                    elif route == "/slo":
                        self._send_json(server.slo())
                    elif route == "/tasks":
                        self._send_json(
                            {"tasks": _flightrec.FLIGHT_RECORDER.tasks()}
                        )
                    elif route.startswith("/tasks/"):
                        view = _flightrec.FLIGHT_RECORDER.view(
                            route[len("/tasks/"):]
                        )
                        if view is None:
                            self._send_json(
                                {"error": "no flight record"}, 404
                            )
                        else:
                            self._send_json(view)
                    elif route == "/traces":
                        self._send_json(_tracestore.TRACE_STORE.index())
                    elif route.startswith("/traces/"):
                        waterfall = _tracestore.TRACE_STORE.waterfall(
                            route[len("/traces/"):]
                        )
                        if waterfall is None:
                            self._send_json(
                                {"error": "no such trace"}, 404
                            )
                        else:
                            self._send_json(waterfall)
                    elif route in ("/", "/healthz"):
                        self._send(200, b"ok\n", "text/plain")
                    else:
                        self._send(404, b"not found\n", "text/plain")
                except BrokenPipeError:  # client went away mid-write
                    pass
                except Exception as err:  # noqa: BLE001 - ops must not crash
                    try:
                        self._send(
                            500, f"error: {err!r}\n".encode(), "text/plain"
                        )
                    except Exception:  # noqa: BLE001
                        pass

            def do_POST(self) -> None:  # noqa: N802 - http.server contract
                try:
                    url = urlparse(self.path)
                    route = url.path.rstrip("/") or "/"
                    if route != "/profile":
                        self._send(404, b"not found\n", "text/plain")
                        return
                    length = int(self.headers.get("Content-Length") or 0)
                    body = self.rfile.read(length) if length else b""
                    params: dict = {}
                    if body.strip():
                        try:
                            parsed = json.loads(body)
                            if isinstance(parsed, dict):
                                params = parsed
                        except ValueError:
                            self._send_json(
                                {"error": "body must be a JSON object"}, 400
                            )
                            return
                    for key, values in parse_qs(url.query).items():
                        params.setdefault(key, values[0])
                    self._send_json(*server.profile(params))
                except BrokenPipeError:
                    pass
                except Exception as err:  # noqa: BLE001 - ops must not crash
                    try:
                        self._send(
                            500, f"error: {err!r}\n".encode(), "text/plain"
                        )
                    except Exception:  # noqa: BLE001
                        pass

        self._httpd = ThreadingHTTPServer((self.host, port), Handler)
        self._httpd.daemon_threads = True
        self.port = int(self._httpd.server_address[1])
        # A live ops endpoint implies the whole introspection plane: the
        # history sampler (backing /history and the SLO windows), the SLO
        # engine (evaluating per sample), and the flight recorder (backing
        # /tasks).  Each is individually env-disableable and idempotent.
        _history.ensure_history()
        _slo.ensure_slo_engine()
        _flightrec.ensure_flight_recorder()
        _tracestore.ensure_trace_store()
        # Only after the bind succeeded: a failed construction must not
        # leave an orphaned listener on the event stream (ensure_ops_server
        # retries on every executor init, which would accumulate them).
        _events.add_listener(self._listener)
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name="covalent-tpu-ops",
            daemon=True,
        )
        self._thread.start()

    # -- payload assembly --------------------------------------------------

    def status(self) -> dict[str, Any]:
        """The /status JSON: merged provider views + heartbeat snapshot."""
        out: dict[str, Any] = {
            "ts": round(time.time(), 3),
            "pid": os.getpid(),
            "uptime_s": round(time.time() - self.started_at, 3),
            "heartbeats": MONITOR.snapshot(),
            "in_flight": {},
        }
        with _providers_lock:
            providers = dict(_providers)
        fleet_views: dict[str, Any] = {}
        autoscale_views: dict[str, Any] = {}
        for name, provider in providers.items():
            try:
                view = provider()
            except Exception as err:  # noqa: BLE001 - one bad provider
                view = {"error": repr(err)}
            if view is None:
                # Provider's owner was garbage collected: prune the entry.
                unregister_status_provider(name)
                continue
            # Aggregate every provider's in-flight map at the top level so
            # "is electron X running" is one key lookup for operators/CI.
            in_flight = view.get("in_flight")
            if isinstance(in_flight, dict):
                out["in_flight"].update(in_flight)
            # Same for live serving sessions: "is session X open" must be
            # one lookup whichever executor holds it (sids are uuid-unique
            # across executors, so a flat merge cannot collide).
            serving = view.get("serving")
            if isinstance(serving, dict) and serving:
                out.setdefault("serving", {}).update(serving)
            if name.partition(":")[0] == "fleet" and view:
                # The scheduler's live view (queue depth, per-tenant
                # backlog, per-pool capacity/in-use/breakers) is a
                # first-class /status section, not buried in providers.
                # One scheduler (the common case) IS the section; several
                # live ones nest by provider name instead of silently
                # overwriting each other.
                fleet_views[name] = view
            elif name.partition(":")[0] == "autoscale" and view:
                # The autoscale controller's live view (targets, last
                # decisions, cooldown state) gets the same first-class
                # treatment as the fleet section.
                autoscale_views[name] = view
            elif view:
                out.setdefault("providers", {})[name] = view
        if len(fleet_views) == 1:
            out["fleet"] = next(iter(fleet_views.values()))
        elif fleet_views:
            out["fleet"] = fleet_views
        if len(autoscale_views) == 1:
            out["autoscaler"] = next(iter(autoscale_views.values()))
        elif autoscale_views:
            out["autoscaler"] = autoscale_views
        return out

    def history(self, params: dict) -> dict[str, Any]:
        """The /history payload: ring index, or one metric's windowed view.

        ``?metric=<name>&window=<seconds>`` answers the kind-aware query
        (rates for counters, percentiles for histograms, timelines for
        gauges); ``&agg=trend`` swaps the stats for per-window
        least-squares slopes (the autoscale controller's question);
        without ``metric`` the ring describes itself so dashboards can
        discover what is queryable.
        """
        metric = (params.get("metric") or [""])[0]
        if not metric:
            return _history.HISTORY.describe()
        try:
            window_s = float((params.get("window") or ["60"])[0])
        except ValueError:
            window_s = 60.0
        agg = (params.get("agg") or [""])[0]
        return _history.HISTORY.query(metric, window_s=window_s, agg=agg)

    def slo(self) -> dict[str, Any]:
        """The /slo payload: a fresh evaluation of every configured SLO."""
        engine = _slo.get_engine() or _slo.ensure_slo_engine()
        if engine is None:
            return {"disabled": True, "slos": {}}
        return engine.evaluate()

    def profile(self, params: dict) -> "tuple[dict[str, Any], int]":
        """The POST /profile action: capture a resident-runtime trace.

        Tries every registered profile provider (each owns one executor's
        resident runtimes) until one captures; 503 when none can — no
        executor alive, or none with a warm resident runtime.
        """
        with _providers_lock:
            providers = dict(_profile_providers)
        errors: dict[str, str] = {}
        for name, provider in providers.items():
            try:
                info = provider(dict(params))
            except Exception as err:  # noqa: BLE001 - one bad provider
                errors[name] = repr(err)
                continue
            if info:
                return {"provider": name, **info}, 200
        return (
            {
                "error": "no resident runtime available to profile",
                "providers": len(providers),
                **({"failures": errors} if errors else {}),
            },
            503,
        )

    def events_tail(self, n: int = 0) -> str:
        """Last ``n`` (default: all buffered) events as JSONL."""
        events = list(self._tail)
        if n > 0:
            events = events[-n:]
        return "".join(
            json.dumps(event, default=repr) + "\n" for event in events
        )

    def close(self) -> None:
        _events.remove_listener(self._listener)
        self._httpd.shutdown()
        self._httpd.server_close()


_server_lock = threading.Lock()
_server: OpsServer | None = None


def ensure_ops_server(port: int | None = None) -> OpsServer | None:
    """Start the process-wide ops server once; None when not configured.

    ``port`` overrides the environment (tests/embedders); with neither an
    explicit port nor ``COVALENT_TPU_OPS_PORT`` this is a no-op, so the
    call is safe on every executor/runner startup path.
    """
    global _server
    with _server_lock:
        if _server is not None:
            return _server
        if port is None:
            raw = os.environ.get(_PORT_ENV, "").strip()
            if not raw:
                return None
            try:
                port = int(raw)
            except ValueError:
                from ..utils.log import app_log

                app_log.warning("ignoring non-integer %s=%r", _PORT_ENV, raw)
                return None
        try:
            _server = OpsServer(port)
        except OSError as err:
            from ..utils.log import app_log

            app_log.warning("ops server failed to bind port %s: %s", port, err)
            return None
    _events.emit(
        "ops.server_started", host=_server.host, port=_server.port
    )
    return _server
