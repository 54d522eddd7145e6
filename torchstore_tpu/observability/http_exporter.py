"""Opt-in live HTTP metrics endpoint: ``/metrics`` + ``/healthz`` (+
``/metrics.json``, ``/history.json``, ``/slo.json`` — the surfaces
``scripts/ts_top.py`` polls in --url mode).

Set ``TORCHSTORE_TPU_METRICS_PORT`` and every torchstore process starts a
stdlib ``http.server`` thread serving its own registry in Prometheus text —
``curl host:PORT/metrics`` scrapes a LIVE run instead of waiting for the
periodic file dump, and ``/healthz`` gives load balancers a
liveness probe (200 + JSON with pid/uptime).

Port contention is expected, not an error: volume actors inherit the same
env var as the client that spawned them, so the FIRST process to bind gets
the configured port and every sibling falls back to an ephemeral one; each
process publishes its actual bound port in the ``ts_metrics_http_port``
gauge, so a fleet snapshot (``ts.fleet_snapshot()``) doubles as endpoint
discovery. Zero cost when the env var is unset.

The endpoint is UNAUTHENTICATED (a registry dump, no control surface), so
it binds loopback by default; set ``TORCHSTORE_TPU_METRICS_HOST=0.0.0.0``
to deliberately expose it for cross-host scraping (e.g. a Prometheus
server on another machine).
"""

from __future__ import annotations

import atexit
import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from torchstore_tpu.observability import metrics as obs_metrics

ENV_METRICS_PORT = "TORCHSTORE_TPU_METRICS_PORT"
ENV_METRICS_HOST = "TORCHSTORE_TPU_METRICS_HOST"

_START_TIME = time.time()


class _Handler(BaseHTTPRequestHandler):
    # Liveness probes every few seconds must not spam operator logs.
    def log_message(self, format, *args):  # noqa: A002 - stdlib signature
        pass

    def _send(self, code: int, content_type: str, body: str) -> None:
        payload = body.encode()
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        self.wfile.write(payload)

    def do_GET(self) -> None:  # noqa: N802 - stdlib signature
        path = self.path.split("?", 1)[0]
        try:
            if path == "/metrics":
                self._send(
                    200,
                    "text/plain; version=0.0.4; charset=utf-8",
                    obs_metrics.get_registry().render_prometheus(),
                )
            elif path == "/healthz":
                self._send(
                    200,
                    "application/json",
                    json.dumps(
                        {
                            "status": "ok",
                            "pid": os.getpid(),
                            "uptime_s": round(time.time() - _START_TIME, 3),
                        }
                    ),
                )
            elif path == "/metrics.json":
                self._send(
                    200,
                    "application/json",
                    obs_metrics.get_registry().render_json(),
                )
            elif path == "/history.json":
                self._send(
                    200,
                    "application/json",
                    json.dumps(self._history_doc()),
                )
            elif path == "/slo.json":
                from torchstore_tpu.observability import (
                    timeline as obs_timeline,
                )

                self._send(
                    200, "application/json", json.dumps(obs_timeline.slo_report())
                )
            else:
                self._send(404, "text/plain", "not found\n")
        except (BrokenPipeError, ConnectionResetError):
            pass  # scraper went away mid-response

    def _history_doc(self) -> dict:
        """This process's retained time-series rings
        (``/history.json?series=<glob>[,<glob>...]&since=<s>&level=<i>``)
        — what ts_top.py polls in --url mode."""
        from urllib.parse import parse_qs

        from torchstore_tpu.observability import history as obs_history

        query = parse_qs(
            self.path.split("?", 1)[1] if "?" in self.path else ""
        )
        series = None
        if query.get("series"):
            series = [
                g for raw in query["series"] for g in raw.split(",") if g
            ] or None
        since = None
        if query.get("since"):
            try:
                since = float(query["since"][0])
            except ValueError:
                since = None
        level = None
        if query.get("level"):
            try:
                level = int(query["level"][0])
            except ValueError:
                level = None
        return obs_history.history(series=series, since=since, level=level)


class MetricsHTTPExporter:
    """One process's metrics server: a daemon thread around a
    ``ThreadingHTTPServer``. ``port`` is the actually-bound port (differs
    from the requested one after an ephemeral fallback)."""

    def __init__(self, host: str, port: int) -> None:
        self._server = ThreadingHTTPServer((host, port), _Handler)
        self._server.daemon_threads = True
        self.host = host
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name="torchstore-tpu-metrics-http",
            daemon=True,
        )
        self._thread.start()
        obs_metrics.gauge(
            "ts_metrics_http_port",
            "Port this process's live /metrics endpoint is bound to",
        ).set(self.port)

    def close(self) -> None:
        """Stop serving and release the port (idempotent)."""
        try:
            self._server.shutdown()
            self._server.server_close()
        except Exception:
            pass
        self._thread.join(timeout=5.0)


_exporter_lock = threading.Lock()
_exporter: Optional[MetricsHTTPExporter] = None


def start_http_exporter(
    port: int, host: Optional[str] = None
) -> MetricsHTTPExporter:
    """Explicitly start an exporter (tests, embedding apps). Raises
    ``OSError`` if the port is taken — use :func:`maybe_start_http_exporter`
    for the fall-back-to-ephemeral behavior."""
    return MetricsHTTPExporter(
        host if host is not None else os.environ.get(ENV_METRICS_HOST, "127.0.0.1"),
        port,
    )


def get_http_exporter() -> Optional[MetricsHTTPExporter]:
    return _exporter


def stop_http_exporter() -> None:
    global _exporter
    with _exporter_lock:
        exporter, _exporter = _exporter, None
    if exporter is not None:
        exporter.close()


def reinit_after_fork() -> Optional[MetricsHTTPExporter]:
    """Re-arm in an actor child. Under forkserver, an inherited exporter
    has a DEAD serving thread but a live listening fd — close the fd
    (never ``shutdown()``: it waits on serve_forever's ack, which no
    thread will ever give) and start fresh against the child's env
    (falling back to an ephemeral port, since the spawner usually still
    holds the configured one). Under spawn, the child's own import already
    started a live, serving exporter — keep it; closing its socket under a
    running serve_forever thread would leave a zombie."""
    global _exporter
    with _exporter_lock:
        exporter = _exporter
        if exporter is not None and exporter._thread.is_alive():
            return exporter
        _exporter = None
    if exporter is not None:
        try:
            exporter._server.server_close()
        except Exception:
            pass
    return maybe_start_http_exporter()


def maybe_start_http_exporter() -> Optional[MetricsHTTPExporter]:
    """Start the env-gated exporter once per process when
    ``TORCHSTORE_TPU_METRICS_PORT`` is set. Idempotent. Sibling processes
    that lose the port race (volume actors inherit the same env) fall back
    to an ephemeral port — discover it via the ``ts_metrics_http_port``
    gauge in ``ts.fleet_snapshot()``. Called from ``torchstore_tpu``
    import."""
    global _exporter
    raw = os.environ.get(ENV_METRICS_PORT)
    if not raw:
        return None
    with _exporter_lock:
        if _exporter is not None:
            return _exporter
        try:
            port = int(raw)
        except ValueError:
            from torchstore_tpu.logging import get_logger

            get_logger("torchstore_tpu.observability").warning(
                "ignoring malformed %s=%r", ENV_METRICS_PORT, raw
            )
            return None
        host = os.environ.get(ENV_METRICS_HOST, "127.0.0.1")
        try:
            _exporter = MetricsHTTPExporter(host, port)
        except OSError:
            # A sibling process (the spawner, or an earlier volume) holds
            # the configured port; serve on an ephemeral one instead.
            try:
                _exporter = MetricsHTTPExporter(host, 0)
            except OSError:
                return None
        atexit.register(stop_http_exporter)
        from torchstore_tpu.logging import get_logger

        get_logger("torchstore_tpu.observability").info(
            "metrics http exporter serving on %s:%d (/metrics, /healthz)",
            host,
            _exporter.port,
        )
        return _exporter
