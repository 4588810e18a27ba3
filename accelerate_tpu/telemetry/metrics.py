"""Pillar 7 — live metrics endpoint: Prometheus text over stdlib HTTP.

A running training job or decode service should be scrapable without
touching its process: :class:`MetricsServer` runs a daemon
``http.server`` thread serving ``GET /metrics`` in Prometheus text
exposition format (version 0.0.4), plus a ``GET /healthz``
readiness+liveness probe (JSON; 200 while every registered health source
reports ready, 503 otherwise — the decode service registers
"programs warmed ∧ pool allocated ∧ not draining").  Every scrape renders *live* — the
server holds no state beyond its provider callables, so the numbers are
whatever the telemetry hub / :class:`~..serving.DecodeService` report at
that instant.

Metric namespace: ``atpu_<provider>_<field>``; nested dicts flatten with
``_``; names ending ``_total`` are typed ``counter``, everything else
``gauge``.  A :class:`LatencyHistogram` value renders as a native
Prometheus histogram — cumulative ``_bucket{le="..."}`` series plus
``_sum``/``_count`` — so step/TTFT/TPOT latencies expose full
distributions a server-side ``histogram_quantile()`` can aggregate across
the fleet, instead of point-in-time p50/p99 gauges that cannot be merged.
Providers are fail-soft: one raising provider becomes a comment line in
the scrape, never a 500.

Wiring: ``TelemetryKwargs(metrics_port=...)`` / ``$ACCELERATE_METRICS_PORT``
starts one automatically (port 0 = ephemeral, read ``server.port``);
``Telemetry.serve_metrics()`` starts one on demand; a ``DecodeService``
constructed with a telemetry hub registers its ``metrics()`` snapshot as
the ``serving`` provider (occupancy, queue depth, block-pool free %, and
sliding-window TTFT/TPOT percentiles).
"""

from __future__ import annotations

import re
import threading
from bisect import bisect_left
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Optional

from ..logging import get_logger

logger = get_logger(__name__)

CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

# the grammar of one exposition sample line this module emits: a bare
# metric name, optionally the one label histograms require
# (`_bucket{le="..."}`), then the value.  Exported so the endpoint
# tests validate the SAME grammar the renderer produces —
# a format change here updates every validator with it.
SAMPLE_LINE_RE = re.compile(
    r"^[a-zA-Z_][a-zA-Z0-9_]*(\{le=\"[^\"]+\"\})? [-+0-9eE.naif]+$"
)

# default latency bucket bounds (ms): log-ish spacing from sub-ms decode
# steps to multi-minute cold compiles; +Inf is implicit
DEFAULT_LATENCY_BUCKETS_MS = (
    1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0, 5000.0, 10000.0, 30000.0, 60000.0,
)


class LatencyHistogram:
    """Cumulative Prometheus histogram recorder.

    ``observe()`` is two integer bumps and a float add — cheap enough for
    the capture hot path and the serving completion path.  Rendering emits
    the standard ``_bucket{le=...}`` / ``_sum`` / ``_count`` series, which
    (unlike the sliding-window p50/p99 gauges they replace) are monotonic
    counters a Prometheus server can rate() and quantile() over any window
    and aggregate across ranks/replicas.  Writer/scraper races read a
    bucket count at most one observation stale — monotonicity is preserved
    because counts only ever grow.
    """

    def __init__(self, buckets=DEFAULT_LATENCY_BUCKETS_MS):
        self.buckets = tuple(sorted(float(b) for b in buckets))
        if not self.buckets:
            raise ValueError("histogram needs at least one bucket bound")
        # per-bound counts (NON-cumulative internally; cumulated at render)
        self._counts = [0] * (len(self.buckets) + 1)  # [+Inf] last
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        self._counts[bisect_left(self.buckets, float(value))] += 1
        self.sum += float(value)
        self.count += 1

    def cumulative_counts(self) -> list[int]:
        """Per-bound cumulative counts, ``+Inf`` last (== ``count``)."""
        out, running = [], 0
        for c in self._counts:
            running += c
            out.append(running)
        return out

    def render_lines(self, name: str) -> list[str]:
        lines = [f"# TYPE {name} histogram"]
        cumulative = self.cumulative_counts()
        for bound, c in zip(self.buckets, cumulative):
            le = f"{bound:g}"
            lines.append(f'{name}_bucket{{le="{le}"}} {c}')
        lines.append(f'{name}_bucket{{le="+Inf"}} {cumulative[-1]}')
        lines.append(f"{name}_sum {self.sum}")
        lines.append(f"{name}_count {cumulative[-1]}")
        return lines


def register_provider(providers: list, name: str, fn: Callable[[], dict]) -> str:
    """Replace-or-append a ``(name, fn)`` snapshot source — the one
    registry semantics shared by the hub and the server (latest wins on a
    name collision: the restart-the-service-in-one-process case)."""
    for i, (existing, _) in enumerate(providers):
        if existing == name:
            providers[i] = (name, fn)
            return name
    providers.append((name, fn))
    return name

_NAME_OK_RE = re.compile(r"[^a-zA-Z0-9_]")


def _metric_name(*parts: str) -> str:
    name = "_".join(p for p in parts if p)
    name = _NAME_OK_RE.sub("_", name)
    if not name or not (name[0].isalpha() or name[0] == "_"):
        name = "_" + name
    return name


def _flatten(values: dict, prefix: str = "") -> list:
    flat = []
    for key, value in values.items():
        name = f"{prefix}_{key}" if prefix else str(key)
        if isinstance(value, LatencyHistogram):
            flat.append((name, value))
        elif isinstance(value, dict):
            flat.extend(_flatten(value, name))
        elif isinstance(value, bool):
            flat.append((name, int(value)))
        elif isinstance(value, (int, float)) and value == value:  # drop NaN
            flat.append((name, value))
        # None / strings / lists have no Prometheus sample type: skipped
    return flat


def render_prometheus(sections: list) -> str:
    """``[(provider, values_dict), ...]`` → text exposition.  Scalar values
    render as counter/gauge samples; :class:`LatencyHistogram` values
    render as native histogram series.  Duplicate metric names (two
    providers under one name) keep the first sample — duplicates are
    invalid exposition."""
    lines: list[str] = []
    seen: set[str] = set()
    for provider, values in sections:
        for key, value in _flatten(values):
            name = _metric_name("atpu", provider, key)
            if name in seen:
                continue
            seen.add(name)
            if isinstance(value, LatencyHistogram):
                lines.extend(value.render_lines(name))
                continue
            kind = "counter" if name.endswith("_total") else "gauge"
            lines.append(f"# TYPE {name} {kind}")
            lines.append(f"{name} {value}")
    return "\n".join(lines) + "\n"


def telemetry_metrics(telemetry) -> dict:
    """The hub's scrape snapshot: step counters, replay phase timings,
    recompile/fault counters, collective bytes, and the latest sampled
    device-time split."""
    out = {
        "steps_total": telemetry.steps_total,
        "recompiles_total": telemetry.recompiles_total,
        "resilience_events_total": len(telemetry.resilience_events),
        "fleet_events_total": len(telemetry.fleet_events),
        "eager_dataloader_wait_ms_total": round(
            telemetry.eager_dataloader_wait_ms, 3
        ),
        # native histogram: replay step latency distribution (_bucket series)
        "step_latency_ms": telemetry.step_hist,
    }
    for key, value in telemetry.timeline.summary().items():
        if isinstance(value, (int, float)) and (
            key.startswith("replay_") or key.startswith("build_")
        ):
            out[key] = value
    if telemetry.collective_records:
        last = telemetry.collective_records[-1]
        for key in (
            "dp_collective_bytes",
            "dp_collective_bytes_uncompressed",
            "compression_ratio",
        ):
            value = last.stats.get(key)
            if isinstance(value, (int, float)):
                out[key] = value
    if telemetry.device_records:
        dev = telemetry.device_records[-1]
        out["device_window_ms"] = dev.window_ms
        out["device_busy_ms"] = dev.busy_ms
        out["device_idle_ms"] = dev.idle_ms
        out["device_compute_ms"] = dev.compute_ms
        out["device_collective_ms"] = dev.collective_ms
        out["device_transfer_ms"] = dev.transfer_ms
        out["device_collective_share"] = dev.collective_share
        out["device_samples_total"] = len(telemetry.device_records)
        if dev.mfu is not None:
            out["device_mfu"] = dev.mfu
    # flight-recorder self-health (docs/telemetry.md §flight recorder):
    # ring depth, drop count and staleness — an alert on
    # atpu_telemetry_flightrec_last_event_age_seconds is the cheapest
    # external hang detector there is.  _flatten drops the None age of a
    # ring that has never recorded.
    rec = getattr(telemetry, "flightrec", None)
    if rec is not None:
        out["flightrec"] = rec.health()
    return out


class _Handler(BaseHTTPRequestHandler):
    server_version = "atpu-metrics/1.0"

    def do_GET(self):  # noqa: N802 (http.server API)
        if self.path.split("?", 1)[0] in ("/metrics", "/metrics/"):
            body = self.server.render_fn().encode("utf-8")
            self.send_response(200)
            self.send_header("Content-Type", CONTENT_TYPE)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif self.path.split("?", 1)[0] in ("/healthz", "/healthz/"):
            # readiness + liveness probe (docs/serving.md §fault
            # tolerance): 200 while every registered health source reports
            # ready (for the decode service: programs warmed ∧ pool
            # allocated ∧ not draining), 503 otherwise — the orchestrator's
            # drain/route-away signal
            import json as _json

            status, payload = self.server.health_fn()
            body = (_json.dumps(payload) + "\n").encode("utf-8")
            self.send_response(status)
            self.send_header("Content-Type", "application/json; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif self.path in ("", "/"):
            body = b"accelerate_tpu metrics endpoint; scrape /metrics\n"
            self.send_response(200)
            self.send_header("Content-Type", "text/plain; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        else:
            self.send_error(404)

    def log_message(self, *args):  # scrapes must not spam the job's stderr
        pass


class MetricsServer:
    """One daemon HTTP thread serving live Prometheus text on ``/metrics``.

    ``telemetry`` (optional) contributes the hub snapshot plus every
    provider registered on the hub (``register_metrics_provider`` — the
    decode service self-registers there); ``add_provider``/``add_service``
    attach additional sources directly.  ``port=0`` binds an ephemeral port
    (read it back from ``.port`` — tests and multi-job hosts)."""

    def __init__(self, telemetry=None, port: int = 0, host: str = "127.0.0.1"):
        self.telemetry = telemetry
        self._requested = (host, int(port))
        self._providers: list = []  # (name, callable) -> dict
        self._health_providers: list = []  # (name, callable) -> dict
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    # -- providers -----------------------------------------------------------
    def add_provider(self, name: str, fn: Callable[[], dict]) -> str:
        """Register a snapshot callable (replace-or-append, latest wins)."""
        return register_provider(self._providers, name, fn)

    def add_service(self, service) -> str:
        """Scrape a :class:`~..serving.DecodeService` (its ``metrics()``
        snapshot) under the ``serving`` namespace; its ``health()``
        snapshot joins ``/healthz`` too when the service exposes one."""
        if hasattr(service, "health"):
            self.add_health_provider("serving", service.health)
        return self.add_provider("serving", service.metrics)

    def add_health_provider(self, name: str, fn: Callable[[], dict]) -> str:
        """Register a readiness source for ``/healthz`` (``fn() -> dict``
        with a ``"ready"`` bool; replace-or-append, latest wins)."""
        return register_provider(self._health_providers, name, fn)

    def _sections(self) -> list:
        sections: list = []
        if self.telemetry is not None:
            hub = self.telemetry
            sections.append(("telemetry", lambda: telemetry_metrics(hub)))
            sections.extend(getattr(hub, "_metrics_providers", []))
        sections.extend(self._providers)
        return sections

    def render(self) -> str:
        rendered = []
        failures = []
        for name, fn in self._sections():
            try:
                values = fn()
                if isinstance(values, dict):
                    rendered.append((name, values))
                else:
                    failures.append((name, "provider returned non-dict"))
            except Exception as exc:  # one bad provider must not kill a scrape
                failures.append((name, f"{type(exc).__name__}: {exc}"))
        body = render_prometheus(rendered)
        for name, err in failures:
            body += f"# provider {name} failed: {err}\n"
        return body

    def health(self) -> tuple:
        """``/healthz`` body: ``(status_code, payload)``.  Liveness is the
        response itself (the thread answered); readiness is the AND over
        every registered health source's ``"ready"``.  A raising provider
        reads as not-ready (fail-closed: an orchestrator must not route
        traffic at a replica whose own health check is broken); an empty
        snapshot (a dropped weakref'd service) is skipped."""
        sources: list = []
        if self.telemetry is not None:
            sources.extend(getattr(self.telemetry, "_health_providers", []))
        sources.extend(self._health_providers)
        payload: dict = {"live": True, "ready": True, "services": {}}
        for name, fn in sources:
            try:
                snapshot = fn()
            except Exception as exc:
                snapshot = {"ready": False, "error": f"{type(exc).__name__}: {exc}"}
            if not snapshot:
                continue
            payload["services"][name] = snapshot
            payload["ready"] = payload["ready"] and bool(snapshot.get("ready", True))
        return (200 if payload["ready"] else 503), payload

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "MetricsServer":
        if self._httpd is not None:
            return self
        httpd = ThreadingHTTPServer(self._requested, _Handler)
        httpd.daemon_threads = True
        httpd.render_fn = self.render
        httpd.health_fn = self.health
        self._httpd = httpd
        self._thread = threading.Thread(
            target=httpd.serve_forever, name="atpu-metrics", daemon=True
        )
        self._thread.start()
        logger.info("metrics endpoint serving on %s", self.url)
        return self

    @property
    def port(self) -> Optional[int]:
        return self._httpd.server_address[1] if self._httpd is not None else None

    @property
    def url(self) -> Optional[str]:
        if self._httpd is None:
            return None
        host, port = self._httpd.server_address[:2]
        return f"http://{host}:{port}/metrics"

    def close(self) -> None:
        httpd, self._httpd = self._httpd, None
        if httpd is None:
            return
        httpd.shutdown()
        httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
