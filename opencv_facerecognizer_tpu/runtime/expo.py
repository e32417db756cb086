"""Live exposition surface: a read-only HTTP endpoint over the serving
runtime's observability state (observability layer, beside
``utils.tracing``).

Everything the runtime already knows about itself — ``Metrics.summary()``,
the admission ledger, the brownout level and the tracer's recent spans —
was previously reachable only by
publishing a ``stats`` control command into the frame stream, which (a)
needs a connector client and (b) is unusable once the loop itself is the
thing being debugged. ``ExpoServer`` exposes the same state over plain
HTTP GET, served by its own threads so a wedged serving loop still
answers (the counters, ledger and spans are all lock-light reads):

======================  =====================================================
path                    payload
======================  =====================================================
``/``                   index: endpoints, brownout level, tracer stats
``/metrics``            ``Metrics.summary()`` (counters + gauges +
                        percentiles; empty windows report explicit nulls)
``/prom``               the same state in Prometheus text format
                        (``runtime.promtext.render``: counters/gauges/
                        rolling-histogram families, prefix families folded
                        into labels) — the scrape endpoint
``/health``             the SLO monitor's verdict (``runtime.slo``):
                        health state + per-objective short/long burn
                        rates + active watchdog events. HTTP 200 for
                        ok/warn, **503 for critical** (load balancers and
                        liveness probes key on the status alone); 200
                        with ``{"state": null}`` when no monitor is wired
``/ledger``             ``RecognizerService.ledger()`` — admitted /
                        completed / drops_by_reason / in_system
``/brownout``           ``{"level": n}``
``/spans``              recent spans: ``?topic=<ring>&limit=<max>``
                        (``n`` is an accepted alias; default: all topics
                        merged, newest 256; limit is bounds-checked —
                        non-integer or non-positive values answer 400,
                        values beyond ``SPAN_LIMIT_MAX`` are clamped)
``/replicas``           the topic router's replica registry
                        (``runtime.replication.TopicRouter.registry``):
                        per-replica health, routed counts, observed topic
                        assignment; ``{"replicas": null}`` when no router
                        is wired
``/rollout``            the in-flight embedder rollout's status
                        (``runtime.rollout.RolloutCoordinator.status``):
                        phase, staged-re-embed watermark, dual-score
                        parity verdict; ``{"rollout": null}`` when none
``/tracks``             the temporal identity cache's track registry
                        (``runtime.tracker.IdentityTracker.registry``):
                        per-track stream/box/identity/confirmation state
                        plus hit-rate stats; ``{"tracks": null}`` when no
                        tracker is wired
======================  =====================================================

**Read-only contract**: every verb except GET is answered ``405 Method Not
Allowed`` — this surface can never mutate the service, by construction
(no handler writes anything). Requests/errors are counted on the shared
Metrics surface (``expo_requests`` / ``expo_errors``). The one nuance:
``/health`` reads the monitor's LAST verdict; the evaluation itself runs
on the serving loop's tick and (as a liveness backstop for wedged loops)
on this server's background refresh thread — never on a request thread.
"""

from __future__ import annotations

import json
import logging
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional
from urllib.parse import parse_qs, urlparse

from opencv_facerecognizer_tpu.runtime.promtext import render as render_prom
from opencv_facerecognizer_tpu.runtime.slo import STATE_CRITICAL
from opencv_facerecognizer_tpu.utils import metric_names as mn

#: hard cap on ``/spans`` ``limit=`` — a scrape cannot ask this surface
#: to serialize an unbounded span dump.
SPAN_LIMIT_MAX = 10000
SPAN_LIMIT_DEFAULT = 256


class _BadQuery(ValueError):
    """A malformed query parameter — mapped to HTTP 400 (the bounds-check
    contract: bad input is answered, never guessed at)."""


class ExpoServer:
    """Read-only HTTP exposition of the serving runtime's state (module
    docstring). ``port=0`` binds an ephemeral port (read ``.port`` after
    construction). ``start()`` spawns the HTTP threads plus a background
    refresh loop (the SLO monitor's backstop tick); ``stop()`` tears both
    down. Never wired into the serving hot path — a wedged loop still
    answers."""

    def __init__(self, service=None, tracer=None, metrics=None,
                 host: str = "127.0.0.1", port: int = 0,
                 refresh_s: float = 2.0,
                 slo=None, router=None, rollout=None, registry=None):
        self.service = service
        self.tracer = tracer if tracer is not None else getattr(
            service, "tracer", None)
        self.metrics = metrics if metrics is not None else getattr(
            service, "metrics", None)
        #: optional runtime.slo.SLOMonitor behind ``/health``; the refresh
        #: thread ticks it as a backstop so the verdict stays current even
        #: when the serving loop (its primary ticker) is wedged — which is
        #: exactly when an orchestrator polls /health hardest.
        self.slo = slo if slo is not None else getattr(service, "slo", None)
        #: optional runtime.replication.TopicRouter behind ``/replicas``:
        #: the replica registry (health, routed counts, observed topic
        #: assignment) as a read-only snapshot — what an orchestrator
        #: polls to see where failover moved the traffic.
        self.router = router
        #: optional runtime.rollout.RolloutCoordinator behind ``/rollout``:
        #: phase / staged watermark / parity-window verdict as a read-only
        #: snapshot (the ``rollout_*`` gauges carry the same numbers on
        #: /prom; this is the structured view an operator polls while
        #: deciding whether to cut over). Falls back to the service's
        #: attached coordinator so late attachment is visible.
        self.rollout = rollout
        #: optional runtime.registry.ModelRegistry behind ``/registry``:
        #: the served (role, version) manifest plus any in-flight swap
        #: coordinator's phase/parity — the structured view an operator
        #: polls during a detector/cascade swap (the ``model_version_*``
        #: and ``registry_*`` gauges carry the same numbers on /prom).
        #: Falls back to the service's attached registry, like rollout.
        self.registry = registry
        self.refresh_s = float(refresh_s)
        self._started_t = time.monotonic()
        self._stop = threading.Event()
        self._refresh_thread: Optional[threading.Thread] = None
        self._thread: Optional[threading.Thread] = None
        expo = self

        class Handler(BaseHTTPRequestHandler):
            # Read-only contract: GET answers; every mutating verb is 405.
            def do_GET(self):  # noqa: N802 — http.server API
                expo._handle_get(self)

            def do_POST(self):  # noqa: N802
                expo._reject(self)

            do_PUT = do_DELETE = do_PATCH = do_POST  # noqa: N815

            def log_message(self, fmt, *args):  # silence per-request stderr
                pass

        self._httpd = ThreadingHTTPServer((host, int(port)), Handler)
        self._httpd.daemon_threads = True
        self.host, self.port = self._httpd.server_address[:2]

    # ---- lifecycle ----

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True, name="ocvf-expo")
        self._thread.start()
        self._refresh_thread = threading.Thread(target=self._refresh_loop,
                                                daemon=True,
                                                name="ocvf-expo-refresh")
        self._refresh_thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        if self._refresh_thread is not None:
            self._refresh_thread.join(timeout=2.0)
            self._refresh_thread = None

    def _refresh_loop(self) -> None:
        """The SLO monitor's backstop tick, off the hot path: ``/health``
        stays current even when the serving loop, its primary ticker, is
        wedged."""
        while not self._stop.wait(timeout=self.refresh_s):
            if self.slo is not None:
                try:
                    # Backstop tick (interval-throttled inside the
                    # monitor): /health must reflect reality even when
                    # the serving loop stopped ticking.
                    self.slo.tick()
                except Exception:  # noqa: BLE001 — refresh must never die
                    logging.getLogger(__name__).exception(
                        "expo slo backstop tick failed")
                    if self.metrics is not None:
                        # slo_tick_errors, not expo_errors: the EVALUATION
                        # failed — same counter as the supervisor's
                        # backstop, so triage points at the monitor, not
                        # the HTTP surface.
                        self.metrics.incr(mn.SLO_TICK_ERRORS)

    # ---- request handling ----

    def payload(self, path: str, query: Dict[str, Any]) -> Dict[str, Any]:
        """The JSON body for one GET path; raises ``KeyError`` on unknown
        paths (mapped to 404). Pure reads — nothing here mutates the
        service (the read-only contract's enforcement by construction)."""
        service = self.service
        if path in ("/", "/index"):
            return {
                "endpoints": ["/", "/metrics", "/prom", "/health", "/ledger",
                              "/brownout", "/spans", "/replicas",
                              "/rollout", "/registry", "/tracks"],
                "uptime_s": round(time.monotonic() - self._started_t, 1),
                "brownout_level": getattr(service, "brownout_level", None),
                "health": (self.slo.state if self.slo is not None else None),
                "tracer": (self.tracer.stats()
                           if self.tracer is not None else None),
            }
        if path == "/metrics":
            return dict(self.metrics.summary()) if self.metrics else {}
        if path == "/health":
            if self.slo is None:
                return {"state": None, "detail": "no SLO monitor wired"}
            return dict(self.slo.verdict())
        if path == "/ledger":
            return service.ledger() if service is not None else {}
        if path == "/brownout":
            return {"level": getattr(service, "brownout_level", None)}
        if path == "/spans":
            limit = self._span_limit(query)
            if self.tracer is None:
                return {"topics": [], "spans": []}
            topic = (query.get("topic") or [None])[0]
            return {"topics": self.tracer.topics(),
                    "spans": self.tracer.snapshot(topic=topic, limit=limit)}
        if path == "/replicas":
            # Same unwired shape as /health: a null payload with a
            # pointer, never a 404 — the path is part of the contract.
            if self.router is None:
                return {"replicas": None, "detail": "no topic router wired"}
            return {"replicas": self.router.registry()}
        if path == "/rollout":
            coordinator = (self.rollout if self.rollout is not None
                           else getattr(service, "rollout", None))
            if coordinator is None:
                return {"rollout": None, "detail": "no rollout in flight"}
            return {"rollout": coordinator.status()}
        if path == "/registry":
            # Versioned model registry (ISSUE 18): the durable manifest's
            # served roles/versions plus any in-flight swap's phase and
            # detection-parity window. Same unwired shape as /rollout:
            # null payload with a pointer, never a 404.
            registry = (self.registry if self.registry is not None
                        else getattr(service, "registry", None))
            if registry is None:
                return {"registry": None, "detail": "no model registry wired"}
            swap = getattr(service, "registry_swap", None)
            return {"registry": registry.status(),
                    "swap": swap.status() if swap is not None else None}
        if path == "/tracks":
            # Temporal identity cache (ISSUE 17): the replica-local
            # track registry + hit-rate stats as a read-only snapshot —
            # what an operator polls to see WHO the cache thinks is in
            # each stream and how much device work it is absorbing.
            # Same unwired shape as /replicas: null payload, never 404.
            tracker = getattr(service, "tracker", None)
            if tracker is None:
                return {"tracks": None,
                        "detail": "no identity tracker wired"}
            return {"tracks": tracker.registry(),
                    "stats": tracker.stats()}
        raise KeyError(path)

    @staticmethod
    def _span_limit(query: Dict[str, Any]) -> int:
        """Bounds-checked ``limit=`` (alias ``n=``) for ``/spans``: a
        non-integer or non-positive value answers 400 (``_BadQuery``)
        instead of being silently defaulted; oversized asks clamp to
        ``SPAN_LIMIT_MAX``."""
        raw = (query.get("limit") or query.get("n") or [None])[0]
        if raw is None:
            return SPAN_LIMIT_DEFAULT
        try:
            limit = int(raw)
        except (TypeError, ValueError):
            raise _BadQuery(f"limit must be an integer, got {raw!r}")
        if limit <= 0:
            raise _BadQuery(f"limit must be positive, got {limit}")
        return min(limit, SPAN_LIMIT_MAX)

    def _handle_get(self, handler) -> None:
        if self.metrics is not None:
            self.metrics.incr(mn.EXPO_REQUESTS)
        parsed = urlparse(handler.path)
        content_type = "application/json"
        try:
            if parsed.path == "/prom":
                # Prometheus exposition is text, not JSON: rendered from
                # one atomic Metrics snapshot (runtime.promtext).
                text = render_prom(self.metrics) if self.metrics else ""
                self._respond(handler, 200, text.encode("utf-8"),
                              "text/plain; version=0.0.4; charset=utf-8")
                return
            body = self.payload(parsed.path, parse_qs(parsed.query))
            status = 200
            if (parsed.path == "/health"
                    and body.get("state_code") == STATE_CRITICAL):
                # Critical answers 503: a load balancer / liveness probe
                # reads the verdict from the status code alone.
                status = 503
        except _BadQuery as exc:
            body, status = {"error": str(exc)}, 400
        except KeyError:
            body, status = {"error": f"unknown path {parsed.path!r}"}, 404
        except Exception:  # noqa: BLE001 — a handler bug must answer 500
            logging.getLogger(__name__).exception("expo request failed")
            if self.metrics is not None:
                self.metrics.incr(mn.EXPO_ERRORS)
            body, status = {"error": "internal error"}, 500
        blob = json.dumps(body, default=repr).encode("utf-8")
        self._respond(handler, status, blob, content_type)

    @staticmethod
    def _respond(handler, status: int, blob: bytes,
                 content_type: str) -> None:
        try:
            handler.send_response(status)
            handler.send_header("Content-Type", content_type)
            handler.send_header("Content-Length", str(len(blob)))
            handler.end_headers()
            handler.wfile.write(blob)
        except OSError:
            pass  # client went away mid-response

    def _reject(self, handler) -> None:
        """Every non-GET verb: 405 — the read-only contract."""
        if self.metrics is not None:
            self.metrics.incr(mn.EXPO_REQUESTS)
        blob = b'{"error": "read-only endpoint: GET only"}'
        try:
            handler.send_response(405)
            handler.send_header("Allow", "GET")
            handler.send_header("Content-Type", "application/json")
            handler.send_header("Content-Length", str(len(blob)))
            handler.end_headers()
            handler.wfile.write(blob)
        except OSError:
            pass

