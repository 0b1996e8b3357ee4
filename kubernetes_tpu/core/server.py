"""The scheduler server: health/readiness/metrics endpoints + the run loop
wiring.

Re-expresses cmd/kube-scheduler/app/server.go (Run :183 — /healthz,/readyz
:208-229, leader election :310-342, /metrics :376) over http.server. The
SchedulerServer owns a scheduler, a leader elector, and the cache debugger;
serve() exposes the endpoints, run_forever() drives the scheduling loop while
leading.
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from .debugger import CacheDebugger
from .leaderelection import LeaderElector, LeaseStore


class SchedulerServer:
    def __init__(self, scheduler, identity: str = "scheduler-0",
                 lease_store: Optional[LeaseStore] = None,
                 leader_elect: bool = False):
        self.scheduler = scheduler
        self.debugger = CacheDebugger(scheduler)
        self.elector: Optional[LeaderElector] = None
        if leader_elect:
            self.elector = LeaderElector(
                lease_store or LeaseStore(), identity=identity)
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._ready = False

    # -- health (server.go:208-229) ----------------------------------------

    def healthz(self) -> bool:
        return True

    def readyz(self) -> bool:
        # informer-sync analogue: the fake clientset fans out synchronously,
        # so readiness = event handlers wired + (when electing) leadership
        # watchdog alive.
        return self._ready

    def mark_ready(self) -> None:
        self._ready = True

    # -- http --------------------------------------------------------------

    def serve(self, port: int = 0) -> int:
        """Start the HTTP endpoints on `port` (0 = ephemeral); returns the
        bound port."""
        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def do_GET(self):
                if self.path == "/healthz":
                    self._respond(200 if server.healthz() else 500, "ok")
                elif self.path == "/readyz":
                    self._respond(200 if server.readyz() else 503,
                                  "ok" if server.readyz() else "not ready")
                elif self.path == "/metrics":
                    self._respond(200, server.scheduler.expose_metrics(),
                                  "text/plain; version=0.0.4")
                elif self.path == "/metrics/resources":
                    self._respond(200, server.expose_resource_metrics(),
                                  "text/plain; version=0.0.4")
                elif self.path == "/debug/cache":
                    self._respond(200, server.debugger.dump())
                elif self.path == "/debug/comparer":
                    self._respond(200, json.dumps(server.debugger.compare()))
                elif self.path.startswith("/debug/events"):
                    # /debug/events[?object=<ns>/<name>]: the scheduler's
                    # EventRecorder buffer NEWEST-FIRST (recorder.recent()
                    # re-sorts by live timestamp — aggregated events mutate
                    # count/timestamp in place, so insertion order lies).
                    self._respond(200, server.expose_events(self.path),
                                  "application/json")
                else:
                    self._respond(404, "not found")

            def _respond(self, code, body, ctype="text/plain"):
                data = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        t = threading.Thread(target=self._httpd.serve_forever, daemon=True)
        t.start()
        self.mark_ready()
        return self._httpd.server_address[1]

    def expose_events(self, path: str) -> str:
        """/debug/events?object=<key> — the recorder buffer newest-first
        (client-go event read surface, collapsed to the debug plane)."""
        _, _, query = path.partition("?")
        object_key = None
        for part in query.split("&"):
            if part.startswith("object="):
                from urllib.parse import unquote
                object_key = unquote(part.split("=", 1)[1])
        events = self.scheduler.recorder.recent(object_key)
        return json.dumps([
            {"object": e.object_key, "type": e.type, "reason": e.reason,
             "message": e.message, "count": e.count,
             "timestamp": e.timestamp}
            for e in events])

    def expose_resource_metrics(self) -> str:
        """/metrics/resources (app/server.go:376-379 →
        pkg/scheduler/metrics/resources): per-pod resource requests as
        kube_pod_resource_request series, by namespace/pod/node/phase —
        one shared renderer with the apiserver's watch-cache endpoint
        (core/watchcache.py), so the two expositions cannot drift."""
        from .watchcache import RESOURCE_METRICS_HEADER, resource_request_lines
        cs = self.scheduler.clientset
        lines = list(RESOURCE_METRICS_HEADER)
        bindings = getattr(cs, "bindings", {})
        for pod in cs.pods.values():
            req = pod.resource_request()
            # Pending pods get an EMPTY node label (the reference's
            # kube_pod_resource_request convention) — `or ""` keeps a None
            # node_name from rendering as the literal string "None".
            node = bindings.get(pod.uid) or pod.node_name or ""
            lines.extend(resource_request_lines(
                pod.namespace, pod.name, node,
                req.milli_cpu, float(req.memory), req.scalar_resources))
        return "\n".join(lines) + "\n"

    def shutdown(self) -> None:
        """Stop serving; before that, let the scheduler's dispatcher send
        what is queued (bounded) and settle what the apiserver answered, so
        the binary's last ``scheduled=`` line counts every acknowledged
        bind."""
        self.scheduler.shutdown()
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd = None

    # -- run loop ----------------------------------------------------------

    def run_cycles(self, max_cycles: int = 1_000_000) -> int:
        """Drive scheduling while holding leadership (or unconditionally when
        leader election is off)."""
        if self.elector is not None:
            with self.scheduler.stages.stage("loop.idle"):
                leading = self.elector.tick()
            if not leading:
                return 0
        return self.scheduler.run_until_idle(max_cycles)
