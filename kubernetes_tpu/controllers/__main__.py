"""Standalone controller processes:

    python -m kubernetes_tpu.controllers --mode node-lifecycle \
        --api-url http://127.0.0.1:PORT [--fallback URL ...] [--grace S] \
        [--noexec-after S] [--tick S] [--primary-qps Q] [--secondary-qps Q] \
        [--unhealthy-threshold F] [--metrics-port P]

    python -m kubernetes_tpu.controllers --mode workload \
        --api-url http://127.0.0.1:PORT [--fallback URL ...] \
        [--identity NAME] [--lease-ttl S] [--tick S] \
        [--autoscale --min-nodes N --max-nodes N] \
        [--trace-deployments N --trace-gangs N --trace-seed N ...] \
        [--metrics-port P]

    python -m kubernetes_tpu.controllers --mode deschedule \
        --api-url http://127.0.0.1:PORT [--fallback URL ...] \
        [--identity NAME] [--lease-ttl S] [--tick S] \
        [--hysteresis N] [--margin F] [--max-moves N] \
        [--deschedule-device] \
        [--primary-qps Q] [--secondary-qps Q] [--metrics-port P]

Every mode connects an HTTPClientset (reads may land on follower
replicas via --fallback; writes and the heartbeat-ages poll
leader-route), prints the ready line the spawn harness keys on, serves
its own /metrics on an ephemeral port, reconciles until SIGTERM/SIGINT,
then prints one JSON stats line. Two `--mode workload` (or `--mode
deschedule`) processes with distinct --identity race the shared lease:
one runs ACTIVE, the other STANDBY with warm informers, taking over
inside --lease-ttl of a kill9.
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from ..core.apiserver import WORKLOAD_KINDS, HTTPClientset
from .autoscaler import ClusterAutoscaler
from .node_lifecycle import NodeLifecycleController
from .traceprofile import WorkloadProfile
from .workload import WorkloadControllerManager


def _serve_metrics(ctrl, port: int):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):  # noqa: D102 - silence request logs
            pass

        def do_GET(self):  # noqa: N802 - http.server API
            if self.path != "/metrics":
                self.send_response(404)
                self.end_headers()
                return
            data = ctrl.metrics_text().encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

    httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kubernetes-tpu-controllers")
    ap.add_argument("--mode",
                    choices=("node-lifecycle", "workload", "deschedule"),
                    default="node-lifecycle")
    ap.add_argument("--api-url", required=True,
                    help="apiserver base URL (reads; writes leader-route)")
    ap.add_argument("--fallback", action="append", default=[],
                    help="sibling replica URL for read-plane failover "
                         "(repeatable)")
    ap.add_argument("--tick", type=float, default=None)
    ap.add_argument("--metrics-port", type=int, default=0)
    # node-lifecycle knobs
    ap.add_argument("--grace", type=float, default=4.0,
                    help="heartbeat silence before Ready->Unknown")
    ap.add_argument("--noexec-after", type=float, default=2.0,
                    help="further silence before the NoExecute taint")
    ap.add_argument("--primary-qps", type=float, default=2.0)
    ap.add_argument("--secondary-qps", type=float, default=0.1)
    ap.add_argument("--unhealthy-threshold", type=float, default=0.55)
    # workload-manager knobs
    ap.add_argument("--identity", default="workload-manager-0",
                    help="lease holder id (distinct per HA replica)")
    ap.add_argument("--lease-ttl", type=float, default=2.0)
    ap.add_argument("--autoscale", action="store_true")
    ap.add_argument("--min-nodes", type=int, default=0)
    ap.add_argument("--max-nodes", type=int, default=100)
    ap.add_argument("--scale-wave", type=int, default=2)
    ap.add_argument("--pending-age", type=float, default=2.0)
    ap.add_argument("--scale-cooldown", type=float, default=5.0)
    ap.add_argument("--trace-deployments", type=int, default=0,
                    help="feed a Borg-style trace profile: deployments")
    ap.add_argument("--trace-gangs", type=int, default=0)
    ap.add_argument("--trace-rate", type=float, default=2.0)
    ap.add_argument("--trace-lifetime", type=float, default=0.0)
    ap.add_argument("--trace-seed", type=int, default=0)
    # descheduler knobs
    ap.add_argument("--hysteresis", type=int, default=5,
                    help="minimum scored improvement a move must clear")
    ap.add_argument("--margin", type=float, default=0.10,
                    help="low-node-utilization: how far above the mean "
                         "cpu-request utilization a node must sit to "
                         "nominate movers")
    ap.add_argument("--max-moves", type=int, default=64,
                    help="eviction budget per reconcile tick")
    ap.add_argument("--deschedule-device", action="store_true",
                    help="dispatch the what-if matrix through the jitted "
                         "mirror instead of the host walker. This makes the "
                         "descheduler a JAX process of its own: a chip "
                         "belongs to one process, so beside a scheduler "
                         "that holds the chip run it with JAX_PLATFORMS=cpu "
                         "(the harnesses do) or give it its own chip")
    args = ap.parse_args(argv)

    if args.mode == "deschedule":
        from .descheduler import DeschedulerController, default_strategies

        cs = HTTPClientset(args.api_url, fallbacks=args.fallback)
        ctrl = DeschedulerController(
            cs, identity=args.identity, lease_ttl=args.lease_ttl,
            tick=args.tick if args.tick is not None else 0.25,
            hysteresis=args.hysteresis,
            strategies=default_strategies(margin=args.margin),
            primary_qps=args.primary_qps, secondary_qps=args.secondary_qps,
            unhealthy_threshold=args.unhealthy_threshold,
            max_moves_per_tick=args.max_moves,
            device=args.deschedule_device)
        ready = (f"descheduler [{args.identity}]: "
                 f"watching {args.api_url}")
    elif args.mode == "node-lifecycle":
        cs = HTTPClientset(args.api_url, fallbacks=args.fallback)
        ctrl = NodeLifecycleController(
            cs, grace=args.grace, noexec_after=args.noexec_after,
            tick=args.tick if args.tick is not None else 0.5,
            primary_qps=args.primary_qps,
            secondary_qps=args.secondary_qps,
            unhealthy_threshold=args.unhealthy_threshold)
        ready = f"node-lifecycle controller: watching {args.api_url}"
    else:
        cs = HTTPClientset(args.api_url, fallbacks=args.fallback,
                           extra_kinds=WORKLOAD_KINDS)
        autoscaler = None
        if args.autoscale:
            autoscaler = ClusterAutoscaler(
                cs, min_nodes=args.min_nodes, max_nodes=args.max_nodes,
                wave=args.scale_wave, pending_age_s=args.pending_age,
                cooldown_s=args.scale_cooldown)
        profile = None
        if args.trace_deployments or args.trace_gangs:
            profile = WorkloadProfile(
                deployments=args.trace_deployments, gangs=args.trace_gangs,
                arrival_rate=args.trace_rate,
                mean_lifetime_s=args.trace_lifetime, seed=args.trace_seed)
        ctrl = WorkloadControllerManager(
            cs, identity=args.identity, lease_ttl=args.lease_ttl,
            tick=args.tick if args.tick is not None else 0.25,
            autoscaler=autoscaler, profile=profile)
        ready = (f"workload controller-manager [{args.identity}]: "
                 f"watching {args.api_url}")

    httpd = _serve_metrics(ctrl, args.metrics_port)
    mport = httpd.server_address[1]
    ctrl.start()
    # The ready line FIRST (spawn harnesses select()+readline on it).
    print(f"{ready} metrics on 127.0.0.1:{mport}", flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    stop.wait()
    ctrl.stop()
    httpd.shutdown()
    cs.close()
    print(json.dumps({"controller_stats": ctrl.stats()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
