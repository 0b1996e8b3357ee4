"""The scheduler binary: the cmd/kube-scheduler analogue.

    python -m kubernetes_tpu [--config sched.yaml] [--port 10259]
                             [--cluster cluster.yaml] [--leader-elect]
                             [--identity scheduler-0] [--once]

Re-expresses cmd/kube-scheduler/app/server.go's wiring (Run :183): parse the
KubeSchedulerConfiguration, build the (TPU-backed) scheduler, expose
/healthz /readyz /metrics /debug/cache /debug/comparer, optionally campaign
for leadership, and drive the scheduling loop.

Without a real apiserver, `--cluster` bootstraps the clientset from a YAML
manifest (nodes/pods/podGroups in the perf harness's template shapes), and
the process keeps scheduling whatever arrives through the clientset until
interrupted (`--once` exits after the queue drains — the smoke-test mode).
"""

from __future__ import annotations

import argparse
import signal
import sys
import time


def _load_cluster(cs, path: str) -> None:
    import yaml

    from .perf.harness import _make_node_from_template, _make_pod_from_template
    from .api.types import PodGroup

    with open(path) as f:
        doc = yaml.safe_load(f) or {}
    for i, tpl in enumerate(doc.get("nodes", ())):
        count = int(tpl.pop("count", 1))
        for j in range(count):
            cs.create_node(_make_node_from_template(i * 100000 + j, tpl))
    for g in doc.get("podGroups", ()):
        cs.create_pod_group(PodGroup(
            name=g["name"], min_count=int(g.get("minCount", 1)),
            topology_keys=tuple(g.get("topologyKeys", ()))))
    seq = 0
    for tpl in doc.get("pods", ()):
        count = int(tpl.pop("count", 1))
        for _ in range(count):
            cs.create_pod(_make_pod_from_template(f"pod-{seq}", tpl))
            seq += 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kubernetes-tpu-scheduler")
    ap.add_argument("--config", default="",
                    help="KubeSchedulerConfiguration YAML (core/config.py)")
    ap.add_argument("--cluster", default="",
                    help="bootstrap manifest: nodes/pods/podGroups")
    ap.add_argument("--api-url", default="",
                    help="schedule against a remote apiserver "
                         "(core/apiserver.py REST+watch) instead of the "
                         "in-process store; with a replicated control "
                         "plane, point this at the shard's FOLLOWER")
    ap.add_argument("--api-fallbacks", default="",
                    help="comma-separated sibling replica base URLs: the "
                         "reflector rotates to one (and RESUMEs by rv) "
                         "when --api-url's replica dies")
    ap.add_argument("--port", type=int, default=10259,
                    help="healthz/metrics port (0 = ephemeral)")
    ap.add_argument("--leader-elect", action="store_true")
    ap.add_argument("--identity", default="scheduler-0")
    ap.add_argument("--shard-index", type=int, default=-1,
                    help="join the shard plane as shard i of --shard-count "
                         "(requires --api-url; kubernetes_tpu/shard/)")
    ap.add_argument("--shard-count", type=int, default=0,
                    help="total shard slots in the plane")
    ap.add_argument("--shard-lease-duration", type=float, default=3.0,
                    help="shard lease duration in seconds (failover takes "
                         "at most one lease period + one renew interval)")
    ap.add_argument("--once", action="store_true",
                    help="exit once the queue drains (smoke/test mode)")
    ap.add_argument("--platform", default="auto",
                    choices=("auto", "cpu", "tpu"),
                    help="JAX platform: 'cpu' pins the host backend before "
                         "backend init; 'tpu' refuses to serve (non-zero "
                         "exit before the ready line) on any other "
                         "backend; 'auto' takes what JAX finds")
    args = ap.parse_args(argv)

    from .perf.device import device_info, pin_platform
    refused = pin_platform(args.platform)
    if refused:
        print(refused, file=sys.stderr)
        return 3
    device = device_info()  # backend init: a chip belongs to this process

    from .core.config import SchedulerConfiguration
    from .core.server import SchedulerServer
    from .models import TPUScheduler

    cfg = None
    if args.config:
        import yaml
        with open(args.config) as f:
            cfg = SchedulerConfiguration.from_dict(yaml.safe_load(f) or {})
        errs = cfg.validate()
        if errs:
            # kube-scheduler refuses an invalid KubeSchedulerConfiguration
            # (validation.go aggregate -> fatal at startup).
            for e in errs:
                print(f"invalid configuration: {e}", file=sys.stderr)
            return 1
    cs_kw = {}
    if args.api_url:
        from .core.apiserver import HTTPClientset
        from .core.clientset import RetryingClientset
        # Production shape: every write verb retries transient apiserver
        # failures with backoff before surfacing an error to the scheduler
        # (core/backoff.py; docs/RESILIENCE.md). Calls routed through the
        # async API dispatcher retry at that layer TOO — the layers compose
        # (worst case attempts multiply, bounded by both small budgets);
        # the wrapper here is what covers the dispatcher-less sync writes.
        # Shard members open SERVER-FILTERED watch streams (?shard=i/n,
        # core/watchcache.py): foreign plain pods arrive as slim
        # projections, so this shard's event decode scales with 1/n.
        shard = ((args.shard_index, args.shard_count)
                 if args.shard_index >= 0 and args.shard_count > 0 else None)
        cs_kw["clientset"] = RetryingClientset(HTTPClientset(
            args.api_url,
            fallbacks=[u for u in args.api_fallbacks.split(",") if u],
            shard=shard))
    sched = TPUScheduler(config=cfg, **cs_kw)
    if args.cluster:
        _load_cluster(sched.clientset, args.cluster)

    # Observability (docs/OBSERVABILITY.md): label this process's spans so
    # cross-process trace merges attribute stages, and install the flight
    # recorder when a dump directory is configured (the shard harness sets
    # TPU_SCHED_FLIGHTREC_DIR for traced runs and the chaos suites).
    import os
    sched.tracer.proc = (f"shard-{args.shard_index}"
                         if args.shard_index >= 0 else args.identity)
    flight = None
    flight_dir = os.environ.get("TPU_SCHED_FLIGHTREC_DIR", "")
    if flight_dir:
        from .core.spans import FlightRecorder
        flight = FlightRecorder(
            flight_dir, tracer=sched.tracer, recorder=sched.recorder,
            scheduler=sched).install(
            at_exit=True,
            autodump_interval=float(
                os.environ.get("TPU_SCHED_FLIGHTREC_INTERVAL", "5.0")))

    member = None
    if args.shard_index >= 0:
        if not args.api_url or args.shard_count <= args.shard_index:
            print("--shard-index requires --api-url and a larger "
                  "--shard-count", file=sys.stderr)
            return 1
        from .shard import ShardMember
        member = ShardMember(sched, args.shard_index, args.shard_count,
                             lease_duration=args.shard_lease_duration,
                             identity=f"{args.identity}-shard-{args.shard_index}")
        member.start_renewer()  # lease acquired before announcing ready;
        member.tick()           # background renewals survive long drains

    server = SchedulerServer(sched, identity=args.identity,
                             leader_elect=args.leader_elect)
    port = server.serve(args.port)
    # The `serving on 127.0.0.1:<port>` prefix is every harness's ready
    # regex; the backend comes from jax.devices(), never from the
    # environment.
    print(f"kubernetes-tpu-scheduler: serving on 127.0.0.1:{port} "
          f"(profiles: {', '.join(sched.profiles)}; "
          f"backend={device['platform']} "
          f"device_kind={device['kind']!r} "
          f"devices={device['count']})", flush=True)

    stop = {"flag": False}

    def _sig(_s, _f):
        stop["flag"] = True

    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)

    try:
        while not stop["flag"]:
            # Sharded runs also refresh ownership per CYCLE via the
            # scheduler's loop_hook; this outer tick covers idle stretches.
            if member is not None:
                with sched.stages.stage("loop.idle"):
                    member.tick()
            progressed = server.run_cycles()
            if args.once and not progressed:
                active, backoff, _unsched = sched.queue.pending_counts()
                if active == 0 and backoff == 0:
                    # Drained (parked-unschedulable pods don't block exit —
                    # they are reported in the failure count below).
                    break
            if not progressed:
                with sched.stages.stage("loop.idle"):
                    time.sleep(0.02)
    finally:
        server.shutdown()
        if flight is not None:
            flight.dump("shutdown")
            flight.close()
    try:
        print(f"kubernetes-tpu-scheduler: scheduled={sched.scheduled} "
              f"failures={sched.failures}", flush=True)
    except BrokenPipeError:
        # Parent closed our stdout: drop the buffered bytes too, or the
        # interpreter's exit-time flush re-raises outside this guard.
        import os
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
    return 0


if __name__ == "__main__":
    sys.exit(main())
