#!/usr/bin/env python
"""Driver benchmark: schedules a SchedulingBasic-shaped workload (BASELINE.md
SchedulingBasic/5000Nodes_10000Pods, threshold 680 pods/s on upstream CI
hardware — test/integration/scheduler_perf/misc/performance-config.yaml:59)
through the device-backed TPUScheduler and prints ONE JSON line:

    {"metric": ..., "value": pods/s, "unit": "pods/s", "vs_baseline": x}

Compile time is excluded via a same-shape warmup run; the measured window is
steady-state scheduling (queue pop → device kernel → bind), matching the
reference collector's approach of measuring inside the scheduling window
(scheduler_perf util.go:686-694).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_PODS_PER_SEC = 680.0  # SchedulingBasic/5000Nodes_10000Pods


def _fail_if_breaker_charged(fallbacks: dict, result: dict) -> None:
    """A run during which the device-path breaker was charged finished on
    the host Evaluator (that is the breaker's guarantee) — so its rate is
    not a device measurement. The result goes to stderr, the exit code is
    non-zero, and stdout carries no number."""
    from kubernetes_tpu.perf.device import breaker_charges
    charges = breaker_charges(fallbacks)
    if charges:
        print(json.dumps({"error": "device-path breaker charged",
                          "charges": charges, "result": result}),
              file=sys.stderr)
        sys.exit(1)


def build_cluster(n_nodes: int, zones: int = 50):
    from kubernetes_tpu.core import FakeClientset
    from kubernetes_tpu.models import TPUScheduler
    from kubernetes_tpu.testing import make_node

    cs = FakeClientset()
    # BENCH_MAX_BATCH sweeps the session batch tier (dispatch count vs scan
    # length tradeoff on real hardware); default = config.max_batch.
    mb = int(os.environ.get("BENCH_MAX_BATCH", 0)) or None
    sched = TPUScheduler(clientset=cs, max_batch=mb)
    for i in range(n_nodes):
        cs.create_node(
            make_node().name(f"node-{i}")
            .capacity({"cpu": 32, "memory": "256Gi", "pods": 110})
            .zone(f"zone-{i % zones}").obj())
    return cs, sched


def make_pods(n, name_prefix):
    from kubernetes_tpu.testing import make_pod
    # One template prototype, N identity clones sharing spec + signature memo
    # (the reference perf harness stamps pods from a podTemplate the same way).
    proto = (make_pod().name("proto")
             .req({"cpu": "100m", "memory": "128Mi"}).labels({"app": name_prefix})
             .obj())
    return [proto.clone_from_template(f"{name_prefix}-{i}") for i in range(n)]


def main_sharded(n_shards: int, trace: bool = False,
                 replicas: int = 0, deschedule: bool = False) -> None:
    """`bench.py --shards N [--trace] [--replicas R] [--deschedule]`: the
    same SchedulingBasic shape through the multi-process shard plane
    (kubernetes_tpu/shard/harness.py) — one apiserver process + N scheduler
    processes over HTTP. N=1 is the like-for-like single-scheduler baseline
    (same transport, same store); the acceptance comparison is N=2 vs N=1
    pods/s. With --trace, every process dumps its span ring (flight
    recorder) and the merged trace analysis — per-stage p50/p99, chain
    completeness, conflict timeline — rides the detail object
    (docs/OBSERVABILITY.md). With --replicas R, R follower apiservers tail
    the leader's WAL and serve each shard's read plane
    (kubernetes_tpu/replication/); the detail line carries per-replica
    role/lag and the leader's replication counters. With --deschedule, an
    HA descheduler pair rides the run (docs/DESCHEDULE.md) and the detail
    line carries each manager's final stats — moves by strategy,
    blocked-by-reason, what-if batch timings — next to the apiserver's
    eviction counters (the "api" filter includes eviction series)."""
    import tempfile

    from kubernetes_tpu.shard.harness import run_sharded_cluster

    n_nodes = int(os.environ.get("BENCH_NODES", 5000))
    n_pods = int(os.environ.get("BENCH_PODS", 10000))
    flightrec_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else ""
    # PER-SHARD warmup: the uid-hash partition splits the warm burst across
    # shards, so covering each shard's top device-batch tier (the XLA
    # compile the warm phase exists to pay) needs warm_pods to scale with
    # the shard count — otherwise every shard meets its full-queue batch
    # shape for the first time INSIDE the measured window, ~2s of compile
    # per tier that the 1-shard baseline never pays.
    warmup = int(os.environ.get("BENCH_WARMUP", 1024)) * n_shards
    out = run_sharded_cluster(
        n_shards, n_nodes, n_pods, warm_pods=warmup,
        flightrec_dir=flightrec_dir, replicas=replicas,
        deschedule={"managers": 2} if deschedule else None,
        settle_s=(float(os.environ.get("BENCH_SETTLE_S", 10.0))
                  if deschedule else 0.0),
        # 15s, not the chaos tests' 2-3s: the renewer is a Python thread,
        # and on an oversubscribed box (N shards + apiserver on few cores)
        # a tight lease flaps — a starved renewer misses one period, a peer
        # adopts the range, and the overlap burns CPU on duplicate
        # scheduling + 409s until handback. Failover speed is a chaos-test
        # concern, not a throughput-bench one.
        lease_duration=float(os.environ.get("BENCH_LEASE_DURATION", 15.0)))
    detail = {k: out[k] for k in ("shards", "bound", "all_bound",
                                  "elapsed_s", "distinct_bound_pods")}
    detail["api"] = out["api"]
    # Per-shard decoded events/bytes by wire form (watch-cache read plane +
    # shard-filtered streams): the 1/N event-decode claim, measurable on
    # any box — each shard's 'full' count should approach total/N with the
    # remainder arriving slim; 'read_plane' shows where the progress polls
    # landed (followers when --replicas > 0).
    detail["watch_decode"] = out.get("watch_decode")
    # Wire-plane summary (core/wire.py): server bytes by codec/surface,
    # server encode-µs by surface + delta mint/apply counters (PR 18 —
    # attributes any shard-scaling gap to encode CPU), and per-shard
    # decoded bytes by codec — the proof of WHICH plane ran and the
    # decoded-bytes delta vs the JSON baseline (PR-10: 4.87MB full /
    # 1.71MB slim per shard on this workload; PR-13: 2.06MB binary).
    detail["wire"] = out.get("wire")
    detail["read_plane"] = out.get("read_plane")
    if replicas:
        detail["replicas"] = out["replicas"]
        detail["replication"] = out["replication"]
    if deschedule:
        # Descheduler manager final stats (per process): moves_total by
        # strategy, moves_blocked by reason (pdb/budget/gang/hysteresis),
        # what-if batch count + seconds, final utilization stddev.
        detail["deschedule"] = out.get("deschedule")
    detail["shard_metrics"] = out["shard_metrics"]
    # Peak per-process RSS (MiB), sampled by the harness poll loop — the
    # paged read plane's bounded-memory claim as a number.
    detail["rss_mb"] = out.get("rss_mb")
    # N scheduler processes cannot share a chip: the shard plane's
    # children are pinned to the CPU (shard/harness.py _env).
    detail["platform"] = "cpu (sharded subprocesses)"
    # e2e latency truth (scheduler_e2e_scheduling_duration_seconds, merged
    # across shards from /metrics) — the p50/p99 detail line.
    detail["e2e_ms"] = out.get("e2e_ms")
    if trace:
        from kubernetes_tpu import trace as trace_mod
        spans = trace_mod.load_spans([flightrec_dir])
        summary = trace_mod.summarize(spans)
        detail["trace"] = {
            "dir": flightrec_dir,
            "spans": summary["spans"],
            "traces": summary["traces"],
            "processes": summary["processes"],
            "completeness": summary["completeness"],
            "stage_p50_p99_ms": {
                name: [round(st["p50"] * 1e3, 3), round(st["p99"] * 1e3, 3)]
                for name, st in summary["stages"].items()},
            "conflicts": len(summary["conflicts"]),
        }
    result = {
        "metric": (f"pods scheduled/sec ({n_nodes} nodes, {n_pods} pods, "
                   f"{n_shards}-shard plane, HTTP transport)"),
        "value": out["pods_per_sec"],
        "unit": "pods/s",
        "vs_baseline": round(out["pods_per_sec"] / BASELINE_PODS_PER_SEC, 2),
        "detail": detail,
    }
    _fail_if_breaker_charged(out.get("device_path_fallback") or {}, result)
    print(json.dumps(result))


def main(trace: bool = False):
    n_nodes = int(os.environ.get("BENCH_NODES", 5000))
    n_pods = int(os.environ.get("BENCH_PODS", 10000))
    warmup = int(os.environ.get("BENCH_WARMUP", 1024))

    # BENCH_MESH_DEVICES=N: force an N-device virtual CPU mesh so the
    # SPMD plane (sharded state + shard_map row-local dispatch) benches
    # without hardware — the 50k-node SchedulingBasic acceptance shape.
    # Must land in XLA_FLAGS before any backend init.
    nd = int(os.environ.get("BENCH_MESH_DEVICES", 0))
    if nd > 1 and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={nd}").strip()

    # No probe and no fallback: without a TPU this fails, unless the CPU
    # was asked for by name (JAX_PLATFORMS=cpu) — and then says so.
    from kubernetes_tpu.perf.device import (fallbacks_by_reason,
                                            measuring_device)
    device = measuring_device()
    cs, sched = build_cluster(n_nodes)

    # Warmup: compile both kernel traces (fresh + chained carry) with inert
    # n_active=0 dispatches, then run one real warm block for host caches.
    sched.warm_for(make_pods(1, "warmshape")[0])
    for p in make_pods(warmup, "warm"):
        cs.create_pod(p)
    sched.run_until_idle()
    # Snapshot every counter so the detail below covers ONLY the measured
    # window (previously device_scheduled was cumulative and exceeded
    # `scheduled` by exactly the warmup pods, which read as double-counting).
    warm_sched = sched.scheduled
    warm_failures = sched.failures
    # Window-diff every attributable counter (the same step-accounting split
    # the perf table reports — plan_build/device_wait/host_commit — plus the
    # plan-rebuild kinds), so the headline bench can attribute its own
    # number instead of printing an unexplained pods/s. One canonical list,
    # shared with the perf harness.
    from kubernetes_tpu.perf.harness import _ThroughputCollector
    WINDOW = _ThroughputCollector.WINDOW_COUNTERS
    win0 = {a: getattr(sched, a, 0) for a in WINDOW}

    for p in make_pods(n_pods, "bench"):
        cs.create_pod(p)
    t0 = time.perf_counter()
    sched.run_until_idle()
    elapsed = time.perf_counter() - t0

    scheduled = sched.scheduled - warm_sched
    pods_per_sec = scheduled / elapsed if elapsed > 0 else 0.0
    from kubernetes_tpu.shard.harness import rss_mb
    detail = {
        "scheduled": scheduled,
        "failures": sched.failures - warm_failures,
        "elapsed_s": round(elapsed, 2),
        # As JAX reports it (jax.devices()), never from the environment.
        "platform": device["platform"],
        "device": device,
        # The memory claim as a number (post-run VmRSS of this process).
        "rss_mb": {"self": rss_mb()},
    }
    for a in WINDOW:
        d = getattr(sched, a, 0) - win0[a]
        detail[a] = round(d, 3) if isinstance(d, float) else d
    # Score-hint fast path engagement (models/score_hints.py): the share of
    # the window's pods bound host-side off the signature-keyed hint, with
    # zero device dispatches. A/B the dispatch-only baseline with
    # TPU_SCHED_SCORE_HINTS=0 on the same harness.
    if hasattr(sched, "hint_hits") and scheduled:
        detail["hint_hit_rate"] = round(detail.get("hint_hits", 0)
                                        / scheduled, 4)
    # Mesh plane: per-step ici/dcn collective counts of the EXACT dispatch
    # this workload's plan runs (shard_map row-local path vs GSPMD), plus
    # the shard_map engagement counter — the MULTICHIP rows regression-pin
    # the collective budget (docs/PERF.md § mesh plane).
    if getattr(sched, "mesh", None) is not None:
        detail["shard_map_dispatches"] = sched.shard_map_dispatches
        try:
            detail["collectives"] = sched.collective_counts(
                make_pods(1, "probe")[0])
        except Exception as e:  # noqa: BLE001 - detail only, never the run
            detail["collectives"] = {"error": str(e)[:200]}
    # e2e latency detail line (queue admission -> bound; fed from span ends
    # on EVERY bound pod — docs/OBSERVABILITY.md).
    e2e = sched.metrics.e2e_scheduling_duration
    if e2e.count():
        detail["e2e_ms"] = {
            "p50": round(e2e.percentile(0.50) * 1e3, 3),
            "p99": round(e2e.percentile(0.99) * 1e3, 3),
            "count": e2e.count()}
    if trace:
        import tempfile

        from kubernetes_tpu import trace as trace_mod
        from kubernetes_tpu.core import spans as _spans
        d = tempfile.mkdtemp(prefix="bench-trace-")
        path = _spans.default_tracer().dump_jsonl(
            os.path.join(d, f"spans-{os.getpid()}.jsonl"))
        summary = trace_mod.summarize(trace_mod.load_spans([path]))
        detail["trace"] = {
            "dir": d, "spans": summary["spans"],
            "traces": summary["traces"],
            "completeness": summary["completeness"],
            "stage_p50_p99_ms": {
                name: [round(st["p50"] * 1e3, 3), round(st["p99"] * 1e3, 3)]
                for name, st in summary["stages"].items()},
        }
    result = {
        "metric": f"pods scheduled/sec ({n_nodes} nodes, {n_pods} pods, device batch path)",
        "value": round(pods_per_sec, 1),
        "unit": "pods/s",
        "vs_baseline": round(pods_per_sec / BASELINE_PODS_PER_SEC, 2),
        "detail": detail,
    }
    _fail_if_breaker_charged(fallbacks_by_reason(sched), result)
    print(json.dumps(result))


if __name__ == "__main__":
    _trace = "--trace" in sys.argv
    if "--shards" in sys.argv:
        _replicas = (int(sys.argv[sys.argv.index("--replicas") + 1])
                     if "--replicas" in sys.argv else 0)
        main_sharded(int(sys.argv[sys.argv.index("--shards") + 1]),
                     trace=_trace, replicas=_replicas,
                     deschedule="--deschedule" in sys.argv)
        sys.exit(0)
    main(trace=_trace)
