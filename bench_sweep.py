#!/usr/bin/env python
"""Hardware tuning sweep for the headline workload: runs bench-shaped
measured windows across (max_batch, pipeline_depth) combinations on the
CURRENT backend and prints one JSON line per point plus the best.

    python bench_sweep.py                      # default grid
    BENCH_NODES=5000 BENCH_PODS=10000 python bench_sweep.py
    SWEEP_BATCHES=512,1024,2048 SWEEP_DEPTHS=2,3 python bench_sweep.py
    python bench_sweep.py --bottleneck PERF.json   # classify, don't run

The dispatch-count vs scan-length tradeoff (and the latency-hiding value of
pipeline depth) is hardware-specific, so the right tier is measured on the
chip, not guessed. Like bench.py it refuses any backend but a TPU unless
the CPU is asked for by name (JAX_PLATFORMS=cpu), labels every point with
the device JAX reports, and exits non-zero if the device-path breaker was
charged at any point.

`--bottleneck PERF.json` reads a `python -m kubernetes_tpu.perf --out` result
file and prints each workload's dominant-cost classification (plan-build-
bound / device-wait-bound / host-commit-bound / host-path-bound), so
optimization targets can be ranked without hand-reading the table.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def bottleneck(path: str) -> int:
    """Classify every workload in a perf-table result file by dominant
    cost. The step-accounting split (plan_build_s / device_wait_s / host_commit_s,
    models/tpu_scheduler.py) covers the device pipeline; pods that never
    reached it classify as host-path-bound; workloads with no split data
    and no host pods are unattributed."""
    with open(path) as f:
        data = json.load(f)
    out = []
    for r in data.get("results", []):
        det = r.get("detail", {}) or {}
        host_pods = det.get("host_path_pods", 0) or 0
        dev_pods = det.get("device_scheduled", 0) or 0
        split = {
            "plan-build-bound": det.get("plan_build_s", 0.0) or 0.0,
            "device-wait-bound": det.get("device_wait_s", 0.0) or 0.0,
            "host-commit-bound": det.get("host_commit_s", 0.0) or 0.0,
        }
        total = sum(split.values())
        if host_pods > dev_pods:
            kind, share = "host-path-bound", None
        elif total <= 0:
            kind, share = "unattributed", None
        else:
            kind = max(split, key=split.get)
            share = round(split[kind] / total, 2)
        entry = {
            "workload": r.get("workload"),
            "bottleneck": kind,
            "pods_per_second": r.get("pods_per_second"),
            "split_s": {k.split("-")[0]: round(v, 2)
                        for k, v in split.items()},
        }
        if share is not None:
            entry["dominant_share"] = share
        if host_pods:
            entry["host_path_pods"] = host_pods
        for k in ("plan_rebuilds_full", "plan_rebuilds_delta",
                  "plan_rebuilds_resume"):
            if det.get(k) is not None:
                entry[k] = det[k]
        out.append(entry)
        print(json.dumps(entry), flush=True)
    by_kind = {}
    for e in out:
        by_kind[e["bottleneck"]] = by_kind.get(e["bottleneck"], 0) + 1
    print(json.dumps({"summary": by_kind}))
    return 0


def run_point(n_nodes, n_pods, max_batch, depth):
    from bench import make_pods
    from kubernetes_tpu.core import FakeClientset
    from kubernetes_tpu.models import TPUScheduler
    from kubernetes_tpu.testing import make_node

    cs = FakeClientset()
    sched = TPUScheduler(clientset=cs, max_batch=max_batch)
    sched.pipeline_depth = depth
    for i in range(n_nodes):
        cs.create_node(
            make_node().name(f"node-{i}")
            .capacity({"cpu": 32, "memory": "256Gi", "pods": 110})
            .zone(f"zone-{i % 50}").obj())
    sched.warm_for(make_pods(1, "warmshape")[0])
    for p in make_pods(min(max_batch, 1024), "warm"):
        cs.create_pod(p)
    sched.run_until_idle()
    before = sched.scheduled
    for p in make_pods(n_pods, "bench"):
        cs.create_pod(p)
    t0 = time.perf_counter()
    sched.run_until_idle()
    elapsed = time.perf_counter() - t0
    from kubernetes_tpu.perf.device import fallbacks_by_reason
    rate = (sched.scheduled - before) / elapsed if elapsed > 0 else 0.0
    return rate, fallbacks_by_reason(sched)


def main():
    n_nodes = int(os.environ.get("BENCH_NODES", 5000))
    n_pods = int(os.environ.get("BENCH_PODS", 10000))
    batches = [int(b) for b in os.environ.get(
        "SWEEP_BATCHES", "512,1024,2048").split(",")]
    depths = [int(d) for d in os.environ.get("SWEEP_DEPTHS", "2,3").split(",")]

    from bench import _fail_if_breaker_charged
    from kubernetes_tpu.perf.device import measuring_device
    device = measuring_device()
    best = None
    for mb in batches:
        for depth in depths:
            rate, fallbacks = run_point(n_nodes, n_pods, mb, depth)
            point = {"max_batch": mb, "pipeline_depth": depth,
                     "pods_per_s": round(rate, 1),
                     "platform": device["platform"], "device": device}
            _fail_if_breaker_charged(fallbacks, point)
            print(json.dumps(point), flush=True)
            if best is None or rate > best["pods_per_s"]:
                best = point
    print(json.dumps({"best": best}))


if __name__ == "__main__":
    if "--bottleneck" in sys.argv:
        i = sys.argv.index("--bottleneck")
        if i + 1 >= len(sys.argv):
            print("usage: bench_sweep.py --bottleneck PERF.json",
                  file=sys.stderr)
            sys.exit(2)
        try:
            sys.exit(bottleneck(sys.argv[i + 1]))
        except (OSError, json.JSONDecodeError) as e:
            print(f"bench_sweep.py --bottleneck: {e}", file=sys.stderr)
            sys.exit(2)
    main()
