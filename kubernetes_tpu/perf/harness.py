"""The scheduler_perf opcode interpreter.

Config format (mirrors test/integration/scheduler_perf/*/performance-config.yaml):

    - name: SchedulingBasic
      defaultPodTemplate: &pod
        cpu: 100m
        memory: 128Mi
      workloadTemplate:
      - opcode: createNodes
        countParam: $nodes
        nodeTemplate: {cpu: 32, memory: 256Gi, pods: 110, zones: 50}
      - opcode: createPods
        countParam: $measurePods
        podTemplate: *pod
        collectMetrics: true
      workloads:
      - name: 5000Nodes_10000Pods
        labels: [performance]
        params: {nodes: 5000, measurePods: 10000}
        thresholds: {SchedulingThroughput: 680}

Opcodes: createNodes, createPods, createPodGroups, churn, barrier, sleep,
startCollectingMetrics/stopCollectingMetrics (scheduler_perf.go:64-80).
`barrier` drains the scheduler, sampling throughput; createPods with
collectMetrics wraps itself in start/barrier implicitly, as the reference
does for measured pods.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import yaml

from ..api.types import Namespace, PodGroup, Volume
from ..core.scheduler import Scheduler
from ..testing.wrappers import make_node, make_pod
from .device import fallbacks_by_reason

ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"


@dataclass
class Workload:
    name: str
    testcase: str
    labels: List[str]
    params: Dict[str, Any]
    thresholds: Dict[str, float]
    ops: List[Dict[str, Any]]
    default_pod_template: Optional[Dict[str, Any]] = None
    # Per-workload featureGates (misc/performance-config.yaml:65-81 variant
    # style) and the simulated apiserver round-trip for the watch-seam
    # transport (core/remote.py); 0 = in-process clientset.
    feature_gates: Dict[str, bool] = field(default_factory=dict)
    api_rtt_ms: float = 0.0


@dataclass
class PerfResult:
    workload: Workload
    scheduled: int = 0
    failed: int = 0
    elapsed: float = 0.0
    metrics: Dict[str, Dict[str, float]] = field(default_factory=dict)
    # Device/host attribution (TPUScheduler counters; zero on host-only runs):
    # which path the pods took and where the wall-clock went.
    detail: Dict[str, Any] = field(default_factory=dict)

    def meets_thresholds(self) -> bool:
        """Thresholds gate `performance`-, `hollow`-, and `flood`-labeled
        runs only — the reference asserts them on perf hardware, not on
        integration-test variants (scheduler_perf.go:282-368 /
        misc/performance-config.yaml:1-19). `flood` rows assert their
        overload floors (FloodSheds/MaxFloodErrors) wherever they run —
        they ARE the scenario's acceptance contract. A threshold named
        ``Max*`` is a CEILING (e.g. MaxApiserverRssMb — the
        bounded-memory floor of the paged read plane); everything else
        is a floor."""
        if not {"performance", "hollow", "flood"} & set(self.workload.labels):
            return True
        for name, bound in self.workload.thresholds.items():
            got = self.metrics.get(name, {}).get("Average", 0.0)
            if name.startswith("Max"):
                if got > bound:
                    return False
            elif got < bound:
                return False
        return True


def load_config(path: str, scale: float = 1.0) -> List[Workload]:
    """Load testcases → one Workload per (testcase, workload) pair.
    `scale` multiplies every count param (CI runs scaled-down clusters;
    thresholds scale linearly with the count scale)."""
    with open(path) as f:
        testcases = yaml.safe_load(f)
    out: List[Workload] = []
    for tc in testcases:
        for wl in tc.get("workloads", ()):
            params = dict(wl.get("params", {}))
            if scale != 1.0:
                # `shards` is topology, not load — scaling it would silently
                # turn a sharded workload into a single-scheduler one.
                params = {k: (v if k == "shards"
                              else max(1, int(v * scale)))
                          if isinstance(v, int) else v
                          for k, v in params.items()}
            thresholds = {
                k: v * scale if scale != 1.0 else v
                for k, v in wl.get("thresholds", {}).items()}
            gates = dict(tc.get("featureGates", {}))
            gates.update(wl.get("featureGates", {}))
            out.append(Workload(
                name=wl["name"],
                testcase=tc["name"],
                labels=list(wl.get("labels", ())),
                params=params,
                thresholds=thresholds,
                ops=tc.get("workloadTemplate", []),
                default_pod_template=tc.get("defaultPodTemplate"),
                feature_gates=gates,
                api_rtt_ms=float(wl.get("apiRttMs", tc.get("apiRttMs", 0.0))),
            ))
    return out


def _resolve_count(op: Dict[str, Any], params: Dict[str, Any]) -> int:
    if "count" in op:
        return int(op["count"])
    ref = op.get("countParam", "")
    return int(params[ref.lstrip("$")])


class _ThroughputCollector:
    """SchedulingThroughput (util.go:477): samples pods-scheduled per
    interval while collecting; summarizes Average + percentiles."""

    INTERVAL = 0.1

    def __init__(self, sched: Scheduler):
        self.sched = sched
        self.samples: List[float] = []
        self._last_t = 0.0
        self._last_n = 0
        self._t0 = 0.0
        self._n0 = 0
        self.active = False

    WINDOW_COUNTERS = ("plan_build_s", "device_wait_s", "host_commit_s",
                       "device_scheduled", "host_path_pods", "device_batches",
                       "plan_rebuilds_full", "plan_rebuilds_delta",
                       "plan_rebuilds_resume", "delta_dirty_rows",
                       "hint_hits", "hint_misses", "hint_invalidations")

    def start(self) -> None:
        self.active = True
        self._t0 = self._last_t = time.perf_counter()
        self._n0 = self._last_n = self.sched.scheduled
        self._win0 = {a: getattr(self.sched, a, 0) for a in self.WINDOW_COUNTERS}
        self.in_window: Dict[str, float] = {}

    def tick(self) -> None:
        if not self.active:
            return
        now = time.perf_counter()
        if now - self._last_t >= self.INTERVAL:
            rate = (self.sched.scheduled - self._last_n) / (now - self._last_t)
            self.samples.append(rate)
            self._last_t, self._last_n = now, self.sched.scheduled

    def stop(self) -> Dict[str, float]:
        self.active = False
        elapsed = time.perf_counter() - self._t0
        total = self.sched.scheduled - self._n0
        # In-window attribution: the share of the MEASURED window each
        # pipeline stage took (the workload-cumulative counters in `detail`
        # also cover setup/warm phases and cannot attribute the window).
        self.in_window = {"window_s": round(elapsed, 3)}
        for a in self.WINDOW_COUNTERS:
            v = getattr(self.sched, a, None)
            if v is not None:
                d = v - self._win0.get(a, 0)
                self.in_window[a] = round(d, 3) if isinstance(d, float) else d
        # Window scheduled count + hint-hit rate (share of the window's
        # pods bound via the score-hint fast path — the
        # HomogeneousReplicaSurge threshold's denominator).
        self.window_scheduled = total
        avg = total / elapsed if elapsed > 0 else 0.0
        s = sorted(self.samples) or [avg]

        def pct(q: float) -> float:
            return s[min(len(s) - 1, int(q * len(s)))]

        return {"Average": avg, "Perc50": pct(0.50), "Perc90": pct(0.90),
                "Perc95": pct(0.95), "Perc99": pct(0.99)}


def _record_hint_hit_rate(result: "PerfResult",
                          collector: _ThroughputCollector) -> None:
    """HintHitRate metric (HomogeneousReplicaSurge threshold): the share of
    the measured window's scheduled pods bound through the score-hint fast
    path (models/score_hints.py) — zero/absent on host-only schedulers."""
    hits = collector.in_window.get("hint_hits")
    if hits is None:
        return
    rate = hits / max(1, getattr(collector, "window_scheduled", 0))
    result.metrics["HintHitRate"] = {"Average": round(rate, 4)}


def _make_node_from_template(i: int, tpl: Dict[str, Any]):
    zones = int(tpl.get("zones", 0))
    cap = {
        "cpu": tpl.get("cpu", 32),
        "memory": tpl.get("memory", "256Gi"),
        "pods": tpl.get("pods", 110),
    }
    # extended/scalar resources (node-with-extended-resource.yaml shape)
    cap.update(tpl.get("extended", {}))
    b = make_node().name(tpl.get("name", f"node-{i}")).capacity(cap)
    if zones:
        b = b.zone(f"zone-{i % zones}")
    for k, v in tpl.get("labels", {}).items():
        b = b.label(k, v)
    for t in tpl.get("taints", ()):
        b = b.taint(t["key"], t.get("value", ""), t.get("effect", "NoSchedule"))
    for img in tpl.get("images", ()):
        b = b.image(img["name"], int(img.get("sizeBytes", 0)))
    node = b.obj()
    nf = int(tpl.get("declaredFeatures", 0))
    if nf:
        node.declared_features = {f"feature-{j}": True for j in range(nf)}
    return node


# Template → prototype pod. Building a pod from a template parses resource
# quantities and assembles spec objects (~20µs); a createPods op stamps tens
# of thousands of IDENTICAL pods inside the measured window, so the spec is
# built once and each instance is a cheap identity clone sharing the spec and
# the signature memo (Pod.clone_from_template). Keyed by template-dict
# identity (the strong ref in the entry keeps the id stable); pvc templates
# have per-pod volume names and always take the full build path.
_POD_PROTO_CACHE: Dict[Tuple[int, str], Tuple[Dict[str, Any], Any]] = {}


def _make_pod_from_template(name: str, tpl: Dict[str, Any], namespace: str = "default"):
    if not tpl.get("pvc"):
        key = (id(tpl), namespace)
        ent = _POD_PROTO_CACHE.get(key)
        if ent is not None and ent[0] is tpl:
            return ent[1].clone_from_template(name)
        if len(_POD_PROTO_CACHE) > 4096:  # bound gang-workload growth
            _POD_PROTO_CACHE.clear()
        proto = _build_pod_from_template("proto", tpl, namespace)
        _POD_PROTO_CACHE[key] = (tpl, proto)
        return proto.clone_from_template(name)
    return _build_pod_from_template(name, tpl, namespace)


def _build_pod_from_template(name: str, tpl: Dict[str, Any], namespace: str = "default"):
    req = {"cpu": tpl.get("cpu", "100m"), "memory": tpl.get("memory", "128Mi")}
    req.update(tpl.get("extended", {}))  # extended-resource requests
    b = make_pod().name(name).namespace(namespace).req(req)
    for k, v in tpl.get("labels", {}).items():
        b = b.label(k, v)
    if tpl.get("nodeSelector"):
        b = b.node_selector(dict(tpl["nodeSelector"]))
    for tol in tpl.get("tolerations", ()):
        b = b.toleration(tol["key"], tol.get("value", ""),
                         tol.get("operator", "Equal"), tol.get("effect", ""))
    for c in tpl.get("topologySpreadConstraints", ()):
        b = b.spread_constraint(
            c.get("maxSkew", 1),
            c.get("topologyKey", ZONE),
            c.get("whenUnsatisfiable", "DoNotSchedule"),
            c.get("labelSelector", tpl.get("labels", {})),
            node_taints_policy=c.get("nodeTaintsPolicy", "Ignore"))
    for kind, anti in (("podAntiAffinity", True), ("podAffinity", False)):
        aff = tpl.get(kind)
        if aff:
            b = b.pod_affinity(
                aff.get("topologyKey", HOSTNAME if anti else ZONE),
                aff.get("matchLabels", tpl.get("labels", {})),
                anti=anti, weight=aff.get("weight", 0),
                ns_labels=aff.get("namespaceSelector"))
    na = tpl.get("nodeAffinityIn")
    if na:
        b = b.node_affinity_in(na["key"], list(na["values"]))
    pna = tpl.get("preferredNodeAffinity")
    if pna:
        b = b.preferred_node_affinity(
            int(pna.get("weight", 1)), pna["key"], list(pna["values"]))
    if tpl.get("nodeAffinityName"):
        # daemonset-pod.yaml shape: matchFields metadata.name In [node]
        b = b.node_affinity_name(tpl["nodeAffinityName"])
    if tpl.get("hostPort"):
        b = b.host_port(int(tpl["hostPort"]))
    for g in tpl.get("schedulingGates", ()):
        b = b.scheduling_gate(g)
    if tpl.get("image"):
        b = b.image(tpl["image"])
    if tpl.get("priority"):
        b = b.priority(int(tpl["priority"]))
    pod = b.obj()
    if tpl.get("requiredFeatures"):
        nf = int(tpl["requiredFeatures"])
        pod.annotations["features.k8s.io/required"] = ",".join(
            f"feature-{j}" for j in range(nf))
    if tpl.get("finalizers"):
        pod.finalizers = list(tpl["finalizers"])
    for j in range(int(tpl.get("secretVolumes", 0))):
        pod.volumes.append(Volume(name=f"secret-{j}"))
    if tpl.get("pvc"):
        pod.volumes.append(Volume(name="data", pvc_name=tpl["pvc"].format(name=name)))
    if tpl.get("podGroup"):
        pod.pod_group = tpl["podGroup"]
    return pod


class _ThreadedCreator:
    """createPods with a concurrent client: the reference's createPodsOp
    issues creates from the test client while the scheduler schedules
    (scheduler_perf.go createPodsOp → client-go rate-limited creates); here a
    creator thread writes through the clientset and the scheduler's
    off-thread event inbox (Scheduler._threaded) replays the adds on the
    scheduling loop — creation overlaps the measured window instead of
    serializing in front of it."""

    blocks_idle = True  # _drain must not exit while creates are in flight

    def __init__(self, fn):
        import threading
        self._exc: Optional[BaseException] = None

        def run():
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 - re-raised on main thread
                self._exc = e

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def tick(self) -> bool:
        if self._exc is not None:
            # A failed create op must fail the workload (synchronous creates
            # propagated); surface the creator-thread exception here.
            raise self._exc
        return self._thread.is_alive()


class _RateDeleter:
    """deletePods opcode with skipWaitToCompletion: deletes pods at a fixed
    rate CONCURRENTLY with the measured scheduling window (the reference
    runs this in a goroutine — scheduler_perf.go deletePodsOp)."""

    def __init__(self, cs, pods: List, per_second: float, now=time.perf_counter):
        self.cs = cs
        self.pods = list(pods)
        self.per_second = max(per_second, 1e-9)
        self.now = now
        self._t0 = now()
        self._done = 0

    def tick(self) -> bool:
        due = int((self.now() - self._t0) * self.per_second)
        while self._done < min(due, len(self.pods)):
            self.cs.delete_pod(self.pods[self._done])
            self._done += 1
        return self._done < len(self.pods)


class _Churner:
    """churn opcode (scheduler_perf.go:72): every interval, create/delete (or
    recreate) objects WHILE the measured window runs — exercising mid-session
    invalidations, queue moves, and device-mirror refreshes for real."""

    def __init__(self, cs, pod_tpl: Dict[str, Any], number: int,
                 interval_ms: float, mode: str = "recreate",
                 churn_nodes: bool = False, now=time.perf_counter):
        self.cs = cs
        self.pod_tpl = pod_tpl
        self.number = number
        self.interval = max(interval_ms, 1.0) / 1000.0
        self.mode = mode
        self.churn_nodes = churn_nodes
        self.now = now
        self._next = now()
        self._seq = 0
        self._live_pods: List = []
        self._live_nodes: List = []

    def tick(self) -> bool:
        while self.now() >= self._next:
            self._next += self.interval
            self._seq += 1
            p = _make_pod_from_template(f"churn-pod-{self._seq}", self.pod_tpl)
            self.cs.create_pod(p)
            self._live_pods.append(p)
            if self.churn_nodes:
                n = _make_node_from_template(0, {"name": f"churn-node-{self._seq}"})
                self.cs.create_node(n)
                self._live_nodes.append(n)
            if self.mode == "recreate" and len(self._live_pods) > self.number:
                self.cs.delete_pod(self._live_pods.pop(0))
                if len(self._live_nodes) > self.number:
                    self.cs.delete_node(self._live_nodes.pop(0).name)
        return True  # churns for the whole workload


def _drain(sched: Scheduler, collector: _ThroughputCollector,
           tickers: Optional[List] = None, max_cycles: int = 10_000_000) -> None:
    """barrier opcode: drive scheduling until the queue stops yielding.
    Active tickers (churners, rate deleters) run interleaved with the
    scheduling loop — i.e. concurrently with the measured window."""
    n = 0
    tickers = tickers if tickers is not None else []
    while n < max_cycles:
        for t in list(tickers):
            if not t.tick():
                tickers.remove(t)
        progressed = sched.schedule_one()
        collector.tick()
        if not progressed:
            sched.queue.flush_backoff_completed()
            sched.flush_expired_waiters()
            if not sched.schedule_one():
                if any(getattr(t, "blocks_idle", False) for t in tickers):
                    # A creator thread is still writing: wait for its events
                    # instead of declaring the queue drained.
                    sched.drain_event_inbox() or time.sleep(0.0002)
                    continue
                break
        n += 1


def _warm_group_shapes(sched, cs, wl: Workload, start_op) -> None:
    """Warm device kernel tiers for createPodGroups ops that run inside the
    upcoming measured window: the plain session tier for default-algorithm
    gangs, and the stacked placement tier for topology-constrained ones."""
    warm = getattr(sched, "warm_for", None)
    if warm is None:
        return
    started = False
    for op in wl.ops:
        if op is start_op:
            started = True
            continue
        if not started or op.get("opcode") != "createPodGroups":
            continue
        tpl = dict(op.get("podTemplate") or wl.default_pod_template or {})
        pod = _make_pod_from_template("warm-group-template", tpl)
        tkey = op.get("topologyKey")
        if tkey:
            warm_p = getattr(sched, "warm_for_placements", None)
            if warm_p is not None:
                domains = {n.labels.get(tkey) for n in cs.nodes.values()}
                domains.discard(None)
                warm_p(pod, int(op.get("groupSize", 2)),
                       max(1, len(domains)))
        else:
            warm(pod)


def run_sharded_workload(wl: Workload,
                         n_shards: Optional[int] = None) -> PerfResult:
    """Run a createNodes/createPods workload through the MULTI-PROCESS shard
    plane (shard/harness.py): one apiserver process, N scheduler processes,
    everything over HTTP. The measured window is first-measured-create →
    all-bound, so the reported pods/s composes shard throughput the way the
    acceptance criterion counts it (1-shard vs N-shard, same transport)."""
    from ..shard.harness import run_sharded_cluster

    n_nodes = n_pods = 0
    node_tpl: Dict[str, Any] = {}
    pod_tpl: Dict[str, Any] = dict(wl.default_pod_template or {})
    for op in wl.ops:
        if op["opcode"] == "createNodes":
            n_nodes += _resolve_count(op, wl.params)
            node_tpl = op.get("nodeTemplate", {})
        elif op["opcode"] == "createPods":
            n_pods += _resolve_count(op, wl.params)
            pod_tpl = dict(op.get("podTemplate") or pod_tpl)
        else:
            raise ValueError(
                f"sharded workloads support createNodes/createPods only, "
                f"got {op['opcode']!r}")
    shards = int(n_shards or wl.params.get("shards", 2))
    # Adversarial-tenant flood (overload plane, docs/RESILIENCE.md):
    # `floodThreads` in params spawns that many flood workers hammering
    # single-pod creates in their own namespace for the measured window —
    # the apiserver's flow control must shed them (429 + Retry-After)
    # while the measured tenant's pods keep binding.
    flood = None
    if wl.params.get("floodThreads"):
        flood = {"threads": int(wl.params["floodThreads"]),
                 "namespace": wl.params.get("floodNamespace", "flood-tenant")}
    out = run_sharded_cluster(
        shards, n_nodes, n_pods,
        lease_duration=float(wl.params.get("leaseDuration", 3.0)),
        warm_pods=int(wl.params.get("warmPods", min(256, max(1, n_pods // 8)))),
        zones=int(node_tpl.get("zones", 50)),
        node_capacity={"cpu": node_tpl.get("cpu", 32),
                       "memory": node_tpl.get("memory", "256Gi"),
                       "pods": node_tpl.get("pods", 110)},
        pod_request={"cpu": pod_tpl.get("cpu", "100m"),
                     "memory": pod_tpl.get("memory", "128Mi")},
        flood=flood)
    result = PerfResult(workload=wl, scheduled=out["bound"],
                        failed=0 if out["all_bound"] else 1,
                        elapsed=out["elapsed_s"])
    rate = out["pods_per_sec"]
    result.metrics["SchedulingThroughput"] = {
        "Average": rate, "Perc50": rate, "Perc90": rate, "Perc95": rate,
        "Perc99": rate}
    if flood is not None and out.get("flood") is not None:
        # FloodSheds floor: the flood really was shed (not absorbed);
        # MaxFloodErrors ceiling: sheds are 429s, never transport failures.
        result.metrics["FloodSheds"] = {"Average": out["flood"]["shed"]}
        result.metrics["MaxFloodErrors"] = {"Average": out["flood"]["errors"]}
    result.detail = dict(out)
    return result


def run_hollow_workload(wl: Workload) -> PerfResult:
    """Run a hollow-plane scale workload (docs/SCALE.md): the node fleet
    is impersonated by a kubernetes_tpu/hollow plane process (register +
    heartbeats + capacity drift + cordon/delete/re-register churn) while
    `shards` scheduler processes bind the measured pods over the paged
    read plane. The result carries the scale-plane acceptance numbers:

    - ``SchedulingThroughput`` — the usual floor;
    - ``MaxApiserverRssMb`` / ``MaxShardRssMb`` — peak RSS CEILINGS
      (sampled by the harness poll loop), the bounded-memory claim;
    - ``MaxUnpagedLists`` — apiserver_list_unpaged_total, asserted 0:
      zero full-cluster single-response LISTs crossed the wire."""
    from ..shard.harness import run_sharded_cluster

    n_nodes = n_pods = 0
    pod_tpl: Dict[str, Any] = dict(wl.default_pod_template or {})
    for op in wl.ops:
        if op["opcode"] == "createNodes":
            n_nodes += _resolve_count(op, wl.params)
        elif op["opcode"] == "createPods":
            n_pods += _resolve_count(op, wl.params)
            pod_tpl = dict(op.get("podTemplate") or pod_tpl)
        else:
            raise ValueError(
                f"hollow workloads support createNodes/createPods only, "
                f"got {op['opcode']!r}")
    params = wl.params
    profile = {
        "heartbeat_s": float(params.get("hollowHeartbeatS", 30.0)),
        "drift": float(params.get("hollowDrift", 0.0)),
        "churn_per_s": float(params.get("hollowChurnPerS", 0.0)),
        "zones": int(params.get("zones", 100)),
        # Failure injection (hollow/profile.py): silenced/flapping slices
        # and zone blackout for node-lifecycle-controller runs
        # (docs/RESILIENCE.md § node lifecycle).
        "silence": float(params.get("hollowSilence", 0.0)),
        "silence_after_s": float(params.get("hollowSilenceAfterS", 0.0)),
        "flap": float(params.get("hollowFlap", 0.0)),
        "flap_period_s": float(params.get("hollowFlapPeriodS", 2.0)),
        "outage_zone": int(params.get("hollowOutageZone", -1)),
        "outage_after_s": float(params.get("hollowOutageAfterS", 0.0)),
        # Capacity-imbalance knob (profile.imbalance, docs/DESCHEDULE.md):
        # churn re-registrations land capacity-skewed off the one seed —
        # the descheduler rows' drift source.
        "imbalance": float(params.get("hollowImbalance", 0.0)),
        "seed": int(params.get("hollowSeed", 0)),
    }
    # Standing workload-manager row (ROADMAP: trace profile at hollow
    # scale): `workloadManagers` spawns the HA manager pair; trace*
    # params feed the seeded Borg-marginal deployment/gang arrival feed.
    workload = None
    if params.get("workloadManagers"):
        workload = {"managers": int(params["workloadManagers"]),
                    "lease_ttl": float(params.get("workloadLeaseTtlS", 2.0))}
        if params.get("traceDeployments") or params.get("traceGangs"):
            workload["trace"] = {
                "deployments": int(params.get("traceDeployments", 0)),
                "gangs": int(params.get("traceGangs", 0)),
                "rate": float(params.get("traceRate", 2.0)),
                "lifetime": float(params.get("traceLifetimeS", 0.0)),
                "seed": int(params.get("traceSeed", 0))}
    # Descheduler rows (docs/DESCHEDULE.md): `deschedule: true` spawns
    # the HA descheduler pair; the rebalance happens inside the
    # `settleS` window after the last measured pod binds.
    deschedule = None
    if params.get("deschedule"):
        deschedule = {
            "managers": int(params.get("descheduleManagers", 2)),
            "lease_ttl": float(params.get("descheduleLeaseTtlS", 2.0)),
            "tick": float(params.get("descheduleTickS", 0.5)),
            "hysteresis": int(params.get("descheduleHysteresis", 5)),
            "margin": float(params.get("descheduleMargin", 0.10)),
            "max_moves": int(params.get("descheduleMaxMoves", 64))}
    # PDB-cleanliness oracle: `pdbMinAvailable` posts one PDB over the
    # measured pods' {app: sharded} selector before rebalance starts;
    # every progress poll then asserts the bound count never dips below
    # the floor once it has been reached — a dip means an eviction the
    # server should have 429'd (the zero-violations-at-every-poll
    # contract). The count rides the existing summary poll: no extra
    # read traffic.
    pdb_min = int(params.get("pdbMinAvailable", 0))
    warm_pods = int(params.get("warmPods", min(256, max(1, n_pods // 8))))
    pdb_state = {"created": False, "armed": False, "polls": 0,
                 "violations": 0}

    def _pdb_cb(bound: int, cluster) -> None:
        from ..shard.harness import _call
        if not pdb_state["created"]:
            try:
                _call(cluster.base, "POST", "/api/v1/pdbs",
                      {"name": "measured-pdb", "namespace": "default",
                       "minAvailable": pdb_min,
                       "matchLabels": {"app": "sharded"}})
            except Exception:  # noqa: BLE001 - next poll retries
                return
            pdb_state["created"] = True
        # The cb's `bound` excludes warm pods; the server's PDB gate
        # counts the whole {app: sharded} matched set (warm + measured),
        # so compare the same total the gate compares.
        total_bound = bound + warm_pods
        pdb_state["polls"] += 1
        if total_bound >= pdb_min:
            pdb_state["armed"] = True
        elif pdb_state["armed"]:
            pdb_state["violations"] += 1

    out = run_sharded_cluster(
        int(params.get("shards", 1)), n_nodes, n_pods,
        hollow=profile,
        # Fleet-conductor seams (docs/SCALE.md § fleet conductor): split
        # the hollow fleet across N plane processes by name-prefix range,
        # and give every shard a virtual device mesh so row-local plans
        # dispatch mesh-SPMD (the 100k fusion row runs both).
        hollow_procs=int(params.get("hollowProcs", 1)),
        mesh_devices=int(params.get("meshDevices", 0)),
        replicas=int(params.get("replicas", 0)),
        lease_duration=float(params.get("leaseDuration", 15.0)),
        warm_pods=warm_pods,
        timeout=float(params.get("timeoutS", 3600.0)),
        workload=workload,
        deschedule=deschedule,
        settle_s=float(params.get("settleS", 0.0)),
        progress_cb=(_pdb_cb if pdb_min else None),
        pod_request={"cpu": pod_tpl.get("cpu", "100m"),
                     "memory": pod_tpl.get("memory", "128Mi")})
    result = PerfResult(workload=wl, scheduled=out["bound"],
                        failed=0 if out["all_bound"] else 1,
                        elapsed=out["elapsed_s"])
    rate = out["pods_per_sec"]
    result.metrics["SchedulingThroughput"] = {
        "Average": rate, "Perc50": rate, "Perc90": rate, "Perc95": rate,
        "Perc99": rate}
    rss = out.get("rss_mb") or {}
    result.metrics["MaxApiserverRssMb"] = {"Average": max(
        [rss.get("apiserver", 0.0)] + list(rss.get("followers", ())))}
    result.metrics["MaxShardRssMb"] = {"Average": max(
        list(rss.get("shards", ())) or [0.0])}
    # Peak RSS of the hollow plane processes themselves: at 100k nodes
    # split across members, the impersonation layer's memory is part of
    # the bounded-memory claim too.
    result.metrics["MaxHollowRssMb"] = {"Average": float(
        rss.get("hollow", 0.0) or 0.0)}
    # Zero-unpaged must hold on EVERY replica (the shards list from
    # followers): the replication detail scrapes each one, leader
    # included; without replicas, fall back to the leader's counter.
    reps = out.get("replication")
    if reps:
        unpaged = sum(float(rep.get("listUnpaged", 0)) for rep in reps)
        relisted = sum(float(rep.get("relistedWatches", 0)) for rep in reps)
    else:
        api = out.get("api") or {}
        unpaged = float(api.get("apiserver_list_unpaged_total", 0.0))
        relisted = float(api.get("apiserver_relisted_watches_total", 0.0))
    result.metrics["MaxUnpagedLists"] = {"Average": unpaged}
    # Watch-plane health ceiling: a relisted watch means a watcher fell
    # off the cache ring and re-LISTed — at 100k nodes that is a paged
    # but still fleet-sized read. The fusion row pins it to zero.
    result.metrics["MaxRelistedWatches"] = {"Average": relisted}
    if workload is not None:
        # Standing trace-row floors: the trace profile really fed
        # (profile_fed counts deployment/gang arrivals minted) and the
        # reconcilers really created pods through the deterministic-name
        # /409 seam (summed over both managers — only the active one
        # creates, but a takeover splits the count).
        wls = [s for s in (out.get("workload") or []) if s]
        result.metrics["WorkloadTraceFed"] = {"Average": float(
            sum(int(s.get("profile_fed", 0)) for s in wls))}
        result.metrics["WorkloadPodsCreated"] = {"Average": float(sum(
            int((s.get("replicasets") or {}).get("pods_created", 0))
            + int((s.get("gangs") or {}).get("pods_created", 0))
            for s in wls))}
    if deschedule is not None:
        dss = [s for s in (out.get("deschedule") or []) if s]
        # Post-rebalance utilization stddev (milli-cpu): the ACTIVE
        # manager's last reconcile computed it; standbys report 0.0, so
        # take the max over managers that actually held the lease.
        # MaxUtilizationStddevMilli is the convergence CEILING the
        # ChurnDriftRebalance row pins.
        result.metrics["MaxUtilizationStddevMilli"] = {"Average": max(
            [float(s.get("util_stddev_milli", 0.0)) for s in dss
             if int(s.get("active_ticks", 0))] or [0.0])}
        # DescheduleMoves floor: the rebalance actually moved pods (a
        # zero here means the drift never formed or hysteresis ate it).
        result.metrics["DescheduleMoves"] = {"Average": float(sum(
            sum(int(v) for v in (s.get("moves") or {}).values())
            for s in dss))}
        # Exactly-once contract as a ceiling: every eviction the server
        # committed came back around as exactly one scheduler requeue —
        # a gap either way means a lost or double-counted move.
        api = out.get("api") or {}
        requeues = sum(
            float(sm.get("scheduler_eviction_requeues_total", 0.0))
            for sm in out.get("shard_metrics") or [])
        evictions = float(api.get("apiserver_pod_evictions_total", 0.0))
        result.metrics["MaxEvictionRequeueGap"] = {
            "Average": abs(requeues - evictions)}
    if pdb_min:
        # Zero-PDB-violations-at-every-poll: once the bound count reached
        # the PDB floor it never dipped below it again — every rebalance
        # eviction was budget-gated server-side.
        result.metrics["MaxPdbViolations"] = {
            "Average": float(pdb_state["violations"])}
    result.detail = dict(out)
    if pdb_min:
        result.detail["pdb"] = dict(pdb_state)
    return result


def run_workload(wl: Workload, sched: Optional[Scheduler] = None) -> PerfResult:
    """Execute one workload's opcode list (the RunBenchmarkPerfScheduling
    inner loop, scheduler_perf.go:282+)."""
    from ..models.tpu_scheduler import TPUScheduler

    if wl.params.get("hollow") and sched is None:
        # Hollow-plane scale workloads (HollowNodeScale): the node fleet
        # is impersonated by a hollow plane process, pods bind through
        # real scheduler shards over the paged read plane.
        return run_hollow_workload(wl)
    if wl.params.get("shards") and sched is None:
        # Sharded workloads (ShardedSchedulingBasic) run the multi-process
        # shard plane — one apiserver + N scheduler processes — rather than
        # an in-process scheduler loop.
        return run_sharded_workload(wl)

    # Each workload builds a fresh scheduler/framework; proto pods (and their
    # framework-id-keyed signature holders) must not outlive the frameworks
    # they were signed against (CPython id() reuse would alias a stale memo).
    _POD_PROTO_CACHE.clear()

    if sched is None:
        cfg = None
        cs_arg = {}
        if wl.feature_gates or wl.api_rtt_ms:
            from ..core.config import SchedulerConfiguration
            cfg = SchedulerConfiguration(
                feature_gates=dict(wl.feature_gates),
                async_dispatch_threads=wl.feature_gates.get(
                    "SchedulerAsyncAPICalls", False))
        if wl.api_rtt_ms:
            from ..core.remote import RemoteClientset
            cs_arg["clientset"] = RemoteClientset(rtt=wl.api_rtt_ms / 1000.0)
        if any(op.get("topologyKey") for op in wl.ops
               if op.get("opcode") == "createPodGroups"):
            # Topology-constrained gangs need the placement plugin set
            # (GenericWorkload-gated in the reference).
            from ..core.registry import gang_placement_profiles
            sched = TPUScheduler(profile_factory=gang_placement_profiles,
                                 config=cfg, **cs_arg)
        elif any(op.get("opcode") == "createResourceSlices" for op in wl.ops):
            # DRA workloads need the DynamicResources plugin
            # (DynamicResourceAllocation-gated in the reference).
            from ..core.registry import DEFAULT_PLUGINS, build_framework
            plugins = DEFAULT_PLUGINS + (("DynamicResources", 0),)
            sched = TPUScheduler(profile_factory=lambda h: {
                "default-scheduler": build_framework(h, plugins=plugins)},
                config=cfg, **cs_arg)
        else:
            sched = TPUScheduler(config=cfg, **cs_arg)
    cs = sched.clientset
    collector = _ThroughputCollector(sched)
    params = wl.params
    pod_seq = 0
    node_seq = 0
    created_nodes: List[str] = []
    result = PerfResult(workload=wl)
    tickers: List = []
    created_pods: Dict[str, List] = {}  # namespace -> pods (deletePods targets)
    t0 = time.perf_counter()

    def _create_pods(op, tpl, namespace, count):
        nonlocal pod_seq
        claim_tpl = tpl.get("resourceClaimTemplate")
        pv_tpl = op.get("persistentVolumeTemplate")
        pvc_tpl = op.get("persistentVolumeClaimTemplate")
        batch = []
        for _ in range(count):
            if pv_tpl is not None and pvc_tpl is not None:
                # One pre-bound PV+PVC pair per pod (the reference's
                # persistentVolumeTemplatePath/persistentVolumeClaimTemplatePath
                # prep: pv-aws.yaml / pv-csi.yaml / pvc.yaml with
                # pv.kubernetes.io/bind-completed).
                from ..api.storage import PersistentVolume, PersistentVolumeClaim
                from ..api.resource import parse_quantity
                cap = int(parse_quantity(str(pv_tpl.get("capacity", "1Gi"))))
                modes = tuple(pv_tpl.get("accessModes", ("ReadOnlyMany",)))
                pv = PersistentVolume(
                    name=f"pv-{pod_seq}", capacity=cap, access_modes=modes,
                    csi_driver=pv_tpl.get("csi", ""),
                    labels=dict(pv_tpl.get("labels", {})))
                pvc = PersistentVolumeClaim(
                    name=f"pvc-{pod_seq}", namespace=namespace, request=cap,
                    access_modes=modes)
                pv.claim_ref = pvc.key
                pvc.volume_name = pv.name
                pvc.annotations["pv.kubernetes.io/bind-completed"] = "true"
                cs.create_pv(pv)
                cs.create_pvc(pvc)
                tpl = dict(tpl, pvc="pvc-%d" % pod_seq)
            p = _make_pod_from_template(f"pod-{pod_seq}", tpl, namespace=namespace)
            if claim_tpl:
                # resourceClaimTemplate: one generated claim per pod
                # (dra/performance-config.yaml SchedulingWithResourceClaimTemplate)
                from ..api.dra import DeviceRequest, ResourceClaim
                cname = f"{p.name}-claim"
                cs.create_resource_claim(ResourceClaim(
                    name=cname, namespace=namespace,
                    requests=[DeviceRequest(
                        name="req",
                        count=int(claim_tpl.get("count", 1)),
                        selectors=dict(claim_tpl.get("selectors", {})),
                        expression=claim_tpl.get("expression", ""))]))
                p.resource_claims = [cname]
            pod_seq += 1
            cs.create_pod(p)
            batch.append(p)
        created_pods.setdefault(namespace, []).extend(batch)
        return batch

    for op in wl.ops:
        opcode = op["opcode"]
        if opcode == "createNodes":
            count = _resolve_count(op, params)
            tpl = op.get("nodeTemplate", {})
            tpl = {k: (params[v[1:]] if isinstance(v, str) and v.startswith("$")
                       else v) for k, v in tpl.items()}
            csi_alloc = op.get("csiNodeAllocatable")  # {driver: count}
            if tpl.get("name"):
                # Named template (node-with-name.yaml): names must be unique,
                # so multi-count named ops get an index suffix.
                for i in range(count):
                    t = dict(tpl, name=tpl["name"] if count == 1 else f"{tpl['name']}-{i}")
                    created_nodes.append(cs.create_node(_make_node_from_template(i, t)).name)
            else:
                # Continue the node name sequence across ops: a second
                # unnamed createNodes in the same workload must not overwrite
                # the first op's node-<i> names.
                for i in range(count):
                    created_nodes.append(
                        cs.create_node(_make_node_from_template(node_seq + i, tpl)).name)
                node_seq += count
            if csi_alloc:
                from ..api.storage import CSINode
                for name in created_nodes[-count:]:
                    cs.create_csi_node(CSINode(
                        node_name=name,
                        driver_limits={d: int(c) for d, c in csi_alloc.items()}))
        elif opcode == "createNamespaces":
            count = _resolve_count(op, params) if ("count" in op or "countParam" in op) else 1
            prefix = op.get("prefix", "ns")
            labels = dict(op.get("labels", {}))
            for i in range(count):
                cs.create_namespace(Namespace(name=f"{prefix}-{i}", labels=labels))
        elif opcode == "createPodSets":
            # one createPods op per namespace prefix-i (affinity NS-selector
            # configs; scheduler_perf.go createPodSetsOp)
            count = _resolve_count(op, params)
            prefix = op.get("namespacePrefix", "ns")
            inner = op["createPodsOp"]
            tpl = inner.get("podTemplate") or wl.default_pod_template or {}
            per_ns = _resolve_count(inner, params)
            for i in range(count):
                _create_pods(inner, tpl, f"{prefix}-{i}", per_ns)
            _drain(sched, collector, tickers)
        elif opcode == "createPods":
            count = _resolve_count(op, params)
            tpl = op.get("podTemplate") or wl.default_pod_template or {}
            namespace = op.get("namespace", "default")
            collect = bool(op.get("collectMetrics"))
            if collect:
                # Compile the kernel shapes outside the measured window
                # (the reference's measured runs start against a warm
                # scheduler process; XLA compilation is our cold-start).
                if tpl.get("resourceClaimTemplate") or op.get(
                        "persistentVolumeTemplate"):
                    # Claim/volume pods plan with the counted-aux kernel
                    # variant (has_aux) — a template-only warm pod would
                    # compile the WRONG tier. Schedule ONE real
                    # measured-shaped pod (claim/PV included) before the
                    # window opens instead.
                    _create_pods(op, tpl, namespace, 1)
                    _drain(sched, collector, tickers)
                else:
                    warm = getattr(sched, "warm_for", None)
                    if warm is not None:
                        warm(_make_pod_from_template("warm-template", tpl,
                                                     namespace=namespace))
                collector.start()
                # Measured creates run on a concurrent client thread (the
                # reference's createPodsOp issues creates from the test
                # client while the scheduler runs); setup creates stay
                # synchronous for determinism.
                tickers.append(_ThreadedCreator(
                    lambda op=op, tpl=tpl, namespace=namespace, count=count:
                    _create_pods(op, tpl, namespace, count)))
            else:
                _create_pods(op, tpl, namespace, count)
            if not op.get("skipWaitToCompletion"):
                _drain(sched, collector, tickers)
            if collect:
                result.metrics["SchedulingThroughput"] = collector.stop()
                _record_hint_hit_rate(result, collector)
                result.detail["in_window"] = collector.in_window
        elif opcode == "deletePods":
            namespace = op.get("namespace", "default")
            targets = created_pods.get(namespace, [])
            rate = float(op.get("deletePodsPerSecond", 100))
            deleter = _RateDeleter(cs, targets, rate)
            if op.get("skipWaitToCompletion"):
                tickers.append(deleter)  # deletes overlap the measured window
            else:
                while deleter.tick():
                    time.sleep(0.001)
        elif opcode == "createPodGroups":
            count = _resolve_count(op, params)
            size = int(op.get("groupSize", 2))
            tkeys = (op["topologyKey"],) if op.get("topologyKey") else ()
            tpl = dict(op.get("podTemplate") or wl.default_pod_template or {})
            for g in range(count):
                name = f"group-{g}"
                cs.create_pod_group(PodGroup(name=name, min_count=size,
                                             topology_keys=tkeys))
                tpl_g = dict(tpl, podGroup=name)
                for i in range(size):
                    cs.create_pod(_make_pod_from_template(f"pod-{pod_seq}", tpl_g))
                    pod_seq += 1
            _drain(sched, collector, tickers)
        elif opcode == "churn":
            # Concurrent churn (scheduler_perf.go:72): the churner ticks
            # inside _drain, i.e. DURING the measured window.
            tickers.append(_Churner(
                cs,
                op.get("podTemplate") or wl.default_pod_template or {"cpu": "4"},
                number=int(op.get("number", 1)),
                interval_ms=float(op.get("intervalMilliseconds", 1000)),
                mode=op.get("mode", "recreate"),
                churn_nodes=bool(op.get("churnNodes", True)),
            ))
        elif opcode == "barrier":
            _drain(sched, collector, tickers)
        elif opcode == "sleep":
            time.sleep(float(op.get("duration", 0.1)))
        elif opcode == "startCollectingMetrics":
            # Compile the kernel shapes LATER ops will hit before the window
            # opens (group sessions / stacked placement evaluation — the
            # reference measures against a warm scheduler process; XLA
            # compilation is our cold-start analogue).
            _warm_group_shapes(sched, cs, wl, op)
            collector.start()
        elif opcode == "stopCollectingMetrics":
            result.metrics["SchedulingThroughput"] = collector.stop()
            _record_hint_hit_rate(result, collector)
            result.detail["in_window"] = collector.in_window
        elif opcode == "createResourceSlices":
            # One slice per node with N devices (dra configs' resource-slice
            # prep; devices get a model attribute for selector exercises).
            # Slices attach to the MOST RECENTLY created `count` nodes — the
            # dra configs create the DRA nodes immediately before this op.
            from ..api.dra import Device, ResourceSlice
            count = _resolve_count(op, params)
            per_node = int(op.get("devicesPerNode", 4))
            driver = op.get("driver", "gpu.example.com")
            targets = created_nodes[-count:]
            for name in targets:
                cs.create_resource_slice(ResourceSlice(
                    node_name=name, driver=driver,
                    devices=[Device(name=f"{name}-dev{j}",
                                    attributes={"model": "a100", "index": str(j)})
                             for j in range(per_node)]))
        elif opcode == "allocResourceClaims":
            # DRA pre-allocation (dra/performance-config.yaml): allocate all
            # pending claims against the current ResourceSlices.
            from ..plugins.dynamicresources import allocate_pending_claims
            allocate_pending_claims(cs)
        else:
            raise ValueError(f"unknown opcode {opcode!r}")

    result.elapsed = time.perf_counter() - t0
    result.scheduled = sched.scheduled
    result.failed = sched.failures
    for attr in _ThroughputCollector.WINDOW_COUNTERS + (
            "placement_device_evals", "shard_map_dispatches"):
        v = getattr(sched, attr, None)
        if v is not None:
            result.detail[attr] = round(v, 3) if isinstance(v, float) else v
    # Device→host fallbacks by reason: `python -m kubernetes_tpu.perf`
    # fails a run whose breaker was charged (perf/device.py).
    result.detail["device_path_fallback"] = fallbacks_by_reason(sched)
    # Per-extension-point latency (scheduler_perf.go:866-871 collects the
    # framework_extension_point_duration_seconds histogram per workload).
    hist = sched.metrics.framework_extension_point_duration
    points = {}
    for key in list(hist._totals):
        label = key[0] if key[1] == "Success" else f"{key[0]}/{key[1]}"
        points[label] = {
            "count": hist.count(*key),
            "p50_ms": round(hist.percentile(0.50, *key) * 1e3, 3),
            "p99_ms": round(hist.percentile(0.99, *key) * 1e3, 3),
        }
    if points:
        result.detail["extension_points"] = points
    # e2e scheduling latency (queue admission -> bound; fed from span ends
    # — docs/OBSERVABILITY.md): p50/p99 truth next to the throughput number.
    e2e = sched.metrics.e2e_scheduling_duration
    if e2e.count():
        result.detail["e2e_ms"] = {
            "count": e2e.count(),
            "p50": round(e2e.percentile(0.50) * 1e3, 3),
            "p99": round(e2e.percentile(0.99) * 1e3, 3),
        }
    # Preemption-storm attribution (the PreemptionStorm rows): attempts,
    # victim totals, and async victim-deletion results in the detail line.
    m = sched.metrics
    if m.preemption_attempts.value() or m.workload_preemption_attempts._values:
        result.detail["preemption"] = {
            "attempts": int(m.preemption_attempts.value()),
            "victims": int(m.preemption_victims.count()),
            "workload_attempts": {
                k[0]: int(v)
                for k, v in m.workload_preemption_attempts._values.items()},
            "workload_victims": int(m.workload_preemption_victims.count()),
        }
    # in-flight invariant (scheduler_perf.go:878-880 checkEmptyInFlightEvents)
    assert not sched.queue._in_flight, "in-flight events remain after workload"
    close = getattr(cs, "close", None)
    if close is not None:
        close()  # stop the per-workload apiserver thread (core/remote.py)
    return result
