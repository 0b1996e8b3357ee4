"""A minimal REST + watch apiserver over the FakeClientset store, and the
HTTP client/reflector that lets a scheduler run against it across a REAL
process boundary (no shared objects — JSON on the wire).

Re-expresses the scheduler-relevant slice of the reference's L2/L3 stack:

- apiserver REST surface (staging/src/k8s.io/apiserver collapsed to the
  verbs the scheduler uses): create/delete pods and nodes, the binding and
  status subresources, and a `?watch=true` chunked event stream per
  resource. A watch opens with resourceVersion=0 semantics: the server
  streams ADDED for every existing object, then a SYNC marker, then live
  events — so nothing can fall between a separate LIST and the watch
  registration.
- client-go's reflector/informer seam (tools/cache/reflector.go:470
  ListAndWatch → shared_informer.go:841 processLoop): HTTPClientset
  consumes the stream on its own thread, maintains the informer's local
  object cache, and fans events into the scheduler's registered handlers —
  which the scheduler's off-thread inbox (core/scheduler.py _threaded)
  replays on the scheduling loop. Handler registration replays the cache
  under the dispatch lock, so attach-time replay cannot race live events.

The JSON codec covers the full scheduling-relevant pod/node spec (requests,
tolerations, selectors, node+pod affinity, topology spread, gates, host
ports, PVC volumes, resource claims, nominations, deletion state); GVK /
admission stay out of scope (SURVEY §7). The etcd seam is re-expressed by
an optional durable store (`data_dir`, core/wal.py): every committed write
appends a WAL record, snapshots compact the log, and a restarted server
replays snapshot+WAL — recovering objects, rv counters, the boot epoch, and
the watch backlog, so clients resume (`RESUME`) instead of re-listing
across a ``kill -9``.
"""

from __future__ import annotations

import copy
import queue
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional
from urllib import request as urlrequest

from ..api.labels import LabelSelector, Requirement
from ..api.resource import Resource
from ..api.types import (
    Affinity,
    Container,
    ContainerPort,
    Node,
    NodeAffinity,
    NodeSelector,
    NodeSelectorTerm,
    Pod,
    PodAffinity,
    PodAffinityTerm,
    PodAntiAffinity,
    PreferredSchedulingTerm,
    Toleration,
    TopologySpreadConstraint,
    Volume,
    WeightedPodAffinityTerm,
)
from . import spans as _spans
from . import wire
from .clientset import FakeClientset
from .flowcontrol import FlowController
from .metrics import Histogram
from .watchcache import (
    ShardFilter,
    WatchCache,
    encode_stream_item,
    mint_continue,
    parse_continue,
    pod_from_slim,
    shard_of_wire,
    slim_object,
    wire_key,
    wire_plain,
)


def _lease_clock() -> float:
    """Lease clock: one process-local monotonic source. Expiry is always
    computed server-side against this clock, so shard clients never compare
    wall clocks across processes."""
    return time.monotonic()


# ---------------------------------------------------------------------------
# JSON codec — full scheduling-relevant spec
# ---------------------------------------------------------------------------


def _req_to_wire(r: Requirement) -> dict:
    return {"key": r.key, "op": r.operator, "values": list(r.values)}


def _req_from_wire(d: dict) -> Requirement:
    return Requirement(d["key"], d["op"], tuple(d.get("values", ())))


def _sel_to_wire(s: Optional[LabelSelector]) -> Optional[dict]:
    if s is None:
        return None
    return {"matchLabels": dict(s.match_labels),
            "matchExpressions": [_req_to_wire(r) for r in s.match_expressions]}


def _sel_from_wire(d: Optional[dict]) -> Optional[LabelSelector]:
    if d is None:
        return None
    return LabelSelector.of(
        d.get("matchLabels", {}),
        [_req_from_wire(r) for r in d.get("matchExpressions", ())])


def _nsel_to_wire(ns: Optional[NodeSelector]) -> Optional[list]:
    if ns is None:
        return None
    return [{"matchExpressions": [_req_to_wire(r) for r in t.match_expressions],
             "matchFields": [_req_to_wire(r) for r in t.match_fields]}
            for t in ns.terms]


def _nsel_from_wire(terms: Optional[list]) -> Optional[NodeSelector]:
    if terms is None:
        return None
    return NodeSelector(terms=tuple(
        NodeSelectorTerm(
            match_expressions=tuple(_req_from_wire(r)
                                    for r in t.get("matchExpressions", ())),
            match_fields=tuple(_req_from_wire(r)
                               for r in t.get("matchFields", ())))
        for t in terms))


def _pterm_to_wire(t: PodAffinityTerm) -> dict:
    return {"labelSelector": _sel_to_wire(t.label_selector),
            "namespaces": list(t.namespaces),
            "topologyKey": t.topology_key,
            "namespaceSelector": _sel_to_wire(t.namespace_selector)}


def _pterm_from_wire(d: dict) -> PodAffinityTerm:
    return PodAffinityTerm(
        label_selector=_sel_from_wire(d.get("labelSelector")),
        namespaces=tuple(d.get("namespaces", ())),
        topology_key=d.get("topologyKey", ""),
        namespace_selector=_sel_from_wire(d.get("namespaceSelector")))


def _affinity_to_wire(a: Optional[Affinity]) -> Optional[dict]:
    if a is None:
        return None
    out: dict = {}
    if a.node_affinity is not None:
        out["nodeAffinity"] = {
            "required": _nsel_to_wire(a.node_affinity.required),
            "preferred": [{"weight": p.weight,
                           "term": _nsel_to_wire(NodeSelector((p.preference,)))[0]}
                          for p in a.node_affinity.preferred],
        }
    for attr, key in (("pod_affinity", "podAffinity"),
                      ("pod_anti_affinity", "podAntiAffinity")):
        pa = getattr(a, attr)
        if pa is not None:
            out[key] = {
                "required": [_pterm_to_wire(t) for t in pa.required],
                "preferred": [{"weight": w.weight,
                               "term": _pterm_to_wire(w.term)}
                              for w in pa.preferred],
            }
    return out or None


def _affinity_from_wire(d: Optional[dict]) -> Optional[Affinity]:
    if not d:
        return None
    na = None
    if "nodeAffinity" in d:
        nd = d["nodeAffinity"]
        na = NodeAffinity(
            required=_nsel_from_wire(nd.get("required")),
            preferred=tuple(
                PreferredSchedulingTerm(
                    weight=p["weight"],
                    preference=_nsel_from_wire([p["term"]]).terms[0])
                for p in nd.get("preferred", ())))

    def _pa(key, cls):
        if key not in d:
            return None
        pd = d[key]
        return cls(
            required=tuple(_pterm_from_wire(t) for t in pd.get("required", ())),
            preferred=tuple(
                WeightedPodAffinityTerm(weight=w["weight"],
                                        term=_pterm_from_wire(w["term"]))
                for w in pd.get("preferred", ())))

    return Affinity(node_affinity=na,
                    pod_affinity=_pa("podAffinity", PodAffinity),
                    pod_anti_affinity=_pa("podAntiAffinity", PodAntiAffinity))


def pod_to_wire(p: Pod) -> dict:
    req = p.resource_request()
    return {
        "name": p.name, "namespace": p.namespace, "uid": p.uid,
        "nodeName": p.node_name, "schedulerName": p.scheduler_name,
        "nominatedNodeName": p.nominated_node_name,
        "labels": dict(p.labels), "annotations": dict(p.annotations),
        "priority": p.priority, "podGroup": p.pod_group,
        "deletionTs": p.deletion_ts, "finalizers": list(p.finalizers),
        "requests": {"cpu": req.milli_cpu, "memory": req.memory,
                     "ephemeral": req.ephemeral_storage,
                     "scalar": dict(req.scalar_resources)},
        "hostPorts": [{"port": hp.host_port, "protocol": hp.protocol,
                       "hostIP": hp.host_ip}
                      for hp in p.host_ports()],
        "tolerations": [
            {"key": t.key, "operator": t.operator, "value": t.value,
             "effect": t.effect} for t in p.tolerations],
        "nodeSelector": dict(p.node_selector),
        "affinity": _affinity_to_wire(p.affinity),
        "topologySpread": [
            {"maxSkew": c.max_skew, "topologyKey": c.topology_key,
             "whenUnsatisfiable": c.when_unsatisfiable,
             "labelSelector": _sel_to_wire(c.label_selector),
             "minDomains": c.min_domains,
             "nodeAffinityPolicy": c.node_affinity_policy,
             "nodeTaintsPolicy": c.node_taints_policy}
            for c in p.topology_spread_constraints],
        "schedulingGates": list(p.scheduling_gates),
        "volumes": [{"name": v.name, "pvc": v.pvc_name} for v in p.volumes],
        "resourceClaims": list(getattr(p, "resource_claims", ()) or ()),
    }


def pod_from_wire(d: dict) -> Pod:
    req = Resource(milli_cpu=int(d["requests"]["cpu"]),
                   memory=int(d["requests"]["memory"]),
                   ephemeral_storage=int(d["requests"].get("ephemeral", 0)),
                   scalar_resources=dict(d["requests"].get("scalar", {})))
    ports = tuple(ContainerPort(host_port=int(hp["port"]),
                                protocol=hp.get("protocol", "TCP"),
                                host_ip=hp.get("hostIP", ""))
                  for hp in d.get("hostPorts", ()))
    p = Pod(
        name=d["name"], namespace=d.get("namespace", "default"),
        uid=d["uid"], node_name=d.get("nodeName", ""),
        scheduler_name=d.get("schedulerName", "default-scheduler"),
        labels=dict(d.get("labels", {})),
        annotations=dict(d.get("annotations", {})),
        priority=int(d.get("priority", 0)),
        containers=[Container(name="c0", requests=req, ports=ports)],
        tolerations=[Toleration(key=t["key"], operator=t["operator"],
                                value=t.get("value", ""),
                                effect=t.get("effect", ""))
                     for t in d.get("tolerations", ())],
        node_selector=dict(d.get("nodeSelector", {})),
        affinity=_affinity_from_wire(d.get("affinity")),
        topology_spread_constraints=[
            TopologySpreadConstraint(
                max_skew=c["maxSkew"], topology_key=c["topologyKey"],
                when_unsatisfiable=c["whenUnsatisfiable"],
                label_selector=_sel_from_wire(c.get("labelSelector")),
                min_domains=c.get("minDomains"),
                node_affinity_policy=c.get("nodeAffinityPolicy", "Honor"),
                node_taints_policy=c.get("nodeTaintsPolicy", "Ignore"))
            for c in d.get("topologySpread", ())],
        scheduling_gates=list(d.get("schedulingGates", ())),
        volumes=[Volume(name=v["name"], pvc_name=v.get("pvc"))
                 for v in d.get("volumes", ())],
    )
    p.nominated_node_name = d.get("nominatedNodeName", "")
    p.deletion_ts = d.get("deletionTs")
    p.finalizers = list(d.get("finalizers", ()))
    p.pod_group = d.get("podGroup", "")
    claims = d.get("resourceClaims", ())
    if claims:
        p.resource_claims = list(claims)
    return p


def node_to_wire(n: Node) -> dict:
    return {
        "name": n.name, "uid": n.uid, "labels": dict(n.labels),
        "unschedulable": n.unschedulable,
        "allocatable": {"cpu": n.allocatable.milli_cpu,
                        "memory": n.allocatable.memory,
                        "ephemeral": n.allocatable.ephemeral_storage,
                        "pods": n.allocatable.allowed_pod_number,
                        "scalar": dict(n.allocatable.scalar_resources)},
        "taints": [{"key": t.key, "value": t.value, "effect": t.effect}
                   for t in n.taints],
        "declaredFeatures": dict(n.declared_features),
    }


def node_from_wire(d: dict) -> Node:
    from ..api.types import Taint
    alloc = Resource(milli_cpu=int(d["allocatable"]["cpu"]),
                     memory=int(d["allocatable"]["memory"]),
                     ephemeral_storage=int(d["allocatable"].get("ephemeral", 0)),
                     allowed_pod_number=int(d["allocatable"]["pods"]),
                     scalar_resources=dict(d["allocatable"].get("scalar", {})))
    n = Node(
        name=d["name"], uid=d["uid"], labels=dict(d.get("labels", {})),
        unschedulable=bool(d.get("unschedulable", False)),
        capacity=alloc.clone(), allocatable=alloc,
        taints=[Taint(key=t["key"], value=t.get("value", ""),
                      effect=t.get("effect", "NoSchedule"))
                for t in d.get("taints", ())],
    )
    n.declared_features = dict(d.get("declaredFeatures", {}))
    return n


def pod_group_to_wire(g) -> dict:
    """PodGroup / CompositePodGroup wire. One kind ("podgroups") carries
    both object classes — a `composite` flag picks the decode — because
    they share a handler channel everywhere else (the FakeClientset fans
    both through on_pod_group_event, handlers type-switch)."""
    from ..api.types import CompositePodGroup
    d = {"name": g.name, "namespace": g.namespace, "uid": g.uid,
         "priority": int(g.priority),
         "parentName": g.parent_name,
         "composite": isinstance(g, CompositePodGroup)}
    if not d["composite"]:
        d["minCount"] = int(g.min_count)
        d["labels"] = dict(g.labels)
        d["topologyKeys"] = list(g.topology_keys)
    return d


def pod_group_from_wire(d: dict):
    from ..api.types import CompositePodGroup, PodGroup
    if d.get("composite"):
        return CompositePodGroup(
            name=d["name"], namespace=d.get("namespace") or "default",
            uid=d.get("uid", ""), parent_name=d.get("parentName", ""),
            priority=int(d.get("priority", 0)))
    return PodGroup(
        name=d["name"], namespace=d.get("namespace") or "default",
        uid=d.get("uid", ""), min_count=int(d.get("minCount", 0)),
        priority=int(d.get("priority", 0)),
        labels=dict(d.get("labels", {})),
        topology_keys=tuple(d.get("topologyKeys", ())),
        parent_name=d.get("parentName", ""))


# Node-lifecycle plane (kubernetes_tpu/controllers/): the taint the
# controller PUTs on a silent node, and the annotation an evicted-then-
# recreated pod carries (stamped server-side in the eviction subresource,
# under the write lock) so the scheduler can count eviction requeues.
UNREACHABLE_TAINT = "node.kubernetes.io/unreachable"
EVICTED_ANNOTATION = "node-lifecycle.kubernetes.io/evicted"

# Workload-plane kinds (controllers/workload.py): server-owned wire-dict
# maps keyed "ns/name" — no store-dict twin, the HTTP verb is the only
# writer and the broadcast (WAL -> watch cache -> fanout) IS the commit.
# They ride every durability/replication surface the store kinds do: WAL
# records, apply_frame, snapshots, watch/list/paged-list.
WORKLOAD_KINDS = ("replicasets", "deployments", "pdbs")
# Most a watch stream gathers into one socket write before it flushes (the
# attach replay's page buffer uses the same size).
STREAM_WRITE_BYTES = 65536


# ---------------------------------------------------------------------------
# The apiserver
# ---------------------------------------------------------------------------


class _WatchStream:
    """One attached watch stream: its event queue plus the optional
    per-stream shard filter (``?watch=true&shard=i/n``). The filter runs
    on the fanout path (broadcast lock); the queue decouples the stream's
    socket from the write plane exactly as before."""

    __slots__ = ("q", "filter", "replay_rv", "replay_epoch", "replay_slim")

    def __init__(self, flt: Optional[ShardFilter] = None):
        self.q: "queue.Queue" = queue.Queue()
        self.filter = flt
        # Lazy-cursor attach replay (docs/SCALE.md): a non-resumable attach
        # no longer materializes the full ADDED replay into this queue —
        # the stream's consumer thread pages the watch-cache snapshot
        # itself (list_page) up to `replay_rv`, then emits SYNC and goes
        # live off the queue. None = resumed (or TOO_OLD'd) attach.
        # `replay_slim` freezes the slim decision AT ATTACH, in lockstep
        # with the filter prime that records the slimmed set: if
        # selector_refs drops to 0 only mid-replay, the replay must keep
        # serving fulls — slimming then would leave pods the later
        # selector-transition upgrade burst can't find in `_slimmed`.
        self.replay_rv: Optional[int] = None
        self.replay_epoch: Optional[str] = None
        self.replay_slim: bool = False


class _ShipStream:
    """One attached replication follower: its frame queue plus the ack
    bookkeeping `_await_shipped` reads. `sent_seq` is the highest frame seq
    whose bytes sendall() handed to the kernel — once there, a leader
    SIGKILL cannot lose them (the kernel flushes the buffer before FIN).
    `acked` drops a stream out of the ack quorum when it lags (a stalled
    follower must not convoy every acked write behind its backpressure).
    The queue is BOUNDED (the same window as the ship backlog): a
    connected-but-stalled follower must not make the leader accumulate
    the entire subsequent write history in memory — on overflow the
    stream is marked `dead`, detached, and the follower re-attaches
    (usually via 410 -> snapshot resync), mirroring the watch-backlog
    contract."""

    __slots__ = ("q", "sent_seq", "acked", "dead")

    def __init__(self, since: int, maxsize: int):
        self.q: "queue.Queue" = queue.Queue(maxsize=maxsize)
        self.sent_seq = since
        self.acked = True
        self.dead = False


class APIServer:
    """REST + watch over an owned FakeClientset store.

    Watch streams support resourceVersion resume (the reference's
    watch-cache window): every event is stamped with a per-kind monotonic
    `rv` and retained in a bounded backlog. A client reconnecting with
    `?watch=true&resourceVersion=N` gets a RESUME marker plus a replay of
    every event it missed — no full re-list — when the window still covers
    N; otherwise (compaction, the 410 Gone analogue) it gets the usual full
    ADDED replay + SYNC and performs reflector Replace semantics.

    With ``data_dir`` set, the server is durable (core/wal.py): writes are
    WAL-logged before fanout, periodically compacted into a snapshot, and a
    restart recovers state + rv counters + epoch + backlog — the etcd3
    store seam (etcd3/store.go:284) collapsed to one process."""

    # Sentinel returned by upsert_lease when this replica is not the
    # leader (distinct from None = CAS loss / LeaseHeld): the HTTP layer
    # maps it to 421 NotLeader, and the check lives UNDER the write lock
    # so a racing demote() cannot let a lease write slip through.
    NOT_LEADER = object()

    def __init__(self, store: Optional[FakeClientset] = None,
                 backlog: int = 8192, data_dir: Optional[str] = None,
                 fsync: bool = False, snapshot_every: int = 2048):
        self.store = store or FakeClientset()
        self._watchers: Dict[str, List[_WatchStream]] = {
            "pods": [], "nodes": [], "podgroups": [],
            **{k: [] for k in WORKLOAD_KINDS}}
        self._lock = threading.Lock()
        # Shard-plane coordination (shard/leases.py): named lease records,
        # renewed through PUT /api/v1/leases/<name> with holder-CAS semantics
        # and SERVER-side clocks (expiry is computed here, so shard processes
        # never compare wall clocks). Ride the WAL like STATUS records.
        self.leases: Dict[str, dict] = {}
        # Omega-style optimistic commit validation: per-node committed usage,
        # maintained incrementally so the binding subresource can reject an
        # overcommitting bind in O(1) (409 OutOfCapacity → the losing
        # scheduler requeues through its backoffQ and re-plans against the
        # watch-fed truth).
        self._usage: Dict[str, dict] = {}
        # Serializes MUTATING verbs end-to-end (check + store write + WAL):
        # the store itself is unlocked dicts, and ThreadingHTTPServer runs
        # one thread per request — without this, two concurrent binding
        # POSTs could both pass the already-bound check (double bind), two
        # same-uid creates could both pass the 409 check, and a compaction
        # could snapshot a store another thread is mid-mutation. One writer
        # at a time is also the etcd model the reference stands on. Watch
        # streams and GETs stay unserialized.
        self._write_lock = threading.Lock()
        from collections import deque
        import uuid
        self._seq: Dict[str, int] = {"pods": 0, "nodes": 0, "podgroups": 0,
                                     **{k: 0 for k in WORKLOAD_KINDS}}
        # Watch-cache read plane (core/watchcache.py): per-kind rv-indexed
        # event ring (the RESUME window — what the old `_backlog` deques
        # held, now carrying the decoded event too so filtered streams can
        # replay) + a wire-object snapshot serving LIST / summary / uid
        # hydration / /metrics/resources under its OWN lock — reads no
        # longer touch the store dicts or the write lock at all.
        self.watch_cache: Dict[str, WatchCache] = {
            "pods": WatchCache("pods", capacity=backlog),
            "nodes": WatchCache("nodes", capacity=backlog),
            "podgroups": WatchCache("podgroups", capacity=backlog),
            **{k: WatchCache(k, capacity=backlog) for k in WORKLOAD_KINDS}}
        self.watch_slim_events = 0       # events delivered as slim wire
        self.watch_filtered_events = 0   # events dropped entirely
        # Wire-plane accounting (core/wire.py): bytes served/consumed per
        # (codec, surface) — the `apiserver_wire_bytes_total{codec,surface}`
        # series that proves which plane (binary vs JSON) actually ran on
        # each hot surface. Bumped on stream/handler threads without a
        # lock: a lost increment under race is observability noise, never
        # state (same posture as node_heartbeats). PRE-SEEDED with every
        # (codec, surface) pair so the dict never grows after init — a
        # concurrent /metrics iteration must never see a structural
        # mutation (RuntimeError), only a slightly stale count.
        self.wire_bytes: Dict[tuple, int] = {
            (codec, surface): 0
            for codec in (wire.JSON, wire.BINARY)
            for surface in ("watch", "ship", "list", "snapshot", "bindings",
                            "status")}
        # Encode-CPU accounting (PR 18): µs spent building wire bytes per
        # surface, accumulated on the stream/handler threads that pay it.
        # PRE-SEEDED like wire_bytes (never grows after init); guarded by
        # its own tiny lock — a float += is a read-modify-write, and
        # unlike a lost count a lost TIME sample would skew the
        # encode-µs/event ratios the bench detail line divides out.
        self.wire_encode_us: Dict[str, float] = {
            s: 0.0 for s in ("watch", "ship", "list", "snapshot",
                             "bindings", "status")}
        self._enc_us_lock = threading.Lock()
        # Per-SERVER negotiation override: True = answer every Accept
        # offer with JSON (a pre-wire server, for interop tests/mixed
        # fleets, without pinning the whole process the way
        # TPU_SCHED_WIRE=json does).
        self.json_only = False
        # Paged LIST plane (`?limit=&continue=`, docs/SCALE.md): pages
        # served, continuation tokens that expired off the rv ring (the
        # 410 Gone analogue), full-cluster single-response LISTs served
        # (the legacy path the 50k plane must keep at zero), and object
        # pages streamed by the replication snapshot bootstrap.
        self.list_pages = 0
        self.list_continue_410 = 0
        self.list_unpaged = 0
        self.snapshot_bootstrap_pages = 0
        self.watch_replay_pages = 0  # lazy-cursor attach replay pages served
        self.node_heartbeats = 0   # kubelet/hollow heartbeat sink hits
        # Node-lifecycle health plane: per-node last-heartbeat stamp
        # (monotonic, LEADER-LOCAL — heartbeats are a sink, never WAL'd, so
        # a promoted replica starts empty and the controller re-ages the
        # fleet from first sight). Own lock: stamped on the heartbeat fast
        # path which must not touch the write or broadcast locks.
        self.node_hb: Dict[str, float] = {}
        self._hb_lock = threading.Lock()
        # Eviction idempotency ledger (pod uid -> last eviction intent id):
        # rides the WAL as "evictions" records so a controller retry —
        # across its own restart or an apiserver failover — replays as a
        # no-op instead of double-evicting. An entry lives only for the
        # evicted-pending window: it is dropped when the pod re-binds or
        # is deleted (derived from the pod's own WAL'd BOUND/DELETED
        # records, so every replica and recovery prunes identically) —
        # a pod that re-binds to a once-failed node can be evicted again
        # under the same deterministic intent, and the ledger never grows
        # with pods that no longer need replay protection. Mutated only
        # under the write lock (eviction subresource / bind / delete /
        # frame apply / recovery).
        self.evictions: Dict[str, str] = {}
        self.pod_evictions = 0           # evictions committed
        self.pod_evictions_replayed = 0  # idempotent replays answered
        # Workload plane (WORKLOAD_KINDS): server-owned wire-dict maps
        # keyed "ns/name". The HTTP verbs are the only writers (under the
        # write lock) and the broadcast is the commit — there is no
        # FakeClientset twin for these kinds.
        self.workloads: Dict[str, Dict[str, dict]] = {
            k: {} for k in WORKLOAD_KINDS}
        # PodDisruptionBudget precondition on voluntary disruptions
        # (eviction subresource + ?voluntary=true deletes): denials
        # answered 429 so the caller backs off and retries after the
        # workload heals. Involuntary paths (zone Full, node delete) are
        # never budget-checked.
        self.evictions_budget_denied = 0
        # Overload protection (core/flowcontrol.py, docs/RESILIENCE.md
        # § overload & fairness): every mutating request is classified into
        # a flow and admitted through per-priority-level bounded-concurrency
        # fair queues BEFORE it can touch `_write_lock`; a full queue sheds
        # with 429 + Retry-After. Replication/lease control traffic rides
        # the exempt lane — a tenant flood can never starve failover.
        self.flowcontrol = FlowController()
        # Recent shipped frames by global seq: the replication window a
        # follower can resume from without a snapshot bootstrap.
        self._repl_backlog = deque(maxlen=backlog)
        # Boot epoch: rv counters restart at 0 with a fresh server, so a
        # client's rv from a PREVIOUS server instance must never resume
        # against this one's unrelated event history — resume requires the
        # epoch to match, otherwise the full re-list (Replace) runs. With a
        # durable store (data_dir) the counters RESUME instead of restarting,
        # so recovery re-announces the PERSISTED epoch and clients ride the
        # RESUME path straight across a process death.
        self.epoch = uuid.uuid4().hex[:12]
        self.resumed_watches = 0   # incremental reconnects served
        self.relisted_watches = 0  # full-list attaches served
        self.bind_conflicts = 0    # rebind-to-a-different-node rejections
        self.capacity_conflicts = 0  # overcommitting binds rejected (Omega)
        self.lease_conflicts = 0     # held-lease PUTs rejected (CAS losers)
        self.lease_transitions = 0   # holder changes (acquire + failover)
        self.compaction_failures = 0
        # Replication plane (kubernetes_tpu/replication/, docs/RESILIENCE.md):
        # every WAL record is a shippable frame stamped with a global
        # monotonic `seq` and the fencing `epoch`. A follower tails
        # GET /replication/wal, replays frames into its own store+WAL, and
        # serves the read plane; mutating verbs answer 421 NotLeader with a
        # redirect to `leader_url`. `promote()` flips follower->leader.
        self.role = "leader"
        self.leader_url = ""      # where NotLeader redirects point
        self.advertise_url = ""   # this replica's own base URL (set by serve)
        self.replica_rank = 0     # election order; 0 = the seed leader
        self.repl_peers: Dict[int, str] = {}  # rank -> follower base URL
        self.repl_epoch = 1
        self._repl_seq = 0
        self._ship_streams: List["_ShipStream"] = []
        self._ship_cond = threading.Condition()
        self.ship_wait_timeouts = 0   # acked writes that outran a follower
        self.ship_streams_dropped = 0  # stalled followers force-detached
        self.repl_frames_applied = 0  # follower: frames replayed locally
        self.repl_frames_rejected = 0  # stale-epoch frames fenced off
        self.repl_lag = 0              # follower: leader head seq - applied
        self.repl_resyncs = 0          # snapshot bootstraps performed
        self.failovers: Dict[str, int] = {}  # promotion reason -> count
        # Durability (core/wal.py): WAL + snapshot compaction + recovery.
        self.persistence = None
        self.recovered_objects = 0
        if data_dir is not None:
            from .wal import DurableStore
            self.persistence = DurableStore(
                data_dir, fsync=fsync, snapshot_every=snapshot_every)
            self._recover()
        self.store.on_pod_event(self._pod_event)
        self.store.on_node_event(self._node_event)
        # Muted registration: on_pod_group_event replays every existing
        # group at subscribe time (informer list semantics) — recovered
        # groups were already reinstalled into the watch cache and must
        # not re-broadcast as fresh WAL'd events.
        self._pg_mute = True
        self.store.on_pod_group_event(self._pod_group_event)
        self._pg_mute = False
        self._httpd: Optional[ThreadingHTTPServer] = None
        # Accepted connections (REST keep-alive + watch streams), so
        # shutdown() can tear them down: pooled clients (KeepAliveClient)
        # park idle connections whose handler threads would otherwise keep
        # this DEAD server's store reachable — and keep the process's port
        # reference alive across a restart-in-place. set add/discard are
        # GIL-atomic; handler setup/finish are the only writers.
        self._conns: set = set()
        # Trace context of the bind currently committing (core/spans.py):
        # set around _bind_one under the write lock, read by the BOUND
        # broadcast that fires synchronously inside store.bind on the same
        # thread — so the slim BOUND event and the WAL record carry the
        # binder's trace id out to every watcher.
        self._bind_ctx = None
        self._binding = False  # a bind is committing: its stages are timed
        self.tracer = _spans.default_tracer()
        # The three stages a bind passes through here, for EVERY bind (the
        # spans are for sampled pods): apiserver_stage_duration_seconds
        # {stage} on /metrics. Observed under the write lock / event lock.
        self.stage_duration = Histogram(
            "apiserver_stage_duration_seconds",
            "Latency of the apiserver's stages of a bind: api.bind "
            "(binding subresource commit), wal.append (durable append of "
            "the BOUND event), bound.fanout (fanout to the watch streams).",
            ("stage",))
        # Collector pauses: the binary's main installs a spans.GcClock.
        self.gc_clock = None

    # -- durability (WAL + snapshot; core/wal.py) ---------------------------

    def _recover(self) -> None:
        """Replay snapshot+WAL into the owned store and resume the watch
        plane where the dead process left off: per-kind rv counters, the
        persisted epoch, and an event backlog rebuilt from the WAL tail so
        reflectors reconnecting with their last rv get RESUME, not Replace."""
        import itertools

        from .wal import WALQuarantineError

        rings: Dict[str, list] = {"pods": [], "nodes": [], "podgroups": [],
                                  **{k: [] for k in WORKLOAD_KINDS}}
        # Recovery-time wire state: key -> the object's CURRENT wire dict,
        # seeded from the snapshot and advanced record by record — the
        # base a WAL'd DELTA record materializes against. Tracking the
        # exact wire dicts (not store round-trips) keeps a materialized
        # object byte-identical to the one the leader broadcast.
        wire_state: Dict[str, Dict[str, dict]] = {
            k: {} for k in ("pods", "nodes", "podgroups") + WORKLOAD_KINDS}
        snap, records = self.persistence.load()
        if self.persistence.epoch is not None:
            self.epoch = self.persistence.epoch
        else:
            self.persistence.init_epoch(self.epoch)
        # Replication fencing epoch: recover the persisted generation (a
        # promoted-then-restarted replica must come back in the generation
        # it won, or it would fence off its own shipped frames).
        self.repl_epoch = max(self.repl_epoch, self.persistence.repl_epoch)
        # Recover the persisted ROLE too: a deposed leader that restarts
        # must come back fenced (follower, redirecting at the winner) —
        # restarting read-write would fork history at the winner's epoch.
        if self.persistence.role == "follower":
            self.role = "follower"
            self.leader_url = self.persistence.leader_url or self.leader_url
        if snap is not None:
            self._seq.update(snap.get("seq", {}))
            repl = snap.get("repl") or {}
            self._repl_seq = max(self._repl_seq, int(repl.get("seq", 0)))
            # Ledger before pods: a bound pod's upsert prunes its entry,
            # so the "entry => pod unbound" invariant self-heals even
            # against a snapshot written before pruning existed.
            for w in snap.get("evictions", ()):
                if w.get("uid"):
                    self.evictions[w["uid"]] = w.get("intent", "")
            for w in snap.get("pods", ()):
                self._apply_recovered("pods", "ADDED", w)
                wire_state["pods"][wire_key("pods", w)] = w
            for w in snap.get("nodes", ()):
                self._apply_recovered("nodes", "ADDED", w)
                wire_state["nodes"][wire_key("nodes", w)] = w
            for w in snap.get("podgroups", ()):
                self._apply_recovered("podgroups", "ADDED", w)
                wire_state["podgroups"][wire_key("podgroups", w)] = w
            for k in WORKLOAD_KINDS:
                for w in snap.get(k, ()):
                    self._apply_recovered(k, "ADDED", w)
                    wire_state[k][wire_key(k, w)] = w
            for w in snap.get("leases", ()):
                self._install_lease(w)
        for rec in records:
            kind = rec.get("kind")
            if rec.get("type") == "DELTA" and kind in wire_state:
                # Materialize the WAL'd DELTA against the tracked base —
                # a missing/mismatched base in a CRC-verified log is the
                # same failure class as a CRC miss: damage in the middle
                # of acked history, so quarantine, never guess.
                base = wire_state[kind].get(rec.get("key"))
                if base is None:
                    raise WALQuarantineError(
                        self.persistence._wal_path, -1,
                        wire.DeltaBaseMismatch(
                            f"WAL DELTA for {kind}/{rec.get('key')} "
                            f"has no recovered base"))
                full = wire.apply_patch(base, rec.get("patch") or [])
                delta_rec = rec
                rec = {"kind": kind, "type": "MODIFIED", "object": full,
                       "rv": delta_rec.get("rv"),
                       "seq": delta_rec.get("seq"),
                       "epoch": delta_rec.get("epoch")}
            else:
                delta_rec = None
            seq = rec.get("seq")
            if seq is not None and seq > self._repl_seq:
                self._repl_seq = seq
                # Rebuild the replication ship window too, so followers that
                # resume against a restarted leader ride frames, not a
                # snapshot bootstrap (session streams re-ship the delta).
                self._repl_backlog.append(
                    (seq, wire.WireItem(rec, delta=delta_rec)))
            if kind == "leases":
                # Lease holders survive the restart but their clocks do not
                # (renew stamps are this process's monotonic clock): restore
                # renewed-at-recovery, so a live holder keeps its lease and a
                # dead one expires exactly one lease period after recovery.
                self._install_lease(rec.get("object") or {})
                continue
            if kind == "evictions":
                # Eviction intent ledger: replayed so a controller retry
                # after OUR restart still answers idempotently.
                obj = rec.get("object") or {}
                if obj.get("uid"):
                    self.evictions[obj["uid"]] = obj.get("intent", "")
                continue
            if kind not in ("pods", "nodes", "podgroups") + WORKLOAD_KINDS:
                continue
            self._apply_recovered(kind, rec.get("type", ""), rec.get("object"))
            self._track_wire_state(wire_state[kind], kind,
                                   rec.get("type", ""), rec.get("object"))
            rv = rec.get("rv")
            if rv is not None and rv > self._seq[kind]:
                self._seq[kind] = rv
            # Rebuild the watch-cache ring exactly as _broadcast framed it
            # (the deque's maxlen keeps only the freshest `backlog` events).
            if rv is not None:
                event = {k: v for k, v in rec.items()
                         if k not in ("kind", "seq", "epoch")}
                delta_ev = (None if delta_rec is None else
                            {k: v for k, v in delta_rec.items()
                             if k not in ("kind", "seq", "epoch")})
                rings[kind].append(
                    (rv, event, wire.WireItem(event, delta=delta_ev)))
        # Object resource_versions were not persisted; fast-forward the
        # store's counter past everything ever minted so recovered and new
        # objects never share a version.
        self.store._rv_counter = itertools.count(
            self._seq["pods"] + self._seq["nodes"] + 1)
        # Seed the read plane from the recovered store (the ring keeps only
        # the freshest `backlog` events, trimmed by the deque maxlen).
        # Recovery is single-threaded, but cache mutation uniformly holds
        # the broadcast lock (the analyzer's rule has no special cases).
        with self._lock:
            cap = self.watch_cache["pods"]._ring.maxlen or 8192
            self.watch_cache["pods"].reinstall(
                [pod_to_wire(p) for p in self.store.pods.values()],
                self._seq["pods"], ring=rings["pods"][-cap:])
            self.watch_cache["nodes"].reinstall(
                [node_to_wire(n) for n in self.store.nodes.values()],
                self._seq["nodes"], ring=rings["nodes"][-cap:])
            self.watch_cache["podgroups"].reinstall(
                [pod_group_to_wire(g) for g in
                 list(self.store.pod_groups.values())
                 + list(self.store.composite_pod_groups.values())],
                self._seq["podgroups"], ring=rings["podgroups"][-cap:])
            for k in WORKLOAD_KINDS:
                self.watch_cache[k].reinstall(
                    list(self.workloads[k].values()),
                    self._seq[k], ring=rings[k][-cap:])
        self.recovered_objects = len(self.store.pods) + len(self.store.nodes)
        # Recovered nodes heartbeat-age from NOW: clocks never cross a
        # process boundary (same contract as lease renew stamps) — a live
        # node re-stamps within one period, a dead one ages out exactly one
        # grace period after recovery.
        now = time.monotonic()
        with self._hb_lock:
            for name in self.store.nodes:
                self.node_hb[name] = now
        # Rebuild the Omega commit-validation usage table from the recovered
        # bound pods — incremental maintenance resumes from here.
        self._usage.clear()
        for pod in self.store.pods.values():
            if pod.node_name:
                self._usage_apply(pod.node_name, pod, +1)

    @staticmethod
    def _track_wire_state(state: Dict[str, dict], kind: str, typ: str,
                          obj: Optional[dict]) -> None:
        """Advance the recovery-time wire-dict map by one WAL record — the
        exact base the NEXT DELTA record in the log materializes against
        (mirrors WatchCache._apply_object, including BOUND's
        copy-on-write nodeName patch)."""
        if type(obj) is not dict:
            return
        if typ == "BOUND":
            cur = state.get(obj.get("uid", ""))
            if cur is not None:
                state[obj["uid"]] = dict(cur,
                                         nodeName=obj.get("nodeName", ""))
            return
        try:
            key = wire_key(kind, obj)
        except KeyError:
            return
        if typ == "DELETED":
            state.pop(key, None)
        else:
            state[key] = obj

    def _apply_recovered(self, kind: str, typ: str, wire: Optional[dict]) -> None:
        """Apply one recovered object directly to the store dicts — no
        handler fanout (there are no watchers yet) and idempotent upserts
        (a compaction snapshot may slightly lead the WAL it truncated)."""
        if wire is None:
            return
        if kind in WORKLOAD_KINDS:
            # Workload kinds have no store twin: the server-owned wire-dict
            # map IS the state. Same idempotent-upsert posture as the rest.
            key = f'{wire.get("namespace") or "default"}/{wire.get("name")}'
            if typ == "DELETED":
                self.workloads[kind].pop(key, None)
            else:
                self.workloads[kind][key] = wire
            return
        if kind == "pods":
            if typ == "BOUND":
                # Slim bind record: patch the already-recovered pod in place
                # (its ADDED/snapshot record precedes it in the log; a pod
                # deleted later is corrected by the following DELETED).
                pod = self.store.pods.get(wire.get("uid", ""))
                if pod is not None:
                    pod.node_name = wire.get("nodeName", "")
                    if pod.node_name:
                        self.store.bindings[pod.uid] = pod.node_name
                        # Re-bind resolves the evicted-pending window: the
                        # ledger prunes here exactly as the leader's live
                        # bind path did.
                        self.evictions.pop(pod.uid, None)
                return
            pod = pod_from_wire(wire)
            if typ == "DELETED":
                self.store.pods.pop(pod.uid, None)
                self.store.bindings.pop(pod.uid, None)
                self.evictions.pop(pod.uid, None)
            else:
                self.store.pods[pod.uid] = pod
                if pod.node_name:
                    self.store.bindings[pod.uid] = pod.node_name
                    self.evictions.pop(pod.uid, None)
                else:
                    self.store.bindings.pop(pod.uid, None)
        elif kind == "podgroups":
            g = pod_group_from_wire(wire)
            target = (self.store.composite_pod_groups
                      if wire.get("composite") else self.store.pod_groups)
            key = f"{g.namespace}/{g.name}"
            if typ == "DELETED":
                target.pop(key, None)
            else:
                target[key] = g
        else:
            node = node_from_wire(wire)
            if typ == "DELETED":
                self.store.nodes.pop(node.name, None)
            else:
                self.store.nodes[node.name] = node

    def _install_lease(self, w: dict) -> None:
        """Install one recovered/replicated lease record with its renew
        stamp restarted on THIS process's clock (clocks never cross a
        process boundary: a live holder keeps its lease for one more
        period, a dead one expires exactly one period from now)."""
        if not w.get("name"):
            return
        self.leases[w["name"]] = {
            "holder": w.get("holder", ""),
            "duration": float(w.get("duration", 15.0)),
            "renew": _lease_clock(),
            "transitions": int(w.get("transitions", 0))}

    def _wal_status(self, pod) -> None:
        """Persist a non-evented status patch (nominatedNodeName): an
        rv-less `STATUS` record — recovery upserts the object but the watch
        backlog never sees it (parity with its non-evented live fanout).
        It still rides the replication stream (followers must recover the
        nomination too)."""
        with self._lock:
            wire = pod_to_wire(pod)
            self._repl_append(
                {"kind": "pods", "type": "STATUS", "object": wire})
            # Keep the read plane's object snapshot current (LIST must show
            # nominations) without a ring entry — parity with the
            # non-evented live fanout.
            self.watch_cache["pods"].note_event(None, "STATUS", wire)

    def _repl_append(self, rec: dict, stamped: bool = False,
                     delta: Optional[dict] = None) -> int:
        """Commit one WAL frame — the ONE persist→backlog→ship sequence
        both write paths share: the leader stamps a fresh seq + fencing
        epoch; a follower replaying a SHIPPED frame (`stamped=True`,
        apply_frame) keeps the leader's stamps and adopts its seq. Caller
        holds the broadcast lock (`_lock`) — seq order IS commit order.

        ``delta`` is the record's DELTA twin (minted in the watch cache
        before the event installed): the WAL stores IT (recovery
        materializes against the recovered base) and session ship
        streams forward it; plain binary and JSON followers still get
        the full record off the same WireItem."""
        if stamped:
            seq = int(rec["seq"])
            self._repl_seq = seq
        else:
            self._repl_seq += 1
            seq = self._repl_seq
            rec = dict(rec, seq=seq, epoch=self.repl_epoch)
            if delta is not None:
                delta = dict(delta, seq=seq, epoch=rec["epoch"])
        # ONE WireItem per frame: the WAL append and every attached ship
        # stream share its per-codec encodings (a binary WAL + N binary
        # followers = one binary encode, total).
        item = wire.WireItem(rec, delta=delta)
        if self.persistence is not None:
            self.persistence.append(item)
        self._repl_backlog.append((seq, item))
        self._ship_fanout(seq, item)
        return seq

    def _ship_fanout(self, seq: int, item) -> None:
        """Feed one frame (a shared WireItem) to every attached ship
        stream. Caller holds the broadcast lock. A stream whose bounded
        queue overflows (stalled follower: no socket error, it just
        stopped reading) is marked dead and detached — it re-attaches
        from its applied seq, or resyncs."""
        dead = []
        for st in self._ship_streams:
            try:
                st.q.put_nowait((seq, item))
            except queue.Full:
                st.dead = True
                self.ship_streams_dropped += 1
                dead.append(st)
        for st in dead:
            self._ship_streams.remove(st)

    def _count_wire(self, codec: str, surface: str, n: int) -> None:
        """Attribute `n` served/consumed wire bytes to (codec, surface)."""
        key = (codec, surface)
        self.wire_bytes[key] = self.wire_bytes.get(key, 0) + n

    def _count_encode_us(self, surface: str, seconds: float) -> None:
        """Attribute encode wall time to a wire surface (stream/handler
        threads; never under the broadcast lock)."""
        with self._enc_us_lock:
            self.wire_encode_us[surface] += seconds * 1e6

    def _snapshot_state(self) -> dict:
        """Full-state compaction snapshot. The calling thread holds BOTH the
        write lock (its own verb — no other store mutation can be in
        flight) and the broadcast lock (no event can interleave); bindings
        ride on nodeName."""
        return {
            "epoch": self.epoch,
            "seq": dict(self._seq),
            "repl": {"seq": self._repl_seq, "epoch": self.repl_epoch},
            "pods": [pod_to_wire(p) for p in list(self.store.pods.values())],
            "nodes": [node_to_wire(n) for n in list(self.store.nodes.values())],
            "podgroups": [pod_group_to_wire(g) for g in
                          list(self.store.pod_groups.values())
                          + list(self.store.composite_pod_groups.values())],
            "leases": [dict(rec, name=name, renew=None)
                       for name, rec in list(self.leases.items())],
            "evictions": [{"uid": u, "intent": i}
                          for u, i in list(self.evictions.items())],
            **{k: list(self.workloads[k].values())
               for k in WORKLOAD_KINDS},
        }

    # -- Omega commit validation (per-node committed usage) -----------------

    def _usage_apply(self, node_name: str, pod, sign: int) -> None:
        """Incrementally maintain the committed-usage aggregate the binding
        subresource validates against. Caller holds the write lock (or is
        single-threaded recovery)."""
        req = pod.resource_request()
        u = self._usage.setdefault(
            node_name, {"cpu": 0, "mem": 0, "eph": 0, "pods": 0, "scalar": {}})
        u["cpu"] += sign * req.milli_cpu
        u["mem"] += sign * req.memory
        u["eph"] += sign * req.ephemeral_storage
        u["pods"] += sign
        for k, v in req.scalar_resources.items():
            u["scalar"][k] = u["scalar"].get(k, 0) + sign * v

    def _bind_overcommits(self, node_name: str, pod) -> bool:
        """Would committing `pod` onto `node_name` exceed the node's
        allocatable? The shared-state transaction check (Omega §3): every
        scheduler plans optimistically against its own watch-fed view; the
        single store is where conflicting plans meet, and the loser gets a
        409 instead of an overcommitted node. A bind to a node the store
        does not know is left to the scheduler's own validation."""
        node = self.store.nodes.get(node_name)
        if node is None:
            return False
        u = self._usage.get(
            node_name, {"cpu": 0, "mem": 0, "eph": 0, "pods": 0, "scalar": {}})
        req = pod.resource_request()
        alloc = node.allocatable
        if (u["cpu"] + req.milli_cpu > alloc.milli_cpu
                or u["mem"] + req.memory > alloc.memory
                or u["eph"] + req.ephemeral_storage > alloc.ephemeral_storage
                or u["pods"] + 1 > alloc.allowed_pod_number):
            return True
        return any(u["scalar"].get(k, 0) + v > alloc.scalar_resources.get(k, 0)
                   for k, v in req.scalar_resources.items())

    def _bind_one(self, uid: str, node: str, tctx: Optional[str] = None):
        """One bind attempt (caller holds the write lock) → (code, payload).
        Shared by the single binding subresource and the bulk endpoint.
        ``tctx`` is the binder's wire trace context (X-Trace-Context header
        / bulk-item tctx field); absent, the context derives from the pod
        uid — deterministic sampling means both sides agree anyway."""
        tr = self.tracer
        ctx = (_spans.parse_ctx(tctx) if tctx else None) \
            or tr.context_for(uid)
        t0 = time.perf_counter()
        self._binding = True
        self._bind_ctx = ctx if tr.wants(ctx) else None
        try:
            code, payload = self._bind_one_locked(uid, node)
        finally:
            self._binding = False
            self._bind_ctx = None
        seconds = time.perf_counter() - t0
        self.stage_duration.observe(seconds, "api.bind")
        tr.record("api.bind", ctx, seconds, node=node, code=code)
        return code, payload

    def _bind_one_locked(self, uid: str, node: str):
        pod = self.store.pods.get(uid)
        if pod is None:
            return 404, {"error": "pod not found"}
        if pod.node_name:
            # Already bound: a same-node POST is a retry replay of a bind
            # whose reply was lost (pre-crash write, recovered from the
            # WAL) — idempotent success, no re-fired event. A different
            # node is a genuine conflict (409, registry AlreadyExists
            # analogue): a pod must never be bound twice.
            if pod.node_name == node:
                return 200, {"bound": True}
            self.bind_conflicts += 1
            return 409, {"error": "AlreadyBound"}
        if self._bind_overcommits(node, pod):
            # Optimistic-concurrency loser (Omega transaction validation):
            # another scheduler's commits filled this node first. 409 →
            # conflict-driven requeue.
            self.capacity_conflicts += 1
            return 409, {"error": "OutOfCapacity"}
        self.store.bind(pod, node)
        self._usage_apply(node, pod, +1)
        # A successful (re-)bind closes the evicted-pending window: drop
        # the idempotency ledger entry so a LATER failure of this pod's
        # new home — including a re-bind onto a recovered node that
        # failed before — mints a fresh evictable wave instead of being
        # swallowed by a stale already=True. Replicas/recovery derive the
        # same prune from this bind's own WAL'd BOUND record.
        self.evictions.pop(uid, None)
        return 200, {"bound": True}

    # -- shard leases (PUT-CAS + server-side expiry) ------------------------

    def _lease_wire(self, name: str, rec: dict, now: float) -> dict:
        age = now - rec["renew"]
        return {"name": name, "holder": rec["holder"],
                "leaseDurationSeconds": rec["duration"],
                "ageSeconds": round(age, 3),
                "transitions": rec["transitions"],
                "expired": (not rec["holder"]) or age >= rec["duration"]}

    def list_leases(self) -> List[dict]:
        now = _lease_clock()
        with self._lock:
            return [self._lease_wire(n, r, now)
                    for n, r in sorted(self.leases.items())]

    def upsert_lease(self, name: str, holder: str,
                     duration: float) -> Optional[dict]:
        """Acquire-or-renew under CAS semantics: a held, unexpired lease
        only renews for its CURRENT holder; anyone else gets None (HTTP
        409) — the resourcelock's update-if-expired collapsed to one verb.
        The record rides the WAL so a `kill -9`'d apiserver recovers the
        holder table (with clocks restarted, see _recover)."""
        now = _lease_clock()
        with self._write_lock:
            if self.role != "leader":
                return self.NOT_LEADER
            rec = self.leases.get(name)
            if (rec is not None and rec["holder"] and rec["holder"] != holder
                    and now - rec["renew"] < rec["duration"]):
                self.lease_conflicts += 1
                return None
            if rec is None:
                rec = {"holder": "", "duration": float(duration),
                       "renew": now, "transitions": 0}
                self.leases[name] = rec
            if rec["holder"] != holder:
                rec["transitions"] += 1
                self.lease_transitions += 1
            rec["holder"] = holder
            rec["duration"] = float(duration)
            rec["renew"] = now
            with self._lock:
                self._repl_append({
                    "kind": "leases", "type": "LEASE",
                    "object": {"name": name, "holder": holder,
                               "duration": rec["duration"],
                               "transitions": rec["transitions"]}})
                if (self.persistence is not None
                        and self.persistence.should_compact()):
                    # Renewals are the steady-state WAL traffic of an
                    # idle sharded plane (N shards × 3 appends per lease
                    # period, forever); without compacting here — the
                    # broadcast path never runs on a quiet cluster —
                    # the WAL and its replay time grow without bound.
                    # Same locking posture as _broadcast: this thread
                    # holds the write lock, so the store snapshot is
                    # stable, and a failed compaction must not fail the
                    # renewal.
                    try:
                        self.persistence.write_snapshot(
                            self._snapshot_state())
                    except Exception:  # noqa: BLE001
                        self.compaction_failures += 1
            return self._lease_wire(name, rec, now)

    # -- replication (WAL shipping + leader/follower roles) -----------------
    #
    # The reference splits its control plane into a replicated log (etcd3)
    # and read-serving watch caches; this section rebuilds that split
    # natively: every committed write is a shippable WAL frame
    # (seq+epoch-stamped by _repl_append), followers tail
    # GET /replication/wal and replay frames via apply_frame, and a leader
    # kill -9 promotes a follower (promote) fenced by the monotonic
    # replication epoch. docs/RESILIENCE.md § replication.

    def replication_status(self) -> dict:
        """The discovery document election and client leader-resolution
        read: role, rank, fencing epoch, applied head, redirect target —
        plus the tail's election counters when one is attached
        (`repl_tail`, set by the follower binary): 'why is this follower
        not converging' must be answerable from the outside."""
        out = {"role": self.role, "rank": self.replica_rank,
               "replEpoch": self.repl_epoch, "seq": self._repl_seq,
               "watchEpoch": self.epoch, "leader": self.leader_url,
               "lag": self.repl_lag}
        tail = getattr(self, "repl_tail", None)
        if tail is not None:
            thread = tail._thread
            out["tail"] = {
                "elections": tail.elections, "deferrals": tail.deferrals,
                "reconnects": tail.reconnects, "bootstraps": tail.bootstraps,
                "fenced": tail.fenced_streams,
                "alive": thread is not None and thread.is_alive(),
                "lastContactAge": round(
                    time.monotonic() - tail.last_contact, 3)}
        return out

    def apply_frame(self, rec: dict,
                    stream_epoch: Optional[int] = None) -> bool:
        """Follower-side replay of one shipped WAL frame: append to the
        LOCAL WAL first, then upsert the store and fan the event out to
        this replica's own watch streams — the exact write-path ordering
        the leader uses, so an event a local watcher saw is always
        recoverable here too. Returns False for a frame from a stale
        fencing epoch (a deposed leader's append — rejected, the tail must
        disconnect).

        ``stream_epoch`` is the generation the SERVING leader claims
        (election/announcement/HB): a frame stamped with an older epoch is
        still legitimate when it is part of a newer leader's committed
        history — a lagging survivor that adopted the winner's epoch
        before catching up must not fence off the pre-promotion frames it
        still needs. Only a frame whose OWN stamp and whose stream's claim
        are both stale is a deposed leader's append."""
        seq = int(rec.get("seq", 0))
        ep = int(rec.get("epoch", 0))
        with self._write_lock:
            with self._lock:
                if max(ep, int(stream_epoch or 0)) < self.repl_epoch:
                    self.repl_frames_rejected += 1
                    return False
                if seq <= self._repl_seq:
                    return True  # reconnect overlap: already applied
                if ep > self.repl_epoch:
                    # A legitimately promoted leader's first frames carry
                    # the bumped epoch: adopt it (and persist — fencing
                    # must survive our own restart).
                    self.repl_epoch = ep
                    if self.persistence is not None:
                        self.persistence.set_repl_epoch(ep)
                delta_rec = None
                if rec.get("type") == "DELTA":
                    # Shipped field-path patch: materialize the full
                    # object against OUR watch-cache base BEFORE anything
                    # installs this frame's state. A base-rv mismatch
                    # raises DeltaBaseMismatch out of apply_frame — the
                    # tail catches it and snapshot-resyncs; a patch is
                    # never applied onto a divergent base.
                    full = self.watch_cache[rec["kind"]] \
                        .materialize_delta(rec)
                    delta_rec = rec
                    rec = {"kind": rec["kind"], "type": "MODIFIED",
                           "object": full, "rv": rec.get("rv"),
                           "seq": seq, "epoch": ep}
                # The local WAL + our own ship fanout carry the delta
                # twin (same WireItem routing the leader used), while
                # the full record serves JSON/plain-binary peers.
                self._repl_append(rec, stamped=True, delta=delta_rec)
                self.repl_frames_applied += 1
                kind = rec.get("kind")
                if kind == "leases":
                    self._install_lease(rec.get("object") or {})
                elif kind == "evictions":
                    # Replicated intent ledger: a promoted follower must
                    # answer an in-flight eviction wave's retries
                    # idempotently — losing this would double-evict.
                    obj = rec.get("object") or {}
                    if obj.get("uid"):
                        self.evictions[obj["uid"]] = obj.get("intent", "")
                elif kind in ("pods", "nodes", "podgroups") + WORKLOAD_KINDS:
                    self._apply_recovered(kind, rec.get("type", ""),
                                          rec.get("object"))
                    rv = rec.get("rv")
                    if rv is not None:
                        if rv > self._seq[kind]:
                            self._seq[kind] = rv
                        event = {k: v for k, v in rec.items()
                                 if k not in ("kind", "seq", "epoch")}
                        delta_ev = None
                        if delta_rec is not None:
                            delta_ev = {k: v for k, v in delta_rec.items()
                                        if k not in ("kind", "seq",
                                                     "epoch")}
                        # Same fanout as the leader's broadcast: this
                        # follower's watch cache + its own (possibly
                        # filtered) streams stay converged in the shared
                        # rv space — clients RESUME against any replica.
                        self._fan_event(kind, event,
                                        wire.WireItem(event,
                                                      delta=delta_ev))
                    else:
                        # rv-less STATUS: snapshot upsert, no ring entry
                        # (parity with its non-evented live fanout).
                        self.watch_cache[kind].note_event(
                            None, rec.get("type", ""), rec.get("object"))
                # Compaction runs LAST, after the frame is in the store and
                # _repl_seq has advanced: a snapshot taken between append
                # and apply would exclude the triggering frame while
                # write_snapshot resets the WAL that just absorbed it — the
                # frame would exist nowhere durable, and recovery would
                # fast-forward straight past the hole (silent divergence).
                if (self.persistence is not None
                        and self.persistence.should_compact()):
                    try:
                        self.persistence.write_snapshot(
                            self._snapshot_state())
                    except Exception:  # noqa: BLE001
                        self.compaction_failures += 1
        return True

    def install_snapshot(self, snap: dict) -> None:
        """Cold-follower bootstrap: replace local state with a leader
        snapshot (GET /replication/snapshot) and persist it as OUR
        compaction snapshot, so a restart recovers locally and re-tails
        from the snapshot's seq. Adopts the leader's WATCH epoch too —
        rv continuity across replicas is what lets clients RESUME against
        any of them."""
        with self._write_lock:
            with self._lock:
                self.store.pods.clear()
                self.store.nodes.clear()
                self.store.bindings.clear()
                self.store.pod_groups.clear()
                self.store.composite_pod_groups.clear()
                self.leases.clear()
                self.evictions.clear()
                for k in WORKLOAD_KINDS:
                    self.workloads[k].clear()
                self._seq.update(snap.get("seq", {}))
                # Ledger before pods (see _recover): bound-pod upserts
                # prune their entries, keeping "entry => pod unbound".
                for w in snap.get("evictions", ()):
                    if w.get("uid"):
                        self.evictions[w["uid"]] = w.get("intent", "")
                for w in snap.get("pods", ()):
                    self._apply_recovered("pods", "ADDED", w)
                for w in snap.get("nodes", ()):
                    self._apply_recovered("nodes", "ADDED", w)
                for w in snap.get("podgroups", ()):
                    self._apply_recovered("podgroups", "ADDED", w)
                for k in WORKLOAD_KINDS:
                    for w in snap.get(k, ()):
                        self._apply_recovered(k, "ADDED", w)
                for w in snap.get("leases", ()):
                    self._install_lease(w)
                repl = snap.get("repl") or {}
                self._repl_seq = int(repl.get("seq", 0))
                self.repl_epoch = max(self.repl_epoch,
                                      int(repl.get("epoch", 1)))
                if snap.get("epoch"):
                    self.epoch = snap["epoch"]
                self.repl_resyncs += 1
                # A RESYNC skipped frames: any ATTACHED watch stream has a
                # gap its client cannot see, and the retained backlog spans
                # it. Clear the resume window and end those streams
                # (sentinel); reconnecting clients full-re-list against the
                # installed state (reflector Replace heals their caches).
                self._repl_backlog.clear()
                self.watch_cache["pods"].reinstall(
                    list(snap.get("pods", ())), self._seq.get("pods", 0))
                self.watch_cache["nodes"].reinstall(
                    list(snap.get("nodes", ())), self._seq.get("nodes", 0))
                self.watch_cache["podgroups"].reinstall(
                    list(snap.get("podgroups", ())),
                    self._seq.get("podgroups", 0))
                for k in WORKLOAD_KINDS:
                    self.watch_cache[k].reinstall(
                        list(snap.get(k, ())), self._seq.get(k, 0))
                for kind in self._watchers:
                    for w in self._watchers[kind]:
                        w.q.put(None)
                if self.persistence is not None:
                    self.persistence.epoch = self.epoch
                    self.persistence.set_repl_epoch(self.repl_epoch)
                    try:
                        self.persistence.write_snapshot(self._snapshot_state())
                    except Exception:  # noqa: BLE001
                        self.compaction_failures += 1

    def promote(self, reason: str = "leader_lost") -> None:
        """Follower -> leader: bump the fencing epoch (persisted BEFORE the
        first write of the new generation), rebuild the Omega usage table
        from replicated truth, fast-forward the store's rv mint, flip to
        read-write, and tell every attached client (FAILOVER marker) so
        writes re-resolve and schedulers reconcile any bind the dead
        leader acked but never shipped."""
        import itertools
        with self._write_lock:
            with self._lock:
                if self.role == "leader":
                    return
                self.repl_epoch += 1
                self.role = "leader"
                self.leader_url = self.advertise_url
                if self.persistence is not None:
                    self.persistence.set_repl_epoch(self.repl_epoch)
                    self.persistence.set_role("leader", self.advertise_url)
                self.repl_lag = 0
                self.failovers[reason] = self.failovers.get(reason, 0) + 1
            self._usage.clear()
            for pod in self.store.pods.values():
                if pod.node_name:
                    self._usage_apply(pod.node_name, pod, +1)
            self.store._rv_counter = itertools.count(
                self._seq["pods"] + self._seq["nodes"] + 1)
        # Forensic moment: a 100%-sampled replication.promote span marks the
        # takeover instant, and the flight recorder dumps the ring around it.
        tr = self.tracer
        tr.record("replication.promote", tr.proc_ctx(),
                  epoch=self.repl_epoch, reason=reason, seq=self._repl_seq,
                  rank=self.replica_rank)
        _spans.request_dump("replication_promote")
        self._emit_control({"type": "FAILOVER", "epoch": self.repl_epoch,
                            "leader": self.advertise_url})

    def demote(self, leader_url: str, epoch: int) -> None:
        """Deposed-leader fencing: a peer announced a NEWER fencing epoch
        (or won an EQUAL-epoch race by rank — the /replication/leader
        handler decides that tie-break before calling). Stop accepting
        writes immediately (NotLeader from here on) and point clients at
        the winner; this replica's divergent tail, if any, resolves via
        snapshot resync when its tail re-attaches."""
        with self._write_lock:
            with self._lock:
                if int(epoch) < self.repl_epoch:
                    return  # the claimant is from an older generation
                self.role = "follower"
                self.leader_url = leader_url
                self.repl_epoch = int(epoch)
                if self.persistence is not None:
                    # Persist the DEPOSED role too: restarting read-write
                    # at the winner's epoch would fork history unfenceably.
                    self.persistence.set_repl_epoch(self.repl_epoch)
                    self.persistence.set_role("follower", leader_url)
                self.failovers["deposed"] = self.failovers.get("deposed", 0) + 1
        self._emit_control({"type": "FAILOVER", "epoch": self.repl_epoch,
                            "leader": leader_url})

    def note_leader(self, leader_url: str, epoch: int) -> bool:
        """Follower bookkeeping when its tail re-attaches: record the
        (possibly new) leader and, when leadership actually MOVED, notify
        local watch clients with a FAILOVER marker so their write routing
        re-resolves and their schedulers reconcile. Returns True when the
        leader changed."""
        with self._lock:
            changed = (leader_url != self.leader_url
                       or epoch > self.repl_epoch)
            self.leader_url = leader_url
            if epoch > self.repl_epoch:
                self.repl_epoch = int(epoch)
                if self.persistence is not None:
                    self.persistence.set_repl_epoch(self.repl_epoch)
        if changed:
            self._emit_control({"type": "FAILOVER", "epoch": self.repl_epoch,
                                "leader": leader_url})
        return changed

    def _emit_control(self, event: dict) -> None:
        """Push a control marker (FAILOVER) to every live watch stream of
        both kinds — rv-less and never WAL'd, like BOOKMARK. One shared
        WireItem: each stream's consumer encodes it in its own codec."""
        item = wire.WireItem(event)
        with self._lock:
            for kind in self._watchers:
                for w in self._watchers[kind]:
                    w.q.put(item)

    def _attach_ship(self, since: int):
        """Attach a follower's ship stream at `since` (its last applied
        seq). Under the broadcast lock: the backlog replay and live-queue
        registration cannot let a frame fall between them. Returns None
        when the window no longer covers `since` — the follower must
        snapshot-bootstrap (RESYNC)."""
        with self._lock:
            if since > self._repl_seq:
                # The follower is AHEAD of this server (it applied frames a
                # torn-tailed restart of ours discarded): histories
                # diverged — only a snapshot resync reconverges them.
                return None
            covered = (since == self._repl_seq
                       or (self._repl_backlog
                           and self._repl_backlog[0][0] <= since + 1))
            if not covered:
                return None
            st = _ShipStream(since, self._repl_backlog.maxlen or 8192)
            for seq, data in self._repl_backlog:
                if seq > since:
                    st.q.put_nowait((seq, data))
            self._ship_streams.append(st)
        return st

    def _detach_ship(self, st) -> None:
        with self._lock:
            if st in self._ship_streams:
                self._ship_streams.remove(st)
        with self._ship_cond:
            self._ship_cond.notify_all()

    def _ship_mark_sent(self, st, seq: int) -> None:
        """Ship thread: frame bytes for `seq` are in the kernel send buffer
        (sendall returned) — a leader SIGKILL can no longer lose them."""
        with self._ship_cond:
            st.sent_seq = max(st.sent_seq, seq)
            if not st.acked and st.sent_seq >= self._repl_seq:
                st.acked = True  # lagging follower caught back up
            self._ship_cond.notify_all()

    def _await_shipped(self, seq: int, timeout: float = 0.25) -> bool:
        """Reply gating for acked mutations: wait (briefly, outside every
        lock) until each in-quorum follower stream has `seq` on the wire.
        This is what turns a leader kill -9 from 'acked writes silently
        vanish' into 'acked writes survive on a follower'. A follower that
        cannot keep up inside `timeout` is dropped from the ack quorum
        (counted) instead of convoying the whole write plane — availability
        over completeness, the degraded-mode contract."""
        if not self._ship_streams:
            return True
        deadline = time.monotonic() + timeout
        with self._ship_cond:
            while True:
                laggards = [st for st in self._ship_streams
                            if st.acked and st.sent_seq < seq]
                if not laggards:
                    return True
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    for st in laggards:
                        st.acked = False
                    self.ship_wait_timeouts += 1
                    return False
                self._ship_cond.wait(remaining)

    def expose_metrics(self) -> str:
        """Control-plane counters (conflict/lease/watch planes) in the
        Prometheus text format — scraped by the shard chaos/bench harnesses
        so failover and conflict behavior is observable from outside."""
        out = []
        for name, v in (
                ("apiserver_bind_conflicts_total", self.bind_conflicts),
                ("apiserver_capacity_conflicts_total",
                 self.capacity_conflicts),
                ("apiserver_lease_conflicts_total", self.lease_conflicts),
                ("apiserver_lease_transitions_total", self.lease_transitions),
                ("apiserver_resumed_watches_total", self.resumed_watches),
                ("apiserver_relisted_watches_total", self.relisted_watches),
                ("apiserver_compaction_failures_total",
                 self.compaction_failures),
                ("apiserver_replication_frames_applied_total",
                 self.repl_frames_applied),
                ("apiserver_replication_frames_rejected_total",
                 self.repl_frames_rejected),
                ("apiserver_replication_resyncs_total", self.repl_resyncs),
                ("apiserver_replication_ship_wait_timeouts_total",
                 self.ship_wait_timeouts),
                ("apiserver_replication_ship_streams_dropped_total",
                 self.ship_streams_dropped),
                # Watch-cache read plane (core/watchcache.py): reads served
                # from the cache (list/summary/uids//metrics/resources),
                # RESUME replays from the ring, resume rvs that fell off
                # the window (410-too-old -> full re-list), and the
                # shard-filter's slimmed/suppressed event counts.
                ("apiserver_watch_cache_hits_total",
                 sum(wc.hits for wc in self.watch_cache.values())),
                ("apiserver_watch_cache_resumes_total",
                 sum(wc.resumes for wc in self.watch_cache.values())),
                ("apiserver_watch_cache_too_old_total",
                 sum(wc.too_old for wc in self.watch_cache.values())),
                # Incremental paged-LIST key index: full re-sorts actually
                # paid (lazy builds after reinstall / first page) — a
                # churning hollow fleet must hold this near-constant
                # instead of re-sorting 50k keys per page.
                ("apiserver_watch_cache_key_resorts_total",
                 sum(wc.key_resorts for wc in self.watch_cache.values())),
                ("apiserver_watch_events_slim_total", self.watch_slim_events),
                ("apiserver_watch_events_filtered_out_total",
                 self.watch_filtered_events),
                # Paged LIST plane (docs/SCALE.md): pages served, expired
                # continuations (410 -> the client restarts its list),
                # legacy full-cluster single-response LISTs (zero on a
                # paged-only plane — the 50k acceptance counter), and
                # snapshot-bootstrap object pages streamed to followers.
                ("apiserver_list_pages_total", self.list_pages),
                ("apiserver_list_continue_410_total", self.list_continue_410),
                ("apiserver_list_unpaged_total", self.list_unpaged),
                ("apiserver_watch_replay_pages_total",
                 self.watch_replay_pages),
                ("apiserver_snapshot_bootstrap_pages_total",
                 self.snapshot_bootstrap_pages),
                ("apiserver_node_heartbeats_total",
                 self.node_heartbeats),
                # Eviction subresource (node-lifecycle controller plane):
                # committed DELETE-then-recreate evictions, and idempotent
                # intent replays answered without touching the pod —
                # exactly-once across controller restart and failover.
                ("apiserver_pod_evictions_total", self.pod_evictions),
                ("apiserver_pod_evictions_replayed_total",
                 self.pod_evictions_replayed),
                # PDB precondition: voluntary disruptions denied because
                # committing them would take a workload below minAvailable.
                ("apiserver_pod_evictions_budget_denied_total",
                 self.evictions_budget_denied),
                # WAL CRC plane (core/wal.py): complete-but-corrupt middle
                # records detected at recovery (each one quarantined boot).
                ("apiserver_wal_crc_failures_total",
                 self.persistence.crc_failures
                 if self.persistence is not None else 0)):
            out.append(f"# TYPE {name} counter")
            out.append(f"{name} {v}")
        # Flow-control plane (core/flowcontrol.py): per-priority-level
        # admission counters + live seat/queue gauges — the series the
        # flood chaos scenario reads to prove the exempt lane bypassed
        # tenant queues while the flood was shed.
        fc = self.flowcontrol.snapshot()
        for metric, key in (("rejected", "rejected"),
                            ("dispatched", "dispatched"),
                            ("queued", "queued")):
            name = f"apiserver_flowcontrol_{metric}_total"
            out.append(f"# TYPE {name} counter")
            for level in sorted(fc):
                out.append('%s{priority_level="%s"} %d'
                           % (name, level, fc[level][key]))
        for name, key in (("apiserver_flowcontrol_current_seats", "seats"),
                          ("apiserver_flowcontrol_queue_depth",
                           "queue_depth")):
            out.append(f"# TYPE {name} gauge")
            for level in sorted(fc):
                out.append('%s{priority_level="%s"} %d'
                           % (name, level, fc[level][key]))
        out.append("# TYPE apiserver_failover_total counter")
        for reason, v in sorted(self.failovers.items()):
            out.append('apiserver_failover_total{reason="%s"} %d'
                       % (reason, v))
        # Wire plane: bytes per (codec, surface) — the bench's `wire`
        # summary and the binary-negotiated acceptance check read this.
        out.append("# TYPE apiserver_wire_bytes_total counter")
        for (codec, surface), v in sorted(self.wire_bytes.items()):
            out.append('apiserver_wire_bytes_total{codec="%s",surface="%s"}'
                       ' %d' % (codec, surface, v))
        # Encode CPU per surface (µs) and the delta plane's mint/apply
        # counters — the bench detail line divides micros by events to
        # attribute shard-scaling gaps to encode cost.
        out.append("# TYPE apiserver_wire_encode_micros_total counter")
        with self._enc_us_lock:
            enc_us = dict(self.wire_encode_us)
        for surface, us in sorted(enc_us.items()):
            out.append('apiserver_wire_encode_micros_total{surface="%s"}'
                       ' %d' % (surface, int(us)))
        minted = sum(wc.deltas_minted for wc in self.watch_cache.values())
        applied = sum(wc.deltas_applied for wc in self.watch_cache.values())
        out.append("# TYPE apiserver_wire_deltas_minted_total counter")
        out.append("apiserver_wire_deltas_minted_total %d" % minted)
        out.append("# TYPE apiserver_wire_deltas_applied_total counter")
        out.append("apiserver_wire_deltas_applied_total %d" % applied)
        # Gauges: current role (1 = leader) and replication lag. On the
        # leader, lag is its head minus the slowest attached ship stream;
        # on a follower, the head the tail last heard minus what it applied.
        with self._ship_cond:
            if self._ship_streams:
                lag = max(self._repl_seq - st.sent_seq
                          for st in self._ship_streams)
            else:
                lag = self.repl_lag
        out.append("# TYPE apiserver_replication_role gauge")
        out.append("apiserver_replication_role %d"
                   % (1 if self.role == "leader" else 0))
        out.append("# TYPE apiserver_replication_lag_records gauge")
        out.append("apiserver_replication_lag_records %d" % max(0, lag))
        out.extend(self.stage_duration.expose())
        if self.gc_clock is not None:
            out.extend(self.gc_clock.expose("apiserver"))
        return "\n".join(out) + "\n"

    # -- event fanout to watch streams -------------------------------------

    def _broadcast(self, kind: str, event: dict) -> None:
        with self._lock:
            self._seq[kind] += 1
            event["rv"] = self._seq[kind]
            # Span context of the committing bind (None for every other
            # event class): times the WAL append and the watcher fanout
            # into the binder's trace (stages wal.append / bound.fanout).
            ctx = self._bind_ctx
            binding = self._binding
            # Mint the event's DELTA twin FIRST — before the WAL append
            # or the fanout installs the new object, while the watch
            # cache's snapshot still holds the exact base every attached
            # receiver (and the WAL's recovered state) already has. The
            # prior wire object is read under the cache's own lock
            # (mint_delta; the delta-base-under-cache-lock rule).
            delta = self.watch_cache[kind].mint_delta(event)
            # WAL append BEFORE fanout: an event a watcher saw is always
            # recoverable. The record is the event itself plus the kind
            # (and the replication seq/epoch stamp), so recovery — and a
            # tailing follower — rebuilds both the store and the watch
            # backlog from one stream.
            _tw = time.perf_counter() if binding else 0.0
            self._repl_append(
                {"kind": kind, **event},
                delta=None if delta is None else {"kind": kind, **delta})
            if binding:
                seconds = time.perf_counter() - _tw
                self.stage_duration.observe(seconds, "wal.append")
                if ctx is not None:
                    self.tracer.record("wal.append", ctx, seconds,
                                       rv=event["rv"])
            if (self.persistence is not None
                    and self.persistence.should_compact()):
                try:
                    # Safe to read the store here: the writing thread
                    # holds _write_lock, so no other mutation is in
                    # flight. write_snapshot is atomic (tmp+replace)
                    # and only resets the WAL after the replace — a
                    # failed compaction leaves snapshot+WAL coherent,
                    # so it must never abort the broadcast (that would
                    # punch a hole in the fanout/backlog at this rv).
                    self.persistence.write_snapshot(self._snapshot_state())
                except Exception:  # noqa: BLE001
                    self.compaction_failures += 1
            item = wire.WireItem(event, delta=delta)
            _tf = time.perf_counter() if binding else 0.0
            self._fan_event(kind, event, item)
            if binding:
                seconds = time.perf_counter() - _tf
                self.stage_duration.observe(seconds, "bound.fanout")
                if ctx is not None:
                    self.tracer.record("bound.fanout", ctx, seconds,
                                       watchers=len(self._watchers[kind]),
                                       rv=event["rv"])

    def _fan_event(self, kind: str, event: dict, item) -> None:
        """The one commit→read-plane fanout both write paths share (the
        leader's _broadcast and a follower's apply_frame): install the
        event into the watch cache (ring + object snapshot), then feed
        every attached stream — full wire, or through its shard filter.
        ``item`` is the event's shared WireItem: every stream's consumer
        encodes it in its OWN codec, once per codec total. Caller holds
        the broadcast lock, AFTER the WAL append: ring order is commit
        order, and a cached/fanned event is always durable."""
        self.watch_cache[kind].note_event(
            event.get("rv"), event.get("type", ""), event.get("object"),
            data=item, event=event)
        # One per-event memo shared across the filtered streams: the slim
        # projection/item is identical for all of them, so N shards pay
        # ONE dict build under the broadcast lock, not N — and the encode
        # itself runs on the consumer threads, once per codec.
        memo: dict = {}
        for w in self._watchers[kind]:
            self._route_to(w, event, item, self.watch_cache[kind], memo)

    def _route_to(self, st: _WatchStream, event: dict, data,
                  wc: WatchCache, memo: Optional[dict] = None) -> None:
        """Deliver one event to one stream through its filter (or raw) —
        the ONE routing+counting sequence the live fanout and the
        attach-time replay both use. Caller holds the broadcast lock."""
        if st.filter is None:
            st.q.put(data)
            return
        outs, slim, dropped = st.filter.route(event, data, wc, memo)
        self.watch_slim_events += slim
        self.watch_filtered_events += dropped
        for d in outs:
            st.q.put(d)

    def _pod_event(self, kind: str, old, new) -> None:
        typ = {"add": "ADDED", "update": "MODIFIED", "delete": "DELETED"}[kind]
        if (kind == "update" and old is not None
                and new.node_name and not old.node_name):
            # Bind commit — the hottest event class on a sharded plane, and
            # the only server-side writer of nodeName (the pod's spec is
            # otherwise the one the watcher already caches from ADDED). A
            # slim BOUND event carries just {uid, nodeName}: N shards each
            # decode every peer's binds, so the full-pod wire encode +
            # pod_from_wire rebuild per bind per watcher is pure scaling tax.
            # A sampled bind adds its trace context (tctx) so every foreign
            # shard's bound.observe span joins the binder's trace — and the
            # WAL record (the event itself) preserves it across recovery.
            obj = {"uid": new.uid, "nodeName": new.node_name}
            if self._bind_ctx is not None:
                obj["tctx"] = _spans.format_ctx(self._bind_ctx)
            self._broadcast("pods", {"type": "BOUND", "object": obj})
            return
        self._broadcast("pods", {"type": typ, "object": pod_to_wire(new)})

    def _node_event(self, kind: str, old, new) -> None:
        typ = {"add": "ADDED", "update": "MODIFIED", "delete": "DELETED"}[kind]
        self._broadcast("nodes", {"type": typ, "object": node_to_wire(new)})

    def _pod_group_event(self, group) -> None:
        # Pod groups are create-only upserts on this surface (the store has
        # no update/delete verb), so every event is ADDED. Muted during
        # registration: the store replays recovered groups at subscribe
        # time and those are already in the WAL + watch cache.
        if self._pg_mute:
            return
        self._broadcast("podgroups",
                        {"type": "ADDED", "object": pod_group_to_wire(group)})

    # -- node-lifecycle health plane (controllers/node_lifecycle.py) --------

    def _note_heartbeats(self, names) -> None:
        """Stamp last-heartbeat for `names` on THIS process's clock. Called
        from the heartbeat sink and node create/PUT paths; never WAL'd."""
        now = time.monotonic()
        with self._hb_lock:
            for n in names:
                self.node_hb[n] = now

    def _drop_heartbeat(self, name: str) -> None:
        with self._hb_lock:
            self.node_hb.pop(name, None)

    def heartbeat_ages(self) -> Dict[str, float]:
        """Seconds since each node's last heartbeat (leader-local truth —
        the GET /api/v1/nodes/heartbeats surface the lifecycle controller
        polls; followers answer 421 so the client leader-routes)."""
        now = time.monotonic()
        with self._hb_lock:
            snap = dict(self.node_hb)
        return {n: round(now - t, 3) for n, t in snap.items()}

    # -- eviction subresource (POST /api/v1/pods/<uid>/eviction) ------------

    def _evict_locked(self, uid: str, body: dict):
        """Evict one bound pod: DELETE-then-recreate-pending, so the
        scheduler re-places it through the normal queue. Caller holds the
        write lock. Idempotent by intent id: the (uid, intent) pair is
        ledgered in `self.evictions` and WAL'd, so any retry — controller
        restart, or replay against a promoted leader — answers
        `already=True` without touching the pod. The entry lives only
        until the pod re-binds (or is deleted): once re-placed, the same
        uid@node intent names a NEW wave — a pod that returns to a
        recovered node must be evictable again when that node fails a
        second time. Mutation-before-ledger is the crash-safe order: a
        crash between them leaves a pending pod the retry sees as
        already-evicted work (no-op), whereas ledger-first could ack an
        eviction that never happened."""
        intent = str(body.get("intent") or "")
        want_node = str(body.get("node") or "")
        if not intent:
            return 400, {"error": "intent required"}
        if self.evictions.get(uid) == intent:
            self.pod_evictions_replayed += 1
            return 200, {"evicted": True, "already": True}
        pod = self.store.pods.get(uid)
        if pod is None:
            return 404, {"error": "pod not found"}
        if not pod.node_name:
            # Already pending (a prior wave's recreate, or never bound):
            # nothing to evict — and NOT a ledger entry, so a later bind
            # to a fresh failing node can still be evicted under a new
            # intent.
            return 200, {"evicted": False, "pending": True}
        if want_node and pod.node_name != want_node:
            # The pod moved since the controller planned this eviction
            # (taint lifted / already rescheduled): refuse — evicting a
            # healthy placement would be the storm the rate limiter exists
            # to prevent.
            return 409, {"error": "NodeMismatch", "node": pod.node_name}
        if pod.finalizers:
            return 409, {"error": "FinalizerParked"}
        denied = self._pdb_blocks_eviction(pod)
        if denied is not None:
            self.evictions_budget_denied += 1
            return 429, denied
        bound_to = pod.node_name
        self.store.delete_pod(pod)
        if uid in self.store.pods:
            return 409, {"error": "FinalizerParked"}
        self._usage_apply(bound_to, pod, -1)
        w = pod_to_wire(pod)
        w["nodeName"] = ""
        w["nominatedNodeName"] = ""
        ann = dict(w.get("annotations") or {})
        ann[EVICTED_ANNOTATION] = intent
        w["annotations"] = ann
        self.store.create_pod(pod_from_wire(w))
        with self._lock:
            self._repl_append({"kind": "evictions", "type": "EVICT",
                               "object": {"uid": uid, "intent": intent,
                                          "node": bound_to}})
        self.evictions[uid] = intent
        self.pod_evictions += 1
        return 200, {"evicted": True, "node": bound_to}

    @staticmethod
    def _pdb_threshold(value, total: int, round_up: bool) -> int:
        """One PDB field — an int or an ``"N%"`` string — resolved against
        the budget's matched-pod census (the reference's
        GetScaledValueFromIntOrPercent split): minAvailable percentages
        round UP (protect at least that share), maxUnavailable percentages
        round DOWN (never disrupt more than that share)."""
        if isinstance(value, str) and value.rstrip().endswith("%"):
            pct = int(value.rstrip()[:-1] or 0)
            scaled = pct * total
            return -(-scaled // 100) if round_up else scaled // 100
        return int(value or 0)

    def _pdb_blocks_eviction(self, pod) -> Optional[dict]:
        """PodDisruptionBudget precondition for VOLUNTARY disruptions
        (eviction subresource, ?voluntary=true deletes). Caller holds the
        write lock. Returns a 429 payload when committing the disruption
        would take a selected workload below its budget floor, else None.

        ``available`` counts BOUND pods (node_name set) in the PDB's
        namespace matching its selector — the same census the chaos suite
        polls; ``matched`` counts every selected pod bound or not (the
        workload-size base percentages and maxUnavailable scale against —
        disruption.go's expectedCount stand-in). Either budget form gates:
        minAvailable blocks when the post-eviction bound count would dip
        below the floor; maxUnavailable blocks when it would dip below
        ``matched - maxUnavailable``. Both present ⇒ both must pass. An
        empty matchLabels selector matches NOTHING (a typo'd PDB must not
        accidentally freeze the whole cluster). Involuntary paths (zone
        Full, node delete) never call this — exactly the reference's
        split (disruption.go guards the Eviction subresource, not the
        node controller's deletes)."""
        labels = pod.labels or {}
        ns = getattr(pod, "namespace", "") or "default"
        for key, pdb in self.workloads["pdbs"].items():
            if (pdb.get("namespace") or "default") != ns:
                continue
            sel = pdb.get("matchLabels") or {}
            if not sel:
                continue
            if any(labels.get(k) != v for k, v in sel.items()):
                continue
            matched = [
                p for p in self.store.pods.values()
                if (getattr(p, "namespace", "") or "default") == ns
                and all((p.labels or {}).get(k) == v
                        for k, v in sel.items())]
            available = sum(1 for p in matched if p.node_name)
            total = len(matched)
            min_avail = self._pdb_threshold(
                pdb.get("minAvailable", 0), total, round_up=True)
            if available - 1 < min_avail:
                return {"error": "DisruptionBudget",
                        "pdb": pdb.get("name", key),
                        "available": available,
                        "matched": total,
                        "minAvailable": min_avail}
            if pdb.get("maxUnavailable") is not None:
                max_unavail = self._pdb_threshold(
                    pdb["maxUnavailable"], total, round_up=False)
                if available - 1 < total - max_unavail:
                    return {"error": "DisruptionBudget",
                            "pdb": pdb.get("name", key),
                            "available": available,
                            "matched": total,
                            "maxUnavailable": max_unavail}
        return None

    def _workload_upsert_locked(self, kind: str, body,
                                create: bool = False):
        """Create/upsert one workload object (WORKLOAD_KINDS). Caller
        holds the write lock. The broadcast IS the commit: WAL record,
        watch-cache upsert, stream fanout — same ordering as every store
        kind, with the server-owned wire dict standing in for the store.
        Create answers 409 AlreadyExists on a duplicate name — the
        retry-safe half of the controllers' exactly-once contract."""
        if not isinstance(body, dict) or not body.get("name"):
            return 400, {"error": "name required"}
        w = dict(body)
        ns = w.get("namespace") or "default"
        w["namespace"] = ns
        w.setdefault("uid", f"{kind}/{ns}/{w['name']}")
        key = f"{ns}/{w['name']}"
        exists = key in self.workloads[kind]
        if create and exists:
            return 409, {"error": "AlreadyExists"}
        self.workloads[kind][key] = w
        self._broadcast(kind, {"type": "MODIFIED" if exists else "ADDED",
                               "object": w})
        return (201 if create else 200), w

    def _workload_delete_locked(self, kind: str, ns: str, name: str):
        key = f"{ns or 'default'}/{name}"
        w = self.workloads[kind].pop(key, None)
        if w is None:
            return 404, {"error": "not found"}
        self._broadcast(kind, {"type": "DELETED", "object": w})
        return 200, {}

    def _attach_watch(self, kind: str, since: Optional[int] = None,
                      epoch: Optional[str] = None,
                      flt: Optional[ShardFilter] = None,
                      paged: bool = False,
                      fresh: bool = False) -> _WatchStream:
        """Attach a watch under the broadcast lock, THEN register for live
        events — no create can fall between snapshot and registration.
        The snapshot and the resume ring both serve from the watch cache
        (never the store dicts, never the write lock).

        since=None (or outside the ring window, or an epoch from another
        server instance): resourceVersion=0 semantics — ADDED for every
        existing object, then a SYNC marker carrying the current rv +
        epoch. since=N inside the window with a matching epoch: a RESUME
        marker, then a replay of exactly the events with rv > N. A shard
        filter (``flt``) routes both replays; a filtered RESUME against a
        selector-ful cluster re-lists instead (the per-stream slim set
        died with the old connection — see core/watchcache.py)."""
        st = _WatchStream(flt)
        wc = self.watch_cache[kind]
        with self._lock:
            seq = self._seq[kind]
            tail = None
            # Resumable iff the rv names THIS server's history (epoch) and
            # NOTHING after `since` was compacted away. Anything else —
            # unknown epoch (server restarted, counters reset), a future
            # rv, a pruned ring window — full-re-lists, never silently
            # resumes (events_since counts the 410-too-old case). A
            # selector-ful FILTERED resume is refused (the old stream's
            # slim set died with it) UNLESS `fresh` marks this attach as
            # the one straight after a completed paged re-list: that
            # client's cache was just rebuilt from full objects, and
            # nothing slims while selector_refs > 0, so there is no slim
            # set to lose.
            resumable = (since is not None and epoch == self.epoch
                         and since <= seq)
            if (resumable and flt is not None and wc.selector_refs > 0
                    and not fresh):
                resumable = False
            if resumable:
                tail = wc.events_since(since)
            if tail is not None:
                st.q.put(wire.WireItem({"type": "RESUME", "rv": seq,
                                        "epoch": self.epoch}))
                for _rv, event, data in tail:
                    self._route_to(st, event, data, wc)
                if flt is not None:
                    # Prime AFTER the replay: the fresh filter's empty slim
                    # map means no replayed event can be suppressed (the
                    # primed projections are built from the CURRENT
                    # snapshot — priming first would make a replayed
                    # MODIFIED that produced that very state compare equal
                    # and be dropped, losing e.g. a deletionTs the client
                    # missed while disconnected). Priming afterwards only
                    # seeds the upgrade set for a later selector
                    # transition.
                    flt.prime(wc)
                    if wc.selector_refs > 0:
                        # Only reachable on a `fresh` attach (non-fresh
                        # selector-ful filtered resumes are refused
                        # above): the paged list that just rebuilt this
                        # client slimmed while refs were still 0, and a
                        # selector source landed in the list→attach gap.
                        # Upgrade everything the list slimmed NOW — the
                        # in-band burst in route() only fires on the
                        # next event, which a quiet cluster may never
                        # send.
                        for item in flt.upgrade_all(wc):
                            st.q.put(item)
                self.resumed_watches += 1
            elif paged and since is not None:
                # A paged client re-lists through `?limit=&continue=`
                # (Replace semantics, bounded pages) instead of consuming
                # a full ADDED replay materialized into this queue: tell
                # it the resume window is gone and close the stream — it
                # re-lists, then re-attaches with fresh=true at the list
                # anchor.
                st.q.put(wire.WireItem({"type": "TOO_OLD", "rv": seq,
                                        "epoch": self.epoch}))
                st.q.put(None)
            else:
                # Lazy-cursor replay (the legacy path materialized a full
                # ADDED event per object INTO this queue, under the
                # broadcast lock — at 50k nodes that is the whole cluster
                # encoded per attaching client). Now the attach only
                # records the snapshot rv; the stream's consumer thread
                # pages the watch-cache snapshot itself (list_page, the
                # cache's own lock) and emits SYNC at this rv. Live events
                # queue from here on as usual — an object mutated while
                # paging upserts twice (pages serve current copy-on-write
                # state), which the client's replayed-ADDED upsert path
                # already absorbs.
                st.replay_rv = seq
                st.replay_epoch = self.epoch
                if flt is not None and wc.selector_refs == 0:
                    # Seed the filter's slim map for the objects the page
                    # replay will slim (pre-attach pods); pods created
                    # DURING the replay are recorded by their own queued
                    # live events routing through the filter. The replay
                    # slims IFF this prime ran (st.replay_slim): decision
                    # and bookkeeping are frozen together, so a
                    # selector_refs flip mid-replay can't produce slims
                    # the upgrade burst has no record of.
                    flt.prime(wc)
                    st.replay_slim = True
                self.relisted_watches += 1
            self._watchers[kind].append(st)
        return st

    def _detach_watch(self, kind: str, st: _WatchStream) -> None:
        with self._lock:
            if st in self._watchers[kind]:
                self._watchers[kind].remove(st)

    # -- http --------------------------------------------------------------

    def serve(self, port: int = 0) -> int:
        server = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # The handler writes responses as several small send()s (status
            # line, headers, body) and clients send headers/body the same
            # way: with Nagle on, each small segment waits on the peer's
            # delayed ACK — measured ~3.8ms/request on LOOPBACK (≈260
            # writes/s ceiling on an idle server). TCP_NODELAY on both
            # sides (see KeepAliveClient) lifts the write plane ~4x.
            disable_nagle_algorithm = True

            def log_message(self, *a):
                pass

            def setup(self):
                super().setup()
                server._conns.add(self.connection)

            def finish(self):
                server._conns.discard(self.connection)
                super().finish()

            def _read_body(self) -> dict:
                # Socket I/O — must run OUTSIDE the write lock (a stalled
                # sender would otherwise wedge the whole write plane).
                # Sniff-decoded (core/wire.py): a negotiated client sends
                # binary frames (bulk bindings, bulk creates), everything
                # else stays the JSON compat plane.
                n = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(n) or b"{}"
                self._body_len = len(raw)
                self._body_codec = (wire.BINARY if raw[0] == wire.MAGIC
                                    else wire.JSON)
                return wire.decode(raw)

            def _body(self) -> dict:
                return self._body_cache

            def _accept(self) -> str:
                """This request's negotiated reply codec (Accept:-style;
                core/wire.py). Error bodies stay JSON regardless — the
                debug plane."""
                if server.json_only:
                    return wire.JSON
                return wire.accept_codec(self.headers.get("Accept"))

            def _json(self, code: int, obj,
                      surface: Optional[str] = None,
                      retry_after: Optional[int] = None) -> None:
                codec = self._accept() if code < 400 else wire.JSON
                _t0 = time.perf_counter()
                data = wire.encode(obj, codec)
                if surface is not None:
                    server._count_encode_us(surface,
                                            time.perf_counter() - _t0)
                    server._count_wire(codec, surface, len(data))
                self.send_response(code)
                self.send_header("Content-Type", wire.mime_for(codec))
                if retry_after is not None:
                    # The shed contract (core/flowcontrol.py): a 429 always
                    # carries Retry-After — the client half honors it with
                    # decorrelated jitter (core/backoff.py), so shed work
                    # returns after the backlog horizon, never as a
                    # synchronized retry storm.
                    self.send_header("Retry-After", str(int(retry_after)))
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def _flow_namespace(self) -> str:
                """The tenant namespace this mutating request bills to
                (workload flow key). Binding/delete paths carry only a uid;
                the pod's namespace resolves through the store dict (a
                GIL-atomic get — no lock, a racing delete just falls back
                to the default flow)."""
                path, body = self.path, self._body_cache
                if path in ("/api/v1/pods", "/api/v1/podgroups") \
                        or path.split("?")[0] in tuple(
                            f"/api/v1/{k}" for k in WORKLOAD_KINDS):
                    if isinstance(body, list):
                        return (body[0].get("namespace", "")
                                if body else "")
                    if isinstance(body, dict):
                        return body.get("namespace", "")
                    return ""
                uid = ""
                if path == "/api/v1/bindings":
                    if isinstance(body, list) and body:
                        uid = body[0].get("uid", "")
                elif path.startswith("/api/v1/pods/"):
                    parts = path.split("/")
                    uid = parts[4] if len(parts) > 4 else ""
                if uid:
                    pod = server.store.pods.get(uid)
                    if pod is not None:
                        return pod.namespace
                return ""

            def _flow_admit(self, method: str):
                """Admission through the priority-and-fairness plane
                (core/flowcontrol.py) — BEFORE `_write_lock`, always. A
                shed request is answered 429 + Retry-After right here
                (returns None); the caller must release the ticket in a
                finally once the write plane is done with it."""
                fc = server.flowcontrol
                level, flow = fc.classify(method, self.path,
                                          self._flow_namespace())
                ticket = fc.admit(level, flow)
                if ticket is None:
                    ra = fc.retry_after(level)
                    self._json(429, {"error": "TooManyRequests",
                                     "retryAfter": ra}, retry_after=ra)
                return ticket

            def do_GET(self):
                path, _, query = self.path.partition("?")
                watch = "watch=true" in query
                paged = "paged=true" in query
                fresh = "fresh=true" in query
                since, epoch, flt, uids = None, None, None, None
                limit, cont = 0, ""
                for part in query.split("&"):
                    if part.startswith("resourceVersion="):
                        try:
                            since = int(part.split("=", 1)[1])
                        except ValueError:
                            pass
                    elif part.startswith("epoch="):
                        epoch = part.split("=", 1)[1]
                    elif part.startswith("limit="):
                        try:
                            limit = int(part.split("=", 1)[1])
                        except ValueError:
                            pass
                    elif part.startswith("continue="):
                        cont = part.split("=", 1)[1]
                    elif part.startswith("shard="):
                        # Server-side shard-filtered stream: shard=i/n
                        # applies the shard/partition.py crc32 map HERE,
                        # so a shard's decode cost scales with 1/n. A spec
                        # that names no real slot (count<=0, index out of
                        # range) is IGNORED, not coerced — a coerced
                        # filter would slim every pod including the
                        # stream owner's own.
                        try:
                            i, _, n = part.split("=", 1)[1].partition("/")
                            idx, cnt = int(i), int(n)
                            if cnt >= 1 and 0 <= idx < cnt:
                                flt = ShardFilter(idx, cnt)
                        except ValueError:
                            pass
                    elif part.startswith("uids="):
                        uids = [u for u in
                                part.split("=", 1)[1].split(",") if u]
                if path == "/api/v1/pods":
                    if watch:
                        return self._stream("pods", since, epoch, flt,
                                            paged=paged, fresh=fresh)
                    # Every non-watch read below serves from the watch
                    # cache under ITS lock — no store-dict iteration, no
                    # write-lock contention, and safe against concurrent
                    # mutation by construction.
                    if "summary=true" in query:
                        # Progress-poll surface: counting is ~3 orders of
                        # magnitude cheaper than wire-encoding the full
                        # list, and pollers (bench/chaos harnesses) only
                        # need the counts — at 10k pods a full-list poll
                        # every 0.5s costs the control plane more CPU than
                        # the binds themselves.
                        s = server.watch_cache["pods"].read_summary()
                        return self._json(200, {"total": s["total"],
                                                "bound": s["bound"]})
                    if uids is not None:
                        # Hydration read (shard adoption): full wire for
                        # pods a filtered stream delivered slim.
                        return self._json(
                            200, server.watch_cache["pods"].get_many(uids))
                    if limit:
                        # Paged LIST (docs/SCALE.md): bounded pages with
                        # rv-anchored continuation tokens — the 50k-node
                        # read path. The whole cluster never rides one
                        # response body.
                        return self._list_paged("pods", limit, cont, flt)
                    server.list_unpaged += 1
                    return self._json(200,
                                      server.watch_cache["pods"].list_wire())
                if path == "/api/v1/nodes":
                    if watch:
                        return self._stream("nodes", since, epoch,
                                            paged=paged, fresh=fresh)
                    if limit:
                        return self._list_paged("nodes", limit, cont)
                    server.list_unpaged += 1
                    return self._json(200,
                                      server.watch_cache["nodes"].list_wire())
                if path == "/api/v1/nodes/heartbeats":
                    # Heartbeat ages are LEADER-LOCAL (the sink is never
                    # WAL'd): a follower answering from its empty/stale map
                    # would age out the whole fleet — 421 so the lifecycle
                    # controller's client leader-routes this GET.
                    if server.role != "leader":
                        return self._json(421, {"error": "NotLeader",
                                                "leader": server.leader_url})
                    return self._json(200, {"ages": server.heartbeat_ages()})
                if path == "/api/v1/podgroups":
                    if watch:
                        return self._stream("podgroups", since, epoch,
                                            paged=paged, fresh=fresh)
                    if limit:
                        return self._list_paged("podgroups", limit, cont)
                    server.list_unpaged += 1
                    return self._json(
                        200, server.watch_cache["podgroups"].list_wire())
                for wk in WORKLOAD_KINDS:
                    if path == f"/api/v1/{wk}":
                        if watch:
                            return self._stream(wk, since, epoch,
                                                paged=paged, fresh=fresh)
                        if limit:
                            return self._list_paged(wk, limit, cont)
                        server.list_unpaged += 1
                        return self._json(
                            200, server.watch_cache[wk].list_wire())
                if path == "/flow":
                    # APF admin surface: current per-level weights + live
                    # admission counters (the POST half re-weights).
                    return self._json(
                        200, {"levels": server.flowcontrol.snapshot(),
                              "weights": server.flowcontrol.weights()})
                if path == "/metrics/resources":
                    # kube_pod_resource_request rendered straight from the
                    # watch cache's wire snapshot: harness pollers scrape
                    # this from FOLLOWER replicas, off the leader entirely.
                    data = server.watch_cache["pods"].render_resources()
                    data = data.encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/plain; version=0.0.4")
                    self.send_header("Content-Length", str(len(data)))
                    self.end_headers()
                    self.wfile.write(data)
                    return
                if path == "/api/v1/leases":
                    return self._json(200, server.list_leases())
                if path == "/replication/status":
                    return self._json(200, server.replication_status())
                if path == "/replication/snapshot":
                    if limit:
                        # Streaming paged bootstrap (docs/SCALE.md): meta
                        # under the locks, object pages streamed from the
                        # watch cache OUTSIDE every lock — a 50k-node
                        # bootstrap neither stalls the write plane for
                        # the encode nor rides one response body.
                        return self._snapshot_stream(limit)
                    # Legacy single-body bootstrap: a consistent full-state
                    # snapshot. Encode UNDER the locks (no write can
                    # interleave), send after releasing them — the socket
                    # write must never run under a held lock.
                    with server._write_lock:
                        with server._lock:
                            snap = server._snapshot_state()
                    return self._json(200, snap)
                if path == "/replication/wal":
                    since, repl_epoch, leader_hint, hb = 0, None, "", 1.0
                    for part in query.split("&"):
                        k, _, v = part.partition("=")
                        try:
                            if k == "from":
                                since = int(v)
                            elif k == "epoch":
                                repl_epoch = int(v)
                            elif k == "hb":
                                hb = max(0.05, float(v))
                        except ValueError:
                            pass
                        if k == "leader":
                            leader_hint = v
                    return self._ship(since, repl_epoch, leader_hint, hb)
                if path == "/metrics":
                    data = server.expose_metrics().encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/plain; version=0.0.4")
                    self.send_header("Content-Length", str(len(data)))
                    self.end_headers()
                    self.wfile.write(data)
                    return
                self._json(404, {"error": "not found"})

            def _write_chunk(self, data: bytes) -> None:
                self.wfile.write(
                    f"{len(data):x}\r\n".encode() + data + b"\r\n")

            def _list_paged(self, kind: str, limit: int, token: str,
                            flt: Optional[ShardFilter] = None) -> None:
                """One page of `?limit=&continue=`: up to `limit` objects
                as chunked json lines (the ship stream's framing) + a PAGE
                trailer carrying the continuation token, the list-anchor
                rv (`listRv` — what the client attaches its watch at) and
                the epoch. Serves entirely from the watch cache under ITS
                lock; an anchor that fell off the resume ring answers 410
                and the client restarts its list."""
                wc = server.watch_cache[kind]
                last_key, anchor = "", None
                if token:
                    tok = parse_continue(token)
                    if tok is None or tok.get("e") != server.epoch:
                        server.list_continue_410 += 1
                        return self._json(410, {"error": "ExpiredContinue"})
                    last_key, anchor = tok.get("k", ""), int(tok.get("rv", 0))
                page = wc.list_page(limit, last_key=last_key,
                                    anchor_rv=anchor)
                if page is None:
                    server.list_continue_410 += 1
                    return self._json(410, {"error": "ExpiredContinue"})
                objs, next_key, anchor, rv = page
                server.list_pages += 1
                codec = self._accept()
                # Slim foreign plain pods through the shard filter exactly
                # as the watch plane would deliver them (selector-free
                # clusters only — core/watchcache.py).
                slim_ok = (flt is not None and kind == "pods"
                           and wc.selector_refs == 0)
                try:
                    # Headers inside the guard too: a client that closed
                    # between request and response must tear only THIS
                    # handler, quietly.
                    self.send_response(200)
                    self.send_header("Content-Type", wire.mime_for(codec))
                    self.send_header("Transfer-Encoding", "chunked")
                    self.end_headers()
                    buf = bytearray()
                    sent = 0
                    enc_s = 0.0
                    for obj in objs:
                        if (slim_ok and wire_plain(obj)
                                and shard_of_wire(obj, flt.count)
                                != flt.index):
                            obj = slim_object(obj)
                            server.watch_slim_events += 1
                        _t0 = time.perf_counter()
                        buf += wire.encode({"type": "ADDED", "object": obj},
                                           codec)
                        enc_s += time.perf_counter() - _t0
                        if len(buf) >= 65536:
                            sent += len(buf)
                            self._write_chunk(bytes(buf))
                            buf.clear()
                    trailer = {"type": "PAGE", "rv": rv, "listRv": anchor,
                               "epoch": server.epoch}
                    if next_key:
                        trailer["continue"] = mint_continue(
                            anchor, next_key, server.epoch)
                    _t0 = time.perf_counter()
                    buf += wire.encode(trailer, codec)
                    server._count_encode_us(
                        "list", enc_s + time.perf_counter() - _t0)
                    server._count_wire(codec, "list", sent + len(buf))
                    self._write_chunk(bytes(buf))
                    self.wfile.write(b"0\r\n\r\n")
                    self.wfile.flush()
                except (BrokenPipeError, ConnectionResetError, OSError):
                    self.close_connection = True

            def _snapshot_stream(self, limit: int) -> None:
                """Streaming replication bootstrap: SNAP_META (the control
                cut — seq map, repl seq/epoch, leases — captured under the
                locks), then object pages from the watch cache streamed
                OUTSIDE every lock, then SNAP_END. Objects may be AHEAD of
                the meta seq; the follower re-tails from meta seq and the
                frame replay upsert-heals every difference (docs/SCALE.md
                bootstrap contract). A torn stream (no SNAP_END) is never
                installed."""
                with server._write_lock:
                    with server._lock:
                        meta = {
                            "epoch": server.epoch,
                            "seq": dict(server._seq),
                            "repl": {"seq": server._repl_seq,
                                     "epoch": server.repl_epoch},
                            "leases": [dict(rec, name=name, renew=None)
                                       for name, rec in
                                       list(server.leases.items())],
                            # Intent ledger rides the meta cut (small,
                            # bounded): a bootstrapping replica must
                            # answer an in-flight wave's retries
                            # idempotently from its very first frame.
                            "evictions": [
                                {"uid": u, "intent": i} for u, i in
                                list(server.evictions.items())],
                            "role": server.role,
                        }
                codec = self._accept()
                try:
                    self.send_response(200)
                    self.send_header("Content-Type", wire.mime_for(codec))
                    self.send_header("Transfer-Encoding", "chunked")
                    self.end_headers()
                    sent = 0
                    data = wire.encode({"type": "SNAP_META", **meta}, codec)
                    sent += len(data)
                    self._write_chunk(data)
                    for kind in ("pods", "nodes", "podgroups") \
                            + WORKLOAD_KINDS:
                        last = ""
                        while True:
                            objs, next_key, _a, _rv = (
                                server.watch_cache[kind].list_page(
                                    limit, last_key=last))
                            server.snapshot_bootstrap_pages += 1
                            buf = bytearray()
                            enc_s = 0.0
                            for obj in objs:
                                _t0 = time.perf_counter()
                                buf += wire.encode(
                                    {"kind": kind, "object": obj}, codec)
                                enc_s += time.perf_counter() - _t0
                                if len(buf) >= 65536:
                                    sent += len(buf)
                                    self._write_chunk(bytes(buf))
                                    buf.clear()
                            server._count_encode_us("snapshot", enc_s)
                            if buf:
                                sent += len(buf)
                                self._write_chunk(bytes(buf))
                            if not next_key:
                                break
                            last = next_key
                    data = wire.encode({"type": "SNAP_END"}, codec)
                    sent += len(data)
                    self._write_chunk(data)
                    server._count_wire(codec, "snapshot", sent)
                    self.wfile.write(b"0\r\n\r\n")
                    self.wfile.flush()
                except (BrokenPipeError, ConnectionResetError, OSError):
                    self.close_connection = True

            def _replay_lazy(self, kind: str, st, codec: str,
                             enc=None) -> None:
                """The attach-time replay as a lazy cursor into the watch
                cache's snapshot: bounded pages in sorted-key order
                (list_page — the cache's own lock, never the broadcast or
                write lock), encoded and sent on this stream's consumer
                thread. Shard filters slim statelessly here, exactly as
                the paged LIST plane does; live events committed while
                paging are already queued and upsert over the replay."""
                wc = server.watch_cache[kind]
                flt = st.filter
                last = ""
                sent = 0
                while server._httpd is not None:
                    page = wc.list_page(500, last_key=last)
                    if page is None:  # unanchored pages never expire
                        break
                    objs, next_key, _anchor, _rv = page
                    server.watch_replay_pages += 1
                    buf = bytearray()
                    for obj in objs:
                        if (st.replay_slim and kind == "pods"
                                and wire_plain(obj)
                                and shard_of_wire(obj, flt.count)
                                != flt.index):
                            obj = slim_object(obj)
                            server.watch_slim_events += 1
                        ev = {"type": "ADDED", "object": obj}
                        _t0 = time.perf_counter()
                        # Replay frames ride the session table too — the
                        # whole cluster's names intern once, so the live
                        # tail that follows ships refs from frame one.
                        data = (enc.encode(ev) if enc is not None
                                else wire.encode(ev, codec))
                        server._count_encode_us(
                            "watch", time.perf_counter() - _t0)
                        sent += len(data)
                        buf += f"{len(data):x}\r\n".encode() + data + b"\r\n"
                        if len(buf) >= 65536:
                            self.wfile.write(bytes(buf))
                            buf.clear()
                    if buf:
                        self.wfile.write(bytes(buf))
                    self.wfile.flush()
                    if not next_key:
                        break
                    last = next_key
                server._count_wire(codec, "watch", sent)

            def _stream(self, kind: str, since: Optional[int] = None,
                        epoch: Optional[str] = None,
                        flt: Optional[ShardFilter] = None,
                        paged: bool = False, fresh: bool = False) -> None:
                # watch.Interface: hold the connection open, one JSON event
                # per line (chunked); blocking queue — no idle polling. A
                # BOOKMARK heartbeat goes out on idle (~10s) so a quiet
                # cluster keeps the client's read timeout from killing the
                # watch (the reference's watch bookmarks serve the same
                # liveness role).
                codec = self._accept()
                enc = None
                if codec == wire.BINARY and wire.accept_session(
                        self.headers.get("Accept")):
                    # Session intern table: per-connection, constructed
                    # and touched ONLY on this consumer thread (never the
                    # broadcast lock) — the second half of the analyzer's
                    # delta-base-under-cache-lock rule. Its MIME also
                    # signals delta capability: WireItems queued here may
                    # encode as DELTA records against the client's cache.
                    enc = wire.SessionEncoder()
                self.send_response(200)
                self.send_header("Content-Type",
                                 wire.mime_for(codec,
                                               session=enc is not None))
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()
                st = server._attach_watch(kind, since, epoch, flt,
                                          paged=paged, fresh=fresh)
                idle = 0.0
                try:
                    if st.replay_rv is not None:
                        # Lazy-cursor attach replay: page the snapshot on
                        # THIS consumer thread (watch-cache lock only, one
                        # bounded page at a time — the full cluster never
                        # materializes in the stream queue or under the
                        # broadcast lock), then SYNC at the attach rv.
                        self._replay_lazy(kind, st, codec, enc)
                        data = wire.encode(
                            {"type": "SYNC", "rv": st.replay_rv,
                             "epoch": st.replay_epoch}, codec)
                        server._count_wire(codec, "watch", len(data))
                        self._write_chunk(data)
                        self.wfile.flush()
                    while server._httpd is not None:
                        try:
                            data = st.q.get(timeout=0.5)
                            idle = 0.0
                        except queue.Empty:
                            idle += 0.5
                            if idle < 10.0:
                                continue
                            idle = 0.0
                            data = wire.encode({"type": "BOOKMARK"}, codec)
                        if data is None:
                            # Stream-end sentinel (snapshot RESYNC skipped
                            # frames): close; the client re-lists fresh.
                            break
                        # One write for everything that is queued by now
                        # (one chunk an event, as ever). Sending one small
                        # segment an event, this loop was seen, behind a
                        # burst, to hand an idle reader some ten events a
                        # second for minutes, with every socket queue
                        # empty (PERF.md, PR 28: cause not established);
                        # draining the queue per write has not.
                        buf = bytearray()
                        end = False
                        while True:
                            # Encode HERE, on this stream's own thread,
                            # in THIS stream's codec — never under the
                            # broadcast lock the fanout path holds;
                            # WireItems cache the result so it happens
                            # once per codec, not per stream (session
                            # frames are per-connection and never
                            # cached).
                            _t0 = time.perf_counter()
                            data = encode_stream_item(data, codec, enc)
                            server._count_encode_us(
                                "watch", time.perf_counter() - _t0)
                            server._count_wire(codec, "watch", len(data))
                            buf += f"{len(data):x}\r\n".encode()
                            buf += data
                            buf += b"\r\n"
                            if len(buf) >= STREAM_WRITE_BYTES:
                                break
                            try:
                                data = st.q.get_nowait()
                            except queue.Empty:
                                break
                            if data is None:
                                end = True
                                break
                        self.wfile.write(bytes(buf))
                        self.wfile.flush()
                        if end:
                            break
                except (BrokenPipeError, ConnectionResetError):
                    pass
                finally:
                    server._detach_watch(kind, st)
                    # End of stream (server shutdown): close the TCP
                    # connection instead of waiting for another request on
                    # it, so the client's reflector sees EOF immediately
                    # and re-lists against the next server.
                    self.close_connection = True

            def _ship(self, since: int, repl_epoch: Optional[int],
                      leader_hint: str, hb: float) -> None:
                """Replication ship stream: WAL frames with seq > `since`,
                one json line per chunk, heartbeats (`HB`, carrying the
                head seq + fencing epoch) on idle. The queue is loaded and
                registered under the broadcast lock (_attach_ship); every
                socket send happens OUT HERE, lock-free — a slow follower
                backpressures only its own queue, never the write plane."""
                from urllib.parse import unquote
                if repl_epoch is not None and repl_epoch > server.repl_epoch:
                    # The follower has seen a newer generation: this
                    # replica was deposed while partitioned. Fence off.
                    # The hint is the follower's TAIL TARGET — by
                    # construction this very server — so it never names
                    # the winner: demote without a redirect target and
                    # let clients re-resolve through status probing.
                    hint = unquote(leader_hint).rstrip("/")
                    if hint == server.advertise_url:
                        hint = ""
                    server.demote(hint, repl_epoch)
                    return self._json(409, {
                        "error": "StaleEpoch",
                        "replEpoch": server.repl_epoch})
                st = server._attach_ship(since)
                if st is None:
                    # The ship window no longer covers `since` (compaction
                    # outran the follower): 410 Gone — snapshot bootstrap.
                    return self._json(410, {"error": "ResyncRequired",
                                            "seq": server._repl_seq})
                codec = self._accept()
                enc = None
                if codec == wire.BINARY and wire.accept_session(
                        self.headers.get("Accept")):
                    # Session ship stream: per-connection intern table on
                    # THIS handler thread, and the delta-capability
                    # signal — DELTA twins ship as-is; the follower
                    # materializes against its own watch-cache base.
                    enc = wire.SessionEncoder()
                self.send_response(200)
                self.send_header("Content-Type",
                                 wire.mime_for(codec,
                                               session=enc is not None))
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()
                try:
                    while server._httpd is not None and not st.dead:
                        try:
                            seq, item = st.q.get(timeout=hb)
                            # Shared frame WireItem: the plain encode is
                            # cached per codec, so N binary followers
                            # reuse the WAL append's bytes; session
                            # followers get the delta twin when one was
                            # minted.
                            _t0 = time.perf_counter()
                            data = (item.session_bytes(enc)
                                    if enc is not None
                                    else item.bytes(codec))
                            server._count_encode_us(
                                "ship", time.perf_counter() - _t0)
                        except queue.Empty:
                            seq = None
                            # HBs carry this replica's ROLE: a follower
                            # tailing a stream whose server was deposed
                            # must not count these as leader liveness.
                            hb_ev = {"type": "HB", "seq": server._repl_seq,
                                     "epoch": server.repl_epoch,
                                     "role": server.role}
                            data = (enc.encode(hb_ev) if enc is not None
                                    else wire.encode(hb_ev, codec))
                        server._count_wire(codec, "ship", len(data))
                        self.wfile.write(
                            f"{len(data):x}\r\n".encode() + data + b"\r\n")
                        self.wfile.flush()
                        if seq is not None:
                            server._ship_mark_sent(st, seq)
                except (BrokenPipeError, ConnectionResetError, OSError):
                    pass
                finally:
                    server._detach_ship(st)
                    self.close_connection = True

            def do_POST(self):
                self._body_cache = self._read_body()
                if self.path == "/replication/peers":
                    # Replication-internal wiring (accepted in ANY role):
                    # the harness injects the rank -> base URL map after
                    # every replica's ephemeral port is known. Not WAL'd —
                    # topology, not state. Exempt lane by construction:
                    # answered before admission ever runs.
                    server.flowcontrol.count_exempt()
                    server.repl_peers = {
                        int(k): v for k, v in
                        (self._body().get("peers") or {}).items()}
                    return self._json(200, {"peers": len(server.repl_peers)})
                if self.path == "/replication/leader":
                    # Promotion announcement (accepted in ANY role): the
                    # freshly promoted leader pushes its generation to
                    # every peer, so surviving followers re-tail
                    # immediately (instead of waiting out their own
                    # silence detection) and a stale co-leader demotes
                    # itself even though no follower ever tails it. Two
                    # followers promoting CONCURRENTLY land on the same
                    # epoch — the rank tie-break (lower announcer rank
                    # wins) stands one of them down; its forked tail
                    # resolves via snapshot resync on re-attach.
                    server.flowcontrol.count_exempt()
                    body = self._body()
                    ep = int(body.get("epoch", 0))
                    rank = int(body.get("rank", 1 << 30))
                    url = (body.get("leader") or "").rstrip("/")
                    if server.role == "leader":
                        if (ep > server.repl_epoch
                                or (ep == server.repl_epoch
                                    and rank < server.replica_rank)):
                            server.demote(url, ep)
                    elif url and ep >= server.repl_epoch:
                        server.note_leader(url, ep)
                    return self._json(200, {"replEpoch": server.repl_epoch})
                if self.path == "/flow":
                    # Live APF re-weight (operator plane, accepted in ANY
                    # role — each replica admits with its own controller).
                    # Applied under the FlowController's OWN lock, never
                    # the write lock: re-weighting mid-storm must not queue
                    # behind the flooded write plane it is trying to fix.
                    server.flowcontrol.count_exempt()
                    body = self._body()
                    level = str(body.get("level") or "")
                    try:
                        got = server.flowcontrol.set_weights(
                            level, body.get("weights") or {})
                    except KeyError:
                        return self._json(404, {"error": "unknown level"})
                    except ValueError as e:
                        return self._json(400, {"error": str(e)})
                    return self._json(200, {"level": level, "weights": got})
                if server.role != "leader":
                    return self._json(421, {"error": "NotLeader",
                                            "leader": server.leader_url})
                # Flow-control admission strictly BEFORE the write lock: a
                # shed request (429 + Retry-After, sent inside _flow_admit)
                # must never have contended for — let alone held — the
                # write plane's lock.
                ticket = self._flow_admit("POST")
                if ticket is None:
                    return
                try:
                    with server._write_lock:
                        if server.role != "leader":
                            # Re-checked UNDER the lock: a demote() racing
                            # the unlocked fast-path check above must not
                            # let this write commit on a freshly deposed
                            # replica (it would be stamped with the
                            # WINNER's epoch — unfenceable divergence).
                            code, obj, seq = 421, {
                                "error": "NotLeader",
                                "leader": server.leader_url}, 0
                        else:
                            code, obj = self._post_locked()
                            seq = server._repl_seq
                    # Reply gating, OUTSIDE every lock: an acked write is
                    # on the wire to each in-quorum follower before the
                    # client hears 200 — a leader kill -9 cannot silently
                    # lose it.
                    server._await_shipped(seq)
                finally:
                    server.flowcontrol.release(ticket)
                if self.path == "/api/v1/bindings":
                    # Bulk-binding wire accounting: the request envelope
                    # (in its sniffed codec) and the per-item verdict
                    # reply (negotiated) both land on the same surface.
                    server._count_wire(self._body_codec, "bindings",
                                       self._body_len)
                    return self._json(code, obj, surface="bindings")
                self._json(code, obj)

            def _post_locked(self):
                if self.path == "/api/v1/pods":
                    body = self._body()
                    if isinstance(body, list):
                        # Bulk create: one request, one lock acquisition,
                        # one HTTP turnaround for a whole creation burst.
                        # Per-object creates cost ~1.5ms of control-plane
                        # turnaround each under load — at 10k pods that is
                        # ~45s of a 60s sharded bench spent just ARRIVING.
                        # Wire semantics match looped single creates: one
                        # ADDED event per pod (watchers see no difference),
                        # duplicates skipped and reported, never re-fired.
                        dup = 0
                        for w in body:
                            pod = pod_from_wire(w)
                            if pod.uid in server.store.pods:
                                dup += 1
                                continue
                            server.store.create_pod(pod)
                            if pod.node_name:
                                server._usage_apply(pod.node_name, pod, +1)
                        return 201, {"created": len(body) - dup,
                                     "alreadyExists": dup}
                    pod = pod_from_wire(body)
                    # AlreadyExists (409, like the reference registry):
                    # duplicate creates — e.g. a client retrying a write
                    # whose reply was lost — must not re-fire ADDED events
                    # or reset a pod the scheduler already bound.
                    if pod.uid in server.store.pods:
                        return 409, {"error": "AlreadyExists"}
                    server.store.create_pod(pod)
                    if pod.node_name:  # created pre-bound: commit its usage
                        server._usage_apply(pod.node_name, pod, +1)
                    return 201, pod_to_wire(pod)
                if self.path == "/api/v1/nodes":
                    body = self._body()
                    if isinstance(body, list):
                        dup = 0
                        for w in body:
                            node = node_from_wire(w)
                            if node.name in server.store.nodes:
                                dup += 1
                                continue
                            server.store.create_node(node)
                            server._note_heartbeats((node.name,))
                        return 201, {"created": len(body) - dup,
                                     "alreadyExists": dup}
                    node = node_from_wire(body)
                    if node.name in server.store.nodes:
                        return 409, {"error": "AlreadyExists"}
                    server.store.create_node(node)
                    # Registration counts as the first heartbeat: a node is
                    # never born already-silent.
                    server._note_heartbeats((node.name,))
                    return 201, node_to_wire(node)
                if (self.path.startswith("/api/v1/nodes/")
                        and self.path.endswith("/status")):
                    # Kubelet heartbeat sink (parity stub, no event). The
                    # hollow plane's bulk form (`/api/v1/nodes/status`,
                    # {"names": [...]}) rides the same branch — one
                    # request per fleet slice, counted per node. Each name
                    # stamps the lifecycle controller's freshness map.
                    body = self._body()
                    names = (body.get("names") if isinstance(body, dict)
                             else None) or ()
                    if not names:
                        nm = self.path.split("/")[4]
                        names = (nm,) if nm != "status" else ()
                    # The bulk form is the largest client->server stream
                    # at hollow scale: attribute its request bytes to the
                    # "status" surface so the bench proves which codec
                    # actually carried it.
                    server._count_wire(self._body_codec, "status",
                                       self._body_len)
                    server.node_heartbeats += max(1, len(names))
                    server._note_heartbeats(names)
                    return 200, {}
                if self.path == "/api/v1/podgroups":
                    body = self._body()
                    g = pod_group_from_wire(body)
                    target = (server.store.composite_pod_groups
                              if body.get("composite")
                              else server.store.pod_groups)
                    if f"{g.namespace}/{g.name}" in target:
                        return 409, {"error": "AlreadyExists"}
                    if body.get("composite"):
                        server.store.create_composite_pod_group(g)
                    else:
                        server.store.create_pod_group(g)
                    return 201, pod_group_to_wire(g)
                for wk in WORKLOAD_KINDS:
                    if self.path.split("?")[0] == f"/api/v1/{wk}":
                        return server._workload_upsert_locked(
                            wk, self._body(), create=True)
                if self.path == "/api/v1/bindings":
                    # Bulk binding commits: one request, one write-lock
                    # acquisition for a whole drained dispatcher queue
                    # (api_dispatcher bulk path). Per-item verdicts ride a
                    # 200 envelope — one pod's conflict must not fail its
                    # batch-mates' commits.
                    out = [dict(payload, code=code) for code, payload in
                           (server._bind_one(item.get("uid", ""),
                                             item.get("node", ""),
                                             tctx=item.get("tctx"))
                            for item in self._body())]
                    return 200, out
                parts = self.path.split("/")
                if (self.path.startswith("/api/v1/pods/")
                        and self.path.endswith("/eviction")):
                    return server._evict_locked(parts[4], self._body())
                if (self.path.startswith("/api/v1/pods/")
                        and self.path.endswith("/binding")):
                    return server._bind_one(
                        parts[4], self._body()["node"],
                        tctx=self.headers.get(_spans.TRACE_HEADER))
                if (self.path.startswith("/api/v1/pods/")
                        and self.path.endswith("/status")):
                    pod = server.store.pods.get(parts[4])
                    if pod is None:
                        return 404, {"error": "pod not found"}
                    body = self._body()
                    server.store.patch_pod_status(
                        pod,
                        nominated_node_name=body.get("nominatedNodeName", ""),
                        phase=body.get("phase", ""))
                    # Status patches fan out no watch event (store parity),
                    # but their scheduling-relevant slice (nominations) must
                    # still survive a restart: WAL an rv-less STATUS record
                    # — replayed as an upsert, never entering the backlog.
                    server._wal_status(pod)
                    return 200, {}
                return 404, {"error": "not found"}

            def do_PUT(self):
                self._body_cache = self._read_body()
                if server.role != "leader":
                    return self._json(421, {"error": "NotLeader",
                                            "leader": server.leader_url})
                if self.path.startswith("/api/v1/leases/"):
                    # upsert_lease serializes under the write lock itself
                    # (it is also an in-process API); don't wrap it twice.
                    # Its own under-the-lock role check covers the
                    # demote() race (NOT_LEADER sentinel -> 421). Lease CAS
                    # is the EXEMPT flow-control lane: shard/leader lease
                    # renewals are what failover detection runs on, and a
                    # tenant flood must never queue them behind itself.
                    server.flowcontrol.count_exempt()
                    body = self._body()
                    got = server.upsert_lease(
                        self.path.split("/")[4],
                        body.get("holder", ""),
                        float(body.get("leaseDurationSeconds", 15.0)))
                    if got is APIServer.NOT_LEADER:
                        return self._json(421, {"error": "NotLeader",
                                                "leader": server.leader_url})
                    if got is None:
                        return self._json(409, {"error": "LeaseHeld"})
                    server._await_shipped(server._repl_seq)
                    return self._json(200, got)
                ticket = self._flow_admit("PUT")
                if ticket is None:
                    return
                try:
                    with server._write_lock:
                        if server.role != "leader":
                            code, obj, seq = 421, {
                                "error": "NotLeader",
                                "leader": server.leader_url}, 0
                        else:
                            code, obj = self._put_locked()
                            seq = server._repl_seq
                    server._await_shipped(seq)
                finally:
                    server.flowcontrol.release(ticket)
                self._json(code, obj)

            def _put_locked(self):
                if (self.path.startswith("/api/v1/nodes/")
                        and self.path.endswith("/status")):
                    # heartbeat parity stub — stamps freshness, no event
                    nm = self.path.split("/")[4]
                    if nm != "status":
                        server._note_heartbeats((nm,))
                    return 200, {}
                # Node update (relabel / retaint / capacity change): the
                # store fans a MODIFIED event to every watch stream, so
                # churn workloads run over the wire (eventhandlers.go
                # updateNodeInCache; round-4 VERDICT item 5).
                if self.path.startswith("/api/v1/nodes/"):
                    node = node_from_wire(self._body())
                    if node.name != self.path.split("/")[4]:
                        return 400, {"error": "name mismatch"}
                    server.store.update_node(node)
                    return 200, node_to_wire(node)
                # Workload upsert: PUT /api/v1/{kind}/{ns}/{name} — the
                # path names the object (idempotent spec writes: scale,
                # rolling-update template flips, PDB edits).
                parts = self.path.split("?")[0].split("/")
                if len(parts) >= 6 and parts[3] in WORKLOAD_KINDS:
                    body = self._body()
                    if isinstance(body, dict):
                        body = dict(body, namespace=parts[4] or "default",
                                    name=parts[5])
                    return server._workload_upsert_locked(parts[3], body)
                return 404, {"error": "not found"}

            def do_DELETE(self):
                self._body_cache = {}
                if server.role != "leader":
                    return self._json(421, {"error": "NotLeader",
                                            "leader": server.leader_url})
                ticket = self._flow_admit("DELETE")
                if ticket is None:
                    return
                try:
                    with server._write_lock:
                        if server.role != "leader":
                            code, obj, seq = 421, {
                                "error": "NotLeader",
                                "leader": server.leader_url}, 0
                        else:
                            code, obj = self._delete_locked()
                            seq = server._repl_seq
                    server._await_shipped(seq)
                finally:
                    server.flowcontrol.release(ticket)
                self._json(code, obj)

            def _delete_locked(self):
                path, _, query = self.path.partition("?")
                if path.startswith("/api/v1/pods/"):
                    uid = path.split("/")[4]
                    pod = server.store.pods.get(uid)
                    if pod is not None:
                        if "voluntary=true" in query and pod.node_name:
                            # Voluntary disruption (rolling-update scale-
                            # down): same PDB precondition as the eviction
                            # subresource — a deliberate delete must not
                            # take a workload below minAvailable either.
                            denied = server._pdb_blocks_eviction(pod)
                            if denied is not None:
                                server.evictions_budget_denied += 1
                                return 429, denied
                        bound_to = pod.node_name
                        server.store.delete_pod(pod)
                        if uid not in server.store.pods:
                            # Finalizer-parked deletes keep the pod (and its
                            # committed usage); only a completed delete
                            # releases the node's share — and retires the
                            # pod's eviction-ledger entry (a gone pod needs
                            # no replay protection; the ledger must not
                            # grow with every pod ever evicted).
                            if bound_to:
                                server._usage_apply(bound_to, pod, -1)
                            server.evictions.pop(uid, None)
                    return 200, {}
                if path.startswith("/api/v1/nodes/"):
                    name = path.split("/")[4]
                    server.store.delete_node(name)
                    server._drop_heartbeat(name)
                    return 200, {}
                parts = path.split("/")
                if len(parts) >= 6 and parts[3] in WORKLOAD_KINDS:
                    return server._workload_delete_locked(
                        parts[3], parts[4], parts[5])
                return 404, {"error": "not found"}

        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        t = threading.Thread(target=self._httpd.serve_forever, daemon=True)
        t.start()
        bound_port = self._httpd.server_address[1]
        # Replication identity: this replica's own base URL is what a
        # NotLeader redirect points at once it promotes, and what its
        # status document advertises for election probes.
        self.advertise_url = f"http://127.0.0.1:{bound_port}"
        if self.role == "leader" and not self.leader_url:
            self.leader_url = self.advertise_url
        return bound_port

    def shutdown(self) -> None:
        httpd = self._httpd
        self._httpd = None
        if httpd is not None:
            httpd.shutdown()
            # Tear down accepted connections (parked keep-alive REST conns +
            # watch streams) so their handler threads exit and pooled
            # clients see EOF — a lingering thread would keep serving this
            # dead server's store. Then release the LISTENING socket:
            # restart-in-place must be able to rebind the port immediately
            # (ThreadingHTTPServer.shutdown() alone never closes it).
            for sock in list(self._conns):
                try:
                    import socket as _sock
                    sock.shutdown(_sock.SHUT_RDWR)
                except Exception:  # noqa: BLE001 - already closing
                    pass
            httpd.server_close()
        if self.persistence is not None:
            self.persistence.close()


# ---------------------------------------------------------------------------
# The client: REST writes + reflector-fed informer cache
# ---------------------------------------------------------------------------


def iter_paged(conn, kind: str, limit: int, shard=None,
               max_restarts: int = 8):
    """Drive one complete paged LIST (`?limit=&continue=`) over an open
    HTTPConnection, yielding as lines arrive (bounded buffering):

    - ``("restart", None, (0, ""))`` — a continuation expired off the
      resume ring (410): the whole list restarts; the consumer must reset
      any accumulation;
    - ``("object", wire_dict, (wire_bytes, codec))`` — one listed object,
      with its decode-cost accounting (core/wire.py negotiated codec);
    - ``("done", trailer_dict, (0, ""))`` — the final PAGE trailer
      (carries ``listRv``/``epoch``), after which the generator ends.

    The ONE consumption loop `fetch_paged` (collecting oracle) and the
    reflector's `_paged_list_sync` (per-line dispatch) both ride —
    request building, the 410-restart policy, and trailer parsing cannot
    diverge between them."""
    from urllib.error import URLError

    for _attempt in range(max_restarts):
        token = ""
        expired = False
        while True:
            path = f"/api/v1/{kind}?limit={limit}"
            if shard is not None:
                path += f"&shard={shard[0]}/{shard[1]}"
            if token:
                path += f"&continue={token}"
            conn.request("GET", path, headers=wire.client_headers())
            resp = conn.getresponse()
            if resp.status == 410:
                resp.read()
                expired = True
                break
            if resp.status != 200:
                resp.read()
                raise URLError(f"paged {kind} list: HTTP {resp.status}")
            token = ""
            trailer: Optional[dict] = None
            while True:
                got = wire.read_event(resp)
                if got is None:
                    break
                d, nbytes, codec = got
                if d.get("type") == "PAGE":
                    token = d.get("continue") or ""
                    trailer = d
                elif d.get("object") is not None:
                    yield "object", d["object"], (nbytes, codec)
            if not token:
                yield "done", trailer or {}, (0, "")
                return
        if expired:
            yield "restart", None, (0, "")
    raise URLError(
        f"paged {kind} list: continuation kept expiring "
        f"after {max_restarts} restarts")


def fetch_paged(base_url: str, kind: str, limit: int = 1000,
                timeout: float = 60.0, max_restarts: int = 8) -> List[dict]:
    """Collect one complete paged LIST (`?limit=&continue=`) — the helper
    harnesses and oracles use instead of the full-cluster single-response
    GET."""
    import http.client as _hc

    host = base_url.rstrip("/").split("//", 1)[1]
    conn = _hc.HTTPConnection(host, timeout=timeout)
    try:
        out: List[dict] = []
        for what, payload, _line in iter_paged(conn, kind, limit,
                                               max_restarts=max_restarts):
            if what == "restart":
                out = []
            elif what == "object":
                out.append(payload)
            else:
                break
        return out
    finally:
        conn.close()


class KeepAliveClient:
    """Thread-local persistent HTTP/1.1 connections to one server.

    The apiserver handler already speaks HTTP/1.1 keep-alive; what burned
    CPU was the CLIENT side opening a fresh TCP connection per call (urllib
    does not pool), which also costs the ThreadingHTTPServer one thread
    spawn per request. At bind rates (>100/s per scheduler, every bind a
    POST) the setup tax dominated the write path — the profiled 1-shard
    bench spent 68s of a 78s run inside the serial host-commit loop, most
    of it connection overhead. One pooled connection per calling thread
    keeps the server thread persistent too.

    Transport-failure policy: the pooled connection is dropped, then
    - GET/PUT (idempotent on this surface — list/summary reads, node
      updates, lease renews) transparently retry ONCE on a fresh
      connection;
    - POST/DELETE retry once too, but ONLY when a REUSED connection died
      before yielding any response byte (RemoteDisconnected/reset/EPIPE —
      the keep-alive staleness signature: the server restarted or closed
      the parked conn, and a closed server socket RSTs late data, so the
      request was almost certainly never processed). Every verb on this
      surface tolerates the rare did-process replay: creates answer 409
      AlreadyExists (a caller-visible wart only when the response to a
      processed create was lost mid-crash), same-node bind replays answer
      200, deletes/status are idempotent. All other POST/DELETE failures
      surface a URLError to the caller's retry policy (RetryingClientset
      owns replay-409 forgiveness for ITS replays).
    """

    def __init__(self, base_url: str, timeout: float = 10.0):
        from urllib.parse import urlsplit
        sp = urlsplit(base_url.rstrip("/"))
        self._host = sp.hostname
        self._port = sp.port or 80
        self._base = base_url.rstrip("/")
        self._timeout = timeout
        self._local = threading.local()
        # Wire negotiation state (core/wire.py): None until the first
        # response proves what the server speaks. Request BODIES go out
        # binary only after a binary reply has been seen — a JSON-only
        # server must never receive a frame it cannot parse (the Accept
        # offer itself is always safe). Shared across threads; benignly
        # racy (worst case: one extra JSON body).
        self._server_wire: Optional[bool] = None

    def call(self, method: str, path: str, body: Optional[dict] = None,
             timeout: Optional[float] = None,
             headers: Optional[Dict[str, str]] = None,
             replay: bool = True):
        import http.client as _hc
        import io
        from urllib import error as urlerror

        offer = wire.client_headers()
        body_codec = (wire.BINARY if self._server_wire and offer
                      else wire.JSON)
        if body is not None:
            data = wire.encode(body, body_codec)
        else:
            data = None
        headers = dict(headers or (), **offer,
                       **{"Content-Type": wire.mime_for(body_codec)})
        t = timeout if timeout is not None else self._timeout
        # replay=False: the caller owns replays (HTTPClientset's
        # leader-routed writes — against a REPLICATED control plane a dead
        # connection may mean the leader itself died, and a blind same-host
        # replay would race the promotion; the caller must re-resolve the
        # leader first, then replay through the idempotent/409 surface).
        may_replay = replay and method in ("GET", "PUT")
        for attempt in (0, 1):
            conn = getattr(self._local, "conn", None)
            fresh = conn is None
            if fresh:
                conn = _hc.HTTPConnection(self._host, self._port, timeout=t)
                self._local.conn = conn
                try:  # headers+body go out as separate small segments;
                    # without NODELAY, Nagle holds the second on the
                    # peer's delayed ACK (~ms per request, even loopback)
                    import socket as _sock
                    conn.connect()
                    conn.sock.setsockopt(_sock.IPPROTO_TCP,
                                         _sock.TCP_NODELAY, 1)
                except Exception:  # noqa: BLE001 - connect errors surface
                    pass           # identically from request() below
            elif conn.timeout != t:
                conn.timeout = t
                if conn.sock is not None:
                    conn.sock.settimeout(t)
            try:
                conn.request(method, path, body=data, headers=headers)
                resp = conn.getresponse()
                payload = resp.read()
                status, reason, hdrs = resp.status, resp.reason, resp.msg
                if resp.will_close:
                    self._local.conn = None
                    conn.close()
            except Exception as e:  # noqa: BLE001 - transport failure
                self._local.conn = None
                try:
                    conn.close()
                except Exception:  # noqa: BLE001
                    pass
                # A REUSED connection torn down before yielding any response
                # byte is the keep-alive staleness signature (server
                # restarted or idle-closed the parked conn; a closed server
                # socket RSTs late data, so the request was almost certainly
                # never processed). Replay it once on a fresh connection for
                # every verb: this API surface tolerates the rare
                # did-process case too (creates answer 409 AlreadyExists,
                # same-node bind replays answer 200, deletes/status are
                # idempotent).
                stale = replay and not fresh and isinstance(
                    e, (_hc.RemoteDisconnected, ConnectionResetError,
                        BrokenPipeError))
                if (may_replay or stale) and not fresh and attempt == 0:
                    continue  # stale keep-alive connection: one fresh try
                if isinstance(e, urlerror.URLError):
                    raise
                raise urlerror.URLError(e) from e
            if status >= 400:
                # Error bodies are always JSON (the server's debug-plane
                # contract) — callers' .read()+jloads keep working.
                raise urlerror.HTTPError(f"{self._base}{path}", status,
                                         reason, hdrs, io.BytesIO(payload))
            if offer:
                # Learn the server's plane from a SUCCESS reply: binary
                # content-type => binary bodies from here on; a JSON 2xx
                # despite our offer => JSON-only server (never regress a
                # learned binary peer on a bodyless reply).
                if wire.codec_of_mime(
                        hdrs.get("Content-Type")) == wire.BINARY:
                    self._server_wire = True
                elif payload:
                    self._server_wire = False
            return wire.decode(payload) if payload else None


class HTTPClientset:
    """Clientset over the wire: writes are REST calls; reads serve from the
    reflector-maintained local cache; handler registration taps the informer
    fanout (events arrive on the reflector thread → the scheduler's inbox).

    Only the pod/node surface crosses the wire (the verbs the scheduler
    core exercises); the remaining listers return empty local dicts.

    Against a REPLICATED control plane (kubernetes_tpu/replication/) the
    base URL may be a FOLLOWER: reads (list/watch/RESUME, leases) serve
    from it, while every mutating verb routes through `_write_call` —
    follow a ``421 NotLeader`` redirect to the leader, and on a transport
    failure RE-RESOLVE the leader through ``/replication/status`` before
    the single replay (a blind same-host replay would race a promotion;
    the idempotent create-409 / same-node-bind-200 surface absorbs the
    rare did-process replay). ``fallbacks`` lists sibling read bases: when
    the base itself dies (follower kill), the reflector rotates to the
    next one and RESUMEs by rv — replicas share one rv/epoch space, so no
    re-list. A ``FAILOVER`` watch marker bumps ``failover_count`` (the
    scheduler's reconcile trigger) and pre-warms the leader route."""

    # Binds terminate at the apiserver's binding subresource, whose Omega
    # commit validation rejects overcommits with 409 — the property
    # shard.ShardMember's optimistic session patching relies on. The
    # FakeClientset binds unconditionally and must not claim it.
    validates_bind_capacity = True
    # Every write is a round trip over a socket. A scheduler given this
    # clientset (also under RetryingClientset, whose attribute lookups fall
    # through) hides it: its API dispatcher runs in thread mode and binds
    # go out in bulk (core/scheduler.py _dispatch_mode). The in-process
    # FakeClientset has no round trip and does not say so.
    remote_writes = True

    def __init__(self, base_url: str, sync_timeout: float = 30.0,
                 fallbacks=(), shard=None, extra_kinds=()):
        self.base = base_url.rstrip("/")
        # Opt-in workload-kind reflection (WORKLOAD_KINDS): controllers
        # pass extra_kinds=("replicasets", ...) and get a reflector thread
        # + raw wire-dict cache per kind; the default constructor stays at
        # the three store kinds so existing clients pay nothing new.
        self.extra_kinds = tuple(k for k in extra_kinds
                                 if k in WORKLOAD_KINDS)
        # Server-side shard filtering (core/watchcache.py): with
        # shard=(index, count), the pod watch opens `?shard=i/n` and the
        # server delivers full pod wire only for owned + wire-relevant
        # pods; the rest arrive as slim projections this client MERGES
        # onto its cache (pod_from_slim). The decode counters below are
        # what a sharded perf row surfaces per shard — the measurable 1/N.
        self.shard = tuple(shard) if shard else None
        self.watch_events_full = 0
        self.watch_events_slim = 0
        self.watch_bytes_full = 0
        self.watch_bytes_slim = 0
        # The same decode accounting split by (form, codec): which plane
        # (binary vs JSON) this client's watch/list decode actually ran
        # on — scheduler_watch_decoded_*{form,codec} reads these.
        self.wire_decode_events: Dict[tuple, int] = {
            ("full", wire.JSON): 0, ("full", wire.BINARY): 0,
            ("slim", wire.JSON): 0, ("slim", wire.BINARY): 0,
            ("delta", wire.JSON): 0, ("delta", wire.BINARY): 0}
        self.wire_decode_bytes: Dict[tuple, int] = {
            ("full", wire.JSON): 0, ("full", wire.BINARY): 0,
            ("slim", wire.JSON): 0, ("slim", wire.BINARY): 0,
            ("delta", wire.JSON): 0, ("delta", wire.BINARY): 0}
        # Delta plane (PR 18): per-kind wire-object caches — the base a
        # DELTA patch applies onto. Each kind's maps are touched ONLY by
        # that kind's reflector thread (lock-free by construction).
        # delta_fallbacks counts base-rv mismatches that forced a re-list.
        self._wire: Dict[str, Dict[str, dict]] = {}
        self._wire_rv: Dict[str, Dict[str, Optional[int]]] = {}
        self.delta_fallbacks = 0
        # Read plane: the base plus sibling replicas the reflector may
        # rotate to when the base dies (shared rv/epoch space -> RESUME).
        self._bases: List[str] = [self.base] + [
            b.rstrip("/") for b in fallbacks if b]
        self._base_idx = 0
        self._ka = KeepAliveClient(self.base)
        self._ka_cache: Dict[str, KeepAliveClient] = {self.base: self._ka}
        # Write plane: the resolved leader (None until a redirect or a
        # FAILOVER marker names one — writes optimistically try the base).
        self._leader_base: Optional[str] = None
        self.failover_count = 0  # FAILOVER markers seen (reconcile trigger)
        self.write_redirects = 0  # 421 NotLeader redirects followed
        self.leader_resolutions = 0  # transport-failure re-resolutions
        self.read_rotations = 0  # read-base failovers (dead follower)
        self.pods: Dict[str, Pod] = {}
        self.nodes: Dict[str, Node] = {}
        self.bindings: Dict[str, str] = {}
        # Gang state over the wire: the podgroups reflector fills these
        # ("ns/name" keys, same as the FakeClientset) so multi-process
        # shard members see one gang truth.
        self.pod_groups: Dict[str, object] = {}
        self.composite_pod_groups: Dict[str, object] = {}
        # Workload-kind caches ("ns/name" -> raw wire dict): controllers
        # read desired state straight from these — no typed twin.
        self.workloads: Dict[str, Dict[str, dict]] = {
            k: {} for k in self.extra_kinds}
        self._workload_handlers: Dict[str, List] = {
            k: [] for k in self.extra_kinds}
        # unused-surface listers (volume/DRA plugins see empty cluster state)
        self.namespaces: Dict[str, object] = {}
        self.pvs: Dict[str, object] = {}
        self.pvcs: Dict[str, object] = {}
        self.storage_classes: Dict[str, object] = {}
        self.csi_nodes: Dict[str, object] = {}
        self.resource_slices: Dict[str, list] = {}
        self.resource_claims: Dict[str, object] = {}
        self.device_classes: Dict[str, object] = {}
        self._pod_handlers: List = []
        self._node_handlers: List = []
        self._pod_group_handlers: List = []
        self._dispatch_lock = threading.Lock()
        self._stop = threading.Event()
        self._responses: List = []
        kinds = ("pods", "nodes", "podgroups") + self.extra_kinds
        self._synced = {k: threading.Event() for k in kinds}
        self._fatal: Dict[str, Exception] = {}
        self.last_sync: Dict[str, float] = {}
        # resourceVersion resume (reflector.go lastSyncResourceVersion):
        # the rv of the last event (or SYNC snapshot) each stream consumed;
        # reconnects ask the server to replay from here instead of
        # re-listing. relists/resumes count how each reconnect was served.
        self._last_rv: Dict[str, Optional[int]] = {k: None for k in kinds}
        for k in kinds:  # delta bases: one map pair per reflector thread
            self._wire[k] = {}
            self._wire_rv[k] = {}
        # Server boot epoch (from SYNC/RESUME): sent with the rv so a
        # restarted server (fresh counters) re-lists instead of resuming.
        self._epoch: Dict[str, Optional[str]] = {k: None for k in kinds}
        self.relists: Dict[str, int] = {k: 0 for k in kinds}
        self.resumes: Dict[str, int] = {k: 0 for k in kinds}
        self._threads: List[threading.Thread] = []
        for kind in kinds:
            t = threading.Thread(target=self._watch_loop, args=(kind,),
                                 name=f"reflector-{kind}", daemon=True)
            t.start()
            self._threads.append(t)
        for kind in kinds:
            if not self._synced[kind].wait(sync_timeout):
                self.close()  # stop the reflector threads before raising
                raise TimeoutError(f"reflector {kind} never synced")
            if kind in self._fatal:
                self.close()
                raise ConnectionError(
                    f"reflector {kind}: initial connection failed"
                ) from self._fatal[kind]

    # -- REST --------------------------------------------------------------

    def _call(self, method: str, path: str, body: Optional[dict] = None) -> dict:
        # Pooled keep-alive connections (one per calling thread): the bind
        # path POSTs once per scheduled pod, and per-call connection setup
        # was the dominant cost of the serial host-commit loop. Reads serve
        # from the (possibly follower) read base; mutations leader-route.
        if method == "GET":
            return self._ka.call(method, path, body)
        return self._write_call(method, path, body)

    # -- leader routing (replication/NotLeader redirect protocol) -----------

    def _ka_for(self, base: str) -> KeepAliveClient:
        client = self._ka_cache.get(base)
        if client is None:
            client = self._ka_cache[base] = KeepAliveClient(base)
        return client

    def _set_leader(self, base: str) -> None:
        base = base.rstrip("/")
        if base:
            self._leader_base = base

    def _rotate_read_base(self, from_idx: int) -> None:
        """Advance the shared read base one step. Idempotent per
        `from_idx`: both reflector streams fail together against the same
        dead replica and must not double-advance past a live one."""
        if len(self._bases) <= 1 or self._base_idx != from_idx:
            return
        self._base_idx = (from_idx + 1) % len(self._bases)
        self._ka = self._ka_for(self._bases[self._base_idx])
        self.read_rotations += 1

    def _err_body(self, e) -> dict:
        try:
            return wire.jloads(e.read() or b"{}")
        except Exception:  # noqa: BLE001 - already an error path
            return {}

    def _try_status(self, base: str) -> Optional[dict]:
        try:
            return self._ka_for(base).call(
                "GET", "/replication/status", timeout=2.0)
        except Exception:  # noqa: BLE001 - replica dead/unreachable
            return None

    def _resolve_leader(self) -> Optional[str]:
        """Who leads, per the live replicas' status documents. All claims
        are collected and the HIGHEST fencing epoch wins — a stale leader
        that has not yet learned it was deposed may still claim the role,
        and routing writes to it would lose them into a forked history.
        Followers' leader hints are probed one hop (a follower
        mid-election may still point at the dead leader — that hint fails
        its own probe and is skipped)."""
        self.leader_resolutions += 1
        claims: List = []  # (replEpoch, base) of every role=leader claim
        hints: List[str] = []
        for base in list(self._bases):
            st = self._try_status(base)
            if st is None:
                continue
            if st.get("role") == "leader":
                claims.append((int(st.get("replEpoch", 0)), base))
            elif st.get("leader"):
                hints.append(st["leader"].rstrip("/"))
        seen = {base for _, base in claims} | set(self._bases)
        for url in hints:
            if url in seen:
                continue
            seen.add(url)
            st = self._try_status(url)
            if st is not None and st.get("role") == "leader":
                claims.append((int(st.get("replEpoch", 0)), url))
        if claims:
            return max(claims)[1]
        return None

    def _write_call(self, method: str, path: str, body=None,
                    headers: Optional[Dict[str, str]] = None):
        """One mutating call with the NotLeader-redirect + single-replay
        contract: optimistic send to the resolved leader (or the base),
        follow at most one 421 redirect, and on a transport failure
        RE-RESOLVE the leader before the one replay. Exactly-once rides
        the server's idempotent surface (create->409 AlreadyExists,
        same-node bind->200), including replays that land on a freshly
        PROMOTED leader."""
        from urllib.error import HTTPError, URLError

        from .backoff import TransientAPIError

        if self._leader_base:
            client, tried = self._ka_for(self._leader_base), self._leader_base
        else:
            # The CURRENT read base — after a read-plane rotation self._ka
            # no longer points at self.base, and a redirect naming the
            # original base must still be followed.
            client, tried = self._ka, self._bases[self._base_idx]
        try:
            return client.call(method, path, body, headers=headers,
                               replay=False)
        except HTTPError as e:
            if e.code != 421:
                raise
            info = self._err_body(e)
            leader = (info.get("leader") or "").rstrip("/")
            if leader and leader != tried:
                # NotLeader redirect: one follow. The followed hop can
                # itself answer 421 (a freshly deposed leader pointing
                # onward mid-failover) — that too is "promotion in
                # flight", surfaced retriable, never a hard 4xx failure.
                self.write_redirects += 1
                self._set_leader(leader)
                try:
                    return self._ka_for(leader).call(
                        method, path, body, headers=headers, replay=False)
                except HTTPError as e2:
                    if e2.code != 421:
                        raise
                    self._leader_base = None
                    raise TransientAPIError(
                        "NotLeader after redirect: promotion in flight"
                    ) from e2
            # No redirect target (or a stale one pointing back at who we
            # just asked) — a deposed replica may not know the winner.
            # Try one status-probe resolution; failing that, surface
            # retriable — binds queue behind the retry layers until a
            # leader exists.
            self._leader_base = None
            resolved = self._resolve_leader()
            if resolved and resolved != tried:
                self._set_leader(resolved)
                return self._ka_for(resolved).call(
                    method, path, body, headers=headers, replay=False)
            raise TransientAPIError(
                "NotLeader: promotion in flight") from e
        except URLError:
            # The server we were writing to is gone (leader death /
            # restart). Re-resolve through the read plane FIRST, then
            # replay once — never a blind same-host replay.
            leader = self._resolve_leader()
            if leader is None:
                self._leader_base = None
                raise
            self._set_leader(leader)
            return self._ka_for(leader).call(
                method, path, body, headers=headers, replay=False)

    def create_pod(self, pod: Pod) -> Pod:
        self._call("POST", "/api/v1/pods", pod_to_wire(pod))
        return pod

    def create_node(self, node: Node) -> Node:
        self._call("POST", "/api/v1/nodes", node_to_wire(node))
        return node

    def update_node(self, node: Node) -> Node:
        self._call("PUT", f"/api/v1/nodes/{node.name}", node_to_wire(node))
        return node

    def delete_node(self, name: str) -> None:
        self._call("DELETE", f"/api/v1/nodes/{name}")

    def delete_pod(self, pod: Pod) -> None:
        self._call("DELETE", f"/api/v1/pods/{pod.uid}")

    def evict_pod(self, uid: str, node: str, intent: str) -> dict:
        """Eviction subresource: DELETE-then-recreate-pending, idempotent
        by `intent` (the server's WAL'd ledger answers retries with
        already=True — exactly-once across controller restart/failover).
        `node` guards against evicting a pod that moved since the plan
        (409 NodeMismatch)."""
        return self._call("POST", f"/api/v1/pods/{uid}/eviction",
                          {"intent": intent, "node": node}) or {}

    def create_pod_group(self, group):
        self._call("POST", "/api/v1/podgroups", pod_group_to_wire(group))
        return group

    def create_composite_pod_group(self, cpg):
        self._call("POST", "/api/v1/podgroups", pod_group_to_wire(cpg))
        return cpg

    # -- workload kinds (WORKLOAD_KINDS: raw wire dicts over the wire) ------

    def create_workload(self, kind: str, w: dict) -> dict:
        """POST — 409 AlreadyExists on a duplicate name (the caller's
        create-409-is-success seam handles retries)."""
        return self._call("POST", f"/api/v1/{kind}", dict(w)) or {}

    def put_workload(self, kind: str, w: dict) -> dict:
        """Idempotent named upsert: PUT /api/v1/{kind}/{ns}/{name}."""
        ns = w.get("namespace") or "default"
        return self._call(
            "PUT", f"/api/v1/{kind}/{ns}/{w['name']}", dict(w)) or {}

    def delete_workload(self, kind: str, ns: str, name: str) -> None:
        self._call("DELETE", f"/api/v1/{kind}/{ns or 'default'}/{name}")

    def delete_pod_voluntary(self, uid: str) -> None:
        """Voluntary pod delete (rolling-update scale-down): the server
        runs the PDB precondition and answers 429 DisruptionBudget when
        committing it would breach minAvailable."""
        self._call("DELETE", f"/api/v1/pods/{uid}?voluntary=true")

    def on_workload_event(self, kind: str, handler) -> None:
        """Register (action, old, new_wire_dict) fanout for one reflected
        workload kind (must have been named in extra_kinds)."""
        self._workload_handlers[kind].append(handler)

    def node_heartbeat_ages(self) -> Dict[str, float]:
        """Seconds-since-last-heartbeat per node, leader-routed (the ages
        live only on the leader — followers answer 421 and _write_call
        follows the redirect even though this is a read)."""
        got = self._write_call("GET", "/api/v1/nodes/heartbeats") or {}
        return dict(got.get("ages") or {})

    def bind(self, pod: Pod, node_name: str) -> None:
        # Trace propagation (core/spans.py): a sampled pod's bind carries
        # its context in the X-Trace-Context header and records the
        # bind.post span around the POST round trip.
        tr = _spans.default_tracer()
        ctx = tr.context_for(pod.uid)
        if not tr.wants(ctx):
            self._write_call("POST", f"/api/v1/pods/{pod.uid}/binding",
                             {"node": node_name})
            return
        t0 = time.perf_counter()
        try:
            self._write_call(
                "POST", f"/api/v1/pods/{pod.uid}/binding",
                {"node": node_name},
                headers={_spans.TRACE_HEADER: _spans.format_ctx(ctx)})
        finally:
            tr.record("bind.post", ctx, time.perf_counter() - t0,
                      node=node_name)

    def bind_many(self, pairs) -> list:
        """Bulk binding commits (POST /api/v1/bindings): one request for a
        drained dispatcher bind queue. Per-item verdicts come back in a 200
        envelope; each non-200 maps to the HTTPError the single-bind path
        would have raised (the conflict-requeue seam keys on .code == 409
        and the reason string naming AlreadyBound/OutOfCapacity)."""
        import io
        from urllib.error import HTTPError
        tr = _spans.default_tracer()
        items = []
        sampled = []  # contexts to close bind.post spans for
        for p, node in pairs:
            item = {"uid": p.uid, "node": node}
            ctx = tr.context_for(p.uid)
            if tr.wants(ctx):
                # Bulk-bind batch membership rides per-item tctx fields —
                # the server opens api.bind per item under this context.
                item["tctx"] = _spans.format_ctx(ctx)
                sampled.append(ctx)
            items.append(item)
        t0 = time.perf_counter()
        res = self._call("POST", "/api/v1/bindings", items)
        dur = time.perf_counter() - t0
        for ctx in sampled:
            tr.record("bind.post", ctx, dur, bulk=len(pairs))
        out = []
        for i, (p, _node) in enumerate(pairs):
            item = res[i] if res is not None and i < len(res) else {
                "code": 500, "error": "short bulk-bind response"}
            code = item.get("code", 200)
            out.append(None if code < 400 else HTTPError(
                f"{self.base}/api/v1/bindings", code,
                item.get("error", ""), None,
                io.BytesIO(wire.jdumps(item).encode())))
        return out

    def patch_pod_status(self, pod: Pod, nominated_node_name: str = "",
                         phase: str = "") -> None:
        self._call("POST", f"/api/v1/pods/{pod.uid}/status",
                   {"nominatedNodeName": nominated_node_name, "phase": phase})
        local = self.pods.get(pod.uid)
        if local is not None and nominated_node_name:
            local.nominated_node_name = nominated_node_name

    def update_pod(self, pod: Pod) -> Pod:  # parity stub for the surface
        return pod

    # -- slim-pod hydration (shard adoption; core/watchcache.py) ------------

    def hydrate_pods(self, uids) -> int:
        """Replace slim-cached pods with their full wire (GET ?uids=...,
        served from the server's watch cache). Used when shard ownership
        GROWS past the stream's static filter (adoption): pods this shard
        must now SCHEDULE arrived slim and need their real spec. The local
        binding view is preserved (a racing BOUND flows through the
        ordered stream as usual), and pods deleted meanwhile are skipped.
        No handler fanout: callers re-read `self.pods` — the pods are
        pending and foreign-until-now, so no cache/queue state exists."""
        uids = [u for u in uids if u]
        hydrated = 0
        for i in range(0, len(uids), 64):
            chunk = uids[i:i + 64]
            wires = self._call(
                "GET", "/api/v1/pods?uids=" + ",".join(chunk)) or []
            with self._dispatch_lock:
                for w in wires:
                    pod = pod_from_wire(w)
                    cur = self.pods.get(pod.uid)
                    if cur is None:
                        continue  # deleted while hydrating
                    pod.node_name = cur.node_name
                    pod.deletion_ts = cur.deletion_ts
                    self.pods[pod.uid] = pod
                    hydrated += 1
        return hydrated

    def hydrate_pod(self, uid: str) -> Optional[Pod]:
        """Single-pod hydration (the per-event adoption path): returns the
        full cached pod, or None when it vanished or the fetch failed."""
        try:
            self.hydrate_pods([uid])
        except Exception:  # noqa: BLE001 - transient; sweep retries
            return None
        pod = self.pods.get(uid)
        if pod is None or getattr(pod, "wire_slim", False):
            return None
        return pod

    # -- shard leases (shard/leases.py coordination surface) ----------------

    def list_leases(self) -> List[dict]:
        return self._call("GET", "/api/v1/leases")

    def upsert_lease(self, name: str, holder: str,
                     duration: float) -> Optional[dict]:
        """Acquire-or-renew; None when the lease is held by someone else
        (HTTP 409) — the CAS loss a ShardMember treats as 'not mine'."""
        from urllib.error import HTTPError
        try:
            return self._call("PUT", f"/api/v1/leases/{name}",
                              {"holder": holder,
                               "leaseDurationSeconds": duration})
        except HTTPError as e:
            if e.code == 409:
                return None
            raise

    # -- informer registration (scheduler event handlers) -------------------

    def on_pod_event(self, handler) -> None:
        # Replay-then-subscribe under the dispatch lock: live events cannot
        # interleave with (or duplicate) the attach-time replay.
        with self._dispatch_lock:
            for p in list(self.pods.values()):
                handler("add", None, p)
            self._pod_handlers.append(handler)

    def on_node_event(self, handler) -> None:
        with self._dispatch_lock:
            for n in list(self.nodes.values()):
                handler("add", None, n)
            self._node_handlers.append(handler)

    def on_namespace_event(self, handler) -> None:
        pass

    def on_pod_group_event(self, handler) -> None:
        # Replay-then-subscribe, FakeClientset parity: handlers get every
        # known group (plain then composite) once, then live upserts.
        with self._dispatch_lock:
            for g in list(self.pod_groups.values()):
                handler(g)
            for g in list(self.composite_pod_groups.values()):
                handler(g)
            self._pod_group_handlers.append(handler)

    def on_storage_event(self, handler) -> None:
        pass

    def attach_pv_controller(self, ctrl) -> None:
        pass

    # -- reflector (ListAndWatch: paged list, then watch from the anchor) ---

    def _paged_list_sync(self, kind: str, host: str):
        """Reflector (re-)list as a PAGED list (`?limit=&continue=`,
        docs/SCALE.md): dispatch each object as its line arrives (bounded
        client-side buffering — never a full-cluster response body), run
        the Replace barrier at the end, and return ``(anchor, epoch)`` —
        the list-anchor rv the following watch attach RESUMEs from,
        replaying exactly the events that happened while paging. The
        watermark is NOT published to ``_last_rv`` here: it becomes the
        client's resume point only once the watch's RESUME marker
        confirms the stream is live (a death in the gap re-lists rather
        than resuming past events no stream was attached for). A 410
        ExpiredContinue restarts the list from scratch; transport
        failures raise to the watch loop's failure/rotation handling."""
        import http.client as _hc
        import os as _os

        limit = int(_os.environ.get("TPU_SCHED_LIST_PAGE", "500"))
        shard = self.shard if kind == "pods" else None
        conn = _hc.HTTPConnection(host, timeout=60)
        try:
            seen: set = set()
            trailer: dict = {}
            nwire: Dict[str, dict] = {}
            for what, payload, line in iter_paged(conn, kind, limit,
                                                  shard=shard):
                if what == "restart":
                    # Anchor off the ring mid-list: the iterator restarts
                    # the list; objects already dispatched simply upsert
                    # again, but the Replace seen-set must reset.
                    seen = set()
                    nwire = {}
                    continue
                if what == "done":
                    trailer = payload
                    break
                obj = payload
                # Decode-cost accounting, same split as the watch loop
                # (a filtered paged list delivers foreign plain pods
                # slim); `line` is (wire_bytes, codec) from iter_paged.
                self._note_decode(
                    "slim" if obj.get("slim") else "full",
                    line[1], line[0])
                if not obj.get("slim"):
                    nwire[wire_key(kind, obj)] = obj
                with self._dispatch_lock:
                    seen.add(wire_key(kind, obj))
                    self._dispatch(kind, "ADDED", obj)
            with self._dispatch_lock:
                self._replace_barrier(kind, seen)
            # Replace semantics for the delta bases too: the listed set
            # IS the new base map, every rv unknown (accept-if-unknown —
            # replay ordering guarantees the held state is the minter's
            # base or a convergent ahead-state). Reflector thread only.
            self._wire[kind] = nwire
            self._wire_rv[kind] = {}
            self.relists[kind] += 1
            anchor = trailer.get("listRv")
            return ((int(anchor) if anchor is not None else None),
                    trailer.get("epoch"))
        finally:
            conn.close()

    def _note_decode(self, form: str, codec: str, nbytes: int) -> None:
        """One decoded wire record's cost accounting: by form (full wire
        vs slim projection — the shard filter's 1/N) and by codec (binary
        vs JSON — the wire refactor's raw-bytes lever). Reflector-thread
        only; the legacy aggregate counters stay for existing readers."""
        if form == "slim":
            self.watch_events_slim += 1
            self.watch_bytes_slim += nbytes
        else:
            self.watch_events_full += 1
            self.watch_bytes_full += nbytes
        key = (form, codec)
        self.wire_decode_events[key] = self.wire_decode_events.get(key, 0) + 1
        self.wire_decode_bytes[key] = (
            self.wire_decode_bytes.get(key, 0) + nbytes)

    def _track_wire(self, kind: str, typ: str, obj,
                    rv: Optional[int]) -> None:
        """Advance this kind's delta-base cache exactly the way the
        server's watch cache advanced its snapshot (core/watchcache.py
        `_apply_object` + the `_obj_rv` contract) — bases must be
        bit-identical whenever the recorded rv matches a DELTA's baseRv.
        Reflector-thread only (one thread per kind), so no lock. Slim
        projections and rv-less events POP the base: a stale base
        surviving into the accept-if-unknown path would be a SILENT
        divergence, the one failure mode the delta plane must not have."""
        if type(obj) is not dict:
            return
        try:
            key = wire_key(kind, obj)
        except KeyError:
            return
        w, wrv = self._wire[kind], self._wire_rv[kind]
        if typ == "DELETED" or obj.get("slim"):
            w.pop(key, None)
            wrv.pop(key, None)
            return
        if typ == "BOUND":
            cur = w.get(key)
            if cur is None:
                wrv.pop(key, None)
                return
            obj = dict(cur, nodeName=obj.get("nodeName", ""))
        w[key] = obj
        if rv is not None:
            wrv[key] = rv
        else:
            wrv.pop(key, None)

    def _delta_materialize(self, kind: str, event: dict):
        """Apply a DELTA event onto the cached base. Accept when the base
        exists and its recorded rv is unknown (fresh from a paged list —
        replay ordering makes the held state the minter's base or a
        convergent ahead-state) or equals the event's baseRv; anything
        else returns None and the caller falls back to a full re-list
        (never a silent patch onto a divergent base)."""
        key = event.get("key")
        base = self._wire[kind].get(key)
        have = self._wire_rv[kind].get(key)
        if base is None or (have is not None
                            and have != event.get("baseRv")):
            return None
        return wire.apply_patch(base, event.get("patch") or [])

    def _watch_loop(self, kind: str) -> None:
        """client-go reflector behavior (tools/cache/reflector.go:470): on
        stream EOF/timeout, re-connect with the last-seen resourceVersion.
        Inside the server's backlog window the stream opens with RESUME and
        replays exactly the missed events — the local cache converges
        without a re-list. Outside the window (or on first connect) the
        stream replays ADDED for every live object then SYNC, and objects
        that vanished during the outage dispatch DELETED at the SYNC
        barrier (the reflector's Replace semantics). Only a failure of the
        FIRST connection is fatal (recorded in _fatal so the constructor
        raises instead of returning a dead clientset)."""
        # Raw HTTPConnection so close() can shut the SOCKET down —
        # HTTPResponse.close() on an endless chunked stream would block
        # draining to EOF.
        import http.client as _hc
        import time as _time
        backoff = 0.05
        conn_fails = 0  # consecutive failures against the CURRENT read base
        while not self._stop.is_set():
            base_idx = self._base_idx
            host = self._bases[base_idx].split("//", 1)[1]
            fresh = False
            anchor: Optional[int] = None
            anchor_epoch: Optional[str] = None
            if self._last_rv[kind] is None or self._epoch[kind] is None:
                # No resumable watermark (first sync, or a TOO_OLD/epoch
                # break): paged list FIRST (Replace semantics, bounded
                # pages), then watch from the list anchor — the
                # full-cluster ADDED replay never materializes into a
                # stream queue for this client.
                try:
                    anchor, anchor_epoch = self._paged_list_sync(kind, host)
                    fresh = True
                except Exception as e:  # noqa: BLE001 - list failed
                    if not self._synced[kind].is_set():
                        # Initial sync failed: dead on arrival is an
                        # error, not an empty cluster.
                        self._fatal[kind] = e
                        self._synced[kind].set()
                        return
                    conn_fails += 1
                    if conn_fails >= 3:
                        self._rotate_read_base(base_idx)
                        conn_fails = 0
                    if self._stop.wait(backoff):
                        return
                    backoff = min(backoff * 2, 5.0)
                    continue
            try:
                conn = _hc.HTTPConnection(host, timeout=60)
                path = f"/api/v1/{kind}?watch=true&paged=true"
                if kind == "pods" and self.shard is not None:
                    path += f"&shard={self.shard[0]}/{self.shard[1]}"
                if fresh and anchor is not None and anchor_epoch is not None:
                    # Attach straight after a completed paged list: resume
                    # from the LIST ANCHOR (the ring replays exactly the
                    # events that happened while paging). `fresh` also
                    # allows a selector-ful FILTERED resume for this one
                    # attach (the cache was just rebuilt from full
                    # objects — core/watchcache.py).
                    path += (f"&resourceVersion={anchor}"
                             f"&epoch={anchor_epoch}&fresh=true")
                elif (self._last_rv[kind] is not None
                        and self._epoch[kind] is not None):
                    path += (f"&resourceVersion={self._last_rv[kind]}"
                             f"&epoch={self._epoch[kind]}")
                # stream_headers offers the session plane on top of the
                # plain binary offer (and nothing when the process is
                # JSON-pinned) — the server replying with the session
                # MIME is also its promise to ship DELTA frames.
                conn.request("GET", path, headers=wire.stream_headers())
                resp = conn.getresponse()
                session = (wire.SessionDecoder()
                           if wire.session_of_mime(
                               resp.getheader("Content-Type")) else None)
                conn_fails = 0
            except Exception as e:  # noqa: BLE001 - connect failure
                if not self._synced[kind].is_set():
                    # Initial connection failed: dead on arrival is an error,
                    # not an empty cluster.
                    self._fatal[kind] = e
                    self._synced[kind].set()
                    return
                # Read-plane failover: when the base itself stays dead
                # (follower kill), rotate to a sibling replica and RESUME
                # from the shared rv/epoch space — no re-list, and the
                # stall stays bounded by a few connect backoffs.
                conn_fails += 1
                if conn_fails >= 3:
                    self._rotate_read_base(base_idx)
                    conn_fails = 0
                if self._stop.wait(backoff):
                    return
                backoff = min(backoff * 2, 5.0)
                continue
            self._responses.append(conn)
            got_sync = False
            resync_seen: Optional[set] = set()  # keys replayed pre-SYNC
            try:
                while not self._stop.is_set():
                    got = wire.read_event(resp, session=session)
                    if got is None:
                        break  # EOF: server went away — re-list + re-watch
                    event, nbytes, codec = got
                    typ = event["type"]
                    if typ == "DELTA":
                        obj = self._delta_materialize(kind, event)
                        if obj is None:
                            # Base-rv mismatch: the one legal answer is a
                            # full re-list — clear the watermark and
                            # reconnect fresh. Never patch a divergent
                            # base.
                            self.delta_fallbacks += 1
                            self._last_rv[kind] = None
                            got_sync = True  # progress, not a dead stream
                            break
                        self._note_decode("delta", codec, nbytes)
                        event = {"type": "MODIFIED", "object": obj,
                                 "rv": event.get("rv")}
                        typ = "MODIFIED"
                    elif typ in ("ADDED", "MODIFIED", "DELETED"):
                        # Decode-cost accounting (the 1/N the shard filter
                        # buys, times the codec's bytes-per-event): slim
                        # projections vs full object wire, binary vs JSON.
                        self._note_decode(
                            "slim" if (event.get("object") or {}).get("slim")
                            else "full", codec, nbytes)
                    if typ == "BOOKMARK":
                        continue  # server idle heartbeat
                    if typ == "FAILOVER":
                        # Control-plane leadership moved (promotion, or our
                        # follower re-tailed to a new leader): pre-warm the
                        # write route and bump the reconcile trigger — the
                        # scheduler sweeps for binds the dead leader acked
                        # but never shipped.
                        if event.get("leader"):
                            self._set_leader(event["leader"])
                        self.failover_count += 1
                        continue
                    if typ == "TOO_OLD":
                        # The resume window no longer covers our watermark
                        # (ring overran, or the server is a new epoch):
                        # clear it and re-list PAGED on the next loop
                        # iteration — never a full ADDED replay.
                        self._last_rv[kind] = None
                        got_sync = True  # progress, not a stream failure
                        break
                    if typ == "RESUME":
                        # Incremental reconnect: the server will replay the
                        # missed tail — the local cache stays authoritative,
                        # so no Replace barrier runs.
                        resync_seen = None
                        got_sync = True
                        backoff = 0.05
                        self.resumes[kind] += 1
                        if fresh and anchor is not None:
                            # The stream is LIVE from the list anchor:
                            # publish it as the resume watermark (replayed
                            # events advance it from here). Publishing
                            # earlier would let a death in the list→watch
                            # gap silently resume past unwatched events.
                            self._last_rv[kind] = anchor
                        if event.get("epoch") is not None:
                            self._epoch[kind] = event["epoch"]
                        self._synced[kind].set()
                        self.last_sync[kind] = _time.monotonic()
                        continue
                    if typ == "SYNC":
                        with self._dispatch_lock:
                            self._replace_barrier(kind, resync_seen)
                        resync_seen = None
                        got_sync = True
                        backoff = 0.05  # healthy stream: reset the backoff
                        self.relists[kind] += 1
                        if event.get("rv") is not None:
                            self._last_rv[kind] = event["rv"]
                        if event.get("epoch") is not None:
                            self._epoch[kind] = event["epoch"]
                        self._synced[kind].set()
                        self.last_sync[kind] = _time.monotonic()
                        continue
                    # Delta-base upkeep BEFORE dispatch (this thread owns
                    # the kind's maps; handlers must never see a base the
                    # server no longer diffs against).
                    self._track_wire(kind, typ, event.get("object"),
                                     event.get("rv"))
                    with self._dispatch_lock:
                        if resync_seen is not None:
                            resync_seen.add(wire_key(kind, event["object"]))
                        self._dispatch(kind, typ, event["object"])
                        if event.get("rv") is not None:
                            self._last_rv[kind] = event["rv"]
            except Exception:  # noqa: BLE001 - stream torn down / timeout
                pass
            finally:
                try:
                    self._responses.remove(conn)
                except ValueError:
                    pass
                try:
                    conn.close()
                except Exception:  # noqa: BLE001
                    pass
            # A stream that died before delivering SYNC counts as a failure:
            # back off exponentially (client-go ListAndWatch backoff) so a
            # crash-looping server isn't hammered at ~20 reconnects/sec.
            if self._stop.wait(backoff if not got_sync else 0.05):
                return
            if not got_sync:
                backoff = min(backoff * 2, 5.0)

    def _replace_barrier(self, kind: str, seen: Optional[set]) -> None:
        """End of a (re-)list window: local objects the server did NOT replay
        no longer exist — dispatch their deletion (reflector Replace)."""
        if seen is None:
            return
        if kind == "pods":
            for uid in [u for u in self.pods if u not in seen]:
                self._dispatch(kind, "DELETED", pod_to_wire(self.pods[uid]))
        elif kind == "podgroups":
            for key in [k for k in self.pod_groups if k not in seen]:
                self._dispatch(kind, "DELETED",
                               pod_group_to_wire(self.pod_groups[key]))
            for key in [k for k in self.composite_pod_groups
                        if k not in seen]:
                self._dispatch(
                    kind, "DELETED",
                    pod_group_to_wire(self.composite_pod_groups[key]))
        elif kind in self.workloads:
            cache = self.workloads[kind]
            for key in [k for k in cache if k not in seen]:
                self._dispatch(kind, "DELETED", cache[key])
        else:
            for name in [n for n in self.nodes if n not in seen]:
                self._dispatch(kind, "DELETED", node_to_wire(self.nodes[name]))

    def _dispatch(self, kind: str, typ: str, obj: dict) -> None:
        if typ == "BOUND":
            # Slim bind event: the full pod is already cached (its ADDED
            # preceded it on this ordered stream) — patch nodeName on a copy
            # instead of rebuilding the pod from a full wire dict. The copy
            # keeps old/new distinct for handlers AND shares the spec-derived
            # memos (signature caches) with the cached object.
            old = self.pods.get(obj["uid"])
            if old is None:
                return  # pod unseen on this stream; the next re-list corrects
            tctx = obj.get("tctx")
            if tctx:
                # Foreign-shard observation: this watcher decoded another
                # scheduler's sampled bind — the span joins the binder's
                # trace (same id), closing the cross-process chain.
                ctx = _spans.parse_ctx(tctx)
                if ctx is not None:
                    _spans.default_tracer().event(
                        "bound.observe", ctx, node=obj.get("nodeName", ""))
            pod = copy.copy(old)
            pod.node_name = obj.get("nodeName", "")
            self.pods[pod.uid] = pod
            if pod.node_name:
                self.bindings[pod.uid] = pod.node_name
            else:
                self.bindings.pop(pod.uid, None)
            for h in self._pod_handlers:
                h("update", old, pod)
            return
        action = {"ADDED": "add", "MODIFIED": "update", "DELETED": "delete"}[typ]
        if kind == "pods":
            if obj.get("slim"):
                # Slim projection (shard-filtered stream): MERGE onto the
                # cached copy — the spec is immutable on this surface, so
                # any previously-delivered full wire stays authoritative
                # and only the projection fields (nodeName/deletionTs)
                # patch. Absent a cached copy, pod_from_slim builds the
                # minimal accounting pod and marks it `wire_slim` (the
                # shard plane hydrates before ever SCHEDULING one).
                pod = pod_from_slim(obj, self.pods.get(obj["uid"]))
            else:
                pod = pod_from_wire(obj)
            old = self.pods.get(pod.uid)
            if action == "add" and old is not None:
                # Replayed ADDED of a known object: a re-list replay, or
                # the post-paged-list watch replaying a create a later
                # page had already served — upsert as an update, handlers
                # must never see a duplicate add.
                action = "update"
            if action == "delete":
                self.pods.pop(pod.uid, None)
                self.bindings.pop(pod.uid, None)
            else:
                self.pods[pod.uid] = pod
                if pod.node_name:
                    self.bindings[pod.uid] = pod.node_name
                else:
                    # Re-list replay (or status update) of an UNBOUND pod:
                    # a stale binding from before a server restart must not
                    # survive in the informer cache.
                    self.bindings.pop(pod.uid, None)
            for h in self._pod_handlers:
                h(action, old, pod)
        elif kind == "podgroups":
            g = pod_group_from_wire(obj)
            target = (self.composite_pod_groups if obj.get("composite")
                      else self.pod_groups)
            key = f"{g.namespace}/{g.name}"
            if action == "delete":
                # Replace-barrier correction only (the server has no group
                # delete verb): drop the local copy, no handler channel for
                # group deletion exists (FakeClientset parity).
                target.pop(key, None)
                return
            known = key in target
            target[key] = g
            if not known:
                # Single-arg handler fanout, FakeClientset parity: only
                # first sight fans out — a re-list replay of a known group
                # must not re-register it with the gang queue.
                for h in self._pod_group_handlers:
                    h(g)
        elif kind in self.workloads:
            # Workload kinds cache RAW wire dicts — controllers consume
            # desired state fields directly; no typed object exists.
            cache = self.workloads[kind]
            key = f'{obj.get("namespace") or "default"}/{obj.get("name")}'
            old = cache.get(key)
            if action == "add" and old is not None:
                action = "update"
            if action == "delete":
                cache.pop(key, None)
            else:
                cache[key] = obj
            for h in self._workload_handlers.get(kind, ()):
                h(action, old, obj)
        else:
            node = node_from_wire(obj)
            old = self.nodes.get(node.name)
            if action == "add" and old is not None:
                action = "update"  # replayed ADDED of a known node
            if action == "delete":
                self.nodes.pop(node.name, None)
            else:
                self.nodes[node.name] = node
            for h in self._node_handlers:
                h(action, old, node)

    def close(self) -> None:
        self._stop.set()
        # Snapshot: reflector threads remove() dead connections concurrently.
        for conn in list(self._responses):
            _shutdown_conn(conn)
        for t in self._threads:
            t.join(timeout=2)


def _shutdown_conn(conn) -> None:
    try:
        import socket
        if conn.sock is not None:
            conn.sock.shutdown(socket.SHUT_RDWR)
            conn.sock.close()
    except Exception:  # noqa: BLE001
        pass


def main(argv=None) -> int:
    """Standalone apiserver process (`python -m kubernetes_tpu.core.apiserver
    --port N`): serves the REST+watch surface on a real socket until
    SIGTERM/SIGINT — the other half of the two-OS-process integration seam
    (ref test/integration/framework/test_server.go:78 StartTestServer)."""
    import argparse
    import os
    import signal

    ap = argparse.ArgumentParser(prog="kubernetes-tpu-apiserver")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--data-dir", default="",
                    help="durable store directory (WAL + snapshot, "
                         "core/wal.py); empty = in-memory only")
    ap.add_argument("--fsync", action="store_true",
                    help="fsync every WAL record (survives power loss, not "
                         "just process death)")
    ap.add_argument("--snapshot-every", type=int, default=2048,
                    help="compact the WAL into a snapshot every N records")
    ap.add_argument("--replicate-from", default="",
                    help="run as a FOLLOWER replica of this leader base URL "
                         "(kubernetes_tpu/replication/): tail its WAL, "
                         "serve reads, redirect writes")
    ap.add_argument("--replica-rank", type=int, default=1,
                    help="election order among followers (lowest live rank "
                         "promotes on leader death)")
    ap.add_argument("--repl-lease-duration", type=float, default=0.0,
                    help="leader-lease/failover-detection period in seconds "
                         "(0 on a standalone leader = no replication lease)")
    args = ap.parse_args(argv)
    # The server is thread-per-connection with ~a dozen live threads under
    # a sharded cluster (creators, watch streams, shard write conns). At
    # CPython's default 5ms switch interval a request handler that needs a
    # few GIL slices waits out multiple quanta — measured as ~4ms/request
    # turnaround with the CPU nearly idle (~240 creates/s arrival ceiling).
    # A 1ms interval trades a little context-switch overhead for ~5x lower
    # write-plane latency.
    import sys as _sys
    _sys.setswitchinterval(0.001)
    api = APIServer(data_dir=args.data_dir or None, fsync=args.fsync,
                    snapshot_every=args.snapshot_every)
    repl_lease = args.repl_lease_duration
    tail = None
    if args.replicate_from:
        from ..replication import ReplicationTail
        tail = ReplicationTail(api, args.replicate_from,
                               rank=max(1, args.replica_rank),
                               lease_duration=repl_lease or 2.0)
        # Synchronous initial sync BEFORE announcing ready: a cold
        # follower installs the leader snapshot, a restarted one already
        # recovered its own WAL above and just re-tails the delta.
        tail.bootstrap()
    # Observability (docs/OBSERVABILITY.md): label this process's spans and
    # install the flight recorder into the durable data dir (or the
    # explicit TPU_SCHED_FLIGHTREC_DIR). The periodic dump is what a chaos
    # kill -9 leaves behind — no handler observes SIGKILL.
    api.tracer.proc = ("apiserver" if tail is None
                       else f"apiserver-r{api.replica_rank}")
    flight = None
    flight_dir = os.environ.get("TPU_SCHED_FLIGHTREC_DIR") or args.data_dir
    if flight_dir:
        from .spans import FlightRecorder
        flight = FlightRecorder(flight_dir, tracer=api.tracer,
                                apiserver=api).install(
            at_exit=True,
            autodump_interval=float(
                os.environ.get("TPU_SCHED_FLIGHTREC_INTERVAL", "5.0")))
    api.gc_clock = _spans.GcClock().install()
    port = api.serve(args.port)
    lease = None
    if tail is not None:
        # The tail thread starts only after serve(): election needs this
        # replica's advertise_url to skip itself in the peer probe. The
        # LeaderLease no-ops until a promotion makes this replica leader.
        from ..replication import LeaderLease
        tail.start()
        lease = LeaderLease(api, identity=f"apiserver-r{api.replica_rank}",
                            duration=repl_lease or 2.0).start()
    elif repl_lease > 0:
        from ..replication import LeaderLease
        lease = LeaderLease(api, identity="apiserver-leader",
                            duration=repl_lease).start()
    # "serving on" stays the FIRST line: spawn harnesses select()+readline()
    # on it, and a buffered readline would swallow any earlier line together
    # with this one (leaving select blocked on a drained pipe).
    print(f"kubernetes-tpu-apiserver: serving on 127.0.0.1:{port}",
          flush=True)
    if tail is not None:
        print(f"kubernetes-tpu-apiserver: follower rank="
              f"{api.replica_rank} of {args.replicate_from} "
              f"seq={api._repl_seq} replEpoch={api.repl_epoch}", flush=True)
    if api.persistence is not None:
        p = api.persistence
        print(f"kubernetes-tpu-apiserver: recovered {api.recovered_objects} "
              f"objects (wal={p.replayed_records} torn="
              f"{p.torn_records_discarded}) epoch={api.epoch} "
              f"rv={dict(api._seq)}", flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    stop.wait()
    if tail is not None:
        tail.stop()
    if lease is not None:
        lease.stop()
    api.shutdown()
    api.gc_clock.close()
    if flight is not None:
        flight.dump("shutdown")
        flight.close()
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
