"""PriorityQueue: the three-stage pending-pod store.

Re-expresses pkg/scheduler/backend/queue/scheduling_queue.go (:186-269):
- activeQ   — heap ordered by the QueueSort plugin (priority, FIFO);
- backoffQ  — heap ordered by backoff expiry; exponential backoff
              1s→10s (backoff_queue.go:249 calculateBackoffDuration);
- unschedulableEntities — tried-and-failed pods, flushed to active/backoff
  after podMaxInUnschedulablePodsDuration (5 min) or on cluster events
  (MoveAllToActiveOrBackoffQueue :1817) filtered by per-plugin QueueingHints
  (isPodWorthRequeuing :582, approximated here by the event→plugin map).

Single-threaded by design: the TPU scheduling loop is one pipeline, so `pop`
returns None when empty instead of blocking on a condvar.
"""

from __future__ import annotations

import heapq
import itertools
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

from ..api.types import Pod
from .framework import Status
from .node_info import PodInfo

DEFAULT_POD_INITIAL_BACKOFF = 1.0
DEFAULT_POD_MAX_BACKOFF = 10.0
DEFAULT_MAX_IN_UNSCHEDULABLE_DURATION = 300.0

# Cluster events (framework/types.go ClusterEvent) — used to decide which
# unschedulable pods a delivered event can unblock.
EVENT_POD_ADD = "Pod/Add"
EVENT_POD_DELETE = "Pod/Delete"
EVENT_ASSIGNED_POD_ADD = "AssignedPod/Add"
EVENT_ASSIGNED_POD_DELETE = "AssignedPod/Delete"
EVENT_NODE_ADD = "Node/Add"
EVENT_NODE_UPDATE = "Node/Update"
EVENT_UNSCHEDULABLE_TIMEOUT = "UnschedulableTimeout"
EVENT_FORCE_ACTIVATE = "ForceActivate"
EVENT_STORAGE_ADD = "Storage/Add"  # PV/PVC/StorageClass/CSINode changes

# QueueingHints (scheduling_queue.go:582 isPodWorthRequeuing; per-plugin
# EnqueueExtensions): which cluster events can unblock a pod rejected by a
# given plugin. Plugins absent from the map requeue on any event (the
# reference's default when no hint fn is registered).
QUEUEING_HINTS: Dict[str, Set[str]] = {
    "NodeResourcesFit": {EVENT_NODE_ADD, EVENT_NODE_UPDATE,
                         EVENT_ASSIGNED_POD_DELETE, EVENT_POD_DELETE},
    "NodeAffinity": {EVENT_NODE_ADD, EVENT_NODE_UPDATE},
    "NodeName": {EVENT_NODE_ADD, EVENT_NODE_UPDATE},
    "NodeUnschedulable": {EVENT_NODE_ADD, EVENT_NODE_UPDATE},
    "TaintToleration": {EVENT_NODE_ADD, EVENT_NODE_UPDATE},
    "NodePorts": {EVENT_NODE_ADD, EVENT_ASSIGNED_POD_DELETE, EVENT_POD_DELETE},
    "PodTopologySpread": {EVENT_NODE_ADD, EVENT_NODE_UPDATE, EVENT_ASSIGNED_POD_ADD,
                          EVENT_ASSIGNED_POD_DELETE, EVENT_POD_DELETE},
    "InterPodAffinity": {EVENT_NODE_ADD, EVENT_NODE_UPDATE, EVENT_ASSIGNED_POD_ADD,
                         EVENT_ASSIGNED_POD_DELETE, EVENT_POD_DELETE},
    "DefaultPreemption": {EVENT_ASSIGNED_POD_DELETE, EVENT_POD_DELETE},
    "VolumeBinding": {EVENT_NODE_ADD, EVENT_NODE_UPDATE, EVENT_STORAGE_ADD},
    "VolumeZone": {EVENT_NODE_ADD, EVENT_NODE_UPDATE, EVENT_STORAGE_ADD},
    "NodeVolumeLimits": {EVENT_NODE_ADD, EVENT_ASSIGNED_POD_DELETE,
                         EVENT_POD_DELETE, EVENT_STORAGE_ADD},
    "VolumeRestrictions": {EVENT_ASSIGNED_POD_DELETE, EVENT_POD_DELETE},
    "DynamicResources": {EVENT_NODE_ADD, EVENT_NODE_UPDATE, EVENT_STORAGE_ADD,
                         EVENT_ASSIGNED_POD_DELETE, EVENT_POD_DELETE},
    # Composite trees with topology-constrained leaves are rejected by
    # design on the composite path (schedule_composite_group) — no cluster
    # event changes that, so nothing requeues them before the
    # unschedulable-timeout flush.
    "TopologyPlacementGenerator": set(),
}


@dataclass
class QueuedPodInfo:
    """framework/types.go QueuedPodInfo."""

    pod_info: PodInfo
    timestamp: float = 0.0
    attempts: int = 0
    initial_attempt_timestamp: Optional[float] = None
    # Queue-admission instant (never reset by requeues of THIS info object,
    # unlike `timestamp`): the start of the queue.wait span and of the
    # scheduler_e2e_scheduling_duration_seconds observation.
    enqueued_at: Optional[float] = None
    unschedulable_plugins: Set[str] = field(default_factory=set)
    pending_plugins: Set[str] = field(default_factory=set)
    gated: bool = False
    consecutive_backoff_exempt: bool = False

    @property
    def pod(self) -> Pod:
        return self.pod_info.pod

    @property
    def uid(self) -> str:
        return self.pod_info.pod.uid


@dataclass
class QueuedPodGroupInfo:
    """The gang-scheduling queue entity (scheduling_queue.go
    QueuedPodGroupInfo; invariants :196-206): a PodGroup whose member pods
    have all arrived pops as ONE unit and is scheduled all-or-nothing."""

    group: "object"  # api.types.PodGroup
    members: List[QueuedPodInfo] = field(default_factory=list)
    timestamp: float = 0.0
    attempts: int = 0
    initial_attempt_timestamp: Optional[float] = None
    unschedulable_plugins: Set[str] = field(default_factory=set)
    pending_plugins: Set[str] = field(default_factory=set)
    gated: bool = False
    consecutive_backoff_exempt: bool = False

    @property
    def pod(self) -> Pod:
        """Queue-ordering shim: group entities sort by group priority and
        arrival (the reference's workload-aware lessFn)."""
        return self.members[0].pod if self.members else Pod(name="(empty-group)")

    @property
    def pods(self) -> List[Pod]:
        return [m.pod for m in self.members]

    @property
    def uid(self) -> str:
        return f"pg:{self.group.namespace}/{self.group.name}"


@dataclass
class QueuedCompositeGroupInfo:
    """The queue entity for a whole CompositePodGroup TREE: the root
    composite plus every leaf PodGroup's buffered members. Pops as ONE unit
    and schedules all-or-nothing across levels
    (workload_forest.go buildQueuedPodGroupInfo + schedule_one_podgroup.go
    composite paths)."""

    cpg: "object"  # api.types.CompositePodGroup (the root)
    # [(PodGroup, [QueuedPodInfo, ...])] — one entry per leaf group
    groups: List[Tuple["object", List[QueuedPodInfo]]] = field(default_factory=list)
    timestamp: float = 0.0
    attempts: int = 0
    initial_attempt_timestamp: Optional[float] = None
    unschedulable_plugins: Set[str] = field(default_factory=set)
    pending_plugins: Set[str] = field(default_factory=set)
    gated: bool = False
    consecutive_backoff_exempt: bool = False

    @property
    def pod(self) -> Pod:
        for _g, members in self.groups:
            if members:
                return members[0].pod
        return Pod(name="(empty-composite)")

    @property
    def uid(self) -> str:
        return f"cpg:{self.cpg.namespace}/{self.cpg.name}"


class WorkloadForest:
    """Consistent queue-side view of the PodGroup/CompositePodGroup
    hierarchy (backend/queue/workload_forest.go): child→parent links are
    recorded even before the parent object is observed, so late parents
    retroactively own their children without a full rescan."""

    def __init__(self, composite_enabled: bool = True):
        self.composite_enabled = composite_enabled
        self.pod_groups: Dict[Tuple[str, str], object] = {}
        self.composites: Dict[Tuple[str, str], object] = {}
        # parent cpg key -> {("pg"|"cpg", child key)}
        self.children: Dict[Tuple[str, str], Set[Tuple[str, Tuple[str, str]]]] = {}

    def add_pod_group(self, group) -> None:
        key = (group.namespace, group.name)
        self.pod_groups[key] = group
        parent = getattr(group, "parent_name", "")
        if parent and self.composite_enabled:
            self.children.setdefault((group.namespace, parent), set()).add(
                ("pg", key))

    def add_composite(self, cpg) -> None:
        key = (cpg.namespace, cpg.name)
        self.composites[key] = cpg
        if cpg.parent_name:
            self.children.setdefault((cpg.namespace, cpg.parent_name), set()).add(
                ("cpg", key))

    def root_of_group(self, group):
        """Walk parent links to the outermost observed composite. Returns
        (kind, obj) — ("pg", group) when the group is its own root,
        ("cpg", cpg) for a composite root — or (None, None) while an
        ancestor in the chain is not yet observed (the tree must wait,
        getRootLookupInfoForPod)."""
        if not self.composite_enabled or not getattr(group, "parent_name", ""):
            return "pg", group
        ns = group.namespace
        name = group.parent_name
        cpg = None
        seen = set()
        while name:
            if (ns, name) in seen:
                return None, None  # cycle: never schedulable
            seen.add((ns, name))
            cpg = self.composites.get((ns, name))
            if cpg is None:
                return None, None  # parent not observed yet
            name = cpg.parent_name
        return "cpg", cpg

    def leaf_groups(self, cpg) -> Optional[List[object]]:
        """Every PodGroup in the subtree rooted at `cpg`, or None when a
        composite child has no observed object or a composite has no leaves
        (getLeafPodGroups)."""
        out: List[object] = []
        stack = [(cpg.namespace, cpg.name)]
        visited = set()
        while stack:
            key = stack.pop()
            if key in visited:
                continue
            visited.add(key)
            kids = self.children.get(key)
            if not kids:
                return None  # interior node with no observed children
            for kind, ckey in sorted(kids):
                if kind == "pg":
                    g = self.pod_groups.get(ckey)
                    if g is None:
                        return None
                    out.append(g)
                else:
                    if ckey not in self.composites:
                        return None
                    stack.append(ckey)
        return out or None


class _Heap:
    """Stable heap with O(log n) update/delete by key (backend/heap/heap.go).

    When the queue-sort comparison exposes a `sort_key(qpi)` (PrioritySort
    does), entries carry a plain tuple compared at C speed; otherwise a
    comparison shim routes through the less function."""

    def __init__(self, less: Callable[[QueuedPodInfo, QueuedPodInfo], bool],
                 sort_key: Optional[Callable[[QueuedPodInfo], tuple]] = None):
        self._less = less
        self._sort_key = sort_key
        self._entries: List[List] = []  # [sortkey_tiebreak, seq, qpi, valid]
        self._by_uid: Dict[str, List] = {}
        self._seq = itertools.count()

    class _Key:
        __slots__ = ("qpi", "less")

        def __init__(self, qpi, less):
            self.qpi = qpi
            self.less = less

        def __lt__(self, other):
            return self.less(self.qpi, other.qpi)

    def push(self, qpi) -> None:
        uid = qpi.uid
        self.delete(uid)
        key = (self._sort_key(qpi) if self._sort_key is not None
               else self._Key(qpi, self._less))
        entry = [key, next(self._seq), qpi, True]
        self._by_uid[uid] = entry
        heapq.heappush(self._entries, entry)

    def pop(self) -> Optional[QueuedPodInfo]:
        while self._entries:
            entry = heapq.heappop(self._entries)
            if entry[3]:
                del self._by_uid[entry[2].uid]
                return entry[2]
        return None

    def peek(self) -> Optional[QueuedPodInfo]:
        while self._entries and not self._entries[0][3]:
            heapq.heappop(self._entries)
        return self._entries[0][2] if self._entries else None

    def delete(self, uid: str) -> Optional[QueuedPodInfo]:
        entry = self._by_uid.pop(uid, None)
        if entry is not None:
            entry[3] = False
            return entry[2]
        return None

    def get(self, uid: str) -> Optional[QueuedPodInfo]:
        entry = self._by_uid.get(uid)
        return entry[2] if entry else None

    def __contains__(self, uid: str) -> bool:
        return uid in self._by_uid

    def __len__(self) -> int:
        return len(self._by_uid)

    def items(self):
        return [e[2] for e in self._by_uid.values()]


class _FairTenantHeap:
    """activeQ with per-tenant weighted fair dequeue (the scheduler-side
    half of the overload plane, docs/RESILIENCE.md § overload & fairness;
    the queue-admission analogue of the apiserver's priority-and-fairness
    dequeue in core/flowcontrol.py).

    One :class:`_Heap` per namespace preserves the queue-sort order WITHIN
    a tenant; `pop` picks the tenant by smooth weighted round-robin, so a
    namespace flooding the queue gets its weight's share of scheduling
    cycles and nothing more — the other tenants' heads keep popping at
    their own proportional cadence instead of starving behind the flood's
    (equal-priority) backlog. Same interface as _Heap, so the queue's
    flows (update/delete/activate/requeue) need no special cases."""

    def __init__(self, less: Callable[[QueuedPodInfo, QueuedPodInfo], bool],
                 sort_key: Optional[Callable[[QueuedPodInfo], tuple]] = None,
                 weights: Optional[Dict[str, float]] = None,
                 now: Callable[[], float] = time.monotonic):
        self._less = less
        self._sort_key = sort_key
        self.weights: Dict[str, float] = dict(weights or {})
        self.now = now
        self._heaps: Dict[str, _Heap] = {}
        self._ns_of: Dict[str, str] = {}   # entity uid -> namespace
        self._credit: Dict[str, float] = {}
        self.pops: Dict[str, int] = {}     # per-tenant service counts
        self.last_served: Dict[str, float] = {}

    def _ns(self, qpi) -> str:
        return qpi.pod.namespace or "default"

    def _weight(self, ns: str) -> float:
        return max(1e-6, float(self.weights.get(ns, 1.0)))

    def push(self, qpi) -> None:
        uid = qpi.uid
        self.delete(uid)
        ns = self._ns(qpi)
        heap = self._heaps.get(ns)
        if heap is None:
            heap = self._heaps[ns] = _Heap(self._less, self._sort_key)
            self._credit.setdefault(ns, 0.0)
        heap.push(qpi)
        self._ns_of[uid] = ns

    def pop(self) -> Optional[QueuedPodInfo]:
        nonempty = [ns for ns, h in self._heaps.items() if len(h)]
        if not nonempty:
            return None
        # Smooth WRR: every tenant with queued work earns its weight, the
        # richest tenant is served and charged the round's total — long-run
        # service converges to the weight proportions (fairness unit suite).
        total = 0.0
        for ns in nonempty:
            w = self._weight(ns)
            self._credit[ns] = self._credit.get(ns, 0.0) + w
            total += w
        best = max(nonempty, key=lambda ns: (self._credit[ns], ns))
        self._credit[best] -= total
        qpi = self._heaps[best].pop()
        if qpi is not None:
            self._ns_of.pop(qpi.uid, None)
            self.pops[best] = self.pops.get(best, 0) + 1
            self.last_served[best] = self.now()
        self._gc(best)
        return qpi

    def _gc(self, ns: str) -> None:
        heap = self._heaps.get(ns)
        if heap is not None and not len(heap):
            del self._heaps[ns]
            self._credit.pop(ns, None)

    def peek(self) -> Optional[QueuedPodInfo]:
        for heap in self._heaps.values():
            got = heap.peek()
            if got is not None:
                return got
        return None

    def delete(self, uid: str) -> Optional[QueuedPodInfo]:
        ns = self._ns_of.pop(uid, None)
        if ns is None:
            return None
        got = self._heaps[ns].delete(uid)
        self._gc(ns)
        return got

    def get(self, uid: str) -> Optional[QueuedPodInfo]:
        ns = self._ns_of.get(uid)
        return self._heaps[ns].get(uid) if ns is not None else None

    def __contains__(self, uid: str) -> bool:
        return uid in self._ns_of

    def __len__(self) -> int:
        return len(self._ns_of)

    def items(self):
        return [q for h in self._heaps.values() for q in h.items()]


class Nominator:
    """backend/queue/nominator.go — preemption-nominated pods per node."""

    def __init__(self):
        self._node_to_pods: Dict[str, List[PodInfo]] = {}
        self._pod_to_node: Dict[str, str] = {}
        # Bumped on every add/delete: device sessions and failure memos key
        # on the nomination SET (a changed set changes two-pass filter
        # outcomes), not just on whether any nomination exists.
        self.version = 0

    def add_nominated_pod(self, pi: PodInfo, node_name: str) -> None:
        self.delete_nominated_pod(pi.pod)
        if not node_name:
            return
        self._node_to_pods.setdefault(node_name, []).append(pi)
        self._pod_to_node[pi.pod.uid] = node_name
        self.version += 1

    def delete_nominated_pod(self, pod: Pod) -> None:
        node = self._pod_to_node.pop(pod.uid, None)
        if node is not None:
            self._node_to_pods[node] = [
                p for p in self._node_to_pods.get(node, []) if p.pod.uid != pod.uid
            ]
            if not self._node_to_pods[node]:
                del self._node_to_pods[node]
            self.version += 1

    def all_nominated_pod_infos(self) -> List[PodInfo]:
        return [pi for pis in self._node_to_pods.values() for pi in pis]

    def nominated_pods_for_node(self, node_name: str) -> List[PodInfo]:
        return self._node_to_pods.get(node_name, [])

    def nominated_node_for_pod(self, pod: Pod) -> Optional[str]:
        return self._pod_to_node.get(pod.uid)

    def has_nominated_pods(self) -> bool:
        return bool(self._pod_to_node)


class _UnschedulableMap(dict):
    """unschedulableEntities map with a non-gated uid index, so cluster-event
    requeues (move_all_to_active_or_backoff) never iterate gated pods. The
    index is keyed on insert-time `gated` — every flow that ungates a pod
    pops it from the map first (queue.update / activate), so the value can't
    go stale while stored."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.non_gated = set(u for u, q in self.items() if not q.gated)

    def __setitem__(self, uid, qpi):
        super().__setitem__(uid, qpi)
        if qpi.gated:
            self.non_gated.discard(uid)
        else:
            self.non_gated.add(uid)

    def __delitem__(self, uid):
        super().__delitem__(uid)
        self.non_gated.discard(uid)

    def pop(self, uid, *default):
        self.non_gated.discard(uid)
        return super().pop(uid, *default)

    def clear(self):
        super().clear()
        self.non_gated.clear()


class PriorityQueue:
    def __init__(
        self,
        framework=None,
        initial_backoff: float = DEFAULT_POD_INITIAL_BACKOFF,
        max_backoff: float = DEFAULT_POD_MAX_BACKOFF,
        max_in_unschedulable: float = DEFAULT_MAX_IN_UNSCHEDULABLE_DURATION,
        now: Callable[[], float] = time.monotonic,
        pop_from_backoff_q: bool = True,
        gang_enabled: bool = True,
        queueing_hints_enabled: bool = True,
        composite_enabled: bool = False,
        fair_tenant_dequeue: bool = False,
        tenant_weights: Optional[Dict[str, float]] = None,
    ):
        self.framework = framework
        self.metrics = None  # optional SchedulerMetrics (hint latency series)
        self.queueing_hints_enabled = queueing_hints_enabled
        self.composite_enabled = composite_enabled
        self.forest = WorkloadForest(composite_enabled)
        self.now = now
        self.initial_backoff = initial_backoff
        self.max_backoff = max_backoff
        self.max_in_unschedulable = max_in_unschedulable
        self.pop_from_backoff_q = pop_from_backoff_q
        self.gang_enabled = gang_enabled

        less = framework.less if framework is not None else (lambda a, b: a.timestamp < b.timestamp)
        sort_key = framework.queue_sort_key if framework is not None else None
        # Per-tenant fairness (docs/RESILIENCE.md § overload & fairness):
        # with fair_tenant_dequeue, the activeQ becomes per-namespace heaps
        # popped by smooth weighted round-robin — one flooding tenant gets
        # its weight's share of cycles, not the whole scheduler. Off by
        # default: single-tenant workloads keep the global queue-sort order.
        self.fair_tenant_dequeue = fair_tenant_dequeue
        if fair_tenant_dequeue:
            self.active_q = _FairTenantHeap(less, sort_key=sort_key,
                                            weights=tenant_weights, now=now)
        else:
            self.active_q = _Heap(less, sort_key=sort_key)
        self.backoff_q = _Heap(self._backoff_less)
        self.unschedulable: "_UnschedulableMap" = _UnschedulableMap()
        self.nominator = Nominator()
        # In-flight entities + the SHARED event log (scheduling_queue.go
        # inFlightEvents): each entity records the log position at pop time;
        # events append ONCE to the log instead of once per in-flight entity
        # (device sessions keep ~2 batches of pods in flight, and every own
        # bind fires an AssignedPodAdd — per-entity lists would be O(batch²)
        # per batch). The log clears whenever nothing is in flight.
        self._in_flight: Dict[str, int] = {}  # uid -> event-log index at pop
        self._event_log: List = []
        self.moved_count = 0  # schedulingCycle analogue of moveRequestCycle
        # Gang scheduling (workload_forest.go / pod_group_member_pods.go):
        # member pods buffer until their group has min_count arrivals, then
        # the whole group enters the queue as one entity.
        self.pod_groups: Dict[Tuple[str, str], object] = {}
        self._group_members: Dict[Tuple[str, str], List[QueuedPodInfo]] = {}

    # -- backoff (backoff_queue.go:249) ------------------------------------

    def backoff_duration(self, qpi: QueuedPodInfo) -> float:
        d = self.initial_backoff
        for _ in range(max(0, qpi.attempts - 1)):
            d *= 2
            if d >= self.max_backoff:
                return self.max_backoff
        return d

    def backoff_expiry(self, qpi: QueuedPodInfo) -> float:
        return qpi.timestamp + self.backoff_duration(qpi)

    def is_backing_off(self, qpi: QueuedPodInfo) -> bool:
        if qpi.attempts == 0:
            return False
        return self.backoff_expiry(qpi) > self.now()

    def _backoff_less(self, a: QueuedPodInfo, b: QueuedPodInfo) -> bool:
        return self.backoff_expiry(a) < self.backoff_expiry(b)

    # -- add / pop ---------------------------------------------------------

    def _new_qpi(self, pod: Pod) -> QueuedPodInfo:
        ts = self.now()
        # A pod that already rode the queue (conflict requeue via
        # on_async_bind_error, generic async-error re-add) keeps its
        # ORIGINAL admission instant: pop() stamps it on the pod, so
        # scheduler_e2e_scheduling_duration_seconds covers the whole
        # conflict-retry span instead of restarting at the requeue.
        return QueuedPodInfo(
            pod_info=PodInfo.of(pod), timestamp=ts,
            initial_attempt_timestamp=None,
            enqueued_at=pod.__dict__.get("_enqueued_at", ts),
        )

    def add(self, pod: Pod) -> None:
        """Add (scheduling_queue.go:858) — new pending pod."""
        qpi = self._new_qpi(pod)
        if self.framework is not None and self.framework.pre_enqueue_plugins:
            st = self.framework.run_pre_enqueue_plugins(pod)
            if not st.is_success():
                qpi.gated = True
                qpi.unschedulable_plugins.add(st.plugin)
                self.unschedulable[pod.uid] = qpi
                return
        if pod.pod_group and self.gang_enabled:
            self._add_group_member(qpi)
            return
        self.active_q.push(qpi)

    # -- gang scheduling ---------------------------------------------------

    def register_pod_group(self, group) -> None:
        """PodGroup/CompositePodGroup informer event: record in the forest
        and activate whatever ROOT became complete
        (scheduling_queue.go pod-group invariants + workload_forest.go)."""
        from ..api.types import CompositePodGroup
        if isinstance(group, CompositePodGroup):
            self.forest.add_composite(group)
            if self.composite_enabled:
                # A late parent can complete any subtree: re-check once per
                # DISTINCT root (not per buffered group — each composite
                # check walks the whole tree).
                roots = {}
                for key in list(self._group_members):
                    g = self.pod_groups.get(key)
                    if g is None:
                        continue
                    kind, root = self.forest.root_of_group(g)
                    if kind == "cpg":
                        roots[(root.namespace, root.name)] = root
                for root in roots.values():
                    self._maybe_activate_composite(root)
            return
        key = (group.namespace, group.name)
        self.pod_groups[key] = group
        self.forest.add_pod_group(group)
        self._maybe_activate_group(key)

    def _add_group_member(self, qpi: QueuedPodInfo) -> None:
        pod = qpi.pod
        key = (pod.namespace, pod.pod_group)
        members = self._group_members.setdefault(key, [])
        members.append(qpi)
        existing = self._group_entity(key)
        if existing is not None:
            existing.members = list(members)  # late joiner widens the gang
            return
        self._maybe_activate_group(key)

    def _group_entity(self, key) -> Optional[QueuedPodGroupInfo]:
        group = self.pod_groups.get(key)
        if group is None:
            return None
        uid = f"pg:{key[0]}/{key[1]}"
        ent = self.active_q.get(uid) or self.backoff_q.get(uid) or self.unschedulable.get(uid)
        return ent

    def _maybe_activate_group(self, key) -> None:
        """PodGroupPodsCount gate at ROOT granularity: a flat group becomes
        schedulable once min_count members arrived; a group inside a
        composite tree only when EVERY leaf group of the whole tree is
        complete (podgrouppodscount/ + workload_forest.go)."""
        group = self.pod_groups.get(key)
        if group is None:
            return
        kind, root = self.forest.root_of_group(group)
        if kind == "cpg":
            self._maybe_activate_composite(root)
            return
        if kind is None:
            return  # an ancestor is unobserved: the tree waits
        members = self._group_members.get(key, [])
        if len(members) < max(1, group.min_count):
            return
        if self._group_entity(key) is not None or f"pg:{key[0]}/{key[1]}" in self._in_flight:
            return
        ent = QueuedPodGroupInfo(
            group=group, members=list(members), timestamp=self.now())
        self.active_q.push(ent)
        if self.metrics is not None:
            self.metrics.queue_incoming_entities.inc("active", "GroupComplete")

    def _maybe_activate_composite(self, cpg) -> None:
        leaves = self.forest.leaf_groups(cpg)
        if leaves is None:
            return
        groups = []
        for g in leaves:
            members = self._group_members.get((g.namespace, g.name), [])
            if len(members) < max(1, g.min_count):
                return  # an incomplete leaf holds the whole tree back
            groups.append((g, list(members)))
        uid = f"cpg:{cpg.namespace}/{cpg.name}"
        ent = (self.active_q.get(uid) or self.backoff_q.get(uid)
               or self.unschedulable.get(uid))
        if ent is not None:
            ent.groups = groups  # late joiner widens the queued tree
            return
        if uid in self._in_flight:
            return
        self.active_q.push(QueuedCompositeGroupInfo(
            cpg=cpg, groups=groups, timestamp=self.now()))
        if self.metrics is not None:
            self.metrics.queue_incoming_entities.inc("active", "TreeComplete")

    def remove_group_member(self, pod: Pod) -> None:
        key = (pod.namespace, pod.pod_group)
        members = self._group_members.get(key)
        if not members:
            return
        self._group_members[key] = [m for m in members if m.pod.uid != pod.uid]
        ent = self._group_entity(key)
        if ent is not None:
            ent.members = [m for m in ent.members if m.pod.uid != pod.uid]
            group = self.pod_groups.get(key)
            if group is not None and len(ent.members) < max(1, group.min_count):
                self.active_q.delete(ent.uid)
                self.backoff_q.delete(ent.uid)
                self.unschedulable.pop(ent.uid, None)
        # A queued COMPOSITE entity holding this pod must not schedule it:
        # filter the member IN PLACE (preserving the entity's backoff and
        # attempt state, like the flat-gang path above); the entity only
        # drops when a leaf falls below min_count — buffers then re-activate
        # it when enough members return.
        group = self.pod_groups.get(key)  # may be None when only buffered
        if group is not None and self.composite_enabled:
            kind, root = self.forest.root_of_group(group)
            if kind == "cpg":
                uid = f"cpg:{root.namespace}/{root.name}"
                ent = (self.active_q.get(uid) or self.backoff_q.get(uid)
                       or self.unschedulable.get(uid))
                if ent is not None:
                    ent.groups = [
                        (g, [m for m in ms if m.pod.uid != pod.uid])
                        for g, ms in ent.groups]
                    if any(len(ms) < max(1, g.min_count)
                           for g, ms in ent.groups):
                        self.active_q.delete(uid)
                        self.backoff_q.delete(uid)
                        self.unschedulable.pop(uid, None)

    def clear_group_members(self, group_key: Tuple[str, str], uids) -> None:
        """Members successfully scheduled leave the buffer."""
        members = self._group_members.get(group_key)
        if members:
            self._group_members[group_key] = [
                m for m in members if m.pod.uid not in uids]

    def update(self, old: Optional[Pod], new: Pod) -> None:
        uid = new.uid
        if new.pod_group and self.gang_enabled:
            # A buffered gang member updates in place — falling through to
            # add() would append a duplicate member entry.
            key = (new.namespace, new.pod_group)
            for m in self._group_members.get(key, ()):
                if m.pod.uid == uid:
                    m.pod_info = PodInfo.of(new)
                    return
        if uid in self.unschedulable:
            qpi = self.unschedulable.pop(uid)
            qpi.pod_info = PodInfo.of(new)
            if qpi.gated:
                # re-run PreEnqueue — gates may have been removed
                if self.framework is not None:
                    st = self.framework.run_pre_enqueue_plugins(new)
                    if st.is_success():
                        qpi.gated = False
                        qpi.timestamp = self.now()
                        if new.pod_group and self.gang_enabled:
                            self._add_group_member(qpi)  # rejoin the gang
                        else:
                            self.active_q.push(qpi)
                        return
                self.unschedulable[uid] = qpi
                return
            # spec update may make it schedulable — move to active/backoff
            self._move_to_active_or_backoff(qpi)
            return
        existing = self.active_q.get(uid)
        if existing is not None:
            # delete + re-push: in-place mutation would corrupt heap order
            # when the update changes priority.
            self.active_q.delete(uid)
            existing.pod_info = PodInfo.of(new)
            self.active_q.push(existing)
            return
        existing = self.backoff_q.get(uid)
        if existing is not None:
            self.backoff_q.delete(uid)
            existing.pod_info = PodInfo.of(new)
            self.backoff_q.push(existing)
            return
        if uid not in self._in_flight:
            self.add(new)

    def delete(self, pod: Pod) -> None:
        if pod.pod_group:
            self.remove_group_member(pod)
        self.active_q.delete(pod.uid)
        self.backoff_q.delete(pod.uid)
        self.unschedulable.pop(pod.uid, None)
        self.nominator.delete_nominated_pod(pod)

    def pop(self) -> Optional[QueuedPodInfo]:
        """Pop (scheduling_queue.go:1320 → active_queue.go:315) with the
        pop-from-backoffQ feature: when activeQ is empty, pop the pod whose
        backoff already expired — or, when the gate is on, the earliest-expiry
        backoff pod (SchedulerPopFromBackoffQ)."""
        self.flush_backoff_completed()
        qpi = self.active_q.pop()
        if qpi is None and self.pop_from_backoff_q:
            qpi = self.backoff_q.pop()
        if qpi is None:
            return None
        self._hand_out(qpi)
        return qpi

    def _hand_out(self, qpi, now: Optional[float] = None) -> None:
        """What every entity that leaves the queue is stamped with: one more
        attempt, the instant of its first, the admission instant on the pod,
        its place in the event log. ``now`` is a clock reading the caller
        already holds (a run's)."""
        qpi.attempts += 1
        if qpi.initial_attempt_timestamp is None:
            qpi.initial_attempt_timestamp = self.now() if now is None else now
        eq = getattr(qpi, "enqueued_at", None)
        pi = getattr(qpi, "pod_info", None)
        if eq is not None and pi is not None:
            # Stamp the admission instant on the pod itself: requeue paths
            # that only have the Pod (async bind conflicts build a fresh
            # QueuedPodInfo) recover it in _new_qpi, keeping the e2e
            # histogram honest across conflict retries.
            pi.pod.__dict__["_enqueued_at"] = eq
        self._in_flight[qpi.uid] = len(self._event_log)

    def pop_run(self, limit: int, accept: Callable[[object], Optional[bool]]
                ) -> Tuple[List, Optional[object], float]:
        """A run of the queue's own pop order: the entities that ``limit``
        calls of ``pop()`` would hand out, in that order and stamped as
        ``pop()`` stamps them, on ONE reading of the clock. The order is
        that of the single pops while the clock stands still, which is how
        a run sees it: the backoffQ is flushed once, against that reading,
        so an entity whose backoff runs out while the run is popped is
        promoted by the next flush (the next run, the next ``pop()``) and
        not in the middle of this one. ``accept(entity)`` decides each as
        it leaves: True and it joins the run; None and it is dropped (the
        caller has settled it, ``done()`` included) and does not count
        against ``limit``; False ends the run, and that entity, popped,
        stamped and in flight like the others, is returned apart. Returns
        (run, the refused entity or None, the clock reading)."""
        now = self.now()
        self.flush_backoff_completed(now)
        active_pop = self.active_q.pop
        backoff_pop = self.backoff_q.pop if self.pop_from_backoff_q else None
        hand_out = self._hand_out
        run: List = []
        while len(run) < limit:
            qpi = active_pop()
            if qpi is None:
                qpi = backoff_pop() if backoff_pop is not None else None
                if qpi is None:
                    break
            hand_out(qpi, now)
            ok = accept(qpi)
            if ok:
                run.append(qpi)
            elif ok is not None:
                return run, qpi, now
        return run, None, now

    def done(self, uid: str) -> None:
        """Done (scheduling_queue.go:1326) — scheduling attempt finished."""
        self._in_flight.pop(uid, None)
        if not self._in_flight:
            self._event_log.clear()
        elif len(self._event_log) > 4096:
            # Pipelined scheduling can keep SOMETHING in flight for the whole
            # run; trim the prefix no live entity can reference and rebase
            # (the reference trims inFlightEvents as pods complete). Amortized
            # by the length gate so the min() scan is rare.
            mn = min(self._in_flight.values())
            if mn > 0:
                del self._event_log[:mn]
                for k in self._in_flight:
                    self._in_flight[k] -= mn

    def __len__(self) -> int:
        return len(self.active_q) + len(self.backoff_q) + len(self.unschedulable)

    def pending_counts(self) -> Tuple[int, int, int]:
        return len(self.active_q), len(self.backoff_q), len(self.unschedulable)

    def starvation_by_namespace(self) -> Dict[str, float]:
        """Starvation accounting (`scheduler_queue_starvation_seconds`
        {namespace}): per tenant, how long its LONGEST-waiting runnable
        entity (active + backoff — not the unschedulable pool, which waits
        on cluster events by design) has been queued since admission.
        Computed from live queue contents at scrape time — O(pending),
        zero bookkeeping on the hot add/pop paths."""
        now = self.now()
        out: Dict[str, float] = {}
        for qpi in list(self.active_q.items()) + list(self.backoff_q.items()):
            ns = qpi.pod.namespace or "default"
            start = getattr(qpi, "enqueued_at", None)
            if start is None:
                start = qpi.timestamp
            wait = max(0.0, now - start)
            if wait > out.get(ns, 0.0):
                out[ns] = wait
        return out

    # -- requeue on failure -------------------------------------------------

    def add_unschedulable_if_not_present(self, qpi, pod_scheduling_cycle: int = 0) -> None:
        """AddUnschedulablePodIfNotPresent (scheduling_queue.go:1058): if a
        relevant event arrived while the entity was in flight, skip the
        unschedulable pool and go straight to backoff/active. Entities key by
        their queue uid (pod uid, or "pg:ns/name" for gangs)."""
        uid = qpi.uid
        start = self._in_flight.get(uid)
        events = self._event_log[start:] if start is not None else []
        qpi.timestamp = self.now()
        if events and self._events_relevant(qpi, events):
            self._move_to_active_or_backoff(qpi)
            return
        self.unschedulable[uid] = qpi

    def _events_relevant(self, qpi, events: List) -> bool:
        """isPodWorthRequeuing (scheduling_queue.go:582): does any of the
        events plausibly resolve one of the plugins that rejected this
        entity? Per-plugin QueueingHintFn callbacks (EventsToRegister →
        ClusterEventWithHint; framework/types.go:217) are evaluated over the
        event's (old, new) objects when the plugin registered them; plugins
        without callbacks fall back to the static event map; unknown
        rejection causes requeue on anything. Events arrive as plain strings
        or (event, old, new) tuples."""
        plugins = qpi.unschedulable_plugins
        if not plugins:
            return True
        hint_map = (getattr(self.framework, "queueing_hint_map", None)
                    if self.queueing_hints_enabled else None)
        for ev in events:
            event, old, new = ev if isinstance(ev, tuple) else (ev, None, None)
            if event in (EVENT_UNSCHEDULABLE_TIMEOUT, EVENT_FORCE_ACTIVATE):
                return True
            for p in plugins:
                registered = hint_map.get(p) if hint_map is not None else None
                if registered is None:
                    hints = QUEUEING_HINTS.get(p)
                    if hints is None or event in hints:
                        return True
                    continue
                fns = registered.get(event)
                if fns is None:
                    # Plugin registered its events and this isn't one of
                    # them: the event cannot help this rejection.
                    continue
                pod = qpi.pod
                for fn in fns:
                    if fn is None:
                        return True  # no hint fn: always Queue
                    _m = self.metrics
                    _t0 = time.perf_counter() if _m is not None else 0.0
                    try:
                        queue_it = bool(fn(pod, old, new))
                    except Exception:  # noqa: BLE001 - hint errors → Queue
                        queue_it = True  # (the reference logs and queues)
                    if _m is not None:
                        _m.queueing_hint_execution_duration.observe(
                            time.perf_counter() - _t0, p, event)
                    if queue_it:
                        return True
        return False

    def _move_to_active_or_backoff(self, qpi) -> None:
        if qpi.gated:
            self.unschedulable[qpi.uid] = qpi
            return
        if self.is_backing_off(qpi):
            self.backoff_q.push(qpi)
        else:
            self.active_q.push(qpi)

    def requeue_conflict(self, qpi) -> None:
        """Optimistic-binding conflict (409 from the binding subresource):
        the entity goes straight to the backoffQ — never the unschedulable
        pool, because no cluster event is needed to make it schedulable
        again; it only needs to wait out the backoff so the winning commit
        arrives through the watch feed (Omega's conflict-then-retry)."""
        qpi.timestamp = self.now()
        if qpi.gated:
            self.unschedulable[qpi.uid] = qpi
            return
        if qpi.pod.pod_group and self.gang_enabled:
            # A gang member's conflict re-enters through the group buffer,
            # exactly like add(): a bare backoffQ singleton would later pop
            # and schedule SOLO, outside the gang's all-or-nothing. (Reached
            # from failover-overlap 409s — the partitioner pins gangs whole,
            # so only transient dual ownership can race a gang's binds.)
            self._add_group_member(qpi)
            return
        self.backoff_q.push(qpi)
        if self.metrics is not None:
            self.metrics.queue_incoming_entities.inc("backoff", "BindConflict")

    def has_entity(self, uid: str) -> bool:
        """Is this pod/entity anywhere in the queue's custody (active,
        backoff, unschedulable, in flight, or buffered as a gang member)?
        Shard adoption sweeps use this to avoid double-admitting."""
        if (uid in self.active_q or uid in self.backoff_q
                or uid in self.unschedulable or uid in self._in_flight):
            return True
        return any(m.pod.uid == uid for ms in self._group_members.values()
                   for m in ms)

    def activate(self, pod: Pod) -> None:
        """Activate (scheduling_queue.go:955) — force to activeQ."""
        uid = pod.uid
        qpi = self.unschedulable.pop(uid, None) or self.backoff_q.delete(uid)
        if qpi is not None and not qpi.gated:
            qpi.timestamp = self.now()
            self.active_q.push(qpi)

    def move_all_to_active_or_backoff(self, event: str, old=None, new=None) -> None:
        """MoveAllToActiveOrBackoffQueue (scheduling_queue.go:1817), with
        per-plugin QueueingHint filtering over the event's (old, new)
        objects. Gated pods are skipped via the map's non-gated index —
        cluster events must cost O(requeue-able pods), not O(gated pods)
        (the SchedulingWhileGated perf contract: 10k parked gated pods while
        deletes fire during the window)."""
        self.moved_count += 1
        ev = (event, old, new)
        uids = (list(self.unschedulable.keys()) if event == EVENT_FORCE_ACTIVATE
                else list(self.unschedulable.non_gated))
        for uid in uids:
            qpi = self.unschedulable.get(uid)
            if qpi is None:
                continue
            if qpi.gated and event != EVENT_FORCE_ACTIVATE:
                continue
            if not self._events_relevant(qpi, [ev]):
                continue
            del self.unschedulable[uid]
            self._move_to_active_or_backoff(qpi)
        if self._in_flight:
            self._event_log.append(ev)

    def flush_backoff_completed(self, now: Optional[float] = None) -> None:
        """backoffQ flush loop (scheduling_queue.go Run :503), against the
        clock or against the reading the caller holds."""
        while True:
            qpi = self.backoff_q.peek()
            if qpi is None or self.backoff_expiry(qpi) > (
                    self.now() if now is None else now):
                return
            self.backoff_q.pop()
            self.active_q.push(qpi)

    def flush_unschedulable_left_over(self) -> None:
        """flushUnschedulablePodsLeftover — pods stuck > 5 min."""
        now = self.now()
        for uid in list(self.unschedulable.keys()):
            qpi = self.unschedulable[uid]
            if qpi.gated:
                continue
            if now - qpi.timestamp > self.max_in_unschedulable:
                del self.unschedulable[uid]
                self._move_to_active_or_backoff(qpi)
