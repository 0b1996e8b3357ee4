"""Scheduler cache: assumed-pod-aware aggregate of cluster state with
generation-based incremental snapshots.

Re-expresses pkg/scheduler/backend/cache/cache.go (cacheImpl :61): the cache
holds authoritative NodeInfos, tracks pods assumed-but-not-yet-bound
(AssumePod/ForgetPod/ExpirePod), and refreshes an immutable per-cycle Snapshot
incrementally — only NodeInfos whose generation advanced since the last
UpdateSnapshot are re-cloned (cache.go:206,236-262). The same dirty-generation
walk drives the device mirror's row scatter (kubernetes_tpu/ops.device_state).

The reference's doubly-linked generation list is replaced by a dirty-name set:
equivalent observable behavior, simpler host code.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Callable, Dict, List, NamedTuple, Optional, Set

from ..api.types import Namespace, Node, Pod
from .node_info import NodeInfo, PodInfo, next_generation
from .node_tree import NodeTree


# ---------------------------------------------------------------------------
# Typed cluster-event journal
# ---------------------------------------------------------------------------
#
# The old consumer contract was ONE integer (`Scheduler.cluster_event_seq`):
# a device session could only ask "did anything change since seq S" and tear
# its plan+carry down on any yes. The journal keeps the integer (it is still
# the version every cache consumer keys on) but records WHAT each bump was —
# (kind, node/namespace key, patch-relevant pod facts) — so a session can ask
# "what changed since S" and delta-patch the exact rows an event dirtied
# instead of rebuilding snapshot→features from scratch (the incremental-
# resume generalization of cache.go:206's generation walk; KEP-5598's
# opportunistic batching has the same never-restart-per-event shape).

# Queue-only change: scheduling-gate lift, pending-pod update/delete,
# pod-group registration. Dirties NOTHING node-side — a live session's
# state, plan and carry all stay exact.
EV_QUEUE = "queue"
# Namespace created / labels changed. Only affinity namespaceSelector
# matching reads namespace labels, so this is benign for plans with no
# inter-pod-affinity machinery anywhere in play.
EV_NAMESPACE = "namespace"
# Pod appeared on / left / changed on a node (key = node name). Dirties that
# node's resource aggregates (req_r/nonzero/pod_count rows); dirties
# pod-derived feature tables too unless the pod is `plain` (see
# pod_event_flags) and the plan carries none.
EV_POD_ADD = "pod_add"
EV_POD_REMOVE = "pod_remove"
EV_POD_UPDATE = "pod_update"
# Node object replaced in place with labels/images/declared-features intact
# (key = node name): dirties that row's taint/allocatable/unschedulable
# tensors only. Label or image changes are NOT this kind — they dirty
# host-evaluated per-node feature vectors (sel_match/il_score/na_raw) and
# topology vids, which the delta path does not patch.
EV_NODE_UPDATE = "node_update"
# Node added/removed: row order changes — never delta-patchable.
EV_STRUCTURAL = "structural"
# Everything else (storage objects, reconcile unwinds): full rebuild.
EV_OTHER = "other"


class ClusterEvent(NamedTuple):
    seq: int
    kind: str
    key: str = ""          # node name (pod/node kinds) or namespace name
    # Pod-side facts captured at record time (patch eligibility is decided
    # later, against a specific plan):
    pod_plain: bool = False   # no affinity/spread terms, no PVC/DRA claims
    pod_ports: bool = False   # requests host ports
    # True when the event can only ENLARGE feasibility (pod removed, taint
    # lifted, capacity grown): results already computed on device against the
    # pre-event state remain feasible, so in-flight batches may still commit
    # while the patch waits for the pipeline to drain. Those commits keep
    # their pre-event SCORES — a deliberate relaxation that only applies to
    # events arriving asynchronously mid-session (the threaded inbox seam),
    # where no interleaving against in-flight evaluations is defined and
    # committing them is a legal linearization (the event lands just after).
    # Deterministic (inline) event streams only ever patch at empty-pipeline
    # boundaries, so the bit-identical-to-host-oracle invariant the
    # equivalence suites enforce is unaffected.
    shrink: bool = False


def pod_event_flags(pod: Pod) -> tuple:
    """(pod_plain, pod_ports) for a journal record. `plain` means the pod
    cannot dirty any pod-derived feature table: no affinity/anti-affinity
    terms (required or preferred), no topology-spread constraints, no
    PVC-backed volumes (per-node attach counts), no DRA claims."""
    aff = pod.affinity
    plain = not (
        pod.topology_spread_constraints
        or (aff is not None and (aff.pod_affinity or aff.pod_anti_affinity))
        or any(v.pvc_name for v in pod.volumes)
        or getattr(pod, "resource_claims", None)
    )
    return plain, bool(pod.host_ports())


class EventJournal:
    """Bounded journal of node-state-relevant cluster events.

    `seq` is the authoritative cluster-event version (the scheduler mirrors
    it as `cluster_event_seq`). `since(S)` answers "what changed after S" —
    or None when S has fallen off the retention window, which consumers must
    treat as "anything may have changed" (full rebuild)."""

    __slots__ = ("cap", "seq", "_events")

    def __init__(self, capacity: int = 4096):
        self.cap = capacity
        self.seq = 0
        self._events: deque = deque()

    def record(self, kind: str, key: str = "", pod_plain: bool = False,
               pod_ports: bool = False, shrink: bool = False) -> int:
        self.seq += 1
        self._events.append(ClusterEvent(
            self.seq, kind, key, pod_plain, pod_ports, shrink))
        if len(self._events) > self.cap:
            self._events.popleft()
        return self.seq

    def since(self, seq: int) -> Optional[List[ClusterEvent]]:
        """Events with .seq > seq in order, [] when nothing happened, or
        None when the window was truncated (events older than retention).
        Walks from the RIGHT so the per-invalidation-check cost is
        O(new events), not O(retained window)."""
        if seq >= self.seq:
            return []
        if not self._events or self._events[0].seq > seq + 1:
            return None
        out: List[ClusterEvent] = []
        for e in reversed(self._events):
            if e.seq <= seq:
                break
            out.append(e)
        out.reverse()
        return out


class Snapshot:
    """Immutable per-cycle view (backend/cache/snapshot.go)."""

    def __init__(self):
        self.node_info_map: Dict[str, NodeInfo] = {}
        self.node_info_list: List[NodeInfo] = []
        self.have_pods_with_affinity_list: List[NodeInfo] = []
        self.have_pods_with_required_anti_affinity_list: List[NodeInfo] = []
        self.used_pvc_count: Dict[str, int] = {}
        self.image_num_nodes: Dict[str, int] = {}
        self.generation: int = 0
        self._index: Dict[str, int] = {}
        self._list_members: set = set()

    def get(self, name: str) -> Optional[NodeInfo]:
        return self.node_info_map.get(name)

    def num_nodes(self) -> int:
        return len(self.node_info_list)

    def rebuild_lists(self) -> None:
        self.have_pods_with_affinity_list = [
            ni for ni in self.node_info_list if ni.pods_with_affinity
        ]
        self.have_pods_with_required_anti_affinity_list = [
            ni for ni in self.node_info_list if ni.pods_with_required_anti_affinity
        ]
        self.image_num_nodes = {}
        for ni in self.node_info_list:
            for img in ni.image_states:
                self.image_num_nodes[img] = self.image_num_nodes.get(img, 0) + 1
        self._index = {ni.name: i for i, ni in enumerate(self.node_info_list)}
        self._list_members = (
            {ni.name for ni in self.have_pods_with_affinity_list}
            | {ni.name for ni in self.have_pods_with_required_anti_affinity_list})

    # -- in-cycle what-if mutation (gang simulation, snapshot.go:545/:599) --

    def assume_pod(self, pod: Pod) -> None:
        ni = self.node_info_map.get(pod.node_name)
        if ni is None:
            return
        had_aff = bool(ni.pods_with_affinity)
        had_anti = bool(ni.pods_with_required_anti_affinity)
        ni.add_pod(PodInfo.of(pod))
        # Keep the affinity sublists consistent mid-simulation: PreFilter
        # consumers (InterPodAffinity sublist shortcut, ops/features.py)
        # read them against the SAME snapshot object while gang simulations
        # assume members in (snapshot.go AddPod keeps its lists in step).
        if not had_aff and ni.pods_with_affinity:
            self.have_pods_with_affinity_list.append(ni)
            self._list_members.add(ni.name)
        if not had_anti and ni.pods_with_required_anti_affinity:
            self.have_pods_with_required_anti_affinity_list.append(ni)
            self._list_members.add(ni.name)

    def forget_pod(self, pod: Pod) -> None:
        ni = self.node_info_map.get(pod.node_name)
        if ni is None:
            return
        had_aff = bool(ni.pods_with_affinity)
        had_anti = bool(ni.pods_with_required_anti_affinity)
        ni.remove_pod(pod)
        if had_aff and not ni.pods_with_affinity:
            self.have_pods_with_affinity_list = [
                x for x in self.have_pods_with_affinity_list if x is not ni]
        if had_anti and not ni.pods_with_required_anti_affinity:
            self.have_pods_with_required_anti_affinity_list = [
                x for x in self.have_pods_with_required_anti_affinity_list
                if x is not ni]
        if not ni.pods_with_affinity and not ni.pods_with_required_anti_affinity:
            self._list_members.discard(ni.name)

    # -- placement mutation session (snapshot.go:276 StartMutations / :317
    # EndMutations / :708 AssumePlacement): restrict the visible node list to
    # a candidate placement while simulating a pod group against it. NodeInfo
    # objects are shared with the full list, so in-simulation assume/forget
    # stay visible after the placement is forgotten.

    def assume_placement(self, node_names) -> None:
        assert not hasattr(self, "_placement_saved"), "placement already assumed"
        wanted = set(node_names)
        self._placement_saved = self.node_info_list
        self.node_info_list = [ni for ni in self._placement_saved
                               if ni.name in wanted]
        self.rebuild_lists()

    def forget_placement(self) -> None:
        self.node_info_list = self._placement_saved
        del self._placement_saved
        self.rebuild_lists()

    def placement_active(self) -> bool:
        return hasattr(self, "_placement_saved")


class _PodState:
    __slots__ = ("pod", "deadline", "binding_finished")

    def __init__(self, pod: Pod):
        self.pod = pod
        self.deadline: Optional[float] = None
        self.binding_finished = False


class Cache:
    """cacheImpl (backend/cache/cache.go:61)."""

    def __init__(self, ttl_seconds: float = 0.0, now: Callable[[], float] = time.monotonic):
        self.ttl = ttl_seconds
        self.now = now
        self.nodes: Dict[str, NodeInfo] = {}
        # Snapshot order = zone-interleaved NodeTree order + imaginary
        # placeholders; rebuilt lazily when tree membership changes, so
        # truncated sampling spreads across zones exactly as the reference's
        # updateNodeInfoSnapshotList does (backend/cache/snapshot.go,
        # node_tree.go list()).
        self.node_order: List[str] = []
        self._imaginary: List[str] = []  # pods observed before their node
        self._order_dirty = False
        self.node_tree = NodeTree()
        self.assumed_pods: Set[str] = set()
        self.pod_states: Dict[str, _PodState] = {}
        self.namespaces: Dict[str, Namespace] = {}
        # Cluster-wide PVC reference counts over cached+assumed pods (the
        # device path's claim-sharing eligibility check reads this — a
        # shared claim must not ride the kernel's counted-attach encoding).
        self.pvc_refs: Dict[str, int] = {}
        # Count of cached+assumed pods carrying ANY inter-pod (anti-)affinity
        # term. Zero means pod labels and namespaces are scheduling-inert for
        # affinity-free incoming pods — the live-truth gate behind the
        # namespace-erased session signature (models/tpu_scheduler.py
        # _neutral_sig) and the namespace-event delta classification.
        self.affinity_pod_refs = 0
        # Optional scheduled-group-pods index (core/podgroupstate.py), kept
        # in lockstep with the cache's pod view (assumed + bound) — the
        # scheduler-side truth placement generation pins domains against.
        self.pod_group_state = None
        self._dirty: Set[str] = set()
        self._removed_since_snapshot = False

    # -- nodes -------------------------------------------------------------

    def add_node(self, node: Node) -> NodeInfo:
        ni = self.nodes.get(node.name)
        if ni is None:
            ni = NodeInfo(node)
            self.nodes[node.name] = ni
        else:
            ni.set_node(node)
        if node.name in self._imaginary:  # placeholder became real
            self._imaginary.remove(node.name)
            self._order_dirty = True
        if self.node_tree.add_node(node):
            self._order_dirty = True
        self._dirty.add(node.name)
        return ni

    def update_node(self, node: Node) -> NodeInfo:
        return self.add_node(node)

    def remove_node(self, node_name: str) -> None:
        ni = self.nodes.pop(node_name, None)
        if ni is not None:
            if ni.node is not None:
                self.node_tree.remove_node(ni.node)
            if node_name in self._imaginary:
                self._imaginary.remove(node_name)
            self._order_dirty = True
            self._removed_since_snapshot = True
        self._dirty.discard(node_name)

    # -- namespaces --------------------------------------------------------

    def add_namespace(self, ns: Namespace) -> None:
        self.namespaces[ns.name] = ns

    def namespace_labels(self, name: str) -> Optional[Dict[str, str]]:
        ns = self.namespaces.get(name)
        return ns.labels if ns else None

    # -- pods --------------------------------------------------------------

    def assume_pod(self, pod: Pod, pod_info: Optional[PodInfo] = None) -> None:
        """AssumePod (cache.go): optimistically place the pod on its node
        before the bind API call completes. `pod_info` lets callers reuse the
        queue entity's precomputed PodInfo (QueuedPodInfo.pod_info) instead
        of re-deriving it — this runs once per scheduled pod."""
        if pod.uid in self.pod_states:
            raise ValueError(f"pod {pod.uid} is already assumed/added")
        self._add_pod_to_node(pod, pod_info)
        self.assumed_pods.add(pod.uid)
        self.pod_states[pod.uid] = _PodState(pod)

    def assume_pods(self, run) -> None:
        """``assume_pod`` over a run of ``(pod, pod_info)`` whose
        ``node_name`` is set, in order (a retired batch's pods,
        models/tpu_scheduler.py): what is per pod by nature is done per pod
        (the node's ``add_pod``, the pod's state, the dirty mark), the
        lookups are made once. A pod already assumed or added raises as
        ``assume_pod`` does, with the pods before it assumed."""
        pod_states = self.pod_states
        assumed = self.assumed_pods
        add_to_node = self._add_pod_to_node
        for pod, pod_info in run:
            uid = pod.uid
            if uid in pod_states:
                raise ValueError(f"pod {uid} is already assumed/added")
            add_to_node(pod, pod_info)
            assumed.add(uid)
            pod_states[uid] = _PodState(pod)

    def finish_binding(self, pod: Pod) -> None:
        st = self.pod_states.get(pod.uid)
        if st is not None and pod.uid in self.assumed_pods:
            st.binding_finished = True
            if self.ttl > 0:
                st.deadline = self.now() + self.ttl

    def forget_pod(self, pod: Pod) -> None:
        st = self.pod_states.get(pod.uid)
        if st is None or pod.uid not in self.assumed_pods:
            return
        self._remove_pod_from_node(st.pod)
        self.assumed_pods.discard(pod.uid)
        del self.pod_states[pod.uid]

    def add_pod(self, pod: Pod) -> None:
        """Confirmed (watch-observed) pod add. Replaces the assumed copy."""
        st = self.pod_states.get(pod.uid)
        if st is not None:
            if pod.uid in self.assumed_pods:
                if st.pod.node_name != pod.node_name:
                    self._remove_pod_from_node(st.pod)
                    self._add_pod_to_node(pod)
                self.assumed_pods.discard(pod.uid)
                # The apiserver's own event says the bind landed. A queued
                # bind's event can overtake its acknowledgement's settle
                # (finish_binding, a no-op by then), and the post-restart
                # reconcile tells a completed bind by this flag.
                st.binding_finished = True
            st.pod = pod
            st.deadline = None
        else:
            self._add_pod_to_node(pod)
            self.pod_states[pod.uid] = _PodState(pod)

    def update_pod(self, old: Pod, new: Pod) -> None:
        if new.uid in self.assumed_pods:
            # Watch-confirmed version of a pod we assumed: treat as Add.
            self.add_pod(new)
            return
        st = self.pod_states.get(old.uid)
        if st is None:
            self.add_pod(new)
            return
        self._remove_pod_from_node(st.pod)
        self._add_pod_to_node(new)
        st.pod = new

    def remove_pod(self, pod: Pod) -> None:
        st = self.pod_states.pop(pod.uid, None)
        if st is not None:
            self._remove_pod_from_node(st.pod)
        self.assumed_pods.discard(pod.uid)

    def is_assumed_pod(self, pod: Pod) -> bool:
        return pod.uid in self.assumed_pods

    def cleanup_expired_assumed_pods(self) -> None:
        if self.ttl <= 0:
            return
        now = self.now()
        for uid in list(self.assumed_pods):
            st = self.pod_states[uid]
            if st.binding_finished and st.deadline is not None and now > st.deadline:
                self._remove_pod_from_node(st.pod)
                self.assumed_pods.discard(uid)
                del self.pod_states[uid]

    def _add_pod_to_node(self, pod: Pod, pod_info: Optional[PodInfo] = None) -> None:
        ni = self.nodes.get(pod.node_name)
        if ni is None:
            # Pod on unknown node: create a placeholder NodeInfo (reference
            # keeps an imaginary nodeInfo so pods on deleted nodes still count).
            ni = NodeInfo()
            self.nodes[pod.node_name] = ni
            self._imaginary.append(pod.node_name)
            self._order_dirty = True
        if pod_info is None or pod_info.pod is not pod:
            pod_info = PodInfo.of(pod)
        ni.add_pod(pod_info)
        if self.pod_group_state is not None:
            self.pod_group_state.record_bound(pod)
        for v in pod.volumes:
            if v.pvc_name:
                key = f"{pod.namespace}/{v.pvc_name}"
                self.pvc_refs[key] = self.pvc_refs.get(key, 0) + 1
        aff = pod.affinity
        if aff is not None and (aff.pod_affinity or aff.pod_anti_affinity):
            self.affinity_pod_refs += 1
        self._dirty.add(pod.node_name)

    def _remove_pod_from_node(self, pod: Pod) -> None:
        if self.pod_group_state is not None:
            self.pod_group_state.remove(pod)
        # Symmetric with _add_pod_to_node's unconditional increment: the
        # refcount must drop even when the pod's node has already left the
        # cache (a leak would misclassify future users as 'shared pvc' and
        # silently strip their device eligibility).
        for v in pod.volumes:
            if v.pvc_name:
                key = f"{pod.namespace}/{v.pvc_name}"
                n = self.pvc_refs.get(key, 0) - 1
                if n <= 0:
                    self.pvc_refs.pop(key, None)
                else:
                    self.pvc_refs[key] = n
        aff = pod.affinity
        if aff is not None and (aff.pod_affinity or aff.pod_anti_affinity):
            self.affinity_pod_refs = max(0, self.affinity_pod_refs - 1)
        ni = self.nodes.get(pod.node_name)
        if ni is not None:
            ni.remove_pod(pod)
            self._dirty.add(pod.node_name)

    # -- snapshot ----------------------------------------------------------

    def update_snapshot(self, snapshot: Snapshot) -> Snapshot:
        """UpdateSnapshot (cache.go:206): re-clone only dirty NodeInfos, and
        patch them into the snapshot's lists IN PLACE — the reference's
        generation walk touches O(changed) nodes per cycle, and the daemonset
        workload (15k nodes, one dirty node per bind) holds this to the same
        bound. Full list rebuilds happen only on structural changes or when
        an affinity/image-relevant membership changed."""
        order_refreshed = self._order_dirty
        if self._order_dirty:
            self.node_order = self.node_tree.list() + list(self._imaginary)
            self._order_dirty = False
        structural = order_refreshed or self._removed_since_snapshot or (
            len(snapshot.node_info_list) != len(self.node_order)
        )
        affinity_dirty = structural
        replaced = []
        for name in self._dirty:
            ni = self.nodes.get(name)
            if ni is None:
                continue
            clone = ni.snapshot_clone()
            old = snapshot.node_info_map.get(name)
            if old is None or bool(old.pods_with_affinity) != bool(clone.pods_with_affinity) \
                    or bool(old.pods_with_required_anti_affinity) != bool(clone.pods_with_required_anti_affinity) \
                    or old.image_states.keys() != clone.image_states.keys():
                affinity_dirty = True
            elif name in getattr(snapshot, "_list_members", ()):
                # The re-cloned node sits in an affinity sublist: the list
                # entry must point at the fresh clone.
                affinity_dirty = True
            snapshot.node_info_map[name] = clone
            replaced.append((name, clone))
        if structural:
            snapshot.node_info_map = {
                name: snapshot.node_info_map.get(name) or self.nodes[name].snapshot_clone()
                for name in self.node_order
            }
            # Imaginary nodes (pods observed before their node) stay in the
            # map for accounting but are excluded from the schedulable list,
            # as the reference excludes nil-node entries from nodeInfoList.
            snapshot.node_info_list = [
                snapshot.node_info_map[n] for n in self.node_order
                if n in snapshot.node_info_map and snapshot.node_info_map[n].node is not None
            ]
            snapshot.rebuild_lists()
        else:
            index = getattr(snapshot, "_index", None)
            if index is None:
                snapshot.rebuild_lists()
                index = snapshot._index
            for name, clone in replaced:
                idx = index.get(name)
                if idx is not None and clone.node is not None:
                    snapshot.node_info_list[idx] = clone
                elif clone.node is not None:
                    affinity_dirty = True  # newly visible node: full rebuild
            if affinity_dirty:
                snapshot.rebuild_lists()
        snapshot.generation = next_generation()
        self._dirty.clear()
        self._removed_since_snapshot = False
        return snapshot

    def dirty_nodes(self) -> Set[str]:
        """Names of nodes changed since the last snapshot (device mirror feed)."""
        return set(self._dirty)
