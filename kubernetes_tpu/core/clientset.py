"""Fake control plane: an in-process pod/node store with watch-style fanout.

Plays the role of client-go fake.Clientset + informers in the reference's unit
layer (SURVEY.md §4.2): scheduler event handlers subscribe, API writes (bind,
create, delete) synchronously fan out to them — the process-boundary analogue
of apiserver watch streams collapsed to function calls.
"""

from __future__ import annotations

import copy
import itertools
from typing import Callable, Dict, List, Optional

from ..api.storage import CSINode, PersistentVolume, PersistentVolumeClaim, StorageClass
from ..api.types import CompositePodGroup, Namespace, Node, Pod, PodGroup


class FakeClientset:
    def __init__(self):
        self.pods: Dict[str, Pod] = {}
        self.nodes: Dict[str, Node] = {}
        self.namespaces: Dict[str, Namespace] = {"default": Namespace(name="default")}
        self.pod_groups: Dict[str, PodGroup] = {}  # "ns/name" -> group
        self.composite_pod_groups: Dict[str, CompositePodGroup] = {}
        self.pvs: Dict[str, PersistentVolume] = {}
        self.pvcs: Dict[str, PersistentVolumeClaim] = {}  # "ns/name" -> pvc
        self.storage_classes: Dict[str, StorageClass] = {}
        self.csi_nodes: Dict[str, CSINode] = {}
        self.resource_slices: Dict[str, List] = {}   # node -> [ResourceSlice]
        self.resource_claims: Dict[str, object] = {}  # "ns/name" -> ResourceClaim
        self.device_classes: Dict[str, object] = {}
        self.bindings: Dict[str, str] = {}  # pod uid -> node name
        self._pod_handlers: List = []
        self._node_handlers: List = []
        self._namespace_handlers: List = []
        self._pod_group_handlers: List = []
        self._storage_handlers: List = []
        self._pv_controller = None
        # Monotonic resourceVersion. itertools.count is C-implemented and
        # GIL-atomic: a concurrent client thread (perf harness creators, the
        # threaded watch transport) can write while the scheduling loop
        # binds, without ever minting duplicate versions.
        self._rv_counter = itertools.count(1)
        # Shard leases (shard/leases.py): the in-process analogue of the
        # apiserver's /api/v1/leases surface. `lease_now` is injectable so
        # lease-expiry tests need no real sleeps.
        self.leases: Dict[str, dict] = {}
        import time as _time
        self.lease_now: Callable[[], float] = _time.monotonic

    # -- informer-ish registration ----------------------------------------

    def on_pod_event(self, handler: Callable[[str, Optional[Pod], Pod], None]) -> None:
        """handler(kind, old, new) with kind in add/update/delete."""
        self._pod_handlers.append(handler)

    def on_node_event(self, handler: Callable[[str, Optional[Node], Node], None]) -> None:
        self._node_handlers.append(handler)

    def on_namespace_event(self, handler: Callable[[Namespace], None]) -> None:
        self._namespace_handlers.append(handler)
        for ns in self.namespaces.values():  # replay existing (informer list)
            handler(ns)

    def on_pod_group_event(self, handler: Callable[[PodGroup], None]) -> None:
        self._pod_group_handlers.append(handler)
        for g in self.pod_groups.values():
            handler(g)

    def on_storage_event(self, handler: Callable[[str, object], None]) -> None:
        """handler(kind, obj) for PV/PVC/StorageClass/CSINode/DRA writes —
        the informer feed behind the Storage/Add queueing hints."""
        self._storage_handlers.append(handler)

    def _fire_storage(self, kind: str, obj) -> None:
        for h in self._storage_handlers:
            h(kind, obj)

    # -- writes ------------------------------------------------------------

    def create_node(self, node: Node) -> Node:
        node.resource_version = next(self._rv_counter)
        self.nodes[node.name] = node
        for h in self._node_handlers:
            h("add", None, node)
        return node

    def update_node(self, node: Node) -> Node:
        old = self.nodes.get(node.name)
        node.resource_version = next(self._rv_counter)
        self.nodes[node.name] = node
        for h in self._node_handlers:
            h("update", old, node)
        return node

    def delete_node(self, name: str) -> None:
        node = self.nodes.pop(name, None)
        if node is not None:
            for h in self._node_handlers:
                h("delete", node, node)

    def create_namespace(self, ns: Namespace) -> Namespace:
        self.namespaces[ns.name] = ns
        for h in self._namespace_handlers:
            h(ns)
        return ns

    def create_pod_group(self, group: PodGroup) -> PodGroup:
        self.pod_groups[f"{group.namespace}/{group.name}"] = group
        for h in self._pod_group_handlers:
            h(group)
        return group

    def create_composite_pod_group(self, cpg: CompositePodGroup) -> CompositePodGroup:
        """CompositePodGroup informer feed — delivered through the same
        pod-group handler channel (handlers type-switch)."""
        self.composite_pod_groups[f"{cpg.namespace}/{cpg.name}"] = cpg
        for h in self._pod_group_handlers:
            h(cpg)
        return cpg

    # -- storage (PV controller surface the volume plugins consume) --------

    def create_pv(self, pv: PersistentVolume) -> PersistentVolume:
        self.pvs[pv.name] = pv
        self._fire_storage("pv", pv)
        return pv

    def create_pvc(self, pvc: PersistentVolumeClaim) -> PersistentVolumeClaim:
        self.pvcs[pvc.key] = pvc
        self._fire_storage("pvc", pvc)
        return pvc

    def create_storage_class(self, sc: StorageClass) -> StorageClass:
        self.storage_classes[sc.name] = sc
        self._fire_storage("storage_class", sc)
        return sc

    def create_csi_node(self, cn: CSINode) -> CSINode:
        self.csi_nodes[cn.node_name] = cn
        # Version the CSINode SET (not just its size): replacing a node's
        # driver_limits must invalidate limited-driver caches.
        self.csi_nodes_rv = getattr(self, "csi_nodes_rv", 0) + 1
        self._fire_storage("csi_node", cn)
        return cn

    def create_resource_slice(self, sl) -> object:
        self.resource_slices.setdefault(sl.node_name, []).append(sl)
        if any(getattr(d, "consumes", None) for d in sl.devices):
            # Node-allocatable-consuming devices: their allocation math is
            # outside the device kernel's aux model (eligibility checks this).
            self.has_consuming_devices = True
        self._fire_storage("resource_slice", sl)
        return sl

    def create_resource_claim(self, claim) -> object:
        self.resource_claims[claim.key] = claim
        self.resource_claims_rv = getattr(self, "resource_claims_rv", 0) + 1
        self._fire_storage("resource_claim", claim)
        return claim

    def bump_resource_claims_rv(self) -> None:
        """Out-of-band claim mutations (controller-side allocation) must
        invalidate in-use caches keyed on the claims revision."""
        self.resource_claims_rv = getattr(self, "resource_claims_rv", 0) + 1

    def create_device_class(self, dc) -> object:
        self.device_classes[dc.name] = dc
        self._fire_storage("device_class", dc)
        return dc

    def attach_pv_controller(self, ctrl) -> None:
        """Register the PV controller (core/pv_controller.py) so PreBind's
        provisioning path rides the real control loop."""
        self._pv_controller = ctrl

    def bind_volume(self, pvc: PersistentVolumeClaim, pv_name: str, node_name: str) -> None:
        """VolumeBinding PreBind writes (binder.go BindPodVolumes): bind the
        claim to the decided PV, or — for WaitForFirstConsumer provisioning —
        write the volume.kubernetes.io/selected-node annotation and let the
        PV controller provision (pv_controller.py). Without an attached
        controller, provisioning is simulated inline (unit-test shape)."""
        if pv_name:
            pv = self.pvs[pv_name]
            pv.claim_ref = pvc.key
            pvc.volume_name = pv_name
            pvc.annotations["pv.kubernetes.io/bind-completed"] = "true"
            return
        from ..core.pv_controller import SELECTED_NODE
        pvc.annotations[SELECTED_NODE] = node_name
        if self._pv_controller is not None:
            self._pv_controller.provision(pvc, node_name)
            return
        from ..api.types import NodeSelector, NodeSelectorTerm
        from ..api.labels import IN, Requirement
        provisioned = PersistentVolume(
            name=f"pvc-{pvc.uid}", capacity=pvc.request,
            access_modes=pvc.access_modes, storage_class=pvc.storage_class,
            node_affinity=NodeSelector(terms=(NodeSelectorTerm(
                match_fields=(Requirement("metadata.name", IN, (node_name,)),)),)),
            claim_ref=pvc.key)
        self.pvs[provisioned.name] = provisioned
        pvc.volume_name = provisioned.name

    def create_pod(self, pod: Pod) -> Pod:
        pod.resource_version = next(self._rv_counter)
        self.pods[pod.uid] = pod
        for h in self._pod_handlers:
            h("add", None, pod)
        return pod

    def update_pod(self, pod: Pod) -> Pod:
        old = self.pods.get(pod.uid)
        pod.resource_version = next(self._rv_counter)
        # An update may carry an in-place spec change on the SAME object
        # (clients mutate-and-republish): drop every derived-spec memo,
        # including the template-shared signature holder — the object's spec
        # may have diverged from its template. This is the API-boundary
        # analogue of the old resourceVersion-keyed memo invalidation.
        d = pod.__dict__
        d.pop("_sig_cache", None)
        d.pop("_sig_shared", None)
        d.pop("_req_cache", None)
        d.pop("_hp_cache", None)
        self.pods[pod.uid] = pod
        for h in self._pod_handlers:
            h("update", old, pod)
        return pod

    def delete_pod(self, pod: Pod) -> None:
        p = self.pods.get(pod.uid)
        if p is None:
            return
        if p.finalizers:
            # Graceful deletion: finalizers park the object with a
            # deletionTimestamp; watchers see an update, not a delete, and
            # repeated deletes cannot complete it — only finalizer removal
            # can (pkg/registry/core/pod strategy + apimachinery finalizers).
            if p.deletion_ts is None:
                import time as _t
                p.deletion_ts = _t.time()
                p.resource_version = next(self._rv_counter)
                for h in self._pod_handlers:
                    h("update", p, p)
            return
        self.pods.pop(pod.uid, None)
        for h in self._pod_handlers:
            h("delete", p, p)

    def remove_pod_finalizers(self, pod: Pod) -> None:
        """Clear finalizers; if a delete is pending, it completes now."""
        p = self.pods.get(pod.uid)
        if p is None:
            return
        p.finalizers = []
        if p.deletion_ts is not None:
            self.pods.pop(p.uid, None)
            for h in self._pod_handlers:
                h("delete", p, p)

    def bind(self, pod: Pod, node_name: str) -> None:
        """POST pods/{name}/binding (DefaultBinder target)."""
        stored = self.pods.get(pod.uid)
        if stored is None:
            raise KeyError(f"pod {pod.namespace}/{pod.name} not found")
        old = stored
        new = copy.copy(stored)
        new.node_name = node_name
        new.resource_version = next(self._rv_counter)
        self.pods[pod.uid] = new
        self.bindings[pod.uid] = node_name
        for h in self._pod_handlers:
            h("update", old, new)

    def bind_many(self, pairs) -> list:
        """The bulk binding verb (``HTTPClientset.bind_many``'s signature:
        ``(pod, node name)`` pairs in, one ``None`` or exception a pair
        out): per pair what ``bind`` does, and the same ``update`` event to
        every handler before the next pair is touched. One transaction:
        it stops at the first pair the store refuses, whose exception ends
        the list, and the pairs after it are not attempted and have no
        entry (a remote apiserver answers every item; ``DefaultBinder.
        _bulk_bind`` sends the rest on)."""
        out = []
        for pod, node_name in pairs:
            try:
                self.bind(pod, node_name)
            except Exception as e:  # noqa: BLE001 - the pair's own verdict
                out.append(e)
                break
            out.append(None)
        return out

    def patch_pod_status(self, pod: Pod, nominated_node_name: str = "", phase: str = "") -> None:
        stored = self.pods.get(pod.uid)
        if stored is None:
            return
        if nominated_node_name:
            stored.nominated_node_name = nominated_node_name
        if phase:
            stored.phase = phase

    # -- shard leases (apiserver /api/v1/leases parity) ---------------------

    def _lease_wire(self, name: str, rec: dict, now: float) -> dict:
        age = now - rec["renew"]
        return {"name": name, "holder": rec["holder"],
                "leaseDurationSeconds": rec["duration"],
                "ageSeconds": round(age, 3),
                "transitions": rec["transitions"],
                "expired": (not rec["holder"]) or age >= rec["duration"]}

    def list_leases(self) -> List[dict]:
        now = self.lease_now()
        return [self._lease_wire(n, r, now)
                for n, r in sorted(self.leases.items())]

    def upsert_lease(self, name: str, holder: str,
                     duration: float) -> Optional[dict]:
        """Acquire-or-renew under CAS semantics (same contract as the
        apiserver's PUT /api/v1/leases/<name>): a held, unexpired lease only
        renews for its current holder; anyone else gets None."""
        now = self.lease_now()
        rec = self.leases.get(name)
        if (rec is not None and rec["holder"] and rec["holder"] != holder
                and now - rec["renew"] < rec["duration"]):
            return None
        if rec is None:
            rec = {"holder": "", "duration": float(duration),
                   "renew": now, "transitions": 0}
            self.leases[name] = rec
        if rec["holder"] != holder:
            rec["transitions"] += 1
        rec["holder"] = holder
        rec["duration"] = float(duration)
        rec["renew"] = now
        return self._lease_wire(name, rec, now)


class RetryingClientset:
    """Write-path retry decorator over any clientset (client-go's
    rest/request.go retry + wait.Backoff, collapsed to the verbs the
    scheduler writes). Transient failures — connection resets, timeouts,
    5xx, injected ``TransientAPIError`` — are replayed with exponential
    backoff + seeded jitter; semantic errors (pod not found, validation)
    propagate on the first try. Reads, listers, and informer registration
    delegate untouched, so the wrapper is drop-in wherever a clientset is
    (``TPUScheduler(clientset=RetryingClientset(HTTPClientset(url)))``).

    ``retries_total`` counts replayed calls; ``give_ups`` counts calls
    that exhausted the budget (the final exception propagates — the async
    dispatcher's error inbox / drain_errors owns what happens next)."""

    _WRITE_VERBS = frozenset({
        "create_pod", "update_pod", "delete_pod", "bind", "patch_pod_status",
        # The bulk bind (a thread-mode dispatcher's run of queued binds in
        # one request): a transport failure replays the whole request, which
        # the binding subresource answers idempotently item by item.
        "bind_many",
        "create_node", "update_node", "delete_node",
        "create_namespace", "create_pod_group", "create_composite_pod_group",
        "create_pv", "create_pvc", "create_storage_class", "create_csi_node",
        "create_resource_slice", "create_resource_claim",
        "create_device_class", "bind_volume", "remove_pod_finalizers",
        # Safe to replay blindly: the eviction subresource is idempotent by
        # intent id (the server's WAL'd ledger answers a replay with
        # already=True instead of double-evicting).
        "evict_pod",
    })

    def __init__(self, inner, retry=None):
        from .backoff import RetryConfig, retry_call
        self._inner = inner
        self._retry_cfg = retry or RetryConfig()
        self._retry_call = retry_call
        self.retries_total = 0
        self.give_ups = 0

    def _on_retry(self, _attempt: int, _exc: BaseException) -> None:
        self.retries_total += 1

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if name in RetryingClientset._WRITE_VERBS and callable(attr):
            def retried(*args, _attr=attr, _verb=name, **kwargs):
                state = {"retried": False}

                def on_retry(attempt, exc):
                    state["retried"] = True
                    self._on_retry(attempt, exc)

                try:
                    return self._retry_call(
                        lambda: _attr(*args, **kwargs),
                        config=self._retry_cfg, on_retry=on_retry)
                except BaseException as e:
                    if (state["retried"] and getattr(e, "code", None) == 409
                            and _verb.startswith("create_")):
                        # AlreadyExists on a create REPLAY: the earlier
                        # attempt landed before its reply was lost — the
                        # write is durable, which is what the caller wanted.
                        # A 409 on the FIRST try is a genuine conflict and
                        # raises. bind is deliberately excluded: the server
                        # answers a same-node bind replay 200, so a bind 409
                        # is ALWAYS a real conflict (another scheduler won
                        # the pod) and must reach the conflict-requeue path.
                        return None
                    if self._retry_cfg.retriable(e):
                        self.give_ups += 1  # budget exhausted, still failing
                    raise
            return retried
        return attr
