"""NodeInfo / PodInfo — the per-node aggregate the scheduler filters against.

Re-expresses pkg/scheduler/framework/types.go (NodeInfo struct at types.go:173):
each node carries its pod list, the summed `requested` resource vector,
host-port usage, and affinity-relevant pod sublists, plus a monotonically
increasing `generation` that drives incremental snapshotting
(backend/cache/cache.go:206 UpdateSnapshot).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from ..api.resource import Resource
from ..api.types import Node, Pod

_generation = itertools.count(1)


def next_generation() -> int:
    return next(_generation)


@dataclass
class PodInfo:
    """Wraps a Pod with precomputed scheduling-relevant views
    (reference framework/types.go PodInfo: cached affinity terms, request)."""

    pod: Pod
    required_affinity_terms: tuple = ()
    required_anti_affinity_terms: tuple = ()
    preferred_affinity_terms: tuple = ()
    preferred_anti_affinity_terms: tuple = ()
    cached_request: Optional[Resource] = None

    @classmethod
    def of(cls, pod: Pod) -> "PodInfo":
        aff = pod.affinity
        req_aff = req_anti = pref_aff = pref_anti = ()
        if aff is not None:
            if aff.pod_affinity is not None:
                req_aff = tuple(aff.pod_affinity.required)
                pref_aff = tuple(aff.pod_affinity.preferred)
            if aff.pod_anti_affinity is not None:
                req_anti = tuple(aff.pod_anti_affinity.required)
                pref_anti = tuple(aff.pod_anti_affinity.preferred)
        return cls(
            pod=pod,
            required_affinity_terms=req_aff,
            required_anti_affinity_terms=req_anti,
            preferred_affinity_terms=pref_aff,
            preferred_anti_affinity_terms=pref_anti,
            cached_request=pod.resource_request(),
        )

    @property
    def request(self) -> Resource:
        if self.cached_request is None:
            self.cached_request = self.pod.resource_request()
        return self.cached_request


class NodeInfo:
    """Aggregated node state. Mutable; every mutation bumps `generation`;
    `node_generation` is the generation at which the node itself (its spec:
    allocatable, labels, taints, images) was last set, so a reader that kept
    it tells a change of the node from a change of its pods."""

    __slots__ = (
        "node",
        "pods",
        "pods_with_affinity",
        "pods_with_required_anti_affinity",
        "requested",
        "non_zero_requested",
        "allocatable",
        "used_ports",
        "pvc_ref_counts",
        "image_states",
        "generation",
        "node_generation",
    )

    # Default requests for the "non-zero" aggregate used by scoring
    # (reference framework/types.go DefaultMilliCPURequest/DefaultMemoryRequest).
    DEFAULT_MILLI_CPU = 100
    DEFAULT_MEMORY = 200 * 1024 * 1024

    def __init__(self, node: Optional[Node] = None):
        self.node: Optional[Node] = node
        self.pods: List[PodInfo] = []
        self.pods_with_affinity: List[PodInfo] = []
        self.pods_with_required_anti_affinity: List[PodInfo] = []
        self.requested = Resource()
        self.non_zero_requested = Resource()
        self.allocatable = node.allocatable.clone() if node else Resource()
        # (protocol, host_ip, port) tuples
        self.used_ports: Set[Tuple[str, str, int]] = set()
        self.pvc_ref_counts: Dict[str, int] = {}
        self.image_states: Dict[str, int] = {}  # image name -> size bytes
        if node:
            for img in node.images:
                for name in img.names:
                    self.image_states[name] = img.size_bytes
        self.generation = self.node_generation = next_generation()

    # -- mutations ---------------------------------------------------------

    def set_node(self, node: Node) -> None:
        self.node = node
        self.allocatable = node.allocatable.clone()
        self.image_states = {}
        for img in node.images:
            for name in img.names:
                self.image_states[name] = img.size_bytes
        self.generation = self.node_generation = next_generation()

    def add_pod(self, pi: PodInfo) -> None:
        self.pods.append(pi)
        if pi.required_affinity_terms or pi.preferred_affinity_terms \
                or pi.required_anti_affinity_terms or pi.preferred_anti_affinity_terms:
            self.pods_with_affinity.append(pi)
        if pi.required_anti_affinity_terms:
            self.pods_with_required_anti_affinity.append(pi)
        req = pi.request
        self.requested.add(req)
        self.non_zero_requested.milli_cpu += req.milli_cpu or self.DEFAULT_MILLI_CPU
        self.non_zero_requested.memory += req.memory or self.DEFAULT_MEMORY
        for p in pi.pod.host_ports():
            self.used_ports.add((p.protocol, p.host_ip, p.host_port))
        for v in pi.pod.volumes:
            if v.pvc_name:
                key = f"{pi.pod.namespace}/{v.pvc_name}"
                self.pvc_ref_counts[key] = self.pvc_ref_counts.get(key, 0) + 1
        self.generation = next_generation()

    def remove_pod(self, pod: Pod) -> bool:
        for i, pi in enumerate(self.pods):
            if pi.pod.uid == pod.uid:
                self.pods.pop(i)
                self.pods_with_affinity = [p for p in self.pods_with_affinity if p.pod.uid != pod.uid]
                self.pods_with_required_anti_affinity = [
                    p for p in self.pods_with_required_anti_affinity if p.pod.uid != pod.uid
                ]
                req = pi.request
                self.requested.sub(req)
                self.non_zero_requested.milli_cpu -= req.milli_cpu or self.DEFAULT_MILLI_CPU
                self.non_zero_requested.memory -= req.memory or self.DEFAULT_MEMORY
                for p in pi.pod.host_ports():
                    self.used_ports.discard((p.protocol, p.host_ip, p.host_port))
                for v in pi.pod.volumes:
                    if v.pvc_name:
                        key = f"{pi.pod.namespace}/{v.pvc_name}"
                        n = self.pvc_ref_counts.get(key, 0) - 1
                        if n <= 0:
                            self.pvc_ref_counts.pop(key, None)
                        else:
                            self.pvc_ref_counts[key] = n
                self.generation = next_generation()
                return True
        return False

    # -- views -------------------------------------------------------------

    @property
    def name(self) -> str:
        return self.node.name if self.node else ""

    def snapshot_clone(self) -> "NodeInfo":
        """Clone for an immutable per-cycle snapshot. Pod lists are shared
        copy-on-write style: list objects are copied, PodInfo entries shared."""
        c = NodeInfo.__new__(NodeInfo)
        c.node = self.node
        c.pods = list(self.pods)
        c.pods_with_affinity = list(self.pods_with_affinity)
        c.pods_with_required_anti_affinity = list(self.pods_with_required_anti_affinity)
        c.requested = self.requested.clone()
        c.non_zero_requested = self.non_zero_requested.clone()
        c.allocatable = self.allocatable.clone()
        c.used_ports = set(self.used_ports)
        c.pvc_ref_counts = dict(self.pvc_ref_counts)
        c.image_states = dict(self.image_states)
        c.generation = self.generation
        c.node_generation = self.node_generation
        return c
