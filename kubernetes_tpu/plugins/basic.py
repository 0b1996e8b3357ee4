"""The "cheap" in-tree plugins: NodeName, NodeUnschedulable, NodePorts,
SchedulingGates, PrioritySort, DefaultBinder, ImageLocality, TaintToleration,
NodeAffinity.

Each class mirrors one reference plugin package under
pkg/scheduler/framework/plugins/ (anchor cited per class). Methods follow the
duck-typed extension-point protocol in kubernetes_tpu/core/framework.py.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

from ..api.types import (
    NO_EXECUTE,
    NO_SCHEDULE,
    PREFER_NO_SCHEDULE,
    Node,
    Pod,
    Toleration,
    find_matching_untolerated_taint,
)
from ..core.framework import (
    BIND_QUEUED,
    MAX_NODE_SCORE,
    OK,
    CycleState,
    NodeScore,
    PreFilterResult,
    Status,
    default_normalize_score,
)
from ..core.node_info import NodeInfo

# ---------------------------------------------------------------------------


class NodeName:
    """plugins/nodename: pod.spec.nodeName exact match."""

    name = "NodeName"

    def filter(self, state: CycleState, pod: Pod, node_info: NodeInfo) -> Status:
        if pod.node_name and pod.node_name != node_info.name:
            return Status.unresolvable("node(s) didn't match the requested node name")
        return OK

    def sign(self, pod: Pod):
        return pod.node_name


class NodeUnschedulable:
    """plugins/nodeunschedulable: gate on node.spec.unschedulable, tolerable
    via the unschedulable taint toleration."""

    name = "NodeUnschedulable"
    TAINT_KEY = "node.kubernetes.io/unschedulable"

    def filter(self, state: CycleState, pod: Pod, node_info: NodeInfo) -> Status:
        node = node_info.node
        if node is not None and node.unschedulable:
            if not any(t.tolerates(_UNSCHED_TAINT) for t in pod.tolerations):
                return Status.unresolvable("node(s) were unschedulable")
        return OK

    def sign(self, pod: Pod):
        return tuple((t.key, t.operator, t.value, t.effect) for t in pod.tolerations)


from ..api.types import Taint as _Taint  # noqa: E402

_UNSCHED_TAINT = _Taint(key=NodeUnschedulable.TAINT_KEY, effect=NO_SCHEDULE)


def host_ports_conflict(ports, used_ports) -> bool:
    """nodeports.go Fits → fitsPorts, incl. the 0.0.0.0 wildcard semantics.
    The single source of truth for host AND device paths (the device path
    evaluates this host-side into a static per-node mask — ops/features.py)."""
    for p in ports:
        for (proto, ip, port) in used_ports:
            if port != p.host_port or proto != p.protocol:
                continue
            if ip in ("", "0.0.0.0") or p.host_ip in ("", "0.0.0.0") or ip == p.host_ip:
                return True
    return False


class NodePorts:
    """plugins/nodeports: reject nodes with conflicting host ports."""

    name = "NodePorts"
    _KEY = "PreFilterNodePorts"

    def pre_filter(self, state: CycleState, pod: Pod, nodes) -> Tuple[Optional[PreFilterResult], Status]:
        ports = pod.host_ports()
        if not ports:
            return None, Status.skip()
        state.write(self._KEY, ports)
        return None, OK

    def filter(self, state: CycleState, pod: Pod, node_info: NodeInfo) -> Status:
        ports = state.read(self._KEY)
        if ports is None:
            ports = pod.host_ports()
        if host_ports_conflict(ports, node_info.used_ports):
            return Status.unschedulable("node(s) didn't have free ports for the requested pod ports")
        return OK

    def sign(self, pod: Pod):
        return tuple(sorted((p.protocol, p.host_ip, p.host_port) for p in pod.host_ports()))


class SchedulingGates:
    """plugins/schedulinggates: PreEnqueue gate on spec.schedulingGates."""

    name = "SchedulingGates"

    def pre_enqueue(self, pod: Pod) -> Status:
        if pod.scheduling_gates:
            return Status.unresolvable(
                "waiting for scheduling gates: " + ",".join(pod.scheduling_gates)
            )
        return OK


class PrioritySort:
    """plugins/queuesort: priority desc, then enqueue timestamp asc."""

    name = "PrioritySort"

    def less(self, a, b) -> bool:
        pa = a.pod.priority
        pb = b.pod.priority
        if pa != pb:
            return pa > pb
        return a.timestamp < b.timestamp

    @staticmethod
    def sort_key(qpi) -> tuple:
        """Tuple equivalent of less() for C-speed heap comparisons."""
        return (-qpi.pod.priority, qpi.timestamp)


class DefaultBinder:
    """plugins/defaultbinder: POST /binding (framework/api_calls/
    pod_binding.go:32 PodBindingCall via APIDispatcher). How a bind goes out
    follows the dispatcher's mode, which the scheduler takes from its
    clientset (core/scheduler.py ``_dispatch_mode``): over the in-process
    store the call runs here, on the loop, and OK means bound; against a
    remote apiserver it is queued for the dispatcher's worker, which sends
    runs of queued binds as one bulk request, and BIND_QUEUED means the pod
    stays assumed until the acknowledgement is drained (``_acked``) or the
    failure is (``Handle.on_async_bind_error``).

    Every bound pod feeds scheduler_pod_stage_duration_seconds in both
    modes: ``bind.post`` is the round trip of the request that carried the
    pod (each pod of a bulk request observes that request's), ``bind.queue``
    the time from the enqueue to that request's start (0 inline: not
    observed). The loop's own table gets ``bind.post`` only for time the
    loop was blocked in a bind, which is the inline mode's."""

    name = "DefaultBinder"

    def __init__(self, handle=None):
        self.handle = handle

    def _posted(self, t0: float) -> None:
        """A synchronous bind call returned: its round trip as the scheduler
        sees it, for every pod (the histogram) and as the loop's bind.post
        stage (whose count is the inline mode's single requests)."""
        seconds = time.perf_counter() - t0
        self.handle.metrics.pod_stage_duration.observe(seconds, "bind.post")
        self.handle.stages.leaf("bind.post", seconds)

    def _acked(self, call) -> None:
        """Loop thread, from the dispatcher's done inbox: the apiserver
        answered 200 for this pod at ``call.acked_at``. The two per-pod
        stages come from the instants the worker read."""
        observe = self.handle.metrics.pod_stage_duration.observe
        observe(call.sent_at - call.enqueued_at, "bind.queue")
        observe(call.acked_at - call.sent_at, "bind.post")
        self.handle.on_async_bind_done(call.bind_args[0], call.acked_at)

    def _refused(self, e: Exception, pod: Pod, dispatcher) -> Status:
        """What a synchronous bind that raised ``e`` returns for its pod."""
        if getattr(e, "code", None) == 429:
            # Flow-control shed (core/flowcontrol.py): the bind
            # never ran. Tagged so the binding cycle requeues
            # through the backoffQ with the admission stamp
            # intact — the retry layers already honored
            # Retry-After before this surfaced.
            return Status.bind_shed(str(e))
        if getattr(e, "code", None) == 409:
            # Optimistic-binding loss (AlreadyBound /
            # OutOfCapacity): another scheduler committed first.
            # Tagged so the binding cycle requeues through the
            # backoffQ instead of parking the pod as an error.
            reason = ""
            try:  # the 409 body names which conflict it was
                import json as _json
                reason = _json.loads(e.read()).get("error", "")
            except Exception:  # noqa: BLE001
                pass
            return Status.bind_conflict(reason or str(e))
        if dispatcher is not None:
            from ..core.api_dispatcher import CALL_BINDING
            dispatcher.errors.append(f"{CALL_BINDING}/{pod.uid}: {e!r}")
        return Status.error(str(e))

    def bind(self, state: CycleState, pod: Pod, node_name: str) -> Status:
        dispatcher = getattr(self.handle, "api_dispatcher", None)
        try:
            if dispatcher is None or dispatcher.mode == "inline":
                # Inline mode executes immediately anyway — skip the APICall
                # allocation and go straight to the API (the per-pod tail of
                # the hint walk and of every batch the batch tail refuses).
                # Counter/error accounting matches APIDispatcher._execute.
                t0 = time.perf_counter()
                try:
                    self.handle.clientset.bind(pod, node_name)
                except Exception as e:  # noqa: BLE001
                    self._posted(t0)
                    return self._refused(e, pod, dispatcher)
                self._posted(t0)
                if dispatcher is not None:
                    dispatcher.executed += 1
                return OK
            from ..core.api_dispatcher import APICall, CALL_BINDING
            from ..core import spans as _spans
            _tr = _spans.default_tracer()
            _ctx = _tr.context_for(pod.uid)
            on_error = self.handle.on_async_bind_error
            dispatcher.add(APICall(
                call_type=CALL_BINDING, object_uid=pod.uid,
                trace_ctx=_spans.format_ctx(_ctx) if _tr.wants(_ctx) else None,
                execute=lambda: self.handle.clientset.bind(pod, node_name),
                bind_args=(pod, node_name),
                # Stable bound method: the dispatcher batches consecutive
                # binding calls whose bulk_execute is the SAME callable.
                bulk_execute=self._bulk_bind,
                on_done=self._acked,
                on_error=lambda e, _p=pod: on_error(_p, e)))
        except Exception as e:  # noqa: BLE001
            return Status.error(str(e))
        return BIND_QUEUED

    def bind_run(self, pairs) -> list:
        """Inline mode, a retired batch's run of ``(pod, node name)`` as ONE
        bulk request to a clientset with the bulk verb (models/
        tpu_scheduler.py ``_commit_batch``). One entry a pair the request
        answered, in order: None for a bound pod, else the Status ``bind``
        would have returned for its refusal. The in-process store stops at
        its first refusal and answers no pair after it; a request that
        failed whole refuses every pair. Accounted as the worker's bulk
        request is: every answered pod observes the request's round trip
        as its ``bind.post``, the loop's table gets it once, the dispatcher
        counts one bulk request and the pods it carried."""
        handle = self.handle
        dispatcher = getattr(handle, "api_dispatcher", None)
        t0 = time.perf_counter()
        try:
            results = handle.clientset.bind_many(pairs)
        except Exception as e:  # noqa: BLE001 - nothing is known bound
            results = [e] * len(pairs)
        seconds = time.perf_counter() - t0
        handle.metrics.pod_stage_duration.observe_many(
            [seconds] * len(results), "bind.post")
        handle.stages.leaf("bind.post", seconds, per_pod=False)
        bound = results.count(None)
        if bound != len(results):
            results = [
                None if r is None else self._refused(r, pod, dispatcher)
                for r, (pod, _node) in zip(results, pairs)]
        if dispatcher is not None:
            dispatcher.bind_requests["bulk"] += 1
            dispatcher.bind_request_pods += len(pairs)
            dispatcher.executed += bound
        return results

    def _bulk_bind(self, calls) -> list:
        """Commit a run of queued binding calls as ONE bulk request
        (dispatcher thread worker → clientset.bind_many). Per-bind POSTs
        cap the async worker far below the server's bind capacity: each
        round-trip costs a GIL wakeup in a process whose reflector/
        scheduler threads are busy, so amortizing N binds per wakeup is
        worth ~an order of magnitude in drain rate. A store whose verb
        stops at its first refusal (FakeClientset) is sent the pairs after
        it as the next request: queued binds are independent, each gets its
        own verdict. Falls back to per-call binds for clientsets without a
        bulk verb."""
        cs = self.handle.clientset
        bind_many = getattr(cs, "bind_many", None)
        if bind_many is not None:
            pairs = [c.bind_args for c in calls]
            out = bind_many(pairs)
            while len(out) < len(pairs):
                more = bind_many(pairs[len(out):])
                if not more:
                    break
                out.extend(more)
            return out
        out = []
        for c in calls:
            try:
                cs.bind(*c.bind_args)
                out.append(None)
            except Exception as e:  # noqa: BLE001
                out.append(e)
        return out


class ImageLocality:
    """plugins/imagelocality: score nodes by bytes of the pod's images already
    present, scaled into [23Mi, 1000Mi] and spread-discounted by the fraction
    of nodes that already have the image (imagelocality.go scaledImageScore)."""

    name = "ImageLocality"
    MIN_THRESHOLD = 23 * 1024 * 1024
    MAX_CONTAINER_THRESHOLD = 1000 * 1024 * 1024

    def __init__(self, handle=None):
        self.handle = handle

    @classmethod
    def scaled_score(cls, pod: Pod, node_info: NodeInfo, image_nodes, total_nodes: int) -> int:
        """Pure scoring math (imagelocality.go scaledImageScore + thresholds):
        the single source of truth for host AND device paths — the device path
        precomputes this per node into a static score vector (ops/features.py)."""
        sum_scores = 0
        for c in pod.containers:
            size = node_info.image_states.get(c.image)
            if size is None:
                continue
            spread = 1.0
            if image_nodes is not None:
                spread = image_nodes.get(c.image, 1) / total_nodes
            sum_scores += int(size * spread)
        max_threshold = cls.MAX_CONTAINER_THRESHOLD * max(1, len(pod.containers))
        if sum_scores < cls.MIN_THRESHOLD:
            return 0
        if sum_scores > max_threshold:
            return MAX_NODE_SCORE
        return int(MAX_NODE_SCORE * (sum_scores - cls.MIN_THRESHOLD) / (max_threshold - cls.MIN_THRESHOLD))

    def score(self, state: CycleState, pod: Pod, node_info: NodeInfo) -> Tuple[int, Status]:
        total_nodes = 1
        image_nodes = None
        if self.handle is not None and getattr(self.handle, "snapshot", None) is not None:
            snap = self.handle.snapshot() if callable(self.handle.snapshot) else self.handle.snapshot
            total_nodes = max(1, len(snap.node_info_list))
            image_nodes = getattr(snap, "image_num_nodes", None)
        return self.scaled_score(pod, node_info, image_nodes, total_nodes), OK

    def sign(self, pod: Pod):
        return tuple(sorted(c.image for c in pod.containers))


class TaintToleration:
    """plugins/tainttoleration (taint_toleration.go).

    Filter: first NoSchedule/NoExecute taint not tolerated =>
    UnschedulableAndUnresolvable (:133). Score: count of PreferNoSchedule
    taints intolerable by the pod (:182-194); NormalizeScore reversed (:212).
    """

    name = "TaintToleration"
    _KEY = "PreScoreTaintToleration"

    def events_to_register(self):
        """taint_toleration.go EventsToRegister: node add/update with a
        toleration check (isSchedulableAfterNodeChange)."""
        from ..core.queue import EVENT_NODE_ADD, EVENT_NODE_UPDATE
        return [(EVENT_NODE_ADD, self._hint_node),
                (EVENT_NODE_UPDATE, self._hint_node)]

    @staticmethod
    def _hint_node(pod: Pod, old, new) -> bool:
        if new is None:
            return True
        return find_matching_untolerated_taint(new.taints, pod.tolerations) is None

    def filter(self, state: CycleState, pod: Pod, node_info: NodeInfo) -> Status:
        node = node_info.node
        if node is None:
            return Status.error("node not found")
        taint = find_matching_untolerated_taint(node.taints, pod.tolerations)
        if taint is not None:
            return Status.unresolvable(
                f"node(s) had untolerated taint {{{taint.key}: {taint.value}}}"
            )
        return OK

    def pre_score(self, state: CycleState, pod: Pod, nodes) -> Status:
        tolerations = [
            t for t in pod.tolerations
            if not t.effect or t.effect == PREFER_NO_SCHEDULE
        ]
        state.write(self._KEY, tolerations)
        return OK

    def score(self, state: CycleState, pod: Pod, node_info: NodeInfo) -> Tuple[int, Status]:
        tolerations = state.read(self._KEY) or []
        count = 0
        for taint in node_info.node.taints:
            if taint.effect != PREFER_NO_SCHEDULE:
                continue
            if not any(t.tolerates(taint) for t in tolerations):
                count += 1
        return count, OK

    def normalize_score(self, state: CycleState, pod: Pod, scores: List[NodeScore]) -> None:
        default_normalize_score(MAX_NODE_SCORE, True, scores)

    def sign(self, pod: Pod):
        return tuple((t.key, t.operator, t.value, t.effect) for t in pod.tolerations)


class NodeAffinity:
    """plugins/nodeaffinity (node_affinity.go).

    Filter: nodeSelector AND required node affinity terms. PreFilter narrows
    to specific nodes when terms pin metadata.name (node_affinity.go PreFilter),
    and Skips when the pod expresses no node affinity. Score: sum of matching
    preferred term weights, default-normalized.
    """

    name = "NodeAffinity"

    def events_to_register(self):
        """node_affinity.go EventsToRegister / isSchedulableAfterNodeChange:
        a node event helps only if the new node matches the pod's required
        selector/affinity."""
        from ..core.queue import EVENT_NODE_ADD, EVENT_NODE_UPDATE
        return [(EVENT_NODE_ADD, self._hint_node),
                (EVENT_NODE_UPDATE, self._hint_node)]

    @staticmethod
    def _hint_node(pod: Pod, old, new) -> bool:
        if new is None:
            return True
        return pod.required_node_selector_matches(new)

    @staticmethod
    def narrowed_node_names(pod: Pod) -> Optional[set]:
        """The node names the PreFilterResult narrows the cycle to, or None
        where it does not: every required term pins metadata.name via In
        (a term without such a requirement means no narrowing). The one
        rule of both paths: `pre_filter` answers with it, and the device
        path plans over those rows only (ops/features.py `narrowed_rows`)."""
        na = pod.affinity.node_affinity if pod.affinity else None
        if na is None or na.required is None or not na.required.terms:
            return None
        node_names: set = set()
        for term in na.required.terms:
            term_names = None
            for req in term.match_fields:
                if req.key == "metadata.name" and req.operator == "In":
                    term_names = set(req.values)
            if term_names is None:
                return None
            node_names |= term_names
        return node_names

    def pre_filter(self, state: CycleState, pod: Pod, nodes) -> Tuple[Optional[PreFilterResult], Status]:
        na = pod.affinity.node_affinity if pod.affinity else None
        if not pod.node_selector and (na is None or na.required is None):
            return None, Status.skip()
        node_names = self.narrowed_node_names(pod)
        if node_names is not None:
            return PreFilterResult(node_names), OK
        return None, OK

    def filter(self, state: CycleState, pod: Pod, node_info: NodeInfo) -> Status:
        if not pod.required_node_selector_matches(node_info.node):
            return Status.unresolvable("node(s) didn't match Pod's node affinity/selector")
        return OK

    def pre_score(self, state: CycleState, pod: Pod, nodes) -> Status:
        na = pod.affinity.node_affinity if pod.affinity else None
        if na is None or not na.preferred:
            return Status.skip()
        return OK

    def score(self, state: CycleState, pod: Pod, node_info: NodeInfo) -> Tuple[int, Status]:
        na = pod.affinity.node_affinity if pod.affinity else None
        if na is None:
            return 0, OK
        total = 0
        for pref in na.preferred:
            if pref.preference.matches(node_info.node):
                total += pref.weight
        return total, OK

    def normalize_score(self, state: CycleState, pod: Pod, scores: List[NodeScore]) -> None:
        default_normalize_score(MAX_NODE_SCORE, False, scores)

    def sign(self, pod: Pod):
        na = pod.affinity.node_affinity if pod.affinity else None
        return (
            tuple(sorted(pod.node_selector.items())),
            repr(na) if na else "",
        )
