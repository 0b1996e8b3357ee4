"""The scheduler framework: extension-point vocabulary, Status codes,
CycleState, and the plugin-dispatch runtime.

Re-expresses the stable plugin API of staging/src/k8s.io/kube-scheduler/framework
(interface.go: PreEnqueue :447, QueueSort :461, PreFilter :520, Filter :549,
PostFilter :578, PreScore :632, Score :653, Reserve :670, PreBind :686,
PostBind :703, Permit :714, Bind :727) and the concrete dispatcher
pkg/scheduler/framework/runtime/framework.go (frameworkImpl :58).

Differences from the reference, by design (TPU-first):
- No goroutine Parallelizer: per-node fan-out is replaced either by plain
  loops (host oracle path) or by one dense pods×nodes device kernel
  (kubernetes_tpu/ops.kernel) surfaced through a BatchEvaluator hook.
- Plugins are duck-typed: a plugin implements an extension point by defining
  the method (pre_filter/filter/score/...), mirroring Go interface checks.
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..api.types import Node, Pod
from .node_info import NodeInfo, PodInfo

MAX_NODE_SCORE = 100
MIN_NODE_SCORE = 0
MAX_TOTAL_SCORE = (1 << 63) - 1

# ---------------------------------------------------------------------------
# Status (staging kube-scheduler framework/types.go Code)
# ---------------------------------------------------------------------------

SUCCESS = 0
ERROR = 1
UNSCHEDULABLE = 2
UNSCHEDULABLE_AND_UNRESOLVABLE = 3
WAIT = 4
SKIP = 5
PENDING = 6


@dataclass
class Status:
    code: int = SUCCESS
    reasons: tuple = ()
    plugin: str = ""
    # Optimistic-binding conflict (HTTP 409 from the binding subresource:
    # AlreadyBound / OutOfCapacity): another scheduler's commit won the
    # shared state. Not an error and not unschedulable — the scheduler
    # requeues through the backoffQ and re-plans against the watch feed.
    conflict: bool = False
    # Flow-control shed (HTTP 429 from the apiserver's admission plane,
    # core/flowcontrol.py): the write never ran — like a conflict, the pod
    # only needs to wait out a backoff (the server's Retry-After horizon),
    # never the unschedulable pool, and never the error log.
    shed: bool = False
    # A success that is not settled yet: the bind plugin handed the call to
    # the thread-mode API dispatcher (core/api_dispatcher.py). The pod stays
    # assumed; the scheduler finishes and counts it when the apiserver's
    # acknowledgement comes back (Scheduler._settle_bind), or unwinds it.
    queued: bool = False

    @classmethod
    def bind_conflict(cls, *reasons: str, plugin: str = "") -> "Status":
        return cls(ERROR, tuple(reasons), plugin, conflict=True)

    @classmethod
    def bind_shed(cls, *reasons: str, plugin: str = "") -> "Status":
        return cls(ERROR, tuple(reasons), plugin, shed=True)

    @classmethod
    def unschedulable(cls, *reasons: str, plugin: str = "") -> "Status":
        return cls(UNSCHEDULABLE, tuple(reasons), plugin)

    @classmethod
    def unresolvable(cls, *reasons: str, plugin: str = "") -> "Status":
        return cls(UNSCHEDULABLE_AND_UNRESOLVABLE, tuple(reasons), plugin)

    @classmethod
    def error(cls, *reasons: str, plugin: str = "") -> "Status":
        return cls(ERROR, tuple(reasons), plugin)

    @classmethod
    def skip(cls, plugin: str = "") -> "Status":
        return cls(SKIP, (), plugin)

    def is_success(self) -> bool:
        return self.code == SUCCESS

    def is_skip(self) -> bool:
        return self.code == SKIP

    def is_rejected(self) -> bool:
        return self.code in (UNSCHEDULABLE, UNSCHEDULABLE_AND_UNRESOLVABLE, PENDING)

    def is_unresolvable(self) -> bool:
        return self.code == UNSCHEDULABLE_AND_UNRESOLVABLE

    def message(self) -> str:
        return "; ".join(self.reasons)


OK = Status()
BIND_QUEUED = Status(queued=True)

# Distinguishes "memoized as unsignable (None)" from "not memoized" in the
# template-shared signature holder (sign_pod).
_SIG_MISS = object()


# ---------------------------------------------------------------------------
# CycleState (pkg/scheduler/framework/cycle_state.go)
# ---------------------------------------------------------------------------


class CycleState:
    """Per-scheduling-cycle typed KV store + skip sets."""

    __slots__ = ("_data", "skip_filter_plugins", "skip_score_plugins", "skip_pre_bind_plugins",
                 "recorded_plugin_durations")

    def __init__(self):
        self._data: Dict[str, Any] = {}
        self.skip_filter_plugins: set = set()
        self.skip_score_plugins: set = set()
        self.skip_pre_bind_plugins: set = set()
        self.recorded_plugin_durations: Dict[str, float] = {}

    def write(self, key: str, value: Any) -> None:
        self._data[key] = value

    def read(self, key: str) -> Any:
        return self._data.get(key)

    def delete(self, key: str) -> None:
        self._data.pop(key, None)

    def clone(self) -> "CycleState":
        """Clone for what-if simulation (nominated pods, preemption dry runs).
        Mirrors cycle_state.go Clone(): values implementing clone() are deep-
        cloned so simulations can't corrupt the real cycle's plugin state."""
        c = CycleState()
        c._data = {
            k: (v.clone() if hasattr(v, "clone") else v) for k, v in self._data.items()
        }
        c.skip_filter_plugins = set(self.skip_filter_plugins)
        c.skip_score_plugins = set(self.skip_score_plugins)
        c.skip_pre_bind_plugins = set(self.skip_pre_bind_plugins)
        return c


# ---------------------------------------------------------------------------
# Diagnosis (schedule_one.go Diagnosis / NodeToStatus)
# ---------------------------------------------------------------------------


@dataclass
class Diagnosis:
    node_to_status: Dict[str, Status] = field(default_factory=dict)
    absent_nodes_status: Status = field(default_factory=lambda: Status(UNSCHEDULABLE_AND_UNRESOLVABLE))
    unschedulable_plugins: set = field(default_factory=set)
    pending_plugins: set = field(default_factory=set)
    pre_filter_msg: str = ""


class FitError(Exception):
    """schedule_one.go FitError — pod didn't fit any node."""

    def __init__(self, pod: Pod, num_all_nodes: int, diagnosis: Diagnosis):
        self.pod = pod
        self.num_all_nodes = num_all_nodes
        self.diagnosis = diagnosis
        rejected = sum(1 for s in diagnosis.node_to_status.values() if s.is_rejected())
        super().__init__(
            f"0/{num_all_nodes} nodes are available for pod {pod.namespace}/{pod.name} "
            f"({rejected} rejected): {diagnosis.pre_filter_msg}"
        )


# ---------------------------------------------------------------------------
# PreFilterResult (interface.go PreFilterResult — node subset narrowing)
# ---------------------------------------------------------------------------


@dataclass
class PreFilterResult:
    node_names: Optional[set] = None  # None => all nodes

    def all_nodes(self) -> bool:
        return self.node_names is None

    def merge(self, other: "PreFilterResult") -> "PreFilterResult":
        if self.all_nodes() and other.all_nodes():
            return PreFilterResult(None)
        if self.all_nodes():
            return PreFilterResult(set(other.node_names))
        if other.all_nodes():
            return PreFilterResult(set(self.node_names))
        return PreFilterResult(self.node_names & other.node_names)


@dataclass
class NodeScore:
    name: str
    score: int


# ---------------------------------------------------------------------------
# Framework (profile) runtime
# ---------------------------------------------------------------------------


def default_normalize_score(max_priority: int, reverse: bool, scores: List[NodeScore]) -> None:
    """plugins/helper/normalize_score.go DefaultNormalizeScore."""
    max_count = 0
    for s in scores:
        if s.score > max_count:
            max_count = s.score
    if max_count == 0:
        if reverse:
            for s in scores:
                s.score = max_priority
        return
    for s in scores:
        score = max_priority * s.score // max_count
        if reverse:
            score = max_priority - score
        s.score = score


@dataclass
class Placement:
    """A named candidate node subset for pod-group scheduling (the fork's
    staging kube-scheduler framework Placement; topology_placement.go
    produces one per topology domain)."""

    name: str
    node_names: List[str]


@dataclass
class PlacementProgress:
    """Mid-simulation group progress handed to PlacementFeasible plugins
    (framework.go:2160; GangScheduling gates on scheduled >= min_count)."""

    scheduled: int = 0
    failed: int = 0
    total: int = 0


@dataclass
class PodGroupAssignments:
    """One successful placement simulation: the proposed member→node
    assignments plus the placement's node views — the input PlacementScore
    plugins score (staging framework PodGroupAssignments)."""

    placement: Placement
    proposed: List[Tuple[Pod, str]] = field(default_factory=list)
    nodes: List[Any] = field(default_factory=list)  # NodeInfo


class Framework:
    """One profile's plugin set + dispatch (frameworkImpl equivalent).

    `plugins` is an ordered list of (plugin_instance, weight). Extension-point
    membership is derived from which methods each plugin defines.
    """

    def __init__(
        self,
        profile_name: str = "default-scheduler",
        plugins: Optional[Sequence[Tuple[Any, int]]] = None,
        snapshot_provider: Optional[Callable[[], Any]] = None,
        rng: Optional[random.Random] = None,
    ):
        self.profile_name = profile_name
        self._plugins: List[Tuple[Any, int]] = list(plugins or [])
        self.snapshot_provider = snapshot_provider
        self.rng = rng or random.Random(0)
        self.pre_enqueue_plugins = self._having("pre_enqueue")
        self.queue_sort_plugins = self._having("less")
        self.pre_filter_plugins = self._having("pre_filter")
        self.filter_plugins = self._having("filter")
        self.post_filter_plugins = self._having("post_filter")
        self.pre_score_plugins = self._having("pre_score")
        self.score_plugins = self._having_weighted("score")
        self.reserve_plugins = self._having("reserve")
        self.unreserve_plugins = self._having("unreserve")
        self.permit_plugins = self._having("permit")
        self.pre_bind_plugins = self._having("pre_bind")
        self.bind_plugins = self._having("bind")
        self.post_bind_plugins = self._having("post_bind")
        self.sign_plugins = self._having("sign")
        # Pod-group / placement extension points (fork additions —
        # runtime/framework.go:1212 RunPodGroupPostFilterPlugins, :2208
        # RunPlacementGeneratePlugins, :2160 RunPlacementFeasiblePlugins,
        # :1625 RunPlacementScorePlugins).
        self.placement_generate_plugins = self._having("generate_placements")
        self.placement_feasible_plugins = self._having("placement_feasible")
        self.placement_score_plugins = self._having_weighted("score_placement")
        self.pod_group_post_filter_plugins = self._having("pod_group_post_filter")
        # Per-plugin QueueingHintFn registrations (EventsToRegister →
        # ClusterEventWithHint, framework/types.go:217): plugin name →
        # {event: [hint fn or None]}. Plugins without events_to_register
        # fall back to the queue's static event map.
        self.queueing_hint_map: Dict[str, Dict[str, List[Any]]] = {}
        for p, _w in self._plugins:
            etr = getattr(p, "events_to_register", None)
            if etr is None:
                continue
            m: Dict[str, List[Any]] = {}
            for event, fn in etr():
                m.setdefault(event, []).append(fn)
            self.queueing_hint_map[p.name] = m
        # Optional dense batch evaluator (the TPU backend) — set by
        # kubernetes_tpu/models pipeline when the device profile is active.
        self.batch_evaluator = None

    def _having(self, method: str) -> List[Any]:
        return [p for p, _ in self._plugins if hasattr(p, method)]

    def _having_weighted(self, method: str) -> List[Tuple[Any, int]]:
        return [(p, w) for p, w in self._plugins if hasattr(p, method)]

    def plugin(self, name: str) -> Optional[Any]:
        for p, _ in self._plugins:
            if p.name == name:
                return p
        return None

    # -- queueing ----------------------------------------------------------

    def run_pre_enqueue_plugins(self, pod: Pod) -> Status:
        for p in self.pre_enqueue_plugins:
            st = p.pre_enqueue(pod)
            if not st.is_success():
                st.plugin = p.name
                return st
        return OK

    def less(self, a, b) -> bool:
        """QueueSort comparison via the (single) queue-sort plugin."""
        if self.queue_sort_plugins:
            return self.queue_sort_plugins[0].less(a, b)
        return a.timestamp < b.timestamp

    @property
    def queue_sort_key(self):
        """Tuple-key form of the queue-sort comparison when the plugin
        provides one (heap entries then compare at C speed)."""
        if self.queue_sort_plugins:
            return getattr(self.queue_sort_plugins[0], "sort_key", None)
        return lambda qpi: (qpi.timestamp,)

    # -- filtering ---------------------------------------------------------

    def run_pre_filter_plugins(
        self, state: CycleState, pod: Pod, nodes: Sequence[NodeInfo]
    ) -> Tuple[Optional[PreFilterResult], Status]:
        """runtime/framework.go:934 RunPreFilterPlugins: merge PreFilterResults,
        collect Skip sets, short-circuit on rejection."""
        result: Optional[PreFilterResult] = None
        skipped = set()
        for p in self.pre_filter_plugins:
            r, st = p.pre_filter(state, pod, nodes)
            if st.is_skip():
                skipped.add(p.name)
                continue
            if not st.is_success():
                st.plugin = p.name
                return None, st
            if r is not None and not r.all_nodes():
                result = r if result is None else result.merge(r)
                if not result.node_names:
                    return result, Status.unresolvable(
                        "node(s) didn't satisfy plugin(s) prefilter result", plugin=p.name
                    )
        state.skip_filter_plugins = skipped
        return result, OK

    def run_filter_plugins(self, state: CycleState, pod: Pod, node_info: NodeInfo) -> Status:
        """runtime/framework.go:1105 RunFilterPlugins (per node)."""
        for p in self.filter_plugins:
            if p.name in state.skip_filter_plugins:
                continue
            st = p.filter(state, pod, node_info)
            if not st.is_success():
                st.plugin = p.name
                return st
        return OK

    def run_filter_plugins_with_nominated_pods(
        self, state: CycleState, pod: Pod, node_info: NodeInfo, nominator=None
    ) -> Status:
        """runtime/framework.go:1275: two-pass filter — first pass simulates
        higher/equal-priority nominated pods as if running on the node."""
        nominated = []
        if nominator is not None and node_info.node is not None:
            nominated = [
                pi for pi in nominator.nominated_pods_for_node(node_info.node.name)
                if pi.pod.uid != pod.uid and pi.pod.priority >= pod.priority
            ]
        if nominated:
            state_with = state.clone()
            ni_with = node_info.snapshot_clone()
            for pi in nominated:
                ni_with.add_pod(pi)
                for p in self.pre_filter_plugins:
                    if p.name in state.skip_filter_plugins:
                        continue
                    add_pod = getattr(p, "add_pod", None)
                    if add_pod is not None:
                        st = add_pod(state_with, pod, pi, ni_with)
                        if not st.is_success():
                            st.plugin = p.name
                            return st
            st = self.run_filter_plugins(state_with, pod, ni_with)
            if not st.is_success():
                return st
        return self.run_filter_plugins(state, pod, node_info)

    def run_post_filter_plugins(self, state: CycleState, pod: Pod, filtered_status_map: Dict[str, Status]):
        """runtime/framework.go:1152 — first non-skip result wins."""
        for p in self.post_filter_plugins:
            result, st = p.post_filter(state, pod, filtered_status_map)
            if st.is_success() or st.code == UNSCHEDULABLE_AND_UNRESOLVABLE:
                # copy before stamping: plugins may return shared singletons
                return result, Status(st.code, st.reasons, p.name)
        return None, Status.unschedulable("no postFilter plugin made progress")

    # -- scoring -----------------------------------------------------------

    # -- placement extension points (fork: framework.go:2208,:2160,:1625,
    # :1212) ---------------------------------------------------------------

    def run_placement_generate_plugins(
        self, state: CycleState, group, members, parent: Placement
    ) -> Tuple[List[Placement], Status]:
        """RunPlacementGeneratePlugins: each plugin refines the previous
        round's placements (the reference chains generators through the
        parent placement; with one generator this is one pass)."""
        placements = [parent]
        for p in self.placement_generate_plugins:
            nxt: List[Placement] = []
            for parent_pl in placements:
                out, st = p.generate_placements(state, group, members, parent_pl)
                if not st.is_success():
                    st.plugin = p.name
                    return [], st
                nxt.extend(out)
            placements = nxt
        return placements, OK

    def run_placement_feasible_plugins(
        self, state: CycleState, group, progress: PlacementProgress
    ) -> Status:
        """RunPlacementFeasiblePlugins: group-level gate on the simulation
        outcome (GangScheduling: scheduled >= min_count)."""
        for p in self.placement_feasible_plugins:
            st = p.placement_feasible(state, group, progress)
            if not st.is_success():
                st.plugin = p.name
                return st
        return OK

    def run_placement_score_plugins(
        self, state: CycleState, group, assignments: List[PodGroupAssignments]
    ) -> List[int]:
        """RunPlacementScorePlugins: per-plugin score each candidate
        placement's assignments, normalize, weight, sum — one total per
        placement (deterministic ties: the caller picks the first max)."""
        totals = [0] * len(assignments)
        for p, weight in self.placement_score_plugins:
            scores = []
            for pga in assignments:
                s, st = p.score_placement(state, group, pga)
                if not st.is_success():
                    raise RuntimeError(
                        f"placement score {p.name} failed: {st.message()}")
                scores.append(s)
            norm = getattr(p, "normalize_placement_score", None)
            if norm is not None:
                scores = norm(group, scores)
            for i, s in enumerate(scores):
                totals[i] += weight * s
        return totals

    def run_pod_group_post_filter_plugins(self, state: CycleState, group, members, diagnosis):
        """RunPodGroupPostFilterPlugins (framework.go:1212): give plugins a
        chance to make room for the whole group (pod-group preemption)."""
        for p in self.pod_group_post_filter_plugins:
            result, st = p.pod_group_post_filter(state, group, members, diagnosis)
            if st.is_success() or st.code not in (UNSCHEDULABLE, UNSCHEDULABLE_AND_UNRESOLVABLE):
                st.plugin = p.name
                return result, st
        return None, Status.unschedulable("no pod-group post filter made room")

    def run_pre_score_plugins(self, state: CycleState, pod: Pod, nodes: Sequence[NodeInfo]) -> Status:
        skipped = set()
        for p in self.pre_score_plugins:
            st = p.pre_score(state, pod, nodes)
            if st.is_skip():
                skipped.add(p.name)
                continue
            if not st.is_success():
                st.plugin = p.name
                return st
        state.skip_score_plugins = skipped
        return OK

    def run_score_plugins(
        self, state: CycleState, pod: Pod, nodes: Sequence[NodeInfo]
    ) -> Dict[str, List[NodeScore]]:
        """runtime/framework.go:1405 RunScorePlugins: per-plugin score each
        node, run NormalizeScore, then apply plugin weight."""
        all_scores: Dict[str, List[NodeScore]] = {}
        for p, weight in self.score_plugins:
            if p.name in state.skip_score_plugins:
                continue
            scores = [NodeScore(ni.name, 0) for ni in nodes]
            for i, ni in enumerate(nodes):
                s, st = p.score(state, pod, ni)
                if not st.is_success():
                    raise RuntimeError(f"score plugin {p.name} failed: {st.message()}")
                scores[i].score = s
            normalize = getattr(p, "normalize_score", None)
            if normalize is not None:
                normalize(state, pod, scores)
            for ns in scores:
                if ns.score > MAX_NODE_SCORE or ns.score < MIN_NODE_SCORE:
                    raise RuntimeError(
                        f"plugin {p.name} returns an invalid score {ns.score} for node {ns.name}"
                    )
                ns.score *= weight
            all_scores[p.name] = scores
        return all_scores

    # -- reserve / permit / bind ------------------------------------------

    def run_reserve_plugins_reserve(self, state: CycleState, pod: Pod, node_name: str) -> Status:
        for p in self.reserve_plugins:
            st = p.reserve(state, pod, node_name)
            if not st.is_success():
                st.plugin = p.name
                return st
        return OK

    def run_reserve_plugins_unreserve(self, state: CycleState, pod: Pod, node_name: str) -> None:
        for p in reversed(self.unreserve_plugins):
            p.unreserve(state, pod, node_name)

    def run_permit_plugins(self, state: CycleState, pod: Pod, node_name: str) -> Status:
        for p in self.permit_plugins:
            st = p.permit(state, pod, node_name)
            if st.is_rejected():
                st.plugin = p.name
                return st
            if st.code == WAIT:
                st.plugin = p.name
                return st
            if not st.is_success():
                st.plugin = p.name
                return st
        return OK

    def run_pre_bind_pre_flight(self, state: CycleState, pod: Pod,
                                node_name: str) -> Status:
        """PreBindPreFlight (staging kube-scheduler framework
        interface.go:688-694, runtime/framework.go:1875): ask each PreBind
        plugin whether it intends to do any work for this pod. Plugins
        answering Skip are recorded in state.skip_pre_bind_plugins; returns
        Skip when EVERY PreBind plugin skips (the binding cycle may then
        bypass the PreBind phase entirely — the async-binding enabler)."""
        all_skip = True
        for p in self.pre_bind_plugins:
            flight = getattr(p, "pre_bind_pre_flight", None)
            if flight is None:
                all_skip = False
                continue
            st = flight(state, pod, node_name)
            if st.is_skip():
                state.skip_pre_bind_plugins.add(p.name)
            elif not st.is_success():
                st.plugin = p.name
                return st
            else:
                all_skip = False
        return Status.skip() if all_skip else OK

    def run_pre_bind_plugins(self, state: CycleState, pod: Pod, node_name: str) -> Status:
        for p in self.pre_bind_plugins:
            if p.name in state.skip_pre_bind_plugins:
                continue
            st = p.pre_bind(state, pod, node_name)
            if not st.is_success():
                st.plugin = p.name
                return st
        return OK

    def run_bind_plugins(self, state: CycleState, pod: Pod, node_name: str) -> Status:
        if not self.bind_plugins:
            return Status.error("no bind plugin configured")
        for p in self.bind_plugins:
            st = p.bind(state, pod, node_name)
            if st.is_skip():
                continue
            if st.is_success():
                return st
            # copy before stamping: plugins may return the shared OK/Status
            # singletons, which must never be mutated. `conflict` must ride
            # along — it routes the unwind to the backoffQ requeue.
            return Status(st.code, st.reasons, p.name, conflict=st.conflict)
        return Status.error("all bind plugins skipped")

    def run_post_bind_plugins(self, state: CycleState, pod: Pod, node_name: str) -> None:
        for p in self.post_bind_plugins:
            p.post_bind(state, pod, node_name)

    # -- signatures (OpportunisticBatching / kernel row-block batching) ----

    def sign_pod(self, pod: Pod) -> Optional[tuple]:
        """Pod signature for batch reuse (staging framework/signers.go /
        interface.go:774 SignPlugin). None => unsignable (never batched).

        Memoized two ways:
        - per pod object, keyed by (framework, node_name): pod SPEC objects
          are immutable in place (updates replace the pod object through the
          watch path), and node_name is the only signed field the scheduler
          mutates in place (assume/unwind);
        - per TEMPLATE, when the pod carries a `_sig_shared` holder
          (Pod.clone_from_template): all clones share one memo, so a
          workload of N identical pods signs once, not N times.
        """
        key = (id(self), pod.node_name)
        shared = getattr(pod, "_sig_shared", None)
        if shared is not None:
            hit = shared.get(key, _SIG_MISS)
            if hit is not _SIG_MISS:
                return hit
        else:
            cached = getattr(pod, "_sig_cache", None)
            if cached is not None and cached[0] == key:
                return cached[1]
        sig = []
        out: Optional[tuple] = None
        for p in self.sign_plugins:
            part = p.sign(pod)
            if part is None:
                break
            sig.append((p.name, part))
        else:
            out = tuple(sig) if sig else None
        if shared is not None:
            shared[key] = out
        else:
            pod._sig_cache = (key, out)
        return out
