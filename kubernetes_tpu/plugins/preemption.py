"""Preemption: the DefaultPreemption PostFilter plugin + the dry-run
Evaluator.

Reference anchors:
- pkg/scheduler/framework/preemption/preemption.go — Evaluator.Preempt :181,
  findCandidates :201, DryRunPreemption :425 (per-node victim simulation),
  SelectCandidate / pickOneNodeForPreemption :286;
- plugins/defaultpreemption/default_preemption.go — PostFilter → Evaluator,
  victim ordering (lower priority first, then earlier start later),
  PodEligibleToPreemptOthers;
- async victim deletion (executor.go:171 prepareCandidateAsync): the victims'
  deletes go through the APIDispatcher, inline over an in-process clientset
  (inside the preemptor's cycle) and off the loop in thread mode.

The what-if counts nominated room (SelectVictimsOnNode runs
RunFilterPluginsWithNominatedPods): both filter calls of ``dry_run_on_node``
go through the framework's two-pass filter with the nominator, so the room
held for another preemptor of equal or higher priority is taken, and the
preemptor's own nomination and every nomination of lower priority are not.
The device-batched form of the same what-if (``ops/kernel.py``
``dry_run_preemption``, SURVEY.md §7.7) takes the same lane and answers
``find_candidates`` where the handle has a device backend; the candidate
picked from it is verified by ``dry_run_on_node``. The node is picked by the
source's criteria in the source's order (``select_candidate``).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from ..api.types import Pod
from ..core.framework import (
    OK,
    CycleState,
    Status,
    UNSCHEDULABLE,
    UNSCHEDULABLE_AND_UNRESOLVABLE,
)
from ..core.node_info import NodeInfo, PodInfo


PRIORITY_OFFSET = 1 << 31  # MaxInt32 + 1 (pickOneNodeForPreemption)


@dataclass
class Candidate:
    """One feasible preemption plan (preemption.go candidate)."""

    node_name: str
    victims: List[PodInfo] = field(default_factory=list)
    num_pdb_violations: int = 0


@dataclass
class PostFilterResult:
    nominating_info: Optional[str] = None  # nominated node name


class Evaluator:
    """Preemption dry-run machinery (preemption.go Evaluator)."""

    MIN_CANDIDATE_NODES_PERCENTAGE = 10   # preemption.go minCandidateNodesPercentage
    MIN_CANDIDATE_NODES_ABSOLUTE = 100    # preemption.go minCandidateNodesAbsolute

    def __init__(self, handle, framework):
        self.handle = handle
        self.fw = framework
        self._offset = 0  # rotating start, GetOffsetAndNumCandidates
        self._last_start = None  # start used by the most recent dry run
        self.last_from_device = False  # candidates came from the kernel

    def say_stage(self, **stats) -> None:
        """What the evaluation was, onto the scheduler's ``postfilter.preempt``
        stage where that is the stage open around it (a handle without one
        says nothing)."""
        say = getattr(self.handle, "say_stage", None)
        if say is not None:
            say("postfilter.preempt", **stats)

    def stage_clock(self):
        """``time.perf_counter`` where the ``postfilter.preempt`` stage open
        around the caller is listened to, else ``float`` (every reading 0.0):
        the attempt's parts are clocked for somebody or not at all."""
        stages = getattr(self.handle, "stages", None)
        heard = stages.heard("postfilter.preempt") if stages is not None else None
        return time.perf_counter if heard is not None else float

    # -- eligibility (default_preemption.go PodEligibleToPreemptOthers) ----

    def pod_eligible(self, pod: Pod, snapshot) -> Tuple[bool, str]:
        if pod.preemption_policy == "Never":
            return False, "not eligible due to preemptionPolicy=Never"
        if pod.nominated_node_name:
            ni = snapshot.get(pod.nominated_node_name)
            if ni is not None:
                # A lower-priority pod already terminating on the nominated
                # node means preemption is in flight: don't preempt again.
                for pi in ni.pods:
                    if pi.pod.priority < pod.priority and pi.pod.deletion_ts is not None:
                        return False, "a terminating victim already exists on the nominated node"
        return True, ""

    # -- per-node dry run (preemption.go DryRunPreemption / SimulatePreemption)

    def dry_run_on_node(
        self, state: CycleState, pod: Pod, node_info: NodeInfo
    ) -> Optional[Candidate]:
        """Can `pod` fit on this node after evicting some lower-priority pods?
        Returns the minimal victim set (reprieve pass), or None."""
        ni = node_info.snapshot_clone()
        sim_state = state.clone()
        potential = [pi for pi in ni.pods if pi.pod.priority < pod.priority]
        if not potential:
            return None

        def remove_pod(pi: PodInfo) -> bool:
            if not ni.remove_pod(pi.pod):
                return False
            for p in self.fw.pre_filter_plugins:
                fn = getattr(p, "remove_pod", None)
                if fn is not None and not fn(sim_state, pod, pi, ni).is_success():
                    return False
            return True

        def add_pod(pi: PodInfo) -> bool:
            ni.add_pod(pi)
            for p in self.fw.pre_filter_plugins:
                fn = getattr(p, "add_pod", None)
                if fn is not None and not fn(sim_state, pod, pi, ni).is_success():
                    return False
            return True

        # RunFilterPluginsWithNominatedPods, as SelectVictimsOnNode runs it:
        # the room held for a nominated pod of equal or higher priority is
        # taken (the preemptor's own nomination is not)
        nominator = getattr(self.handle, "nominator", None)

        def passes() -> bool:
            return self.fw.run_filter_plugins_with_nominated_pods(
                sim_state, pod, ni, nominator).is_success()

        for pi in potential:
            if not remove_pod(pi):
                return None
        if not passes():
            return None

        # Reprieve: re-add victims most-important first — higher priority,
        # then EARLIER start time (MoreImportantPod; preemption.go:480-520) —
        # keeping those that still fit.
        potential.sort(key=lambda pi: (-pi.pod.priority, pi.pod.creation_ts))
        victims: List[PodInfo] = []
        for pi in potential:
            if not add_pod(pi):
                return None
            if not passes():
                # can't keep it: evict for real
                if not remove_pod(pi):
                    return None
                victims.append(pi)
        if not victims:
            return None  # pod fit without evicting anyone — not a preemption
        return Candidate(node_name=ni.name, victims=victims)

    def find_candidates(
        self, state: CycleState, pod: Pod, node_to_status: Dict[str, Status],
        force_host: bool = False,
    ) -> List[Candidate]:
        """DryRunPreemption over candidate nodes, capped at ~10% of the
        cluster (floor 100) from a rotating offset — the reference's
        GetOffsetAndNumCandidates (preemption.go:201,425). When the handle
        exposes a device backend, the per-node victim simulation runs as ONE
        batched kernel call (same rotation, same cap, same skip of
        unresolvable nodes); the caller host-verifies the selected
        candidate and passes force_host=True to recompute on divergence."""
        snapshot = self.handle.snapshot() if callable(self.handle.snapshot) else self.handle.snapshot
        nodes = snapshot.node_info_list
        n = len(nodes)
        if n == 0:
            return []
        num_candidates = max(
            n * self.MIN_CANDIDATE_NODES_PERCENTAGE // 100,
            self.MIN_CANDIDATE_NODES_ABSOLUTE)
        if force_host and self._last_start is not None:
            # Host recompute after a device-verify divergence: scan the SAME
            # rotation window the device pass used, and do NOT advance the
            # offset again — a pure-host run would have consumed exactly one
            # offset for this attempt.
            start = self._last_start
        else:
            start = self._offset % n
            self._offset += 1
            self._last_start = start
        self.last_from_device = False
        if not force_host:
            device_fn = getattr(self.handle, "device_dry_run_preemption", None)
            if device_fn is not None:
                cands = device_fn(self.fw, state, pod, node_to_status,
                                  num_candidates, start)
                if cands is not None:
                    self.last_from_device = True
                    self._count_dry_run("device")
                    self.say_stage(engine="device", candidates=len(cands))
                    return cands
        _t_host = time.perf_counter()
        candidates: List[Candidate] = []
        for i in range(n):
            ni = nodes[(start + i) % n]
            st = node_to_status.get(ni.name)
            # Unresolvable rejections can't be fixed by evicting pods
            # (preemption.go nodesWherePreemptionMightHelp).
            if st is not None and st.code == UNSCHEDULABLE_AND_UNRESOLVABLE:
                continue
            cand = self.dry_run_on_node(state, pod, ni)
            if cand is not None:
                candidates.append(cand)
                if len(candidates) >= num_candidates:
                    break
        self._count_dry_run("host")
        self.say_stage(engine="host", candidates=len(candidates),
                       host_ms=round(1e3 * (time.perf_counter() - _t_host), 3))
        return candidates

    def _count_dry_run(self, engine: str) -> None:
        """scheduler_preemption_dry_runs_total{engine}: every candidate
        search by what ran it, the host recompute after a device candidate
        that the host verify refused among them (whether or not a stage
        listens: a run's guard reads it)."""
        metrics = getattr(self.handle, "metrics", None)
        if metrics is not None:
            metrics.preemption_dry_runs.inc(engine)

    # -- selection (preemption.go pickOneNodeForPreemption) ----------------

    @staticmethod
    def select_candidate(candidates: List[Candidate]) -> Optional[Candidate]:
        """pickOneNodeForPreemption, criterion by criterion, each over the
        candidates the one before left tied:

        1. the fewest PDB violations;
        2. the lowest priority of the node's most important victim;
        3. the smallest sum over its victims of ``priority + 2**31`` (the
           source's ``MaxInt32 + 1``: every term is positive, so fewer
           victims beat a smaller plain sum of negative priorities);
        4. the fewest victims;
        5. the LATEST of the nodes' earliest start times, each taken among
           the node's victims of its highest priority
           (``GetEarliestPodStartTime``);
        6. the first found (``min`` keeps the first of equal keys)."""
        if not candidates:
            return None
        if len(candidates) == 1:
            return candidates[0]

        def key(c: Candidate):
            highest = max(pi.pod.priority for pi in c.victims)
            return (
                c.num_pdb_violations,
                highest,
                sum(pi.pod.priority + PRIORITY_OFFSET for pi in c.victims),
                len(c.victims),
                -min(pi.pod.creation_ts for pi in c.victims
                     if pi.pod.priority == highest),
            )

        return min(candidates, key=key)

    # -- commit (preemption.go prepareCandidate / executor.go:171
    # prepareCandidateAsync) ------------------------------------------------

    def prepare_candidate(self, cand: Candidate, pod: Pod) -> None:
        """Evict the victims. Deletions route through the APIDispatcher
        (executor.go:171 prepareCandidateAsync: the scheduling cycle moves on
        while the API calls drain; in thread mode they physically run off the
        loop, in inline mode they complete immediately with identical
        semantics)."""
        cs = self.handle.clientset
        dispatcher = getattr(self.handle, "api_dispatcher", None)
        gates = getattr(self.handle, "gates", None)
        async_ok = True
        if gates is not None:
            try:
                async_ok = gates.enabled("SchedulerAsyncPreemption")
            except ValueError:
                pass
        metrics = getattr(self.handle, "metrics", None)

        def _delete(p):
            # preemption_goroutines_* (executor.go:171 prepareCandidateAsync
            # analogue): each victim deletion is one unit of async work.
            _t0 = time.perf_counter()
            try:
                cs.delete_pod(p)
            except Exception:
                if metrics is not None:
                    metrics.preemption_goroutines_execution_total.inc("error")
                raise
            if metrics is not None:
                metrics.preemption_goroutines_execution_total.inc("success")
                metrics.preemption_goroutines_duration.observe(
                    time.perf_counter() - _t0)

        for pi in cand.victims:
            if dispatcher is not None and async_ok:
                from ..core.api_dispatcher import APICall, CALL_DELETE
                dispatcher.add(APICall(
                    call_type=CALL_DELETE, object_uid=pi.pod.uid,
                    execute=lambda p=pi.pod: _delete(p)))
            else:
                # SchedulerAsyncPreemption off: victims delete synchronously
                # inside the scheduling cycle (pre-gate behavior).
                _delete(pi.pod)
        # Lower-priority pods nominated to this node lose their nomination
        # (preemption.go prepareCandidate → ClearNominatedNodeName).
        nominator = getattr(self.handle, "nominator", None)
        if nominator is not None:
            for pi in list(nominator.nominated_pods_for_node(cand.node_name)):
                if pi.pod.priority < pod.priority:
                    nominator.delete_nominated_pod(pi.pod)
                    pi.pod.nominated_node_name = ""


class PodGroupEvaluator:
    """Pod-group preemption (preemption/podgrouppreemption.go:42
    PodGroupEvaluator): the preemptor is a whole group and the domain is the
    whole cluster. Remove every preemptible lower-priority pod, check the
    group schedules, then reprieve victims most-important-first while the
    group still fits (:139 selectVictimsOnDomain)."""

    def __init__(self, handle):
        self.handle = handle

    def preempt(self, group, members, simulate_fn) -> Tuple[List[PodInfo], Status]:
        """Returns (victims, status). `simulate_fn()` must attempt the whole
        group against the live snapshot and return True on feasibility,
        leaving the snapshot unchanged. NodeInfos are mutated during
        evaluation and ALWAYS restored before returning."""
        snapshot = self.handle.snapshot() if callable(self.handle.snapshot) else self.handle.snapshot
        preemptor_prio = max((m.pod.priority for m in members), default=0)
        potential: List[Tuple[NodeInfo, PodInfo]] = []
        for ni in snapshot.node_info_list:
            for pi in ni.pods:
                if (pi.pod.priority < preemptor_prio
                        and pi.pod.deletion_ts is None):
                    potential.append((ni, pi))
        if not potential:
            return [], Status.unresolvable(
                "pod-group preemption: no lower-priority pods")

        removed: List[Tuple[NodeInfo, PodInfo]] = []
        try:
            for ni, pi in potential:
                if ni.remove_pod(pi.pod):
                    removed.append((ni, pi))
            if not simulate_fn():
                return [], Status.unschedulable(
                    "pod-group preemption: group does not fit even after "
                    "removing all lower-priority pods")
            # Reprieve most-important-first (MoreImportantPod ordering).
            removed.sort(key=lambda t: (-t[1].pod.priority, t[1].pod.creation_ts))
            victims: List[PodInfo] = []
            for ni, pi in list(removed):
                ni.add_pod(pi)
                if simulate_fn():
                    removed.remove((ni, pi))  # reprieved: stays restored
                else:
                    ni.remove_pod(pi.pod)
                    victims.append(pi)
            return victims, OK
        finally:
            for ni, pi in removed:  # restore every still-removed victim
                ni.add_pod(pi)


class DefaultPreemption:
    """plugins/defaultpreemption — PostFilter extension point."""

    name = "DefaultPreemption"

    def __init__(self, handle=None, framework=None):
        self.handle = handle
        self._evaluator: Optional[Evaluator] = None
        self._framework = framework

    def set_framework(self, fw) -> None:
        self._framework = fw
        self._evaluator = None

    @property
    def evaluator(self) -> Evaluator:
        if self._evaluator is None:
            self._evaluator = Evaluator(self.handle, self._framework)
        return self._evaluator

    def post_filter(
        self, state: CycleState, pod: Pod, filtered_status_map: Dict[str, Status]
    ) -> Tuple[Optional[PostFilterResult], Status]:
        snapshot = self.handle.snapshot() if callable(self.handle.snapshot) else self.handle.snapshot
        ok, msg = self.evaluator.pod_eligible(pod, snapshot)
        if not ok:
            self.evaluator.say_stage(engine="none")
            return None, Status.unresolvable(f"preemption: {msg}")
        metrics = getattr(self.handle, "metrics", None)
        if metrics is not None:
            metrics.preemption_attempts.inc()
        _t_eval = time.perf_counter()
        candidates = self.evaluator.find_candidates(state, pod, filtered_status_map)
        if metrics is not None:
            metrics.preemption_evaluation_duration.observe(
                time.perf_counter() - _t_eval)
        if not candidates:
            return None, Status.unresolvable(
                "preemption: 0/%d nodes are available" % max(1, snapshot.num_nodes()))
        # Extender preempt verb (preemption.go callExtenders /
        # extender.go:46-49 ProcessPreemption): preempt-capable extenders
        # narrow the candidate victim map before selection.
        extenders = getattr(self.handle, "extenders", None) or ()
        if any(e.supports_preemption() for e in extenders):
            # Extender-trimmed victim sets are extender-authoritative: the
            # host dry run can't reproduce them, so skip device verification.
            self.evaluator.last_from_device = False
            from ..core.extender import run_extender_preemption
            victim_map = {c.node_name: c.victims for c in candidates}
            victim_map, err = run_extender_preemption(extenders, pod, victim_map)
            if err is not None:
                # Retryable failure (preemption.go callExtenders → AsStatus):
                # the attempt errors; it must NOT park the pod unresolvable.
                return None, Status.error(f"extender preemption: {err}")
            candidates = [
                # num_pdb_violations carries over only because no PDB API
                # exists yet (always 0); with PDBs it must be recomputed
                # from the trimmed victim list.
                Candidate(node_name=c.node_name,
                          victims=victim_map[c.node_name],
                          num_pdb_violations=c.num_pdb_violations)
                for c in candidates
                if c.node_name in victim_map and victim_map[c.node_name]]
            if not candidates:
                return None, Status.unresolvable(
                    "preemption: extenders rejected all candidates")
        clock = self.evaluator.stage_clock()
        _t_select = clock()
        best = self.evaluator.select_candidate(candidates)
        _t_verify = clock()
        if self.evaluator.last_from_device and best is not None:
            # Host-verify the device-selected candidate: the exact per-node
            # dry run must reproduce the victim set. On divergence (a kernel
            # coverage bug), the host loop is authoritative.
            ni = snapshot.get(best.node_name)
            verified = (self.evaluator.dry_run_on_node(state, pod, ni)
                        if ni is not None else None)
            if verified is None or (
                    {pi.pod.uid for pi in verified.victims}
                    != {pi.pod.uid for pi in best.victims}):
                candidates = self.evaluator.find_candidates(
                    state, pod, filtered_status_map, force_host=True)
                if not candidates:
                    return None, Status.unresolvable(
                        "preemption: 0/%d nodes are available"
                        % max(1, snapshot.num_nodes()))
                best = self.evaluator.select_candidate(candidates)
            else:
                best = Candidate(node_name=best.node_name,
                                 victims=verified.victims,
                                 num_pdb_violations=best.num_pdb_violations)
        _t_exec = time.perf_counter()
        self.evaluator.prepare_candidate(best, pod)
        _t_done = time.perf_counter()
        if clock is not float:
            # the attempt's tail beside the dry run's parts: picking the
            # node, the host verify of the device's candidate, the evictions
            self.evaluator.say_stage(
                select_ms=round(1e3 * (_t_verify - _t_select), 3),
                verify_ms=round(1e3 * (_t_exec - _t_verify), 3),
                evict_ms=round(1e3 * (_t_done - _t_exec), 3),
                victims=len(best.victims), nominated=1)
        if metrics is not None:
            metrics.preemption_execution_duration.observe(
                _t_done - _t_exec)
            if best.num_pdb_violations:
                metrics.preemption_pdb_violations.inc(
                    value=best.num_pdb_violations)
        if metrics is not None:
            metrics.preemption_victims.observe(len(best.victims))
        # Success: the scheduler records the nomination and requeues
        # (preemption.go Preempt returns Success + nominated node).
        return PostFilterResult(nominating_info=best.node_name), OK

    # -- pod-group preemption (PodGroupPostFilter; podgrouppreemption.go) ---

    def pod_group_post_filter(
        self, state: CycleState, group, members, diagnosis
    ) -> Tuple[Optional[PostFilterResult], Status]:
        simulate = getattr(self.handle, "simulate_pod_group", None)
        if simulate is None or not members:
            return None, Status.unschedulable("pod-group preemption unavailable")
        ev = PodGroupEvaluator(self.handle)
        metrics = getattr(self.handle, "metrics", None)
        victims, st = ev.preempt(group, members, lambda: simulate(group, members))
        if not st.is_success() or not victims:
            if metrics is not None:
                metrics.workload_preemption_attempts.inc("no_victims")
            return None, st if not st.is_success() else Status.unschedulable(
                "pod-group preemption found no victim set")
        if metrics is not None:
            metrics.preemption_attempts.inc()
            metrics.preemption_victims.observe(len(victims))
            metrics.workload_preemption_attempts.inc("preempted")
            metrics.workload_preemption_victims.observe(len(victims))
            disrupted = {(pi.pod.namespace, pi.pod.pod_group)
                         for pi in victims if pi.pod.pod_group}
            if disrupted:
                metrics.preemption_workload_disruptions.inc(
                    value=len(disrupted))
        cs = self.handle.clientset
        dispatcher = getattr(self.handle, "api_dispatcher", None)
        for pi in victims:
            if dispatcher is not None:
                from ..core.api_dispatcher import APICall, CALL_DELETE
                dispatcher.add(APICall(
                    call_type=CALL_DELETE, object_uid=pi.pod.uid,
                    execute=lambda p=pi.pod: cs.delete_pod(p)))
            else:
                cs.delete_pod(pi.pod)
        return PostFilterResult(), OK
