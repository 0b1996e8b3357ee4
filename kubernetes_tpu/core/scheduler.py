"""The scheduler: run loop + the one-pod scheduling cycle.

Re-expresses pkg/scheduler/scheduler.go (Scheduler struct :69, Run :537) and
pkg/scheduler/schedule_one.go — the hot path:

    schedule_one → scheduling_cycle:
        Cache.update_snapshot                      (cache.go:206)
        find_nodes_that_fit_pod                    (schedule_one.go:630)
            run_pre_filter_plugins
            nominated-node fast path               (:722)
            find_nodes_that_pass_filters           (:779, adaptive sampling
                                                    :866 + rotation :816)
        prioritize_nodes                           (:945)
        select_host                                (:?  reservoir over max)
        assume + reserve + permit                  (:315, :211)
    binding cycle (sync here; async overlap is the device pipeline's job)
        pre-bind → bind → post-bind                (:466,:478,:1100)
    failure → handle_scheduling_failure → requeue  (:1152)

TPU-first deviation: when the active profile has a `batch_evaluator` (the
device backend), schedule_one pulls a *row-block* of same-signature pods and
dispatches one kernel call that runs the whole greedy sequential assignment as
a loop on device (kubernetes_tpu/ops.kernel) — the generalization of
OpportunisticBatching (runtime/batch.go) the survey calls for (§2.4).
"""

from __future__ import annotations

import gc
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..api.types import Pod
from .apiserver import EVICTED_ANNOTATION
from .cache import (
    EV_NAMESPACE,
    EV_NODE_UPDATE,
    EV_OTHER,
    EV_POD_ADD,
    EV_POD_REMOVE,
    EV_POD_UPDATE,
    EV_QUEUE,
    EV_STRUCTURAL,
    Cache,
    EventJournal,
    Snapshot,
    pod_event_flags,
)
from .clientset import FakeClientset
from .framework import (
    MAX_NODE_SCORE,
    CycleState,
    Diagnosis,
    FitError,
    Framework,
    NodeScore,
    Status,
    UNSCHEDULABLE,
    UNSCHEDULABLE_AND_UNRESOLVABLE,
    WAIT,
)
from .node_info import NodeInfo
from .queue import (
    EVENT_ASSIGNED_POD_ADD,
    EVENT_ASSIGNED_POD_DELETE,
    EVENT_NODE_ADD,
    EVENT_NODE_UPDATE,
    PriorityQueue,
    QueuedCompositeGroupInfo,
    QueuedPodGroupInfo,
    QueuedPodInfo,
)

MIN_FEASIBLE_NODES_TO_FIND = 100
MIN_FEASIBLE_NODES_PERCENTAGE_TO_FIND = 5


def num_feasible_nodes_to_find(num_all_nodes: int, percentage: int = 0) -> int:
    """schedule_one.go:866 — adaptive 5-50% sampling, floor 100. The single
    source of truth shared by the host loop and the device kernel's sampling
    emulation (ops/features.py)."""
    if num_all_nodes < MIN_FEASIBLE_NODES_TO_FIND:
        return num_all_nodes
    if percentage > 0:
        pct = percentage
    else:
        pct = 50 - num_all_nodes // 125
        if pct < MIN_FEASIBLE_NODES_PERCENTAGE_TO_FIND:
            pct = MIN_FEASIBLE_NODES_PERCENTAGE_TO_FIND
    return max(num_all_nodes * pct // 100, MIN_FEASIBLE_NODES_TO_FIND)


@dataclass
class ScheduleResult:
    suggested_host: str = ""
    evaluated_nodes: int = 0
    feasible_nodes: int = 0
    waiting: bool = False  # a Permit plugin returned WAIT


class QueuedBind:
    """What settling a queued bind needs, kept from the binding cycle that
    queued it until the apiserver answers."""

    __slots__ = ("fw", "state", "qpi", "node_name", "device", "attempt_t0")

    def __init__(self, fw, state, qpi, node_name, device=False):
        self.fw = fw
        self.state = state
        self.qpi = qpi
        self.node_name = node_name
        self.device = device     # counts in device_scheduled at the settle
        self.attempt_t0 = None   # host cycle: its attempt series wait too


class Handle:
    """framework.Handle (interface.go:844) subset plugins consume."""

    def __init__(self, scheduler: "Scheduler"):
        self._scheduler = scheduler
        self.clientset = scheduler.clientset

    def snapshot(self) -> Snapshot:
        return self._scheduler.snapshot

    def namespace_labels(self, name: str):
        return self._scheduler.cache.namespace_labels(name)

    @property
    def nominator(self):
        return self._scheduler.queue.nominator

    @property
    def metrics(self):
        return self._scheduler.metrics

    @property
    def stages(self):
        return self._scheduler.stages

    @property
    def gates(self):
        return self._scheduler.gates

    @property
    def api_dispatcher(self):
        return self._scheduler.api_dispatcher

    @property
    def extenders(self):
        return self._scheduler.extenders

    @property
    def pod_group_state(self):
        return self._scheduler.pod_group_state

    # waiting pods (Permit WAIT; framework.Handle IterateOverWaitingPods /
    # GetWaitingPod surface, collapsed to allow/reject by uid)
    def allow_waiting_pod(self, uid: str) -> bool:
        return self._scheduler.allow_waiting_pod(uid)

    def reject_waiting_pod(self, uid: str, reason: str = "rejected") -> bool:
        return self._scheduler.reject_waiting_pod(uid, reason)

    def simulate_pod_group(self, group, members) -> bool:
        """Feasibility probe for pod-group preemption (the
        podGroupSchedulingFunc handed to PodGroupEvaluator.Preempt): would the
        group schedule against the CURRENT snapshot, under the SAME algorithm
        a real cycle would use (placement-constrained when the group carries
        topology constraints)? Leaves the snapshot unchanged; the caller owns
        any NodeInfo mutations (victim removals) around this probe."""
        return self._scheduler.group_feasible(group, members)

    def device_dry_run_preemption(self, fw, state, pod, node_to_status,
                                  num_candidates: int, start: int):
        """Batched DryRunPreemption when the scheduler has a device backend
        (models/tpu_scheduler.py); None routes the Evaluator to the exact
        host per-node simulation loop."""
        fn = getattr(self._scheduler, "device_dry_run_preemption", None)
        if fn is None:
            return None
        return fn(fw, state, pod, node_to_status, num_candidates, start)

    def say_stage(self, name: str, **stats) -> None:
        """Stats onto the loop stage ``name`` if that is the one open around
        the caller and somebody listens (``StageLedger.heard``: the
        ``postfilter.preempt`` of the attempt a plugin is running under)."""
        st = self._scheduler.stages.heard(name)
        if st is not None:
            st.say(**stats)

    def on_async_bind_done(self, pod, acked_at: float) -> None:
        """Async dispatcher bind acknowledged (loop thread, from the done
        inbox): settle the pod as of ``acked_at`` (time.perf_counter)."""
        self._scheduler._settle_bind(pod, acked_at)

    def on_async_bind_error(self, pod, exc: Exception) -> None:
        """Async dispatcher bind failure: unwind the assumed placement; the
        pod was never finished nor counted (a queued bind settles only at
        its acknowledgement). A 409 is an optimistic-binding conflict
        (another scheduler won the pod/node): counted, not logged as an
        error — the re-added pod is skipped once the winner's commit lands
        through the watch feed."""
        s = self._scheduler
        s._unsettled.pop(pod.uid, None)
        s._note_async_bind_lost(pod)
        s.state_unwinds += 1
        lost_node = pod.node_name  # captured for the conflict span below
        s.cache.forget_pod(pod)
        pod.node_name = ""
        s.failures += 1
        if getattr(exc, "code", None) == 409:
            # Classify from the 409 BODY ({"error": AlreadyBound|
            # OutOfCapacity}): the HTTPError's str() carries only the HTTP
            # status phrase ("Conflict"), which would land every single
            # (non-bulk) async conflict in the unclassified reason bucket.
            msg = str(exc)
            try:
                import json as _json
                msg = _json.loads(exc.read()).get("error", "") or msg
            except Exception:  # noqa: BLE001 - keep the phrase fallback
                pass
            s._note_bind_conflict(msg, pod, lost_node)
            s.conflict_requeues += 1
            # Same routing as the sync path's _unwind_binding: straight to
            # the backoffQ. Plain queue.add would put the loser on the
            # activeQ, where it re-pops and re-binds against a cache that
            # has not yet seen the winner's BOUND event — a 409 hot loop at
            # full cycle speed until the watch feed catches up.
            s.queue.requeue_conflict(s.queue._new_qpi(pod))
            return
        if getattr(exc, "code", None) == 429:
            # Flow-control shed (core/flowcontrol.py) surviving the retry
            # layers' Retry-After backoff: route through the SAME
            # conflict-style backoff requeue — never the error log, and
            # never a plain add() (activeQ would re-pop into the shed wave
            # at full cycle speed). _new_qpi recovers the pod's original
            # enqueued_at stamp, so the e2e histogram spans the shed retry.
            s._note_bind_shed(pod, lost_node)
            s.queue.requeue_conflict(s.queue._new_qpi(pod))
            return
        if s.clientset.pods.get(pod.uid) is None:
            # Deleted while its bind was queued or in flight (the apiserver
            # answered NotFound): nothing to requeue. Re-adding a pod the
            # informer no longer holds would schedule, fail and re-add it
            # forever, at full cycle speed (the reference's failure handler
            # likewise drops a pod missing from the informer cache).
            return
        s.error_log.append(
            f"async bind {pod.namespace}/{pod.name}: {exc!r}")
        s.queue.add(pod)

    # storage listers (volume plugins)
    @property
    def pvs(self):
        return self._scheduler.clientset.pvs

    @property
    def pvcs(self):
        return self._scheduler.clientset.pvcs

    @property
    def storage_classes(self):
        return self._scheduler.clientset.storage_classes

    @property
    def csi_nodes(self):
        return self._scheduler.clientset.csi_nodes

    # DRA listers (plugins/dynamicresources.py)
    @property
    def resource_slices(self):
        return self._scheduler.clientset.resource_slices

    @property
    def resource_claims(self):
        return self._scheduler.clientset.resource_claims

    @property
    def device_classes(self):
        return self._scheduler.clientset.device_classes


def queue_wait(qpi, now: float) -> float:
    """Seconds from the entity's queue admission to ``now``, its pop (0
    without an admission instant, and never negative)."""
    start = getattr(qpi, "enqueued_at", None)
    return now - start if start is not None and now > start else 0.0


class Scheduler:
    # Queue wait past this horizon force-samples the pod's trace and emits
    # a queue.starved event (overload plane, docs/RESILIENCE.md).
    STARVATION_FORCE_S = 30.0
    # Longest park of an otherwise idle loop while API writes are in flight:
    # what a pod that arrives meanwhile can wait in the event inbox.
    BIND_WAIT_SLICE_S = 0.002

    def __init__(
        self,
        clientset: Optional[FakeClientset] = None,
        profile_factory: Optional[Callable[[Handle], Dict[str, Framework]]] = None,
        percentage_of_nodes_to_score: int = 0,
        seed: int = 0,
        deterministic_ties: bool = False,
        config=None,  # SchedulerConfiguration (core/config.py)
        now: Callable[[], float] = time.monotonic,
    ):
        from .config import SchedulerConfiguration  # local: avoid cycle
        from .features import (
            COMPOSITE_POD_GROUP,
            GENERIC_WORKLOAD,
            SCHEDULER_POP_FROM_BACKOFF_Q,
            SCHEDULER_QUEUEING_HINTS,
            FeatureGates,
        )
        from .metrics import SchedulerMetrics

        self.config: SchedulerConfiguration = config or SchedulerConfiguration()
        self.gates: "FeatureGates" = self.config.gates()
        self.metrics = SchedulerMetrics()
        self.clientset = clientset or FakeClientset()
        self.cache = Cache(now=now)
        self.snapshot = Snapshot()
        self.now = now
        self.rng = random.Random(seed)
        self.percentage_of_nodes_to_score = (
            percentage_of_nodes_to_score or self.config.percentage_of_nodes_to_score)
        # deterministic_ties picks the first max-score node in evaluation
        # order instead of reservoir-sampling among ties (schedule_one.go
        # selectHost) — required for host↔device assignment equivalence.
        self.deterministic_ties = deterministic_ties
        self.next_start_node_index = 0

        handle = Handle(self)
        if profile_factory is not None:
            self.profiles = profile_factory(handle)
        elif config is not None:
            from .registry import build_framework
            self.profiles = {
                p.scheduler_name: build_framework(
                    handle, profile_name=p.scheduler_name,
                    plugins=p.plugins.resolve(), plugin_args=p.plugin_config)
                for p in self.config.profiles
            }
        else:
            from .registry import default_profiles
            self.profiles = default_profiles(handle)
        self.handle = handle
        first = next(iter(self.profiles.values()))
        import os as _os
        self.queue = PriorityQueue(
            framework=first,
            initial_backoff=self.config.pod_initial_backoff_seconds,
            max_backoff=self.config.pod_max_backoff_seconds,
            now=now,
            pop_from_backoff_q=self.gates.enabled(SCHEDULER_POP_FROM_BACKOFF_Q),
            gang_enabled=self.gates.enabled(GENERIC_WORKLOAD),
            queueing_hints_enabled=self.gates.enabled(SCHEDULER_QUEUEING_HINTS),
            composite_enabled=self.gates.enabled(COMPOSITE_POD_GROUP),
            # Per-tenant weighted fair dequeue (overload plane, docs/
            # RESILIENCE.md): config-driven, with an env seam so the shard
            # harness's OS-process schedulers can switch it on uniformly.
            fair_tenant_dequeue=(
                self.config.fair_tenant_dequeue
                or _os.environ.get("TPU_SCHED_FAIR_TENANTS", "") == "1"),
            tenant_weights=self.config.tenant_weights,
        )
        self.queue.metrics = self.metrics  # queueing-hint latency series
        # Extenders (extender.go; config extenders or injected objects).
        from .extender import Extender, http_transport
        self.extenders: List[Extender] = []
        for e in self.config.extenders:
            if isinstance(e, Extender):
                self.extenders.append(e)
            else:
                ext = Extender(
                    name=e.get("name", e.get("urlPrefix", "extender")),
                    filter_verb=e.get("filterVerb", ""),
                    prioritize_verb=e.get("prioritizeVerb", ""),
                    bind_verb=e.get("bindVerb", ""),
                    preempt_verb=e.get("preemptVerb", ""),
                    weight=e.get("weight", 1),
                    ignorable=e.get("ignorable", False),
                    managed_resources=tuple(e.get("managedResources", ())),
                    transport=http_transport(e["urlPrefix"]),
                )
                self.extenders.append(ext)
        # Async API dispatcher (backend/api_dispatcher; SchedulerAsyncAPICalls).
        from .api_dispatcher import APIDispatcher
        self.api_dispatcher = APIDispatcher(
            mode=self._dispatch_mode(), metrics=self.metrics)
        # Queued binds the apiserver has not answered yet, by pod uid: the
        # pod is assumed, and is finished and counted by _settle_bind.
        self._unsettled: Dict[str, QueuedBind] = {}
        # Callback gauges (free until exposed): queue/dispatcher depth series.
        self.metrics.inflight_events._fn = lambda: {
            (): float(len(self.queue._event_log))}
        self.metrics.pending_async_api_calls._fn = lambda: {
            (): float(self.api_dispatcher.pending_count())}
        self.metrics.queued_entities._fn = self._queued_entity_counts
        self.metrics.unschedulable_pods._fn = self._unschedulable_by_plugin
        # Per-tenant starvation gauge (overload plane): computed from live
        # queue contents at scrape time, zero hot-path bookkeeping.
        self.metrics.queue_starvation._fn = lambda: {
            (ns,): v
            for ns, v in self.queue.starvation_by_namespace().items()}
        # Watch decode cost, by wire form (core/watchcache.py shard-filtered
        # streams) and codec (core/wire.py binary vs JSON): counters live on
        # the HTTP clientset's reflector thread; the gauges read them at
        # scrape time so a sharded perf row can show the per-shard
        # decoded-events/bytes 1/N and which plane ran. Empty on a
        # FakeClientset (no wire).
        _cs = self.clientset
        self.metrics.watch_decoded_events._fn = lambda: {
            k: float(v) for k, v in
            getattr(_cs, "wire_decode_events", {}).items()}
        self.metrics.watch_decoded_bytes._fn = lambda: {
            k: float(v) for k, v in
            getattr(_cs, "wire_decode_bytes", {}).items()}
        # Waiting pods (Permit WAIT; framework.go waitingPods registry).
        # _next_wait_deadline makes expiry TIMER-DRIVEN: schedule_one checks
        # it every cycle (O(1)), so a parked pod times out even while the
        # scheduler is continuously busy (runtime/framework.go:2097
        # WaitOnPermit runs on its own timer in the reference).
        self.waiting_pods: Dict[str, tuple] = {}
        self.permit_wait_timeout = 60.0
        self._next_wait_deadline = float("inf")
        # Scheduled-group-pods store (backend/podgroupstate): group members
        # the CACHE considers placed (assumed + bound), maintained by the
        # cache's add/remove flow — placement generation pins a partially
        # scheduled gang's domain against the scheduler-side truth, with no
        # watch-feed lag under thread-mode async binds.
        from .podgroupstate import PodGroupState
        self.pod_group_state = PodGroupState()
        self.cache.pod_group_state = self.pod_group_state
        # Event recorder (schedule_one.go:1138).
        from .tracing import EventRecorder
        self.recorder = EventRecorder()
        # Pod-lifecycle spans (core/spans.py; docs/OBSERVABILITY.md): the
        # process-global tracer — head-sampled, ring-buffered; every stage
        # below checks `tracer.wants(ctx)` before building anything.
        from .spans import StageLedger, default_tracer
        self.tracer = default_tracer()
        # The loop's own account (core/spans.py StageLedger): every boundary
        # of the scheduling loop is one `with self.stages.stage(...)`.
        self.stages = StageLedger(self.tracer, self.metrics)
        # This process's collector policy (core/collector.py), held from
        # here to close(): thresholds sized to a batch, the heap frozen at
        # the loop's first idle after work, its clock the pauses by generation.
        from .collector import POLICY
        self.collector = POLICY
        self._collector_share = POLICY.acquire(self)
        self._worked = False  # a turn did work since the loop was last idle
        # metrics
        self.attempts = 0
        self.scheduled = 0
        self.failures = 0
        self.error_log: List[str] = []
        # Versions node-state-relevant cluster changes (see _on_pod_event).
        # The typed journal records WHAT each bump was, so device sessions
        # can delta-patch instead of tearing down (cache.py EventJournal);
        # cluster_event_seq mirrors journal.seq for all existing consumers.
        self.journal = EventJournal()
        self.cluster_event_seq = 0
        # Versions cache-state UNWINDS that happen outside a scheduling
        # attempt (bind failure after Permit WAIT release, waiter expiry,
        # async bind error): a device session/resume carry or fail memo
        # computed before an unwind no longer reflects the cache.
        self.state_unwinds = 0
        # Placements the watch feed revoked (a re-list/resume after an
        # apiserver restart reported a cache-placed pod as UNBOUND): the
        # assumed-vs-recovered-truth reconciliation below unwound them.
        self.reconcile_unwinds = 0
        # Control-plane failovers this scheduler has reacted to: a FAILOVER
        # watch marker (replicated apiserver promotion) bumps the
        # clientset's failover_count; run_until_idle notices and runs
        # reconcile_bindings — a bind the dead leader acked but never
        # shipped is unbound in the promoted truth and has NO event to
        # trigger the per-event reconcile path above.
        self._seen_failovers = 0
        # Shard plane (kubernetes_tpu/shard/): optional admission predicate —
        # when set, only pods it accepts enter THIS scheduler's queue (the
        # shard-scoped admission seam; the cache still mirrors the whole
        # cluster so every shard plans against full node state). Optimistic
        # binding: a 409 from the binding subresource is counted here and
        # requeued through the backoffQ (see _unwind_binding).
        self.pod_admission: Optional[Callable[[Pod], bool]] = None
        self.shard_member = None  # set by shard.ShardMember (debugger dump)
        # Flow-control sheds (429) this scheduler's binds absorbed: each
        # one requeued through the conflict-style backoff path with its
        # original queue-admission stamp preserved.
        self.shed_requeues = 0
        # Pods re-entering the queue after a node-lifecycle eviction (the
        # server's recreate carries the eviction-intent annotation). One
        # eviction = one recreate event = exactly one bump — the chaos
        # acceptance diffs this against the controller's evictions_total.
        # `_eviction_residue` (uid -> intent) mirrors the server's ledger
        # lifecycle: the annotation stays on the recreated pod, so a
        # re-list (watch Replace after an apiserver failover) replays the
        # same pending pod as a fresh ADDED — a matching residue entry is
        # that replay, not a new eviction. The entry dies when the pod is
        # observed bound (or deleted), because any LATER eviction — even
        # one re-minting the same uid@node intent after the pod returned
        # to a recovered-then-refailed node — must count again.
        self.eviction_requeues = 0
        self._eviction_residue: Dict[str, str] = {}
        # Per-cycle hook (run_until_idle): the shard member's ownership
        # refresh runs here so queue-mutating failover stays on the
        # scheduling thread even through long drains.
        self.loop_hook: Optional[Callable[[], object]] = None
        self.bind_conflicts = 0
        self.conflict_requeues = 0
        # True when every bind terminates at the apiserver's binding
        # subresource, whose Omega-style transaction validation rejects an
        # overcommitting commit with a 409 (set by shard.ShardMember). Lets
        # device sessions treat a peer shard's bind feed optimistically:
        # commit in-flight results as-is and let the store arbitrate,
        # instead of invalidating the session pessimistically.
        self.bind_capacity_validated = False
        # Off-thread watch-event inbox (see _threaded): deque append/popleft
        # are atomic under the GIL, so no lock is needed.
        from collections import deque
        self._event_inbox = deque()
        # Parked cluster events (`cluster_events_parked`): parks counted by
        # the threads that park, under their lock; replays by the loop.
        self._cluster_parks = 0
        self._cluster_replays = 0
        self._cluster_park_lock = threading.Lock()
        self._cluster_replay_fns = set()
        # When the oldest event now parked was parked (perf_counter; 0.0:
        # nothing stamped): the park that finds the inbox empty stamps it,
        # the drain reads it and takes it away (stage inbox.wait).
        self._inbox_oldest_at = 0.0
        # Durations of the own bind confirms handled inside the bulk bind a
        # batch tail has open; None outside one (see _pod_events).
        self._own_confirms: Optional[List[float]] = None
        self._wire_event_handlers()

    # -- event handlers (eventhandlers.go:624 addAllEventHandlers) ---------

    def _wire_event_handlers(self) -> None:
        self.clientset.on_pod_event(self._pod_events())
        self.clientset.on_node_event(self._threaded(
            self._timed_event("node", self._on_node_event), clocked="node"))
        self.clientset.on_namespace_event(self._threaded(self._bump(
            self.cache.add_namespace, EV_NAMESPACE,
            keyfn=lambda ns: ns.name)))
        self.clientset.on_pod_group_event(self._threaded(self._bump(
            self.queue.register_pod_group, EV_QUEUE)))
        self.clientset.on_storage_event(self._threaded(
            self._timed_event("storage", self._on_storage_event)))

    def _timed_event(self, name: str, handler):
        """event_handling_duration_seconds per handler invocation
        (eventhandlers.go handler latency series)."""
        hist = self.metrics.event_handling_duration

        def h(*args):
            t0 = time.perf_counter()
            try:
                handler(*args)
            finally:
                hist.observe(time.perf_counter() - t0, name)
        return h

    def _record_event(self, kind: str, key: str = "", pod_plain: bool = False,
                      pod_ports: bool = False, shrink: bool = False) -> None:
        """Journal one typed event and advance cluster_event_seq."""
        self.cluster_event_seq = self.journal.record(
            kind, key, pod_plain=pod_plain, pod_ports=pod_ports,
            shrink=shrink)

    def _bump(self, handler, kind: str, keyfn=None):
        """Wrap a handler so it versions cluster_event_seq with a typed
        record (namespace labels and pod-group registrations affect
        scheduling outcomes)."""
        def h(*args):
            self._record_event(kind, keyfn(*args) if keyfn else "")
            handler(*args)
        return h

    def _threaded(self, handler, clocked: str = ""):
        """Watch events raised off the scheduling thread (e.g. the thread-mode
        dispatcher's bind fanning out through the clientset) are parked in an
        inbox and replayed by the scheduling loop — the DeltaFIFO seam
        (client-go delta_fifo.go): cache/queue mutation stays single-threaded.
        Events raised on the scheduling thread dispatch inline, preserving the
        synchronous semantics tests rely on. The park that finds the inbox
        empty stamps the clock: that event is the oldest the next drain
        meets (a park that loses the race with a drain between its look and
        its append leaves no stamp, and that drain observes nothing).
        ``clocked`` names a kind whose every parked event carries its own
        park time and observes its wait as it is replayed
        (``scheduler_cluster_event_wait_seconds{kind}``): the few events
        that move the cluster under the pods, not the pods. They are
        counted while they are parked (``cluster_events_parked``), and a
        drain that is asked to hold them stops in front of the first."""
        loop_ident = threading.get_ident()  # get_ident beats current_thread
        inbox = self._event_inbox

        def dispatch(*args):
            if threading.get_ident() == loop_ident:
                handler(*args)
            else:
                if not inbox:
                    self._inbox_oldest_at = time.perf_counter()
                inbox.append((handler, args))
        if not clocked:
            return dispatch
        observe = self.metrics.cluster_event_wait.observe

        def replay(parked_at, *args):
            self._cluster_replays += 1
            observe(time.perf_counter() - parked_at, clocked)
            handler(*args)

        self._cluster_replay_fns.add(replay)

        def dispatch_clocked(*args):
            if threading.get_ident() == loop_ident:
                handler(*args)
            else:
                with self._cluster_park_lock:  # parkers only; the loop
                    self._cluster_parks += 1   # writes the other count
                now = time.perf_counter()
                if not inbox:
                    self._inbox_oldest_at = now
                inbox.append((replay, (now,) + args))
        return dispatch_clocked

    @property
    def cluster_events_parked(self) -> int:
        """Cluster events (``_threaded``'s clocked kinds: a node added,
        updated or deleted) that another thread has parked and the loop has
        not replayed yet. A device session that finds one stops refilling,
        retires what is in flight and ends, so that the next turn replays
        the event with an empty pipeline (models/tpu_scheduler.py)."""
        return self._cluster_parks - self._cluster_replays

    def _pod_events(self):
        """The pod handler the clientset is given: ``_threaded`` over
        ``_timed_event`` over ``_on_pod_event``, and one shorter way in.
        While a batch tail's bulk bind is open (models/tpu_scheduler.py:
        ``_own_confirms`` is its list), an ``update`` that reaches the loop's
        thread with a node name for a pod this scheduler holds assumed is
        the confirm of a bind it sent a moment ago: it gets
        ``_confirm_own_bind`` at once and leaves its duration on the list,
        which the tail hands to ``event_handling_duration_seconds`` in one
        call. Every other event, and every event outside a bulk bind, goes
        the long way."""
        general = self._threaded(self._timed_event("pod", self._on_pod_event))
        loop_ident = threading.get_ident()
        clock = time.perf_counter

        def dispatch(kind, old, new):
            confirms = self._own_confirms
            if (confirms is None or kind != "update" or not new.node_name
                    or new.uid not in self.cache.assumed_pods
                    or threading.get_ident() != loop_ident):
                general(kind, old, new)
                return
            t0 = clock()
            self._confirm_own_bind(old, new)
            confirms.append(clock() - t0)
        return dispatch

    def drain_event_inbox(self, hold_cluster_events: bool = False) -> int:
        """Replay off-thread watch events on the scheduling loop.
        ``hold_cluster_events`` (a device session's drain, batches in
        flight): stop in front of the first parked cluster event and leave
        it, and what was parked behind it, for the turn's own drain."""
        inbox = self._event_inbox
        if not inbox:
            return 0
        held = self._cluster_replay_fns if hold_cluster_events else ()
        if held and inbox[0][0] in held:
            return 0
        parked_at, self._inbox_oldest_at = self._inbox_oldest_at, 0.0
        if parked_at:
            self.metrics.inbox_oldest_wait.observe(
                time.perf_counter() - parked_at)
        n = 0
        with self.stages.stage("inbox.drain"):
            while inbox:
                if held and inbox[0][0] in held:
                    self._inbox_oldest_at = inbox[0][1][0]  # its own park
                    break
                try:
                    handler, args = inbox.popleft()
                except IndexError:
                    break
                handler(*args)
                n += 1
        return n

    def _on_storage_event(self, kind: str, obj) -> None:
        from .queue import EVENT_STORAGE_ADD
        # Device-session validity: only storage objects that change NODE
        # capability can stale an in-flight carry (CSINode limits, device
        # pools, PV topology, binding-mode classes). New claims/PVCs are
        # pod-side state — they unblock WAITING pods (queue move below) but
        # cannot invalidate decisions already made for eligible pods, and
        # bumping the seq per created claim would tear down a session per
        # measured pod (the claim-template workload creates one each).
        if kind not in ("pvc", "resource_claim"):
            self._record_event(EV_OTHER, kind)
        self.queue.move_all_to_active_or_backoff(EVENT_STORAGE_ADD, None, obj)

    def _responsible_for_pod(self, pod: Pod) -> bool:
        """eventhandlers.go responsibleForPod: only queue pods whose
        schedulerName names one of our profiles."""
        return pod.scheduler_name in self.profiles

    def _admits(self, pod: Pod) -> bool:
        """Shard-scoped admission: with no shard plane every pod is ours."""
        return self.pod_admission is None or self.pod_admission(pod)

    def _on_pod_event(self, kind: str, old: Optional[Pod], new: Pod) -> None:
        if (getattr(new, "wire_slim", False) and not new.node_name
                and kind in ("add", "update")
                and self.pod_admission is not None
                and self._responsible_for_pod(new) and self._admits(new)):
            # A slim-projection pod this scheduler ADMITS: shard ownership
            # grew past the watch stream's static `shard=i/n` filter
            # (adoption) — the pod arrived without its real spec
            # (selectors, tolerations, gates). Hydrate from the server's
            # watch cache before any queue state is built from the
            # projection; on a transient fetch failure the pod stays out
            # of the queue and the adoption sweep retries. Gated on an
            # ATTACHED shard plane (pod_admission): before the ShardMember
            # exists, _admits answers True for everything, and the
            # constructor-time handler replay would hydrate every foreign
            # pod — while deadlocking on the clientset's _dispatch_lock,
            # which that replay already holds on this thread.
            hydrate = getattr(self.clientset, "hydrate_pod", None)
            if hydrate is not None:
                full = hydrate(new.uid)
                if full is not None:
                    new = full
        # cluster_event_seq versions node-state-relevant cluster changes so a
        # device batch session (models/tpu_scheduler.py) knows whether the
        # on-device carry still reflects the cluster; the typed journal
        # record lets it patch instead of tearing down. Benign for the
        # carry (no record): pending-pod adds (queue-only) and our own bind
        # confirms (the carry already holds that placement via the assume).
        if kind == "add" and not new.node_name:
            pass
        elif (kind == "update" and new.node_name
                and self.cache.is_assumed_pod(new)):
            # Our own bind confirm: the scheduler already assumed this pod
            # onto the node (note `old` may alias the scheduler's mutated
            # object, so old.node_name can't distinguish the transition —
            # the assumed set can).
            self._confirm_own_bind(old, new)
            return
        else:
            self._record_pod_event(kind, old, new)
        if new.node_name or kind == "delete":
            # Bound or gone closes the evicted-pending window — matching
            # the apiserver's ledger prune — so this pod's NEXT eviction
            # counts even if it re-mints the same uid@node intent.
            self._eviction_residue.pop(new.uid, None)
        if kind == "add":
            if new.node_name:
                self.cache.add_pod(new)
                self.queue.move_all_to_active_or_backoff(
                    EVENT_ASSIGNED_POD_ADD, None, new)
            elif (self._responsible_for_pod(new) and self._admits(new)
                    and not getattr(new, "wire_slim", False)):
                # A still-slim pod (hydration failed) must never be
                # SCHEDULED from its projection; the sweep retries it.
                intent = new.annotations.get(EVICTED_ANNOTATION)
                if intent and self._eviction_residue.get(new.uid) != intent:
                    self._eviction_residue[new.uid] = intent
                    self.eviction_requeues += 1
                self.queue.add(new)
        elif kind == "update":
            if new.node_name:
                if old is not None and not old.node_name:
                    # pending → bound transition (our own bind confirm):
                    # still an AssignedPodAdd for QUEUEING purposes — parked
                    # pods whose affinity/spread terms this pod satisfies
                    # must requeue (eventhandlers.go addPodToCache →
                    # MoveAllToActiveOrBackoffQueue(AssignedPodAdd)). The
                    # cluster_event_seq stays unbumped (the carry already
                    # holds the placement via the assume).
                    self.cache.add_pod(new)
                    self.queue.move_all_to_active_or_backoff(
                        EVENT_ASSIGNED_POD_ADD, None, new)
                else:
                    self.cache.update_pod(old, new)
            else:
                st = self.cache.pod_states.get(new.uid)
                if st is not None and st.binding_finished:
                    # Post-restart reconciliation: the API says this pod is
                    # UNBOUND while the cache holds a placement whose bind
                    # COMPLETED (binding_finished) — the control plane lost
                    # the committed bind (apiserver restarted from a store
                    # that predates it; the re-list/resume replay is the
                    # diff against recovered truth). Unwind the phantom
                    # placement and reschedule; the retry/bind layers will
                    # re-commit it. A placement whose bind is still IN
                    # FLIGHT is deliberately not touched: a stale re-list
                    # can race a healthy bind, and exhaustion of that
                    # bind's retries already unwinds via the bind-error
                    # paths.
                    self.reconcile_unwinds += 1
                    self.state_unwinds += 1
                    self.cache.remove_pod(st.pod)
                    self.queue.move_all_to_active_or_backoff(
                        EVENT_ASSIGNED_POD_DELETE, st.pod, None)
                    if self._responsible_for_pod(new) and self._admits(new):
                        new.node_name = ""
                        self.queue.add(new)
                else:
                    if ((self._admits(new) or self.queue.has_entity(new.uid))
                            and not getattr(new, "wire_slim", False)):
                        # Non-admitted pending pods stay out of the queue;
                        # an already-queued one (ownership shrank after
                        # adoption handback) still takes spec updates — the
                        # optimistic 409 path resolves any overlap. A pod
                        # still in slim projection (hydration failed) must
                        # not fall through update() into a spec-less add.
                        self.queue.update(old, new)
        elif kind == "delete":
            if new.node_name:
                self.cache.remove_pod(new)
                self.queue.move_all_to_active_or_backoff(
                    EVENT_ASSIGNED_POD_DELETE, new, None)
            else:
                self.queue.delete(new)

    def _confirm_own_bind(self, old: Optional[Pod], new: Pod) -> None:
        """The watch feed confirmed one of OUR binds (``new`` is bound and
        in the assumed set): no journal record, since the carry already
        holds the placement via the assume; the evicted-pending window
        closes; the cache takes the confirmed copy; and where ``old`` shows
        the pending -> bound transition, parked pods whose affinity or
        spread terms this pod satisfies requeue (eventhandlers.go
        addPodToCache -> MoveAllToActiveOrBackoffQueue(AssignedPodAdd)).
        Over the in-process store ``old`` is the object this scheduler
        assumed, node name and all, so nothing moves there."""
        self._note_own_bind_confirm(new)
        self._eviction_residue.pop(new.uid, None)
        self.cache.add_pod(new)
        if old is not None and not old.node_name:
            self.queue.move_all_to_active_or_backoff(
                EVENT_ASSIGNED_POD_ADD, None, new)

    def _note_own_bind_confirm(self, new: Pod) -> None:
        """Seam: the watch stream confirmed one of OUR binds (the pod is in
        the assumed set and arrived bound). Subclasses settle any
        optimistic-commit bookkeeping here — models/tpu_scheduler.py drops
        the score-hint take-back tag, since no 409 can follow a confirm."""

    def _record_pod_event(self, kind: str, old: Optional[Pod], new: Pod) -> None:
        """Journal classification for a non-benign watch pod event."""
        plain, ports = pod_event_flags(new)
        if old is not None and old is not new:
            oplain, oports = pod_event_flags(old)
            plain, ports = plain and oplain, ports or oports
        if kind == "add":
            self._record_event(EV_POD_ADD, new.node_name,
                               pod_plain=plain, pod_ports=ports)
        elif kind == "update":
            if new.node_name:
                old_node = old.node_name if old is not None else ""
                if not old_node:
                    # Externally assigned (someone else's bind): load appears
                    # on the node exactly like an assigned-pod add.
                    self._record_event(EV_POD_ADD, new.node_name,
                                       pod_plain=plain, pod_ports=ports)
                elif old_node == new.node_name:
                    self._record_event(EV_POD_UPDATE, new.node_name,
                                       pod_plain=plain, pod_ports=ports)
                else:  # moved between nodes: old row shrinks, new row grows
                    self._record_event(EV_POD_REMOVE, old_node,
                                       pod_plain=plain, pod_ports=ports,
                                       shrink=True)
                    self._record_event(EV_POD_ADD, new.node_name,
                                       pod_plain=plain, pod_ports=ports)
            else:
                st = self.cache.pod_states.get(new.uid)
                if st is not None and st.binding_finished:
                    # Lost-bind reconciliation unwind (below): cache state
                    # moves outside any single node row's aggregates.
                    self._record_event(EV_OTHER, new.uid)
                else:
                    # Pending-pod spec update — the scheduling-gate lift
                    # path. Queue-only: no node state moves.
                    self._record_event(EV_QUEUE, new.uid)
        elif kind == "delete":
            if new.node_name:
                self._record_event(EV_POD_REMOVE, new.node_name,
                                   pod_plain=plain, pod_ports=ports,
                                   shrink=True)
            else:
                self._record_event(EV_QUEUE, new.uid)
        else:
            self._record_event(EV_OTHER, new.uid)

    @staticmethod
    def _node_shrink_only(old, new) -> bool:
        """True when `new` can only ENLARGE feasibility vs `old`: no taint
        added, allocatable not reduced, unschedulable not switched on —
        device results computed against `old` stay feasible under `new`."""
        if new.unschedulable and not old.unschedulable:
            return False
        o_t = {(t.key, t.value, t.effect) for t in old.taints}
        if any((t.key, t.value, t.effect) not in o_t for t in new.taints):
            return False
        oa, na = old.allocatable, new.allocatable
        if (na.milli_cpu < oa.milli_cpu or na.memory < oa.memory
                or na.ephemeral_storage < oa.ephemeral_storage
                or na.allowed_pod_number < oa.allowed_pod_number):
            return False
        return all(na.scalar_resources.get(k, 0) >= v
                   for k, v in oa.scalar_resources.items())

    def _on_node_event(self, kind: str, old, new) -> None:
        if kind == "update" and old is not None and old.name == new.name \
                and old.labels == new.labels and old.images == new.images \
                and old.declared_features == new.declared_features:
            # Taint/allocatable/unschedulable-only change: one row's
            # non-feature tensors — delta-patchable by a live session.
            self._record_event(EV_NODE_UPDATE, new.name,
                               shrink=self._node_shrink_only(old, new))
        elif kind == "update":
            self._record_event(EV_OTHER, new.name)
        else:
            self._record_event(EV_STRUCTURAL, new.name)
        if kind == "add":
            self.cache.add_node(new)
            self.queue.move_all_to_active_or_backoff(EVENT_NODE_ADD, None, new)
        elif kind == "update":
            self.cache.update_node(new)
            self.queue.move_all_to_active_or_backoff(
                EVENT_NODE_UPDATE, old, new)
        elif kind == "delete":
            self.cache.remove_node(new.name)

    def _dispatch_mode(self) -> str:
        """How API writes leave this scheduler, from what it can observe:
        ``thread`` when the clientset says its writes cross a socket
        (``remote_writes``: HTTPClientset, also under RetryingClientset) or
        the configuration asks for it, ``inline`` over the in-process store,
        where there is no round trip to hide and tests and the in-process
        cells rely on the determinism. SchedulerAsyncAPICalls off: inline
        everywhere."""
        from .features import SCHEDULER_ASYNC_API_CALLS
        if not self.gates.enabled(SCHEDULER_ASYNC_API_CALLS):
            return "inline"
        if (self.config.async_dispatch_threads
                or getattr(self.clientset, "remote_writes", False)):
            return "thread"
        return "inline"

    # -- profiles ----------------------------------------------------------

    def framework_for_pod(self, pod: Pod) -> Framework:
        fw = self.profiles.get(pod.scheduler_name)
        if fw is None:
            raise KeyError(f"no profile for scheduler name {pod.scheduler_name!r}")
        return fw

    # -- run loop ----------------------------------------------------------

    def run_until_idle(self, max_cycles: int = 1_000_000) -> int:
        """Drive schedule_one until the queue drains (test/bench harness)."""
        fc = getattr(self.clientset, "failover_count", 0)
        if fc != self._seen_failovers:
            # Control-plane leadership moved (FAILOVER watch marker): drain
            # the inbox so the cache reflects everything the stream already
            # delivered, then sweep for placements whose committed bind the
            # promoted leader does not hold (see reconcile_bindings).
            self._seen_failovers = fc
            self.drain_event_inbox()
            self.reconcile_bindings()
        n = 0
        while n < max_cycles:
            if self.loop_hook is not None:
                self.loop_hook()
            if not self.schedule_one():
                self.queue.flush_backoff_completed()
                self.flush_expired_waiters()
                # Settle acknowledged binds and drain failed ones on THIS
                # thread (the inboxes keep cache/queue mutation off the
                # dispatcher worker), then re-check: an unwound pod goes
                # back onto the queue. With writes in flight the loop parks
                # until the worker has an outcome for it, and for a SHORT
                # slice at most: pods created meanwhile sit in the event
                # inbox, which nothing wakes the loop for (parks of 50 ms
                # were a fifth of a served wave). Only a fully idle
                # dispatcher ends the loop, so the contract is unchanged:
                # on return, the queue is drained AND every accepted write
                # has landed or reported, and has been settled.
                if not self.api_dispatcher.idle():
                    with self.stages.stage("loop.idle"):
                        self.api_dispatcher.wait_for_outcome(
                            self.BIND_WAIT_SLICE_S)
                self.process_async_api_errors()
                if not self.schedule_one():
                    if self.api_dispatcher.idle():
                        break
                    n += 1  # count the wait slice: max_cycles stays a bound
                    continue  # writes still in flight: stay responsive
            n += 1
        return n

    def reconcile_bindings(self) -> int:
        """Failover sweep (scheduling thread only): unwind every cache
        placement whose COMPLETED bind the control plane does not hold.

        The per-event reconcile in _on_pod_event covers binds revoked by a
        re-list/resume replay — but a bind the dead LEADER acked and never
        shipped to the promoted follower produces NO event at all (the
        follower simply never saw it), so after a FAILOVER marker this
        sweep compares the informer truth against the cache directly.
        In-flight binds are deliberately untouched: their retry layers
        re-commit through the idempotent/409 surface."""
        unwound = 0
        for uid, st in list(self.cache.pod_states.items()):
            if not st.binding_finished:
                continue
            api_pod = self.clientset.pods.get(uid)
            if api_pod is None or api_pod.node_name:
                continue  # deleted -> DELETED event path; bound -> coherent
            self.reconcile_unwinds += 1
            self.state_unwinds += 1
            self._record_event(EV_OTHER, uid)
            self.cache.remove_pod(st.pod)
            self.queue.move_all_to_active_or_backoff(
                EVENT_ASSIGNED_POD_DELETE, st.pod, None)
            if self._responsible_for_pod(api_pod) and self._admits(api_pod):
                api_pod.node_name = ""
                self.queue.add(api_pod)
            unwound += 1
        return unwound

    def process_async_api_errors(self) -> int:
        """Run the thread-mode dispatcher's deferred handlers on the
        scheduling loop: on_done for acknowledged calls (a queued bind
        settles here), then on_error for failed ones (the reference's
        dispatcher invokes onError on the scheduling side via the cache
        adapter; backend/api_dispatcher/). Also replays off-thread watch
        events parked by _threaded. Cheap no-op when all three are empty.
        Returns the failures handled."""
        self.drain_event_inbox()
        dispatcher = self.api_dispatcher
        if dispatcher.has_done():
            # The commit's own tail, deferred to the acknowledgement.
            with self.stages.stage("host.commit", annotate=False):
                for call in dispatcher.drain_done():
                    call.on_done(call)
        if not dispatcher.has_errors():
            return 0
        drained = dispatcher.drain_errors()
        for call, exc in drained:
            call.on_error(exc)
        return len(drained)

    def shutdown(self, timeout: float = 3.0) -> None:
        """The process is going down: give the dispatcher ``timeout`` seconds
        to send what is queued, stop it, and settle what was acknowledged.
        A bind that was never sent stays pending at the apiserver for the
        next scheduler, as after a crash."""
        self.api_dispatcher.flush(timeout=timeout)
        self.api_dispatcher.close()
        self.process_async_api_errors()
        self.close()

    def close(self) -> None:
        """Give back this scheduler's share of the process's collector
        policy; the last one restores the thresholds it found and unfreezes
        the heap. A scheduler that is dropped unclosed gives it back when it
        is collected."""
        self._collector_share()

    @property
    def gc_freezes(self) -> int:
        return self.collector.freezes

    @property
    def gc_frozen_objects(self) -> int:
        return gc.get_freeze_count()

    # -- one cycle ---------------------------------------------------------

    def schedule_one(self) -> bool:
        """One turn of the loop: the ledger's `cycle`, whose own self time
        is what no stage below it has a name for."""
        stages = self.stages
        # `pauses`: the collections charged to the table so far (a reader of
        # a trace learns from it that `gc.pause` is booked at all)
        with stages.stage("cycle", pauses=stages.counts["gc.pause"]):
            if self._cycle():
                self._worked = True
                return True
            if self._worked:
                self._worked = False
                self.collector.idle(stages)
            return False

    def _cycle(self) -> bool:
        self.process_async_api_errors()
        if self.waiting_pods and self.now() >= self._next_wait_deadline:
            self.flush_expired_waiters()
        t0 = time.perf_counter()
        qpi = self.queue.pop()
        self.stages.leaf("queue.pop", time.perf_counter() - t0)
        if qpi is None:
            return False
        self.process_one(qpi)
        return True

    def process_one(self, qpi) -> None:
        """One full scheduling+binding cycle for an already-popped entity."""
        if isinstance(qpi, QueuedCompositeGroupInfo):
            self.schedule_composite_group(qpi)
            return
        if isinstance(qpi, QueuedPodGroupInfo):
            _t_pg = time.perf_counter()
            _before = self.metrics.podgroup_schedule_attempts.value("scheduled")
            self.schedule_pod_group(qpi)
            dt = time.perf_counter() - _t_pg
            self.metrics.podgroup_scheduling_algorithm_duration.observe(dt)
            self.metrics.podgroup_scheduling_attempt_duration.observe(
                dt, "scheduled" if self.metrics.podgroup_schedule_attempts.value(
                    "scheduled") > _before else "unschedulable")
            return
        pod = qpi.pod
        if pod.deletion_ts is not None:
            # skipPodSchedule (schedule_one.go:93): the pod is being deleted;
            # don't attempt it — the delete event will clear it from the queue.
            self.queue.done(pod.uid)
            return
        if pod.uid in self.cache.pod_states:
            # skipPodSchedule: the cache already holds a placement for this
            # pod (a reconcile unwind raced the bind-confirm event — the
            # re-queued copy predates the confirmation). Scheduling it again
            # would double-place it.
            self.queue.done(pod.uid)
            return
        fw = self.framework_for_pod(pod)
        self.attempts += 1
        t0 = time.perf_counter()
        ctx = self.tracer.context_for(pod.uid)
        eq = getattr(qpi, "enqueued_at", None)
        if eq is not None and self.now() - eq >= self.STARVATION_FORCE_S:
            # A pod that waited past the starvation horizon is FORCE-
            # sampled (overload forensics): its whole trace — queue.wait
            # through bind — survives into the flight ring regardless of
            # the head-sampling rate.
            ctx = self.tracer.context_for(pod.uid, force=True)
            self.tracer.event("queue.starved", ctx,
                              wait=round(self.now() - eq, 3),
                              namespace=pod.namespace)
        self.record_queue_wait(qpi, ctx)
        # The host path's whole cycle (algorithm + bind) is one host.commit
        # stage per pod — table only, no profiler annotation per pod. The
        # slow-stage rule reports it on EVERY outcome (utiltrace logs via
        # defer: bound, unschedulable, Permit WAIT, or error); the span
        # enters the pod's trace only when it bound.
        with self.stages.stage(
                "host.commit", (ctx,) if self.tracer.wants(ctx) else (),
                annotate=False, pod=f"{pod.namespace}/{pod.name}",
                path="host") as stage:
            stage.span = False
            self._process_one_staged(fw, CycleState(), qpi, stage, t0)

    def _process_one_staged(self, fw, state, qpi, stage, t0) -> None:
        pod = qpi.pod
        try:
            result = self.scheduling_cycle(fw, state, qpi)
        except FitError as fe:
            self.handle_fit_error(fw, state, qpi, fe, t0)
            return
        except Exception as e:  # noqa: BLE001
            self.error_log.append(f"{pod.namespace}/{pod.name}: {e!r}")
            self.handle_scheduling_failure(fw, qpi, Status.error(str(e)), None)
            self.queue.done(pod.uid)
            self.metrics.schedule_attempts.inc("error", fw.profile_name)
            return
        if result.waiting:
            # WaitOnPermit (framework.go:2097): the pod stays reserved
            # (assumed in the cache) until a Permit plugin allows or rejects
            # it, or the wait times out (flush_expired_waiters).
            self.park_waiting_pod(fw, state, qpi, result)
            self.queue.done(pod.uid)
            return
        bound = self.run_binding_cycle(fw, state, qpi, result)
        self.queue.done(pod.uid)
        if bound:
            # Host-path commit span: the whole cycle (algorithm + bind
            # enqueue) — the device path records finer-grained stages.
            stage.attrs["node"] = result.suggested_host
            stage.span = True
            rec = self._unsettled.get(pod.uid)
            if rec is not None:
                rec.attempt_t0 = t0  # the attempt's series wait for the ack
                return
        self._observe_attempt(fw, qpi, bound, time.perf_counter() - t0)

    def _observe_attempt(self, fw: Framework, qpi: QueuedPodInfo, bound: bool,
                         elapsed: float, ack_age: float = 0.0) -> None:
        """The host cycle's attempt series (algorithm + binding), at the end
        of the binding: now, or ``ack_age`` seconds ago for a queued bind."""
        result = "scheduled" if bound else "error"
        self.metrics.schedule_attempts.inc(result, fw.profile_name)
        self.metrics.scheduling_attempt_duration.observe(
            elapsed, result, fw.profile_name)
        if bound and qpi.initial_attempt_timestamp is not None:
            self.metrics.pod_scheduling_sli_duration.observe(
                self.now() - ack_age - qpi.initial_attempt_timestamp,
                str(qpi.attempts))
        if bound:
            self.metrics.pod_scheduling_attempts.observe(max(1, qpi.attempts))

    def handle_fit_error(self, fw: Framework, state: CycleState,
                         qpi: QueuedPodInfo, fe: FitError, t0: float) -> None:
        """The scheduling-cycle FitError tail (schedule_one.go:169 tail +
        :1152 handleSchedulingFailure): PostFilter (preemption) with the
        diagnosis, nomination recording, requeue, metrics. Shared by the host
        cycle and the device path's vectorized diagnosis."""
        pod = qpi.pod
        if fw.post_filter_plugins:
            _t = time.perf_counter()
            # The stage of a failed attempt's PostFilter: what runs under it
            # says the rest (Handle.say_stage: the dry run's engine, its parts).
            with self.stages.stage("postfilter.preempt"):
                result, post_st = fw.run_post_filter_plugins(
                    state, pod, fe.diagnosis.node_to_status)
            self._observe_point("PostFilter", _t, post_st.is_success())
            nominated = getattr(result, "nominating_info", None) if result else None
            if post_st.is_success() and nominated:
                pod.nominated_node_name = nominated
                self.clientset.patch_pod_status(pod, nominated_node_name=nominated)
                self.queue.nominator.add_nominated_pod(qpi.pod_info, nominated)
        self.handle_scheduling_failure(fw, qpi, Status(UNSCHEDULABLE, (str(fe),)), fe.diagnosis)
        self.queue.done(pod.uid)
        self.metrics.schedule_attempts.inc("unschedulable", fw.profile_name)
        self.metrics.scheduling_attempt_duration.observe(
            time.perf_counter() - t0, "unschedulable", fw.profile_name)

    def scheduling_cycle(self, fw: Framework, state: CycleState, qpi: QueuedPodInfo) -> ScheduleResult:
        pod = qpi.pod
        self.cache.update_snapshot(self.snapshot)
        _t_alg = time.perf_counter()
        result = self.schedule_pod(fw, state, pod)
        self.metrics.scheduling_algorithm_duration.observe(
            time.perf_counter() - _t_alg)
        # assume (schedule_one.go:1060): in-memory commit before binding
        assumed = pod
        assumed.node_name = result.suggested_host
        self.cache.assume_pod(assumed, qpi.pod_info)
        _t = time.perf_counter()
        st = fw.run_reserve_plugins_reserve(state, assumed, result.suggested_host)
        _t = self._observe_point("Reserve", _t, st.is_success())
        if not st.is_success():
            fw.run_reserve_plugins_unreserve(state, assumed, result.suggested_host)
            self.cache.forget_pod(assumed)
            assumed.node_name = ""
            raise RuntimeError(f"reserve failed: {st.message()}")
        st = fw.run_permit_plugins(state, assumed, result.suggested_host)
        self._observe_point("Permit", _t, not st.is_rejected())
        if st.is_rejected():
            fw.run_reserve_plugins_unreserve(state, assumed, result.suggested_host)
            self.cache.forget_pod(assumed)
            assumed.node_name = ""
            raise RuntimeError(f"permit rejected: {st.message()}")
        if st.code == WAIT:
            result.waiting = True  # parks in waiting_pods; binds on Allow
        return result

    # -- gang cycle (schedule_one_podgroup.go) -----------------------------

    def schedule_pod_group(self, qgpi: QueuedPodGroupInfo) -> None:
        """Pod-group scheduling (scheduleOnePodGroup :81 → podGroupCycle :428).

        With placement plugins and a topology-constrained group, the
        PLACEMENT algorithm runs (schedule_one_podgroup.go:971
        podGroupSchedulingPlacementAlgorithm): generate candidate node
        subsets, simulate the group against each under a snapshot placement
        session, gate with PlacementFeasible, score the successful candidates
        with PlacementScore plugins, and commit the best. Otherwise the
        default algorithm (:556): member-wise placement against the snapshot
        (assumed into the snapshot, not the cache, schedule_one.go:1077-1082)
        with LIFO revert on any failure (revertFns :50-75)."""
        self.attempts += 1
        members = sorted(
            qgpi.members,
            key=lambda m: (-m.pod.priority, m.timestamp))
        if not members:
            self.queue.done(qgpi.uid)
            return
        fw = self.framework_for_pod(members[0].pod)
        self.cache.update_snapshot(self.snapshot)

        group = qgpi.group
        if fw.placement_generate_plugins and getattr(group, "topology_keys", ()):
            # A topology-constrained group is scheduled ONLY through the
            # placement algorithm — falling back to unconstrained member-wise
            # placement would violate the constraint (the reference returns
            # "0/N placements are available" in that case).
            self._schedule_group_with_placements(fw, qgpi, members)
            return

        placed: List[Tuple[QueuedPodInfo, CycleState, ScheduleResult]] = []
        failure: Optional[FitError] = None
        for m in members:
            state = CycleState()
            try:
                result = self.schedule_pod(fw, state, m.pod)
            except FitError as fe:
                failure = fe
                qgpi.unschedulable_plugins |= fe.diagnosis.unschedulable_plugins
                break
            m.pod.node_name = result.suggested_host
            self.snapshot.assume_pod(m.pod)  # simulate in-snapshot only
            placed.append((m, state, result))

        if failure is not None:
            # LIFO revert: the snapshot returns to the pre-cycle view.
            for m, _, _ in reversed(placed):
                self.snapshot.forget_pod(m.pod)
                m.pod.node_name = ""
            self._fail_pod_group(fw, qgpi, members, failure.diagnosis)
            return

        # Commit (submitPodGroupAlgorithmResult :812): assume into the cache
        # and run each member's binding cycle (each member keeps ITS
        # simulation CycleState — stateful plugins wrote PreFilter/Reserve
        # data there). Every attempted member leaves the group buffer —
        # commit failures are requeued individually and must not be
        # double-tracked.
        committed = 0
        attempted_uids = set()
        for m, state, result in placed:
            attempted_uids.add(m.pod.uid)
            self.cache.assume_pod(m.pod)
            if self._commit_group_member(fw, m, state, result):
                committed += 1
        _t_store = time.perf_counter()
        group_key = (qgpi.group.namespace, qgpi.group.name)
        self.queue.clear_group_members(group_key, attempted_uids)
        self.queue.done(qgpi.uid)
        self.metrics.store_schedule_results_duration.observe(
            time.perf_counter() - _t_store)
        self.metrics.podgroup_schedule_attempts.inc(
            "scheduled" if committed else "unschedulable")

    def schedule_composite_group(self, qcgi: QueuedCompositeGroupInfo) -> None:
        """The composite tree cycle (schedule_one_podgroup.go composite
        paths + completeCompositePodGroupAlgorithmResult): every leaf
        PodGroup of the root CompositePodGroup simulates member-wise against
        the snapshot; ANY leaf failure rolls the WHOLE tree back (partial
        results are discarded, :51) and parks the root; success commits
        every member. Leaves schedule with the default member-wise
        algorithm (placement-constrained leaves inside composites are out of
        this reduced scope and fail the tree)."""
        self.attempts += 1
        self.cache.update_snapshot(self.snapshot)
        placed: List[Tuple[QueuedPodInfo, CycleState, ScheduleResult]] = []
        failure: Optional[FitError] = None
        for group, members in qcgi.groups:
            ms = sorted(members, key=lambda m: (-m.pod.priority, m.timestamp))
            if not ms:
                continue
            fw = self.framework_for_pod(ms[0].pod)
            if getattr(group, "topology_keys", ()):
                qcgi.unschedulable_plugins.add("TopologyPlacementGenerator")
                break
            for m in ms:
                state = CycleState()
                try:
                    result = self.schedule_pod(fw, state, m.pod)
                except FitError as fe:
                    failure = fe
                    qcgi.unschedulable_plugins |= fe.diagnosis.unschedulable_plugins
                    break
                m.pod.node_name = result.suggested_host
                self.snapshot.assume_pod(m.pod)
                placed.append((m, state, result))
            else:
                continue
            break
        else:
            if placed:
                # Whole tree feasible: commit every member (each keeps ITS
                # simulation CycleState, submitPodGroupAlgorithmResult).
                committed = 0
                attempted: Dict[Tuple[str, str], set] = {}
                for m, state, result in placed:
                    self.cache.assume_pod(m.pod)
                    gkey = (m.pod.namespace, m.pod.pod_group)
                    attempted.setdefault(gkey, set()).add(m.pod.uid)
                    fw = self.framework_for_pod(m.pod)
                    if self._commit_group_member(fw, m, state, result):
                        committed += 1
                for gkey, uids in attempted.items():
                    self.queue.clear_group_members(gkey, uids)
                self.queue.done(qcgi.uid)
                self.metrics.podgroup_schedule_attempts.inc(
                    "scheduled" if committed else "unschedulable")
                return
            # Empty tree (every leaf memberless): nothing was attempted, so
            # parking it unschedulable with an EMPTY plugin set would make
            # every cluster event "relevant" — a busy reactivate/re-park
            # loop until members arrive. Drop the entity instead; the member
            # buffers re-activate the tree when members show up. Member adds
            # that arrived WHILE this entity was in flight were swallowed by
            # the in-flight gate (_maybe_activate_composite), so re-check
            # activation once the slot clears.
            self.queue.done(qcgi.uid)
            self.queue._maybe_activate_composite(qcgi.cpg)
            return

        # LIFO rollback across the whole tree (revertFns :50-75 applied at
        # composite scope: parents propagate failure to children).
        for m, _st, _r in reversed(placed):
            self.snapshot.forget_pod(m.pod)
            m.pod.node_name = ""
        self.failures += 1
        qcgi.timestamp = self.now()
        self.queue.add_unschedulable_if_not_present(qcgi)
        self.queue.done(qcgi.uid)
        self.metrics.podgroup_schedule_attempts.inc("unschedulable")

    def _schedule_group_with_placements(
        self, fw: Framework, qgpi: QueuedPodGroupInfo,
        members: List[QueuedPodInfo],
    ) -> bool:
        """podGroupSchedulingPlacementAlgorithm (schedule_one_podgroup.go:971)
        + findBestPodGroupPlacement (:1173). Owns the whole cycle: commits
        the best feasible placement, or parks the group unschedulable ("0/N
        placements are available")."""
        from .framework import Placement, PlacementProgress, PodGroupAssignments

        group = qgpi.group
        pg_state = CycleState()
        parent = Placement("", [ni.name for ni in self.snapshot.node_info_list])
        placements, st = fw.run_placement_generate_plugins(
            pg_state, group, members, parent)
        if not st.is_success() or not placements:
            self._fail_pod_group(fw, qgpi, members, None)
            return False
        self.metrics.generated_placements.observe(len(placements))
        self.metrics.generated_placements_total.inc(value=len(placements))

        start_save = self.next_start_node_index
        candidates = self._evaluate_placements(
            fw, pg_state, group, members, placements, start_save)
        self.next_start_node_index = start_save

        if not candidates:
            # "0/N placements are available" (schedule_one_podgroup.go:1038)
            self._fail_pod_group(fw, qgpi, members, None)
            return False

        totals = fw.run_placement_score_plugins(
            pg_state, group, [pga for _, _, pga in candidates])
        best_i = max(range(len(totals)), key=lambda i: (totals[i], -i))
        best_placement, assignment, _pga = candidates[best_i]

        # Commit the winning placement's assignments: assume into the cache
        # and run each member's binding cycle; members the placement could
        # not fit are requeued individually (submitPodGroupAlgorithmResult).
        # Each member keeps the CycleState from the WINNING simulation —
        # stateful Reserve/PreBind plugins (VolumeBinding, DynamicResources)
        # wrote their PreFilter/Filter data there
        # (schedule_one_podgroup.go algorithmResult.GetCycleState →
        # submitPodGroupAlgorithmResult).
        committed = 0
        attempted_uids = set()
        for m in members:
            attempted_uids.add(m.pod.uid)
            entry = assignment.get(m.pod.uid)
            if entry is None:
                self.handle_scheduling_failure(
                    fw, m, Status.unschedulable(
                        f"did not fit placement {best_placement.name!r}"), None)
                continue
            node, m_state = entry
            m.pod.node_name = node
            self.cache.assume_pod(m.pod, m.pod_info)
            if self._commit_group_member(fw, m, m_state,
                                         ScheduleResult(suggested_host=node)):
                committed += 1
        group_key = (group.namespace, group.name)
        self.queue.clear_group_members(group_key, attempted_uids)
        self.queue.done(qgpi.uid)
        self.metrics.podgroup_schedule_attempts.inc(
            "scheduled" if committed else "unschedulable")
        return True

    def _evaluate_placements(self, fw: Framework, pg_state: CycleState,
                             group, members: List[QueuedPodInfo],
                             placements, start_index: int) -> List[tuple]:
        """Evaluate every candidate placement; returns the feasible
        candidates as (placement, assignment, PodGroupAssignments) tuples.
        The host loop simulates placements one by one; TPUScheduler
        overrides this with one stacked kernel evaluation of ALL candidates
        (ops/kernel.py schedule_placements)."""
        from .framework import PodGroupAssignments

        _t_pe = time.perf_counter()
        self.metrics.placement_evaluations.inc("host", value=len(placements))
        candidates: List[tuple] = []
        for placement in placements:
            assignment = self._evaluate_placement(
                fw, pg_state, group, members, placement, start_index)
            if assignment is not None:
                pga = PodGroupAssignments(
                    placement,
                    proposed=[(m.pod, assignment[m.pod.uid][0]) for m in members
                              if m.pod.uid in assignment],
                    nodes=[self.snapshot.get(n) for n in placement.node_names])
                candidates.append((placement, assignment, pga))
        self.metrics.placement_evaluation_duration.observe(
            time.perf_counter() - _t_pe)
        return candidates

    def _evaluate_placement(self, fw: Framework, pg_state: CycleState,
                            group, members: List[QueuedPodInfo], placement,
                            start_index: int) -> Optional[Dict[str, tuple]]:
        """Simulate the group against one candidate placement under a
        snapshot placement session. Returns {pod uid: (node, CycleState)}
        when the PlacementFeasible gate passes, else None — the per-member
        CycleState carries stateful-plugin simulation data into the commit
        (schedule_one_podgroup.go initPodSchedulingContext). The snapshot is
        ALWAYS restored (placement and pod assumptions), even on plugin
        exceptions.

        Simulation spec (shared with the device evaluator,
        ops/kernel.py schedule_placements): each simulation evaluates its
        WHOLE candidate — no adaptive truncation — from rotation origin 0.
        Placements are domain-sized (a zone/rack), so full evaluation is the
        point, and a fixed origin makes host and device placement
        evaluation bit-identical."""
        from .framework import PlacementProgress

        self.snapshot.assume_placement(placement.node_names)
        self.next_start_node_index = 0
        pct_save = self.percentage_of_nodes_to_score
        self.percentage_of_nodes_to_score = 100  # evaluate the full candidate
        placed: List[Tuple[QueuedPodInfo, CycleState]] = []
        failed = 0
        try:
            for m in members:
                m_state = CycleState()
                try:
                    result = self.schedule_pod(fw, m_state, m.pod)
                except FitError:
                    failed += 1
                    continue
                m.pod.node_name = result.suggested_host
                self.snapshot.assume_pod(m.pod)
                placed.append((m, m_state))
            progress = PlacementProgress(len(placed), failed, len(members))
            feasible = placed and fw.run_placement_feasible_plugins(
                pg_state, group, progress).is_success()
            assignment = {m.pod.uid: (m.pod.node_name, st) for m, st in placed}
        finally:
            # LIFO revert: the snapshot returns to the placement view, then
            # the full view (snapshot.go revertFns + ForgetPlacement).
            for m, _st in reversed(placed):
                self.snapshot.forget_pod(m.pod)
                m.pod.node_name = ""
            self.snapshot.forget_placement()
            self.percentage_of_nodes_to_score = pct_save
        return assignment if feasible else None

    def group_feasible(self, group, members: List[QueuedPodInfo]) -> bool:
        """Would this group schedule right now, under the same algorithm a
        real cycle would use? The feasibility probe behind pod-group
        preemption (podgrouppreemption.go podGroupSchedulingFunc): a
        topology-constrained group must fit some CANDIDATE PLACEMENT, not
        just the unconstrained cluster."""
        from .framework import Placement

        members = [m for m in members]
        if not members:
            return False
        fw = self.framework_for_pod(members[0].pod)
        start_save = self.next_start_node_index
        pg_state = CycleState()
        if fw.placement_generate_plugins and getattr(group, "topology_keys", ()):
            parent = Placement("", [ni.name for ni in self.snapshot.node_info_list])
            placements, st = fw.run_placement_generate_plugins(
                pg_state, group, members, parent)
            if not st.is_success():
                return False
            try:
                return any(
                    self._evaluate_placement(fw, pg_state, group, members,
                                             placement, start_save) is not None
                    for placement in placements)
            finally:
                self.next_start_node_index = start_save
        # Unconstrained default algorithm: all members must fit.
        placed: List[QueuedPodInfo] = []
        ok = True
        try:
            for m in members:
                try:
                    result = self.schedule_pod(fw, CycleState(), m.pod)
                except FitError:
                    ok = False
                    break
                m.pod.node_name = result.suggested_host
                self.snapshot.assume_pod(m.pod)
                placed.append(m)
        finally:
            for m in reversed(placed):
                self.snapshot.forget_pod(m.pod)
                m.pod.node_name = ""
            self.next_start_node_index = start_save
        return ok

    def _commit_group_member(self, fw: Framework, m: QueuedPodInfo,
                             state: CycleState, result: ScheduleResult) -> bool:
        """Reserve → permit → binding cycle for one group member whose pod is
        already assumed into the cache with node_name set. Returns True when
        the member is committed (bound or parked at Permit WAIT)."""
        node = result.suggested_host
        st = fw.run_reserve_plugins_reserve(state, m.pod, node)
        if st.is_success():
            st = fw.run_permit_plugins(state, m.pod, node)
        if st.code == WAIT:
            self.park_waiting_pod(fw, state, m, result)
            return True
        if not st.is_success():
            fw.run_reserve_plugins_unreserve(state, m.pod, node)
            self.cache.forget_pod(m.pod)
            m.pod.node_name = ""
            self.handle_scheduling_failure(fw, m, st, None)
            return False
        return self.run_binding_cycle(fw, state, m, result)

    def _fail_pod_group(self, fw: Framework, qgpi: QueuedPodGroupInfo,
                        members: List[QueuedPodInfo], diagnosis) -> None:
        """Group-unschedulable tail shared by the placement and default
        algorithms: PodGroupPostFilter hook (framework.go:1212 — a chance to
        make room via pod-group preemption), then park the group."""
        if fw.pod_group_post_filter_plugins:
            _, post_st = fw.run_pod_group_post_filter_plugins(
                CycleState(), qgpi.group, members, diagnosis)
            if post_st.is_success():
                qgpi.timestamp = self.now()
                self.queue.add_unschedulable_if_not_present(qgpi)
                self.queue.done(qgpi.uid)
                self.metrics.podgroup_schedule_attempts.inc("post_filter")
                return
        self.failures += 1
        qgpi.timestamp = self.now()
        self.queue.add_unschedulable_if_not_present(qgpi)
        self.queue.done(qgpi.uid)
        self.metrics.podgroup_schedule_attempts.inc("unschedulable")

    # -- schedulePod (schedule_one.go:572) ---------------------------------

    def schedule_pod(self, fw: Framework, state: CycleState, pod: Pod) -> ScheduleResult:
        if self.snapshot.num_nodes() == 0:
            raise FitError(pod, 0, Diagnosis(pre_filter_msg="no nodes available"))
        feasible, diagnosis = self.find_nodes_that_fit_pod(fw, state, pod)
        if not feasible:
            raise FitError(pod, self.snapshot.num_nodes(), diagnosis)
        if len(feasible) == 1:
            return ScheduleResult(
                suggested_host=feasible[0].name,
                evaluated_nodes=1 + len(diagnosis.node_to_status),
                feasible_nodes=1,
            )
        priority_list = self.prioritize_nodes(fw, state, pod, feasible)
        host = self.select_host(priority_list)
        return ScheduleResult(
            suggested_host=host,
            evaluated_nodes=len(feasible) + len(diagnosis.node_to_status),
            feasible_nodes=len(feasible),
        )

    def _observe_point(self, point: str, t0: float, ok: bool = True) -> float:
        """framework_extension_point_duration_seconds observation; returns a
        fresh perf_counter for chaining (one call per point per cycle —
        Histogram.observe is O(1))."""
        t1 = time.perf_counter()
        self.metrics.framework_extension_point_duration.observe(
            t1 - t0, point, "Success" if ok else "Error", "")
        return t1

    def find_nodes_that_fit_pod(
        self, fw: Framework, state: CycleState, pod: Pod
    ) -> Tuple[List[NodeInfo], Diagnosis]:
        diagnosis = Diagnosis()
        all_nodes = self.snapshot.node_info_list
        _t = time.perf_counter()
        pre_res, st = fw.run_pre_filter_plugins(state, pod, all_nodes)
        _t = self._observe_point("PreFilter", _t, st.is_success())
        if not st.is_success():
            if st.is_rejected():
                diagnosis.pre_filter_msg = st.message()
                diagnosis.unschedulable_plugins.add(st.plugin)
                return [], diagnosis
            raise RuntimeError(f"prefilter failed: {st.message()}")

        # Nominated-node fast path (schedule_one.go:722): if a previous
        # preemption nominated a node, evaluate it first.
        if pod.nominated_node_name:
            ni = self.snapshot.get(pod.nominated_node_name)
            if ni is not None:
                st = fw.run_filter_plugins_with_nominated_pods(
                    state, pod, ni, self.queue.nominator
                )
                if st.is_success():
                    return [ni], diagnosis

        nodes = all_nodes
        if pre_res is not None and not pre_res.all_nodes():
            self.metrics.prefilter_narrowed_pods.inc("host")
            if len(pre_res.node_names) == 1:
                # The daemonset shape narrows 15k nodes to ONE per pod: a map
                # lookup, not an O(all nodes) scan per pod.
                ni = self.snapshot.get(next(iter(pre_res.node_names)))
                nodes = [ni] if ni is not None else []
            else:
                # Preserve snapshot order (rotation parity over the narrowed
                # list, schedule_one.go:630).
                nodes = [ni for ni in all_nodes if ni.name in pre_res.node_names]
        feasible = self.find_nodes_that_pass_filters(fw, state, pod, diagnosis, nodes)
        self._observe_point("Filter", _t)
        # PluginEvaluationTotal at cycle granularity (one evaluation of each
        # enabled plugin per scheduling cycle; the reference's per-node inc
        # would cost a dict write per node per plugin on the hot loop).
        pet = self.metrics.plugin_evaluation_total
        for p in fw.pre_filter_plugins:
            pet.inc(p.name, "PreFilter", fw.profile_name)
        for p in fw.filter_plugins:
            if p.name not in state.skip_filter_plugins:
                pet.inc(p.name, "Filter", fw.profile_name)
        if feasible and self.extenders:
            from .extender import run_extender_filters
            feasible, err = run_extender_filters(self.extenders, pod, feasible, diagnosis)
            if err is not None:
                raise RuntimeError(f"extender filter failed: {err.message()}")
        return feasible, diagnosis

    def num_feasible_nodes_to_find(self, num_all_nodes: int) -> int:
        return num_feasible_nodes_to_find(num_all_nodes, self.percentage_of_nodes_to_score)

    def find_nodes_that_pass_filters(
        self,
        fw: Framework,
        state: CycleState,
        pod: Pod,
        diagnosis: Diagnosis,
        nodes: Sequence[NodeInfo],
    ) -> List[NodeInfo]:
        num_nodes = len(nodes)
        to_find = self.num_feasible_nodes_to_find(num_nodes)
        feasible: List[NodeInfo] = []
        start = self.next_start_node_index % max(1, num_nodes)
        evaluated = 0
        for i in range(num_nodes):
            ni = nodes[(start + i) % num_nodes]
            evaluated += 1
            st = fw.run_filter_plugins_with_nominated_pods(state, pod, ni, self.queue.nominator)
            if st.is_success():
                feasible.append(ni)
                if len(feasible) >= to_find:
                    break
            else:
                diagnosis.node_to_status[ni.name] = st
                if st.plugin:
                    diagnosis.unschedulable_plugins.add(st.plugin)
        self.next_start_node_index = (start + evaluated) % max(1, num_nodes)
        return feasible

    def prioritize_nodes(
        self, fw: Framework, state: CycleState, pod: Pod, nodes: Sequence[NodeInfo]
    ) -> List[NodeScore]:
        _t = time.perf_counter()
        st = fw.run_pre_score_plugins(state, pod, nodes)
        _t = self._observe_point("PreScore", _t, st.is_success())
        if not st.is_success():
            raise RuntimeError(f"prescore failed: {st.message()}")
        plugin_scores = fw.run_score_plugins(state, pod, nodes)
        self._observe_point("Score", _t)
        for p, _w in fw.score_plugins:
            self.metrics.plugin_evaluation_total.inc(
                p.name, "Score", fw.profile_name)
        total = [NodeScore(ni.name, 0) for ni in nodes]
        for scores in plugin_scores.values():
            for i, ns in enumerate(scores):
                total[i].score += ns.score
        if self.extenders:
            from .extender import run_extender_prioritize
            run_extender_prioritize(self.extenders, pod, nodes, total)
        return total

    def select_host(self, node_scores: List[NodeScore]) -> str:
        """Reservoir-sample among max-score nodes (schedule_one.go selectHost),
        seeded RNG so runs are reproducible; first-max when
        deterministic_ties is set (device-parity mode)."""
        best = node_scores[0]
        cnt = 1
        for ns in node_scores[1:]:
            if ns.score > best.score:
                best = ns
                cnt = 1
            elif ns.score == best.score and not self.deterministic_ties:
                cnt += 1
                if self.rng.random() < 1.0 / cnt:
                    best = ns
        return best.name

    # -- binding cycle (schedule_one.go:141 runBindingCycle) ---------------

    def run_binding_cycle(
        self, fw: Framework, state: CycleState, qpi: QueuedPodInfo, result: ScheduleResult
    ) -> bool:
        """Returns True iff the pod was bound (False: unwound + requeued)."""
        pod = qpi.pod
        node_name = result.suggested_host
        _t = time.perf_counter()
        if fw.pre_bind_plugins:
            # PreBindPreFlight (runtime/framework.go:1875): plugins that
            # declare no work for this pod are skipped; all-skip bypasses
            # the PreBind phase.
            st = fw.run_pre_bind_pre_flight(state, pod, node_name)
            if not st.is_success() and not st.is_skip():
                self._unwind_binding(fw, state, qpi, node_name, st)
                return False
            if not st.is_skip():
                st = fw.run_pre_bind_plugins(state, pod, node_name)
                _t = self._observe_point("PreBind", _t, st.is_success())
                if not st.is_success():
                    self._unwind_binding(fw, state, qpi, node_name, st)
                    return False
        # Extender bind delegation (schedule_one.go:1100 bind: an interested
        # extender with a bind verb binds instead of the bind plugins).
        bind_ext = next(
            (e for e in self.extenders
             if e.supports_bind() and e.is_interested(pod)), None) \
            if self.extenders else None
        if bind_ext is not None:
            err = bind_ext.bind(pod, node_name)
            st = Status() if err is None else Status.error(err)
        else:
            st = fw.run_bind_plugins(state, pod, node_name)
        self._observe_point("Bind", _t, st.is_success())
        if not st.is_success():
            self._unwind_binding(fw, state, qpi, node_name, st)
            return False
        if st.queued:
            # On its way through the thread-mode dispatcher: the pod stays
            # assumed, and _settle_bind finishes it at the acknowledgement.
            self._unsettled[pod.uid] = QueuedBind(fw, state, qpi, node_name)
            return True
        self._bound(fw, state, qpi, node_name)
        return True

    def _bound(self, fw: Framework, state: CycleState, qpi: QueuedPodInfo,
               node_name: str, ack_age: float = 0.0) -> None:
        """The apiserver holds the bind: finish it in the cache, count it,
        close the pod's latency series ``ack_age`` seconds ago (0 for a
        synchronous bind, which returned just now)."""
        pod = qpi.pod
        self.cache.finish_binding(pod)
        self.queue.nominator.delete_nominated_pod(pod)
        self.scheduled += 1
        self.observe_bound(qpi, node_name, ack_age)
        self.recorder.eventf(
            pod.namespace + "/" + pod.name, "Normal", "Scheduled",
            ("Successfully assigned %s/%s to %s",
             (pod.namespace, pod.name, node_name)))
        fw.run_post_bind_plugins(state, pod, node_name)

    def _settle_bind(self, pod: Pod, acked_at: float) -> Optional[QueuedBind]:
        """A queued bind was acknowledged at ``acked_at`` (perf_counter, read
        by the dispatcher's worker); runs on the loop's thread, from the
        done inbox. The latency series end at the acknowledgement, not at
        this drain. Returns what was settled."""
        rec = self._unsettled.pop(pod.uid, None)
        if rec is None:
            return None
        ack_age = max(0.0, time.perf_counter() - acked_at)
        self._bound(rec.fw, rec.state, rec.qpi, rec.node_name, ack_age)
        if rec.attempt_t0 is not None:
            self._observe_attempt(rec.fw, rec.qpi, True,
                                  acked_at - rec.attempt_t0, ack_age)
        return rec

    def _note_async_bind_lost(self, pod: Pod) -> None:
        """Seam: a queued bind failed, so whatever was counted on the
        strength of the enqueue is taken back (models/tpu_scheduler.py: the
        score-hint hit)."""

    def _unwind_binding(self, fw, state, qpi: QueuedPodInfo, node_name: str, st: Status) -> None:
        """handleBindingCycleError (schedule_one.go:507): unreserve, forget,
        flush an AssignedPodDelete-equivalent event, requeue. A tagged bind
        CONFLICT (409: another scheduler won the shared state) skips the
        unschedulable pool and goes straight to the backoffQ — by the time
        the backoff elapses the watch feed has delivered the winning commit
        and the retry either skips the pod (already placed) or re-plans
        against the updated node state."""
        pod = qpi.pod
        self.state_unwinds += 1
        fw.run_reserve_plugins_unreserve(state, pod, node_name)
        self.cache.forget_pod(pod)
        pod.node_name = ""
        self.queue.move_all_to_active_or_backoff(
            EVENT_ASSIGNED_POD_DELETE, pod, None)
        if getattr(st, "conflict", False):
            self._note_bind_conflict(st.message(), pod, node_name)
            self.conflict_requeues += 1
            self.queue.requeue_conflict(qpi)
            return
        if getattr(st, "shed", False):
            # Flow-control shed (429): the write plane rejected before any
            # state changed. Same routing as a conflict — straight to the
            # backoffQ with the ORIGINAL enqueued_at preserved (qpi is the
            # popped info object), so scheduler_e2e_scheduling_duration
            # spans the shed-and-retried pod too. 100%-sampled span: shed
            # pods are exactly the ones worth tracing under overload.
            self._note_bind_shed(pod, node_name)
            self.queue.requeue_conflict(qpi)
            return
        self.handle_scheduling_failure(fw, qpi, st, None)

    def _note_bind_shed(self, pod: Pod, node: str = "") -> None:
        """One shed bind's accounting: counter + a FORCED bind.shed span
        (overload forensics — the trace analyzer's overload timeline needs
        every shed, not a sample)."""
        self.shed_requeues += 1
        self.tracer.record(
            "bind.shed", self.tracer.context_for(pod.uid, force=True),
            node=node, pod=f"{pod.namespace}/{pod.name}")

    def _note_bind_conflict(self, message: str, pod: Optional[Pod] = None,
                            node: str = "") -> None:
        reason = ("capacity" if "OutOfCapacity" in message
                  else "already_bound" if "AlreadyBound" in message
                  else "conflict")
        self.bind_conflicts += 1
        self.metrics.bind_conflict_total.inc(reason)
        if pod is not None:
            # Conflict paths sample at 100% (forced context): the trace
            # analyzer's cross-shard conflict timeline is built from these.
            self.tracer.record(
                "bind.conflict", self.tracer.context_for(pod.uid, force=True),
                reason=reason, node=node,
                pod=f"{pod.namespace}/{pod.name}")

    # -- span helpers (core/spans.py; docs/OBSERVABILITY.md) ----------------

    def record_queue_wait(self, qpi, ctx) -> None:
        """Queue wait, recorded at pop time (no hot add-path cost): EVERY
        pod feeds scheduler_pod_stage_duration_seconds{stage="queue.wait"};
        a sampled pod also gets its retroactive queue.admission event +
        queue.wait span. Guarded against double recording when a
        device-popped pod falls back to the host cycle."""
        if getattr(qpi, "_qwait_recorded", False):
            return
        qpi._qwait_recorded = True
        wait = queue_wait(qpi, self.now())
        self.metrics.pod_stage_duration.observe(wait, "queue.wait")
        if self.tracer.wants(ctx):
            self.trace_queue_wait(qpi, ctx, wait, time.time())

    def trace_queue_wait(self, qpi, ctx, wait: float, wall_pop: float) -> None:
        """A sampled pod's two rows of its pop: the retroactive
        queue.admission event and the queue.wait span that ends at
        ``wall_pop``."""
        tr = self.tracer
        tr.record("queue.admission", ctx, start=wall_pop - wait)
        tr.record("queue.wait", ctx, wait, start=wall_pop - wait,
                  attempts=qpi.attempts)

    def observe_bound(self, qpi, node_name: str, ack_age: float = 0.0) -> None:
        """Every successful bind feeds scheduler_e2e_scheduling_duration_
        seconds (queue admission -> the apiserver's acknowledgement, ALL
        pods — the histogram is latency truth, sampling only thins the span
        ring) and closes the sampled pod's trace with its pod.e2e span. A
        queued bind is observed when the loop drains it, ``ack_age`` seconds
        after the acknowledgement, and the series end there."""
        start = getattr(qpi, "enqueued_at", None)
        if start is None:
            return
        e2e = max(0.0, self.now() - ack_age - start)
        self.metrics.e2e_scheduling_duration.observe(e2e)
        tr = self.tracer
        ctx = tr.context_for(qpi.pod.uid)
        if tr.wants(ctx):
            tr.record("pod.e2e", ctx, e2e, node=node_name,
                      attempts=qpi.attempts,
                      start=time.time() - ack_age - e2e)

    # -- failure (schedule_one.go:1152 handleSchedulingFailure) ------------

    # -- waiting pods (Permit WAIT) ----------------------------------------

    def allow_waiting_pod(self, uid: str) -> bool:
        """A Permit plugin allowed a parked pod: run its binding cycle
        (waitingPod.Allow → WaitOnPermit unblocks)."""
        entry = self.waiting_pods.pop(uid, None)
        if entry is None:
            return False
        fw, state, qpi, result, deadline = entry
        self.metrics.permit_wait_duration.observe(
            self.now() - (deadline - self.permit_wait_timeout), "allowed")
        self.run_binding_cycle(fw, state, qpi, result)
        return True

    def reject_waiting_pod(self, uid: str, reason: str = "rejected") -> bool:
        entry = self.waiting_pods.pop(uid, None)
        if entry is None:
            return False
        fw, state, qpi, result, deadline = entry
        self.metrics.permit_wait_duration.observe(
            self.now() - (deadline - self.permit_wait_timeout), "rejected")
        self.state_unwinds += 1
        fw.run_reserve_plugins_unreserve(state, qpi.pod, result.suggested_host)
        self.cache.forget_pod(qpi.pod)
        qpi.pod.node_name = ""
        self.handle_scheduling_failure(fw, qpi, Status.unschedulable(reason), None)
        return True

    def park_waiting_pod(self, fw, state, qpi, result) -> None:
        """Park a WAITing pod and arm the expiry timer (WaitOnPermit)."""
        deadline = self.now() + self.permit_wait_timeout
        self.waiting_pods[qpi.pod.uid] = (fw, state, qpi, result, deadline)
        if deadline < self._next_wait_deadline:
            self._next_wait_deadline = deadline

    def _rearm_wait_deadline(self) -> None:
        self._next_wait_deadline = min(
            (e[4] for e in self.waiting_pods.values()), default=float("inf"))

    def flush_expired_waiters(self) -> int:
        now = self.now()
        expired = [uid for uid, e in self.waiting_pods.items() if e[4] <= now]
        for uid in expired:
            self.reject_waiting_pod(uid, "permit wait timed out")
        self._rearm_wait_deadline()
        return len(expired)

    def _queued_entity_counts(self) -> Dict[tuple, float]:
        """queued_entities gauge callback: queued entities by kind."""
        from .queue import QueuedCompositeGroupInfo, QueuedPodGroupInfo
        counts = {"pod": 0, "podgroup": 0, "composite": 0}
        try:
            return self._queued_entity_counts_unsafe(counts)
        except RuntimeError:
            # /metrics is scraped from the HTTP thread while the scheduling
            # loop mutates the queues; a torn iteration yields a stale scrape
            # rather than a 500.
            return {(k,): float(v) for k, v in counts.items()}

    def _queued_entity_counts_unsafe(self, counts) -> Dict[tuple, float]:
        from .queue import QueuedCompositeGroupInfo, QueuedPodGroupInfo
        for q in (self.queue.active_q, self.queue.backoff_q):
            for ent in q.items():
                if isinstance(ent, QueuedCompositeGroupInfo):
                    counts["composite"] += 1
                elif isinstance(ent, QueuedPodGroupInfo):
                    counts["podgroup"] += 1
                else:
                    counts["pod"] += 1
        for ent in self.queue.unschedulable.values():
            if isinstance(ent, QueuedCompositeGroupInfo):
                counts["composite"] += 1
            elif isinstance(ent, QueuedPodGroupInfo):
                counts["podgroup"] += 1
            else:
                counts["pod"] += 1
        return {(k,): float(v) for k, v in counts.items()}

    def _unschedulable_by_plugin(self) -> Dict[tuple, float]:
        """unschedulable_pods gauge callback: parked pods by rejecting
        plugin (metrics.go UnschedulablePods)."""
        counts: Dict[str, int] = {}
        try:
            for ent in list(self.queue.unschedulable.values()):
                plugins = ent.unschedulable_plugins or {""}
                for p in plugins:
                    counts[p] = counts.get(p, 0) + 1
        except RuntimeError:
            pass  # concurrent scrape during queue mutation: stale is fine
        return {(k,): float(v) for k, v in counts.items()}

    def update_pending_metrics(self) -> None:
        """Refresh the pending_pods gauges (metrics.go pending_pods)."""
        active, backoff, unsched = self.queue.pending_counts()
        gated = sum(1 for q in self.queue.unschedulable.values() if q.gated)
        self.metrics.pending_pods.set(active, "active")
        self.metrics.pending_pods.set(backoff, "backoff")
        self.metrics.pending_pods.set(unsched - gated, "unschedulable")
        self.metrics.pending_pods.set(gated, "gated")

    def _publish_bind_requests(self) -> None:
        """The binding requests' two counters, from counts kept where the
        requests are made: the dispatcher's for queued binds and for the
        bulk request of a batch tail, and for the loop's single-pod
        requests its bind.post stage, entered once for each synchronous
        request (plugins/basic.py DefaultBinder): those less the bulk ones,
        which in inline mode are all the loop's own."""
        dispatcher = self.api_dispatcher
        bulk = dispatcher.bind_requests["bulk"]
        inline = self.stages.counts["bind.post"]
        if dispatcher.mode == "inline":
            inline -= bulk
        singles = dispatcher.bind_requests["single"] + inline
        self.metrics.bind_requests.set_total(float(singles), "single")
        self.metrics.bind_requests.set_total(float(bulk), "bulk")
        self.metrics.bind_request_pods.set_total(
            float(dispatcher.bind_request_pods + inline))

    def expose_metrics(self) -> str:
        """/metrics (app/server.go:376)."""
        self.update_pending_metrics()
        self.stages.publish()
        self._publish_bind_requests()
        out = self.metrics.expose()
        # Step-accounting counters (plan/device/host split, device-vs-host
        # path mix, conflict/unwind tallies): in-process harnesses read
        # these attributes directly, but a shard-plane scheduler is only
        # reachable over HTTP — the split must ride /metrics for a sharded
        # run to be diagnosable from outside (docs/SHARDING.md
        # observability; a sharded perf row's detail).
        extra = []
        for name, val in (
                ("scheduler_plan_build_seconds_total",
                 getattr(self, "plan_build_s", 0.0)),
                ("scheduler_device_wait_seconds_total",
                 getattr(self, "device_wait_s", 0.0)),
                ("scheduler_host_commit_seconds_total",
                 getattr(self, "host_commit_s", 0.0)),
                ("scheduler_host_path_pods_total",
                 getattr(self, "host_path_pods", 0)),
                ("scheduler_device_scheduled_pods_total",
                 getattr(self, "device_scheduled", 0)),
                ("scheduler_state_unwinds_total", self.state_unwinds),
                ("scheduler_conflict_requeues_total", self.conflict_requeues),
                ("scheduler_eviction_requeues_total", self.eviction_requeues),
                ("scheduler_attempts_total", self.attempts)):
            extra.append(f"# TYPE {name} counter")
            extra.append(f"{name} {float(val)}")
        extra.extend(self.collector.expose())
        return out + "\n".join(extra) + "\n"

    def handle_scheduling_failure(
        self, fw: Framework, qpi: QueuedPodInfo, status: Status, diagnosis: Optional[Diagnosis]
    ) -> None:
        self.failures += 1
        if diagnosis is not None:
            qpi.unschedulable_plugins |= diagnosis.unschedulable_plugins
            qpi.pending_plugins |= diagnosis.pending_plugins
        if status.code == UNSCHEDULABLE_AND_UNRESOLVABLE and not qpi.unschedulable_plugins:
            qpi.unschedulable_plugins.add(status.plugin or "unknown")
        pod = qpi.pod
        self.recorder.eventf(
            f"{pod.namespace}/{pod.name}", "Warning", "FailedScheduling",
            status.message())
        self.queue.add_unschedulable_if_not_present(qpi)
