"""Scheduler metrics: Prometheus-style registry + the reference's series.

Re-expresses pkg/scheduler/metrics/metrics.go (names at :265-615) over a
dependency-free metrics core (component-base/metrics analogue). Series are
registered on a module-level Registry; `expose()` renders the Prometheus text
format for a /metrics endpoint.
"""

from __future__ import annotations

import time
from bisect import bisect_left
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

# Histogram buckets (metrics.go uses exponential buckets starting 0.001).
DURATION_BUCKETS = (0.001, 0.002, 0.004, 0.008, 0.016, 0.032, 0.064, 0.128,
                    0.256, 0.512, 1.024, 2.048, 4.096, 8.192, 16.384)


class Metric:
    def __init__(self, name: str, help_text: str, label_names: Tuple[str, ...] = ()):
        self.name = name
        self.help = help_text
        self.label_names = label_names


class Counter(Metric):
    def __init__(self, name, help_text, label_names=()):
        super().__init__(name, help_text, tuple(label_names))
        self._values: Dict[Tuple[str, ...], float] = {}

    def inc(self, *labels: str, value: float = 1.0) -> None:
        key = tuple(labels)
        self._values[key] = self._values.get(key, 0.0) + value

    def set_total(self, total: float, *labels: str) -> None:
        """Publish a total that is accumulated elsewhere (the loop's stage
        table): written at scrape time, so the hot path pays nothing."""
        self._values[tuple(labels)] = total

    def value(self, *labels: str) -> float:
        return self._values.get(tuple(labels), 0.0)

    def total(self) -> float:
        """Every label set summed."""
        return sum(self._values.copy().values())

    def expose(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} counter"]
        # Snapshot-copy before iterating: /metrics renders on an HTTP
        # thread while the scheduling loop mutates the series dicts —
        # sorted() iterates and would raise RuntimeError on a concurrent
        # resize. dict.copy() is a single C-level op under the GIL.
        for key, v in sorted(self._values.copy().items()):
            out.append(f"{self.name}{_fmt_labels(self.label_names, key)} {v}")
        return out


class Gauge(Metric):
    def __init__(self, name, help_text, label_names=(), fn: Optional[Callable] = None):
        super().__init__(name, help_text, tuple(label_names))
        self._values: Dict[Tuple[str, ...], float] = {}
        self._fn = fn  # callback gauge

    def set(self, value: float, *labels: str) -> None:
        self._values[tuple(labels)] = value

    def value(self, *labels: str) -> float:
        return self._values.get(tuple(labels), 0.0)

    def expose(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} gauge"]
        # Callback gauges return a fresh dict; stored values snapshot-copy
        # (concurrent scrape vs scheduling-loop set(), as in Counter).
        values = self._fn() if self._fn is not None else self._values.copy()
        for key, v in sorted(values.items()):
            out.append(f"{self.name}{_fmt_labels(self.label_names, key)} {v}")
        return out


class Histogram(Metric):
    """Counts are stored PER-BUCKET (non-cumulative) so observe() is O(1)
    via bisect — it runs several times per pod on a >10k pods/s path — and
    converted to Prometheus cumulative form at expose/percentile time."""

    def __init__(self, name, help_text, label_names=(), buckets=DURATION_BUCKETS):
        super().__init__(name, help_text, tuple(label_names))
        self.buckets = tuple(buckets)
        self._counts: Dict[Tuple[str, ...], List[int]] = {}
        self._sums: Dict[Tuple[str, ...], float] = {}
        self._totals: Dict[Tuple[str, ...], int] = {}

    def observe(self, value: float, *labels: str) -> None:
        key = labels
        counts = self._counts.get(key)
        if counts is None:
            # +1 slot: the +Inf bucket
            counts = self._counts.setdefault(key, [0] * (len(self.buckets) + 1))
        counts[bisect_left(self.buckets, value)] += 1
        self._sums[key] = self._sums.get(key, 0.0) + value
        self._totals[key] = self._totals.get(key, 0) + 1

    def observe_many(self, values: Sequence[float], *labels: str) -> None:
        """Every value of ``values`` observed: the buckets, the sum and the
        total of that many ``observe`` calls, with the series looked up
        once (a retired batch's pods, models/tpu_scheduler.py)."""
        if not values:
            return
        key = labels
        counts = self._counts.get(key)
        if counts is None:
            counts = self._counts.setdefault(key, [0] * (len(self.buckets) + 1))
        buckets = self.buckets
        for value in values:
            counts[bisect_left(buckets, value)] += 1
        self._sums[key] = self._sums.get(key, 0.0) + sum(values)
        self._totals[key] = self._totals.get(key, 0) + len(values)

    def _cumulative(self, key, counts: Optional[Dict] = None) -> List[int]:
        out = []
        c = 0
        # list() copy: observe() increments slots in place on the
        # scheduling loop while a scrape renders — per-slot reads are
        # GIL-atomic, the copy just pins one consistent-length view.
        for v in list((counts if counts is not None
                       else self._counts).get(key, ())):
            c += v
            out.append(c)
        return out

    def count(self, *labels: str) -> int:
        return self._totals.get(tuple(labels), 0)

    def sum(self, *labels: str) -> float:
        return self._sums.get(tuple(labels), 0.0)

    def percentile(self, q: float, *labels: str) -> float:
        """Bucket-interpolated percentile (perf collector support); mass in
        the +Inf bucket reports the top finite bound."""
        key = tuple(labels)
        total = self._totals.get(key, 0)
        if total == 0:
            return 0.0
        target = q * total
        cum_prev = 0
        cums = self._cumulative(key)
        for i, b in enumerate(self.buckets):
            cum = cums[i]
            if cum >= target:
                lo = self.buckets[i - 1] if i else 0.0
                span = cum - cum_prev
                frac = (target - cum_prev) / span if span else 1.0
                return lo + (b - lo) * frac
            cum_prev = cum
        return self.buckets[-1]

    def expose(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} histogram"]
        # Snapshot-copy all three series dicts before iterating (scrape
        # thread vs scheduling loop; see Counter.expose). A key present in
        # totals but racing into counts/sums reads back zero this scrape.
        totals = self._totals.copy()
        sums = self._sums.copy()
        counts = self._counts.copy()
        for key in sorted(totals):
            cums = self._cumulative(key, counts) or [0] * (len(self.buckets) + 1)
            for i, b in enumerate(self.buckets):
                labels = _fmt_labels(self.label_names + ("le",), key + (str(b),))
                out.append(f"{self.name}_bucket{labels} {cums[i]}")
            inf = _fmt_labels(self.label_names + ("le",), key + ("+Inf",))
            out.append(f"{self.name}_bucket{inf} {cums[-1]}")
            out.append(f"{self.name}_sum{_fmt_labels(self.label_names, key)} {sums.get(key, 0.0)}")
            out.append(f"{self.name}_count{_fmt_labels(self.label_names, key)} {totals[key]}")
        return out


def _fmt_labels(names: Tuple[str, ...], values: Tuple[str, ...]) -> str:
    if not names:
        return ""
    pairs = ",".join(f'{n}="{v}"' for n, v in zip(names, values))
    return "{" + pairs + "}"


class Registry:
    def __init__(self):
        self._metrics: List[Metric] = []

    def register(self, m: Metric) -> Metric:
        self._metrics.append(m)
        return m

    def expose(self) -> str:
        lines: List[str] = []
        for m in self._metrics:
            lines.extend(m.expose())
        return "\n".join(lines) + "\n"


class SchedulerMetrics:
    """The scheduler's series (metrics/metrics.go:265-615 subset that the
    perf harness and tests consume)."""

    def __init__(self):
        self.registry = Registry()
        r = self.registry.register
        self.schedule_attempts = r(Counter(
            "scheduler_schedule_attempts_total",
            "Number of attempts to schedule pods, by result and profile.",
            ("result", "profile")))
        self.scheduling_attempt_duration = r(Histogram(
            "scheduler_scheduling_attempt_duration_seconds",
            "Scheduling attempt latency (scheduling algorithm + binding).",
            ("result", "profile")))
        self.pod_scheduling_sli_duration = r(Histogram(
            "scheduler_pod_scheduling_sli_duration_seconds",
            "E2e latency for a pod being scheduled, from first attempt.",
            ("attempts",)))
        self.e2e_scheduling_duration = r(Histogram(
            "scheduler_e2e_scheduling_duration_seconds",
            "End-to-end pod scheduling latency, queue admission -> bound "
            "(fed from pod.e2e span ends; docs/OBSERVABILITY.md). Extended "
            "buckets: late pods in a large drain legitimately wait tens of "
            "seconds in the queue.",
            buckets=DURATION_BUCKETS + (32.768, 65.536, 131.072)))
        self.pod_stage_duration = r(Histogram(
            "scheduler_pod_stage_duration_seconds",
            "The e2e latency split where it is made, for EVERY pod: "
            "queue.wait (admission -> pop), bind.queue (a queued bind's "
            "wait in the API dispatcher: enqueue -> its request sent; not "
            "observed for an inline bind) and bind.post (the round trip of "
            "the request that carried the pod, single or bulk, as the "
            "scheduler sees it).", ("stage",),
            buckets=DURATION_BUCKETS + (32.768, 65.536, 131.072)))
        self.inbox_oldest_wait = r(Histogram(
            "scheduler_inbox_oldest_wait_seconds",
            "Stage inbox.wait: how long the oldest watch event parked by "
            "another thread (the reflector, a client thread) had waited "
            "when the loop began the drain that replayed it; one "
            "observation a drain. It lies before queue admission, so the "
            "e2e histogram does not hold it.",
            buckets=DURATION_BUCKETS + (32.768, 65.536, 131.072)))
        self.cluster_event_wait = r(Histogram(
            "scheduler_cluster_event_wait_seconds",
            "Stage inbox.wait of one event: how long a cluster event that "
            "another thread parked (kind=node: a node added, updated or "
            "deleted) had waited when the loop replayed it; one observation "
            "an event, where the oldest-wait series has one a drain and "
            "mostly meets pods.", ("kind",),
            buckets=DURATION_BUCKETS + (32.768, 65.536, 131.072)))
        self.bind_requests = r(Counter(
            "scheduler_bind_requests_total",
            "Binding requests sent to the apiserver: single (one pod: an "
            "inline bind on the loop, or a queued bind that went out "
            "alone) or bulk (one POST /api/v1/bindings for a run of queued "
            "binds). Published at scrape time.", ("kind",)))
        self.bind_request_pods = r(Counter(
            "scheduler_bind_request_pods_total",
            "Pods those binding requests carried: over the requests, the "
            "mean batch. Published at scrape time.", ()))
        self.loop_stage_seconds = r(Counter(
            "scheduler_loop_stage_seconds_total",
            "Self time of each stage of the scheduling loop "
            "(core/spans.py StageLedger; docs/OBSERVABILITY.md).",
            ("stage",)))
        self.loop_stages = r(Counter(
            "scheduler_loop_stages_total",
            "Times each stage of the scheduling loop was entered.",
            ("stage",)))
        self.framework_extension_point_duration = r(Histogram(
            "scheduler_framework_extension_point_duration_seconds",
            "Latency per extension point.", ("extension_point", "status", "profile")))
        self.plugin_execution_duration = r(Histogram(
            "scheduler_plugin_execution_duration_seconds",
            "Plugin execution latency.", ("plugin", "extension_point", "status")))
        self.pending_pods = r(Gauge(
            "scheduler_pending_pods",
            "Pending pods by queue (active/backoff/unschedulable/gated).",
            ("queue",)))
        self.queue_incoming_pods = r(Counter(
            "scheduler_queue_incoming_pods_total",
            "Pods added to queues by event and queue.", ("queue", "event")))
        self.preemption_attempts = r(Counter(
            "scheduler_preemption_attempts_total", "Preemption attempts."))
        self.preemption_dry_runs = r(Counter(
            "scheduler_preemption_dry_runs_total",
            "Candidate searches of DefaultPreemption by what ran the "
            "what-if: 'device' (one dry_run_preemption kernel call over "
            "every node) or 'host' (the Evaluator's per-node loop: no device "
            "backend, a preemptor the kernel does not cover, or the "
            "recompute after a device candidate the host verify refused).",
            ("engine",)))
        self.preemption_victim_rows = r(Counter(
            "scheduler_preemption_victim_rows_total",
            "Node rows of the device what-if's victim tensors, by what a "
            "dry run did with them: 'rebuilt' (derived again from the "
            "node's pods: its NodeInfo generation, its place in the list, "
            "or the holder's key had changed) or 'kept' (the row of the "
            "last dry run, as it was).", ("how",)))
        self.preemptor_plans = r(Counter(
            "scheduler_preemptor_plan_total",
            "Plans acquired for one pod of a template planned for before, "
            "by site ('dry_run': the device what-if of a preemption; "
            "'nominated': the evaluation of a nominated pod's own node) and "
            "by how: 'kept' (the template's kept plan, with the nominated "
            "lane, the one-row plan and the start index derived again) or "
            "'built' (no plan was kept, or the events since, the shapes or "
            "the bound pods' terms voided it: a full build, kept in turn).",
            ("site", "how")))
        self.preemption_victims = r(Histogram(
            "scheduler_preemption_victims", "Victims per preemption.",
            buckets=(1, 2, 4, 8, 16, 32, 64)))
        self.batch_attempts = r(Counter(
            "scheduler_batch_attempts_total",
            "Device batch dispatches, by outcome.", ("result",)))
        self.batch_size = r(Histogram(
            "scheduler_batch_size", "Pods per device batch.",
            buckets=(1, 8, 64, 256, 512, 1024, 2048, 4096)))
        self.podgroup_schedule_attempts = r(Counter(
            "scheduler_podgroup_schedule_attempts_total",
            "Gang scheduling attempts, by result.", ("result",)))
        self.generated_placements = r(Histogram(
            "scheduler_podgroup_generated_placements",
            "Candidate placements generated per pod-group cycle "
            "(metrics.RecordGeneratedPlacements).",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128)))
        self.goroutines = r(Gauge(
            "scheduler_goroutines",
            "In-flight concurrent work by kind (metrics.go Goroutines); the "
            "TPU build's analogue counts in-flight device dispatches.",
            ("work",)))
        self.cache_size = r(Gauge(
            "scheduler_cache_size", "Cache object counts.", ("type",)))
        # ---- full reference-series parity (metrics.go:265-615) ------------
        self.pod_scheduling_attempts = r(Histogram(
            "scheduler_pod_scheduling_attempts",
            "Number of attempts to successfully schedule a pod.",
            buckets=(1, 2, 4, 8, 16)))
        self.scheduling_algorithm_duration = r(Histogram(
            "scheduler_scheduling_algorithm_duration_seconds",
            "Scheduling algorithm latency (filter+score, no binding)."))
        self.event_handling_duration = r(Histogram(
            "scheduler_event_handling_duration_seconds",
            "Event handling latency by event kind.", ("event",)))
        self.inflight_events = r(Gauge(
            "scheduler_inflight_events",
            "Entries in the in-flight event log.", (), fn=None))
        self.queued_entities = r(Gauge(
            "scheduler_queued_entities",
            "Queued entities by kind (pod/podgroup/composite).", ("kind",)))
        self.unschedulable_pods = r(Gauge(
            "scheduler_unschedulable_pods",
            "Pods in the unschedulable store, by plugin that rejected them.",
            ("plugin",)))
        self.queue_incoming_entities = r(Counter(
            "scheduler_queue_incoming_entities_total",
            "Group/composite entities added to queues by event.",
            ("queue", "event")))
        # Overload/fairness plane (docs/RESILIENCE.md § overload &
        # fairness): per-tenant starvation truth — how long each
        # namespace's longest-waiting runnable entity has sat in the
        # active/backoff queues. Callback gauge fed from
        # PriorityQueue.starvation_by_namespace at scrape time.
        self.queue_starvation = r(Gauge(
            "scheduler_queue_starvation_seconds",
            "Per-namespace longest wait (seconds) of a runnable queued "
            "entity since queue admission — the starvation signal the "
            "fair-dequeue plane bounds.", ("namespace",)))
        self.permit_wait_duration = r(Histogram(
            "scheduler_permit_wait_duration_seconds",
            "Time pods spend waiting on Permit.", ("result",)))
        self.queueing_hint_execution_duration = r(Histogram(
            "scheduler_queueing_hint_execution_duration_seconds",
            "QueueingHintFn execution latency.", ("plugin", "event")))
        self.plugin_evaluation_total = r(Counter(
            "scheduler_plugin_evaluation_total",
            "Plugin evaluations by plugin/extension point/profile.",
            ("plugin", "extension_point", "profile")))
        # async API dispatcher (backend/api_dispatcher metrics)
        self.async_api_call_execution_total = r(Counter(
            "scheduler_async_api_call_execution_total",
            "Async API calls executed, by call type and result.",
            ("call_type", "result")))
        self.async_api_call_execution_duration = r(Histogram(
            "scheduler_async_api_call_execution_duration_seconds",
            "Async API call execution latency.", ("call_type", "result")))
        self.pending_async_api_calls = r(Gauge(
            "scheduler_pending_async_api_calls",
            "Queued async API calls not yet executed.", ()))
        self.async_api_call_retries = r(Counter(
            "scheduler_async_api_call_retries_total",
            "Transient-failure replays of async API calls (backoff retries "
            "that happened BEFORE a call landed in the error inbox).",
            ("call_type",)))
        # resilience layer (core/backoff.py; docs/RESILIENCE.md)
        self.device_path_fallback = r(Counter(
            "scheduler_device_path_fallback_total",
            "Scheduling work rerouted from the device kernel path to the "
            "host Evaluator, by reason (exception class, 'unsupported', or "
            "'breaker_open').", ("reason",)))
        self.device_breaker_state = r(Gauge(
            "scheduler_device_path_breaker_open",
            "1 while the device-path circuit breaker is open (host path "
            "pinned for the cool-down), else 0.", ()))
        # opportunistic batching (runtime/batch.go series), generalized to
        # device sessions: a "flush" is a session invalidation.
        self.batch_cache_flushed = r(Counter(
            "scheduler_batch_cache_flushed_total",
            "Batch/session state flushes (session invalidations), by reason.",
            ("reason",)))
        self.pod_scheduled_after_flush = r(Counter(
            "scheduler_pod_scheduled_after_flush_total",
            "Pods scheduled in the first batch after a flush.", ()))
        # incremental session resume (event-journal delta rebuilds)
        self.plan_rebuild_total = r(Counter(
            "scheduler_plan_rebuild_total",
            "Device-session plan acquisitions, by kind: 'full' = complete "
            "snapshot→features rebuild, 'resume' = untouched cache hit, "
            "'delta' = journal-driven row patch of a live plan+carry; "
            "'plane' splits mesh (sharded) sessions from single-device — "
            "a mesh 'full' tears down and re-uploads the whole sharded "
            "state, the cost the delta patches exist to avoid.",
            ("kind", "plane")))
        self.plan_rebuild_cause = r(Counter(
            "scheduler_plan_rebuild_cause_total",
            "Full plan rebuilds by what made them full: 'first' (no plan "
            "kept to resume), 'other_pod' (the kept plan is another "
            "template's, profile's or attempt count's), 'nomination' (the "
            "kept plan is this template's and only the nomination set it was "
            "built under has moved), 'journal_overrun' "
            "(more events since it than the journal retains), 'structural' "
            "(a node added or removed since), 'unpatchable' (an event of "
            "another kind no row patch covers), 'patch_failed'.",
            ("cause",)))
        self.nominated_evaluations = r(Counter(
            "scheduler_nominated_evaluations_total",
            "Device-path evaluations of a nominated pod's own node, first "
            "and alone (evaluateNominatedNode): 'bound' there, or "
            "'fell_through' to the ordinary cycle (the node is gone or no "
            "longer takes the pod), 'bind_refused' (the node took the pod "
            "and the apiserver refused the bind: unwound and requeued).",
            ("outcome",)))
        self.plan_ipa_terms = r(Counter(
            "scheduler_plan_ipa_terms_total",
            "What the required inter-pod term tables of the plans built "
            "(loop stage plan.ipa) cost the host: 'matches' = term.matches "
            "evaluations, 'term_pods' = existing pods carrying a required "
            "anti-affinity term that were walked; and what the "
            "InterPodAffinity score-table walk (loop stage plan.ipa_score) "
            "cost: 'score_matches' = its term.matches evaluations, "
            "'pods_walked' = the pods it visited.", ("what",)))
        self.device_batches = r(Counter(
            "scheduler_device_batches_total",
            "Device batches dispatched, by the engine the built plan's "
            "coupling chose (ops/kernel.py coupling): 'scan_carried' = the "
            "scan with scores riding the carry, 'scan_normalised' = the "
            "scan that recomputes and normalises every score at each step "
            "(soft spread, preferred inter-pod terms, PreferNoSchedule, "
            "preferred node affinity), 'lap' = the lap kernel.",
            ("engine",)))
        self.device_scan_steps = r(Counter(
            "scheduler_device_scan_steps_total",
            "Steps of the scan engines' dispatches (ops/kernel.py "
            "schedule_batch loops over the pods a dispatch holds, not over "
            "the plan's padded width): 'run' = the sum of n_active, "
            "'skipped' = the sum of batch_pad - n_active, the steps a "
            "fixed-length scan would have run and placed nothing with.",
            ("kind",)))
        self.commit_pods = r(Counter(
            "scheduler_commit_pods_total",
            "Pods of retired device batches by the host tail that committed "
            "them (models/tpu_scheduler.py _commit_batch): 'batch' = in "
            "passes over the batch (one assume, one bulk bind, one settle), "
            "'single' = one _commit call a pod.", ("tail",)))
        self.queue_popped_pods = r(Counter(
            "scheduler_queue_popped_pods_total",
            "Pods the device path's pops accepted into a device batch "
            "(models/tpu_scheduler.py _refill), by how the verdict was "
            "reached: 'run' = on the session's template (the same shared "
            "signature holder as its head, and nothing per pod that could "
            "change the answer), 'single' = through the full "
            "_session_compatible check, the head of each session and the "
            "members of a gang pack included.", ("how",)))
        self.prefilter_narrowed_pods = r(Counter(
            "scheduler_prefilter_narrowed_pods_total",
            "Pods whose NodeAffinity PreFilterResult narrowed the nodes "
            "their cycle evaluates (every required term pins metadata.name: "
            "the DaemonSet controller's pods), by the path that placed "
            "them: 'device' = dispatched in a batch of a plan over the "
            "named nodes' rows only (models/tpu_scheduler.py "
            "_dispatch_next), 'host' = the host cycle "
            "(core/scheduler.py find_nodes_that_fit_pod).", ("path",)))
        self.host_to_device_transfers = r(Counter(
            "scheduler_host_to_device_transfers_total",
            "Host-to-device transfers of the feature build and the mirror "
            "(ops/device_state.py NodeStateMirror.send: a payload's arrays "
            "packed into one buffer, sent once, taken apart on the device), "
            "by payload: 'features' = a full build's BatchFeatures, 'flush' "
            "= the dirty rows of a flush's scatter or of a session's row "
            "patch, 'rows_state' = the device state of a narrowed plan, "
            "'derive' = what a kept plan derives again. One a payload; the "
            "full upload after a restore (one array a transfer) is not "
            "among them.", ("payload",)))
        self.mirror_rows = r(Counter(
            "scheduler_mirror_rows_total",
            "Rows of the device path's node state brought in line with "
            "their NodeInfo (ops/device_state.py NodeStateMirror), by how: "
            "'encoded' = the whole row, where the node itself is new to the "
            "row or changed (a sync's, a session's row patch); 'by_column' "
            "= the three columns a pod's arrival or departure moves, many "
            "rows in one array pass, at a sync that finds the node it "
            "encoded; 'adopted' = the same columns at a clean session's "
            "end, from the live cache.", ("how",)))
        self.plan_node_shapes = r(Gauge(
            "scheduler_plan_node_shapes",
            "Distinct allocatable shapes (cpu, memory, pod count) among the "
            "nodes of the device path's node state as the newest session "
            "acquired its plan (ops/device_state.py NodeStateMirror.shapes, "
            "a census kept row by row as rows are encoded): 1 on a cluster "
            "of equal nodes, one a node pool otherwise; a pool that joins "
            "or leaves moves it at the next session.", ()))
        self.plan_anti_lane = r(Counter(
            "scheduler_plan_anti_lane_total",
            "Plans built whose anti-affinity filter had something to "
            "refuse (the pod's own required anti terms, or existing pods' "
            "terms hitting it), by BatchPlan.anti_rowlocal: 'true' rides "
            "the lap kernel, 'false' the scan.", ("rowlocal",)))
        self.plan_rebuild_dirty_rows = r(Counter(
            "scheduler_plan_rebuild_dirty_rows_total",
            "Node rows re-encoded + scattered by delta plan patches.", ()))
        self.get_node_hint_duration = r(Histogram(
            "scheduler_get_node_hint_duration_seconds",
            "Batch reuse lookup latency (session-resume check)."))
        # score-hint fast path (models/score_hints.py; KEP-5598
        # OpportunisticBatch, cross-cycle)
        self.hint_cache_hits = r(Counter(
            "scheduler_hint_cache_hits_total",
            "Pods bound through the score-hint fast path (no device "
            "dispatch), by matching signature kind: 'exact' | 'neutral' "
            "(namespace-erased).", ("reason",)))
        self.hint_cache_misses = r(Counter(
            "scheduler_hint_cache_misses_total",
            "Hint-path fall-throughs to the normal batch, by reason: "
            "'empty' = no live hint, 'signature' = different pod shape, "
            "'stale' = freshness fence tripped (see invalidations), "
            "'infeasible' = no node passed the hinted walk, plus "
            "pod-eligibility reasons (claims/unsupported/extender/"
            "unsignable/profile/affinity_gate).", ("reason",)))
        self.hint_cache_invalidations = r(Counter(
            "scheduler_hint_cache_invalidations_total",
            "Hint invalidations, by reason: journal event kinds "
            "(pod_terms/pns_taint/structural/other/namespace), "
            "'journal_gap', 'foreign_attempt', 'state_unwind', "
            "'nomination', 'affinity_transition' (0->1 affinity-pod "
            "transition disables hints cluster-wide), 'bind_conflict' "
            "(single-NODE invalidation, the hint survives), "
            "'device_failure'.", ("reason",)))
        self.hint_sibling_absorbed = r(Counter(
            "scheduler_hint_sibling_absorbed_total",
            "Live hints of ANOTHER pod signature that absorbed a clean "
            "device session at its hint install (two pod templates taking "
            "turns): 'siblings' = entries kept, 'rows' = node rows whose "
            "pod state they took from the session's carry and re-evaluated, "
            "in one array pass. A sibling whose rows are not the session's "
            "is dropped instead: invalidations, reason 'cross_reencode'.",
            ("what",)))
        self.hint_validation_duration = r(Histogram(
            "scheduler_hint_validation_duration_seconds",
            "Host-side hint validate+select latency per consulted pod "
            "(journal replay + the kernel's selection math in numpy)."))
        # shard plane (kubernetes_tpu/shard/): optimistic multi-scheduler
        self.bind_conflict_total = r(Counter(
            "scheduler_bind_conflict_total",
            "Optimistic-binding conflicts (409 from the binding "
            "subresource), by reason: 'already_bound' = another scheduler "
            "bound the pod first, 'capacity' = the commit would overcommit "
            "the node (Omega transaction validation), 'conflict' = "
            "unclassified 409.", ("reason",)))
        self.shard_owned_shards = r(Gauge(
            "scheduler_shard_owned_shards",
            "Shard ranges this scheduler currently owns (1 = its own; more "
            "after adopting an expired peer's range)."))
        self.shard_lease_renewals = r(Counter(
            "scheduler_shard_lease_renewals_total",
            "Successful shard-lease renewals through the apiserver.", ()))
        self.shard_adoptions = r(Counter(
            "scheduler_shard_adoptions_total",
            "Expired peer shard ranges adopted (lease-expiry failover).",
            ()))
        # watch-cache read plane (core/watchcache.py): per-shard decode
        # cost by wire form — 'full' = whole pod/node wire, 'slim' = the
        # shard filter's NodeInfo-accounting projection. Callback gauges
        # fed from the HTTP clientset's reflector counters.
        self.watch_decoded_events = r(Gauge(
            "scheduler_watch_decoded_events",
            "Watch events this scheduler decoded, by wire form "
            "(shard-filtered streams deliver foreign plain pods slim) "
            "and codec (core/wire.py negotiated binary vs JSON).",
            ("form", "codec")))
        self.watch_decoded_bytes = r(Gauge(
            "scheduler_watch_decoded_bytes",
            "Watch stream bytes this scheduler decoded, by wire form "
            "and codec.",
            ("form", "codec")))
        # placement / pod-group series
        self.generated_placements_total = r(Counter(
            "scheduler_generated_placements_total",
            "Candidate placements generated.", ()))
        self.placement_evaluations = r(Counter(
            "scheduler_placement_evaluations_total",
            "Candidate placement evaluations, by backend.", ("backend",)))
        self.placement_evaluation_duration = r(Histogram(
            "scheduler_placement_evaluation_duration_seconds",
            "Latency of evaluating ALL candidate placements for a group."))
        self.podgroup_scheduling_algorithm_duration = r(Histogram(
            "scheduler_podgroup_scheduling_algorithm_duration_seconds",
            "Pod-group scheduling algorithm latency."))
        self.podgroup_scheduling_attempt_duration = r(Histogram(
            "scheduler_podgroup_scheduling_attempt_duration_seconds",
            "Pod-group scheduling attempt latency incl. commit.",
            ("result",)))
        self.store_schedule_results_duration = r(Histogram(
            "scheduler_store_schedule_results_duration_seconds",
            "Latency of persisting scheduling results to the pod-group "
            "state store."))
        # preemption depth series
        self.preemption_evaluation_duration = r(Histogram(
            "scheduler_preemption_evaluation_duration_seconds",
            "Preemption candidate evaluation (dry run) latency."))
        self.preemption_execution_duration = r(Histogram(
            "scheduler_preemption_execution_duration_seconds",
            "Preemption execution (victim preparation) latency."))
        self.preemption_goroutines_duration = r(Histogram(
            "scheduler_preemption_goroutines_duration_seconds",
            "Async victim-deletion work latency (executor.go analogue)."))
        self.preemption_goroutines_execution_total = r(Counter(
            "scheduler_preemption_goroutines_execution_total",
            "Async victim-deletion executions, by result.", ("result",)))
        self.preemption_pdb_violations = r(Counter(
            "scheduler_preemption_pdb_violations_total",
            "Victims selected despite PDB violation (no PDB API yet: "
            "registered for parity, always 0).", ()))
        self.preemption_workload_disruptions = r(Counter(
            "scheduler_preemption_workload_disruptions",
            "Workloads disrupted by pod-group preemption.", ()))
        self.workload_preemption_attempts = r(Counter(
            "scheduler_workload_preemption_attempts_total",
            "Pod-group (workload) preemption attempts, by result.",
            ("result",)))
        self.workload_preemption_victims = r(Histogram(
            "scheduler_workload_preemption_victims",
            "Victims per pod-group preemption.",
            buckets=(1, 2, 4, 8, 16, 32, 64)))
        # DRA binding conditions (dra_bindingconditions_*): the binding-
        # conditions protocol is not implemented (allocation is synchronous
        # in-cycle), registered for name parity and future wiring.
        self.dra_bindingconditions_allocations = r(Counter(
            "scheduler_dra_bindingconditions_allocations_total",
            "DRA allocations carrying binding conditions (not implemented: "
            "allocation is synchronous; always 0).", ("result",)))
        self.dra_bindingconditions_wait_duration = r(Histogram(
            "scheduler_dra_bindingconditions_wait_duration_seconds",
            "Wait for DRA binding conditions (not implemented; empty)."))

    def expose(self) -> str:
        return self.registry.expose()


@dataclass
class _Timer:
    start: float = field(default_factory=time.perf_counter)

    def elapsed(self) -> float:
        return time.perf_counter() - self.start


class MetricAsyncRecorder:
    """Buffered off-thread metric recording (pkg/scheduler/metrics/
    metric_recorder.go MetricAsyncRecorder): hot paths append observations
    to a bounded buffer and a flusher thread applies them to the histograms
    on an interval — the scheduling loop never pays the registry's dict
    work. observe() drops on overflow (the reference's channel send is
    non-blocking too), counting drops for observability."""

    def __init__(self, interval: float = 0.05, capacity: int = 4096):
        import threading
        from collections import deque

        # Unbounded deque + explicit capacity check: deque(maxlen) would
        # silently evict the OLDEST observation when two racing observers
        # both pass a len() check — an uncounted loss. With no maxlen the
        # worst case of the (benign) check-then-append race is a few entries
        # over capacity, all of which still flush.
        self._buf = deque()
        self._capacity = capacity
        self._interval = interval
        self.dropped = 0
        self._stop = threading.Event()
        self._flushed = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="metric-recorder", daemon=True)
        self._thread.start()

    def observe(self, histogram: Histogram, value: float, *labels: str) -> None:
        if len(self._buf) >= self._capacity:
            self.dropped += 1
            return
        self._buf.append((histogram, value, labels))

    def _run(self) -> None:
        while not self._stop.wait(self._interval):
            self.flush_now()
        self.flush_now()

    def flush_now(self) -> None:
        buf = self._buf
        while buf:
            try:
                histogram, value, labels = buf.popleft()
            except IndexError:
                break
            histogram.observe(value, *labels)
        self._flushed.set()

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2)
        self.flush_now()
