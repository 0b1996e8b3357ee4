"""TPUScheduler — the device-backed scheduling pipeline (the framework's
flagship "model").

Control flow (the TPU-era schedule_one, per SURVEY.md §3.2/§7.4):

    pop → accumulate a row-block of consecutive same-signature pods
        → Cache.update_snapshot (host, incremental)
        → NodeStateMirror.sync/flush (device, dirty-row scatter)
        → build_batch (ONE amortized O(pods) PreFilter aggregation)
        → ops.kernel.schedule_batch (jit: the whole greedy sequential
          assignment for the block runs on device — filters, sampling
          emulation, scoring, selection, carry updates)
        → per pod: assume → reserve → permit → binding cycle (host,
          unchanged semantics; schedule_one.go:315,:211,:141)

Pods whose spec exceeds the kernel's coverage (ops/features.py
batch_supported) take the unchanged host path — the reference-shaped
sequential cycle in core/scheduler.py — preserving exact semantics for every
feature while the dense common case rides the device.

Pod signatures come from the profile's Sign plugins
(framework.sign_pod; staging kube-scheduler framework/signers.go), the same
mechanism the reference's OpportunisticBatching uses (runtime/batch.go:33) —
generalized from one-pod hint reuse to true multi-pod kernel batches.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import time as _time
from contextlib import contextmanager
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

_log = logging.getLogger(__name__)

from ..compile_cache import watch_compiles
from ..core import spans as _spans
from ..core.backoff import CircuitBreaker
from ..core.cache import (EV_NAMESPACE, EV_NODE_UPDATE, EV_POD_ADD,
                          EV_POD_REMOVE, EV_POD_UPDATE, EV_QUEUE,
                          EV_STRUCTURAL)
from ..core.features import TPU_BATCH_SCHEDULING
from ..core.framework import OK as _OK_STATUS
from ..core.framework import (UNSCHEDULABLE_AND_UNRESOLVABLE, WAIT, CycleState,
                              FitError, Framework, PlacementProgress,
                              PodGroupAssignments, Status)
from ..core.queue import (QueuedCompositeGroupInfo, QueuedPodGroupInfo,
                          QueuedPodInfo)
from ..core.scheduler import (QueuedBind, Scheduler, ScheduleResult,
                              queue_wait)
from ..ops.codebook import EFFECT_PREFER_NO_SCHEDULE
from ..ops.device_state import (NodeStateMirror, patch_tier,
                                enable_persistent_compilation_cache)
from ..ops.features import (KeptPlan, PreemptionVictims, Unsupported, _pow2,
                            lane_requests, batch_supported, build_batch,
                            diagnose_unschedulable, narrowed_rows,
                            padded_rows, plan_shape, volume_device_support)
from ..ops.kernel import (dry_run_preemption, patch_carry_rows,
                          patch_carry_rows_pinned, schedule_batch,
                          schedule_placements)
from ..parallel.mesh import (collective_report, make_mesh, mesh_host_split,
                             mesh_shard_count, mesh_state_shardings,
                             shard_features, shard_node_state,
                             sharded_lap_schedule)
from ..plugins.basic import DefaultBinder, NodeAffinity
from ..plugins.preemption import Candidate
from .score_hints import ScoreHintCache, hint_eligible


# Sentinel fallback_reason: the popped entity is a pod GROUP that can ride a
# device gang session (schedule_one routes it to run_gang_device_session).
_GANG_SESSION = "__gang_device_session__"
# _collect_batch's word for a head that holds a nomination: a batch of one,
# whose nominated node is evaluated first and alone (_run_nominated).
_NOMINATED = "__nominated_pod__"
# A session whose head is no plain template clone: a holder no pod carries.
_NO_TEMPLATE = (object(), 0, "")

# Batches that may be in flight on the device while the host commits retired
# ones (2 = double buffering).
PIPELINE_DEPTH = 2
# Templates whose built plan is kept to derive from (_preemptor_plan), the
# oldest leaving first: a bound on memory (a plan is some 40 bytes a node
# row on the device), not a choice of path. A plan kept for its session's
# tail alone is not counted: at most one tail lives.
_KEPT_PLANS = 8
# Device-path circuit breaker (core/backoff.py CircuitBreaker): consecutive
# failures that open it, and the seconds it then pins the host path.
DEVICE_BREAKER_THRESHOLD = 3
DEVICE_BREAKER_COOLDOWN_S = 5.0


class _Batch(list):
    """A popped batch and the span contexts of its sampled members, found
    once as the batch is collected: each stage of the batch copies its span
    into those traces without another lookup per pod. ``sampled_at`` holds
    each context's place in the batch (the batch tail closes those pods'
    traces)."""

    __slots__ = ("sampled", "sampled_at")

    def __init__(self, *args):
        super().__init__(*args)
        self.sampled: list = []
        self.sampled_at: List[int] = []


class _SessionDelta:
    """A live session's mutable view, of both session kinds, from where it
    opens (TPUScheduler._open_session) to where it closes (_close_session):
    what it plans for (`fw`, the head `pod`, `sig`, `aux_shape`, and the
    namespace-erased `nsig` where sessions chain on it: plain pods', not
    gangs'), the `plan`, the `node_names` of its rows, how the plan was come
    by (`built`: kind, cause); what the journal patches
    (_note_session_events): the device `state` + `carry`, the seq watermark
    consumed, whether a shrink patch waits for the pipeline to drain; the
    batches `inflight` (entities, results, seq), the rows that took a pod
    (`ok_rows`) and those where carry and host disagree (`dirty_rows`).
    Where the template's PreFilterResult narrows, the plan is over the named
    nodes' rows only (`plan.rows`), and so are `state`, `carry`,
    `node_names` and every row index above."""

    __slots__ = ("fw", "pod", "sig", "nsig", "neutral_ok", "aux_shape", "plan",
                 "node_names", "built", "state", "carry", "start_seq",
                 "start_unwinds", "patch_pending", "busy_patch_rows",
                 "inflight", "ok_rows", "dirty_rows")

    def __init__(self, fw, pod, sig, nsig, neutral_ok, aux_shape):
        self.fw = fw
        self.pod = pod
        self.sig = sig
        self.nsig = nsig
        self.neutral_ok = neutral_ok
        self.aux_shape = aux_shape
        self.patch_pending = False
        self.inflight: list = []
        self.ok_rows: List[int] = []
        self.dirty_rows: List[int] = []
        # Rows patched while the pipeline was BUSY (_consume_session_events):
        # an in-flight batch may have placed onto one after dispatch, which
        # mirror staging does not hold yet, so the session end charges them
        # dirty and adopt() re-encodes them from post-commit staging truth.
        self.busy_patch_rows: list = []


def _pow2_pad(n: int) -> int:
    """Placement-axis pow2 tier (shared by warm + live paths so the warm
    compile always matches the live kernel shape)."""
    return _pow2(max(1, n))


class TPUScheduler(Scheduler):
    """Scheduler with the hot path on device. Falls back per-pod to the host
    path for uncovered features; host and device paths produce identical
    assignments (deterministic_ties is forced on)."""

    def __init__(self, *args, max_batch: Optional[int] = None, mesh="auto",
                 **kwargs):
        kwargs.setdefault("deterministic_ties", True)
        super().__init__(*args, **kwargs)
        self.device_enabled = self.gates.enabled(TPU_BATCH_SCHEDULING)
        self.max_batch = max_batch if max_batch is not None else self.config.max_batch
        enable_persistent_compilation_cache()
        watch_compiles()  # a slow stage says whether a compile ran inside it
        # Multi-chip: with >1 device the node axis shards over a
        # ("cells", "nodes") mesh and the SAME jitted kernel compiles SPMD
        # (GSPMD from committed input shardings; reductions ride ICI
        # collectives — parallelize/parallelism.go:28's scale axis, done the
        # scaling-book way). Single chip runs unsharded, zero overhead.
        # A mesh that cannot be built on the visible devices is an error,
        # never a silent single-device run.
        self.mesh = None
        if mesh == "auto":
            if len(jax.devices()) > 1:
                self.mesh = make_mesh(n_cells=1)
        else:
            self.mesh = mesh  # explicit Mesh, or None to force single-device
        self.mirror = NodeStateMirror()
        self.mirror.transfers = self.metrics.host_to_device_transfers
        self.mirror.rows = self.metrics.mirror_rows
        # the preemption what-if's victim tensors, kept from one preemptor
        # to the next and patched by the snapshot's generations
        self._victims = PreemptionVictims(self.mirror)
        self._plans: dict = {}  # "the keeper of built plans", below
        # _evaluate_placements: the last group cycle's plan, and its masks
        self._placement_plan_cache: dict = {}
        self._placement_mask_cache: dict = {}
        self._holdover: Optional[QueuedPodInfo] = None
        self._after_flush = False  # a flush since the last committed batch
        # metrics
        self.device_scheduled = 0
        self.dispatch_seq = 0  # the last live dispatch's ordinal (`seq`)
        self.shard_map_dispatches = 0
        self.host_path_pods = 0
        # Plan acquisition attribution (scheduler_plan_rebuild_total):
        # full = snapshot→features rebuild, resume = untouched cache hit,
        # delta = journal-driven row patch of a live plan+carry.
        self.plan_rebuilds_full = 0
        self.plan_rebuilds_delta = 0
        self.plan_rebuilds_resume = 0
        # Why the last plan acquisition was a full rebuild ("" where it was
        # none): plan.build and plan.adopt say it (`cause`), and
        # scheduler_plan_rebuild_cause_total counts it.
        self.plan_build_cause = ""
        self.delta_dirty_rows = 0
        # Stacked placement evaluations that ran on device (one per group
        # cycle whose candidate set was kernel-evaluated).
        self.placement_device_evals = 0
        # DryRunPreemption kernel calls (one per device-evaluated PostFilter).
        self.preemption_device_evals = 0
        self._empty_nom_key = None  # _empty_nom_lane: shapes it was made at
        self._empty_nom = None
        # The sampled span context of the entity _pop handed out last (None
        # for group entities and with tracing off): the batch collectors
        # keep it, so a batch's sampled members are found once.
        self._popped_ctx = None
        # What a refill may take on the template's verdict (_refill): the
        # shared signature holder, priority and scheduler name of the
        # session's head, or _NO_TEMPLATE where the head is no plain clone.
        self._session_template = _NO_TEMPLATE
        # The session's template has a PreFilterResult that narrows
        # (_narrows): what its pops say as `narrowed`.
        self._session_narrows = False
        # Terminal-failure memos, a small keyed LRU (_fail_from_memo): state
        # key -> (unschedulable plugins, message)
        self._fail_memo: "dict" = {}
        self._fail_memo_cap = 64
        # Live session's namespace-erased signature (None = exact-sig only)
        # and the node-name→row map behind journal delta patches.
        self._session_neutral_sig = None
        self._session_row_of = None
        # Per-framework commit fast-path eligibility (see _commit).
        self._fast_tail: dict = {}
        # Drivers with ANY CSINode attach limit (volume aux eligibility);
        # recomputed when the CSINode set grows.
        self._limited_drivers = frozenset()
        self._limited_drivers_n = -1
        # Claims referenced by pods already accepted into the CURRENT device
        # session (committed or in flight): a second pod sharing one of them
        # must not join (_session_compatible).
        self._session_claims: set = set()
        # Device-path circuit breaker (core/backoff.py; docs/RESILIENCE.md;
        # _note_device_failure): after N consecutive failures it pins the
        # host path, which produces identical assignments, for a cool-down.
        self.device_breaker = CircuitBreaker(
            failure_threshold=DEVICE_BREAKER_THRESHOLD,
            cooldown=DEVICE_BREAKER_COOLDOWN_S)
        # Chaos seam (testing/faults.py DeviceFaults): called at every
        # device kernel boundary crossing; may raise.
        self._fault_hook = None
        # Signature-keyed score-hint fast path (models/score_hints.py;
        # _try_hint_binds)
        self._hints = ScoreHintCache(self, enabled=self.device_enabled)
        self.hint_hits = 0
        self.hint_misses = 0
        self.hint_invalidations = 0
        # Everything kept that was derived from device state, with how it is
        # dropped. _note_device_failure runs the list: a holder registered
        # HERE cannot outlive a fault (tests/test_plan_keeper.py walks it).
        self._device_holders = {
            "mirror": self.mirror.invalidate,
            "plans": self._plans.clear,
            "victims": self._victims.drop,
            "hints": partial(self._hints.invalidate, "device_failure"),
            "placement_plans": self._placement_plan_cache.clear,
            "placement_masks": self._placement_mask_cache.clear,
            "fail_memo": self._fail_memo.clear,
        }

    # Host/device time split (schedule_one.go:574-style step accounting,
    # re-shaped for the batch pipeline), exported by the perf harness and
    # the benchmark so regressions are attributable, not guessed. Views of
    # the loop's stage table (core/spans.py StageLedger), not counters of
    # their own.

    @property
    def plan_build_s(self) -> float:
        """Snapshot→features host work: `plan.build` with its children
        `plan.ipa` (the required inter-pod term tables) and `plan.ipa_score`
        (the InterPodAffinity score-table walk), the whole build."""
        seconds = self.stages.seconds
        return (seconds["plan.build"] + seconds["plan.ipa"]
                + seconds["plan.ipa_score"])

    @property
    def device_batches(self) -> int:
        """Device batches dispatched: `scheduler_device_batches_total`, which
        counts them by engine at the two dispatch sites, every engine
        summed."""
        return int(self.metrics.device_batches.total())

    @property
    def commit_pods(self) -> dict:
        """Pods of retired batches by the host tail that committed them:
        `scheduler_commit_pods_total{tail}` (`_commit_batch`)."""
        series = self.metrics.commit_pods
        return {tail: int(series.value(tail)) for tail in ("batch", "single")}

    @property
    def popped_pods(self) -> dict:
        """Pods the device path's pops accepted into a batch, by how the
        verdict was reached: `scheduler_queue_popped_pods_total{how}`
        (`_pop_stage`)."""
        series = self.metrics.queue_popped_pods
        return {how: int(series.value(how)) for how in ("run", "single")}

    @property
    def device_wait_s(self) -> float:
        """Time blocked on a device result fetch (`device.wait`)."""
        return self.stages.seconds["device.wait"]

    @property
    def host_commit_s(self) -> float:
        """assume/reserve/permit/bind tails: `host.commit` with its one
        child `bind.post`, which runs under nothing else."""
        seconds = self.stages.seconds
        return seconds["host.commit"] + seconds["bind.post"]

    # -- batch accumulation ------------------------------------------------

    def _pop(self) -> Optional[QueuedPodInfo]:
        self._popped_ctx = None
        while True:
            if self._holdover is not None:
                qpi, self._holdover = self._holdover, None
            else:
                qpi = self.queue.pop()
            if qpi is None:
                return None
            if not isinstance(qpi, (QueuedPodGroupInfo,
                                    QueuedCompositeGroupInfo)):
                if self._skip_pod_schedule(qpi.pod):
                    # (Group/composite entities are never skipped whole —
                    # their .pod is just the first member.)
                    continue
                # queue.wait ends here for device-path pods (host-path
                # pods record in process_one; the qpi guard dedups).
                ctx = self._popped_ctx = (
                    self.tracer.context_for(qpi.pod.uid)
                    if self.tracer.enabled else None)
                self.record_queue_wait(qpi, ctx)
            return qpi

    def _skip_pod_schedule(self, pod) -> bool:
        """skipPodSchedule: deleting pods never dispatch to device, and
        neither do pods the cache already placed (a reconcile unwind raced
        the bind confirm — see core process_one). Such a pod's attempt is
        settled here (`queue.done`) and the caller drops it."""
        if pod.deletion_ts is not None or pod.uid in self.cache.pod_states:
            self.queue.done(pod.uid)
            return True
        return False

    def _take(self, batch: "_Batch", qpi: QueuedPodInfo) -> None:
        """Accept the entity _pop just handed out into `batch`."""
        ctx = self._popped_ctx
        if ctx is not None and ctx.sampled:
            batch.sampled.append(ctx)
            batch.sampled_at.append(len(batch))
        batch.append(qpi)

    @contextmanager
    def _pop_stage(self):
        """The `queue.pop` stage of one batch, opened with the active
        queue's depth as the pop begins (attr `backlog`: on the span and,
        in a profiler session, a stat of the event) and closed with what
        the collector inside it says it ``took`` into a device batch:
        `pods`, and `run`, those of them on the template's verdict
        (`_refill`), which also move
        `scheduler_queue_popped_pods_total{how}`, and `narrowed`, those
        taken into a batch of a template whose PreFilterResult narrows
        (`_narrows`; 0 elsewhere). One `len()` a batch; the
        hint walk's per-pod pops are leaves of the table and say nothing."""
        pods = run = narrowed = 0

        def took(n: int, on_template: int = 0, pinned: int = 0) -> None:
            nonlocal pods, run, narrowed
            pods += n
            run += on_template
            narrowed += pinned

        with self.stages.stage(
                "queue.pop", backlog=len(self.queue.active_q)) as stage:
            yield took
            stage.say(pods=pods, run=run, narrowed=narrowed)
            count = self.metrics.queue_popped_pods.inc
            if run:
                count("run", value=float(run))
            if pods > run:
                count("single", value=float(pods - run))

    def _collect_batch(self, took) -> Tuple[Optional[Framework], List[QueuedPodInfo], Optional[str]]:
        """Pop a maximal run of consecutive identical-signature pods.
        Returns (framework, batch, fallback_reason); fallback_reason set when
        the batch head must take the host path (batch will be length 1).
        ``took`` is the open `queue.pop` stage's (`_pop_stage`): told the
        pods that go to a device batch."""
        head = self._pop()
        if head is None:
            return None, [], None
        if isinstance(head, QueuedCompositeGroupInfo):
            # Composite trees take the host composite cycle (all-or-nothing
            # across levels; core/scheduler.py schedule_composite_group).
            return self.framework_for_pod(head.pod), [head], "composite group entity"
        if isinstance(head, QueuedPodGroupInfo):
            fw, sig = self._gang_device_eligible(head)
            if fw is not None:
                took(len(head.members))
                return fw, [head], _GANG_SESSION
            return self.framework_for_pod(head.pod), [head], "pod group entity"
        fw = self.framework_for_pod(head.pod)
        reason = self._batch_supported_memo(head.pod, fw, as_head=True)
        if reason is None:
            reason = self._nominated_device_block(fw, head.pod)
        if reason is None and self.extenders:
            interested = [e for e in self.extenders if e.is_interested(head.pod)]
            if interested:
                reason = "extender-managed pod"
        sig = fw.sign_pod(head.pod) if reason is None else None
        if sig is None:
            return fw, [head], reason or "unsignable pod"
        # The nominated lane's priority threshold is the head's priority
        # (two-pass counts only >=-priority nominations,
        # framework.go:1280-1284): a different-priority member would need a
        # different lane, so it ends the session instead of joining it.
        self._session_nom_priority = (
            head.pod.priority
            if self.queue.nominator.has_nominated_pods() else None)
        self._session_claims = set(self._claims_of(head.pod))
        self._session_claims.update(
            f"dra:{head.pod.namespace}/{n}"
            for n in getattr(head.pod, "resource_claims", ()) or ())
        self._session_aux_shape = self._aux_shape(head.pod)
        self._session_neutral_sig = self._neutral_sig(fw, head.pod, sig)
        pod = head.pod
        # The head's answers are its template's where nothing but the
        # template went into them: the memo answered batch_supported (no
        # volumes, no claims: _batch_supported_memo), the aux shape is the
        # plain one, and its signature IS the session's.
        shared = pod.__dict__.get("_sig_shared")
        self._session_template = (
            (shared, pod.priority, pod.scheduler_name)
            if shared is not None and not pod.volumes
            and not pod.resource_claims else _NO_TEMPLATE)
        # every pod of the session is the head's template, pin and all
        self._session_narrows = self._narrows(pod)
        batch = _Batch()
        self._take(batch, head)  # still the last entity _pop handed out
        took(1, pinned=int(self._session_narrows))
        if pod.nominated_node_name:
            # evaluateNominatedNode comes before the cycle that could share
            # a batch: the head stays alone until its node has answered
            return fw, batch, _NOMINATED
        return fw, self._refill(batch, fw, sig, took), None

    # -- gang device sessions ----------------------------------------------
    #
    # A pod group scheduled by the DEFAULT algorithm (no topology constraint)
    # is member-wise greedy placement with all-or-nothing commit
    # (schedule_one_podgroup.go:556) — exactly the kernel's scan with a
    # group-granular commit barrier. Groups of identical members ride device
    # sessions like plain pods: whole groups pack into each dispatch, the
    # carry chains across packs, and the host commits a retired pack's
    # groups atomically (any member infeasible ⇒ that group reverts to the
    # exact host cycle for diagnosis/PostFilter and the session invalidates).

    def _gang_device_eligible(self, qgpi: QueuedPodGroupInfo,
                              session_claims=None, session_aux_shape=None):
        """Returns (fw, sig) when the whole group can ride a device session:
        default algorithm, identical batch-supported members, one signature.
        PVC-carrying members are eligible when every member shares ONE
        counted-constraint shape (the plan's aux math models one driver/inc)
        and the members' claims are pairwise distinct and unseen by the
        session (the kernel counts attach units per LANDING; a shared claim
        would double-count what the host counts once per distinct claim).
        DRA resource claims stay on the host group cycle: their commit needs
        a per-member device allocation that can fail mid-group."""
        if not qgpi.members or len(qgpi.members) > self.max_batch:
            return None, None
        if not self.device_enabled or self.queue.nominator.has_nominated_pods():
            return None, None
        p0 = qgpi.members[0].pod
        if p0.scheduler_name not in self.profiles:
            return None, None
        fw = self.framework_for_pod(p0)
        if fw.placement_generate_plugins and getattr(
                qgpi.group, "topology_keys", ()):
            return None, None  # placement algorithm (separate path)
        if self.extenders and any(
                e.is_interested(m.pod) for e in self.extenders
                for m in qgpi.members):
            return None, None
        sig = fw.sign_pod(p0)
        if sig is None:
            return None, None
        aux_shape = self._aux_shape(p0)
        if session_aux_shape is not None and aux_shape != session_aux_shape:
            return None, None  # the live session's plan models one aux shape
        group_claims: set = set()
        for m in qgpi.members:
            if (m.pod.scheduler_name != p0.scheduler_name
                    or fw.sign_pod(m.pod) != sig
                    or self._batch_supported_memo(m.pod, fw) is not None
                    or self._device_unsupported_profile(fw, m.pod) is not None
                    or getattr(m.pod, "resource_claims", None)
                    # a narrowed member: the host group cycle, as before
                    or self._narrows(m.pod)):
                return None, None
            if self._aux_shape(m.pod) != aux_shape:
                return None, None
            for c in self._claims_of(m.pod):
                if c in group_claims or (session_claims is not None
                                         and c in session_claims):
                    return None, None  # shared claim: host counts it once
                group_claims.add(c)
        return fw, sig

    def _sorted_members(self, qgpi: QueuedPodGroupInfo) -> List[QueuedPodInfo]:
        """Host group-cycle member order (schedule_pod_group)."""
        return sorted(qgpi.members, key=lambda m: (-m.pod.priority, m.timestamp))

    def run_gang_device_session(self, fw: Framework, first: QueuedPodGroupInfo) -> None:
        """A session of pod groups from `first` on (`_run_session`)."""
        self._run_session(self._run_gang_device_session, fw, [first],
                          "gang_device_session")

    def _run_gang_device_session(self, fw: Framework,
                                 pack: Optional[List[QueuedPodGroupInfo]],
                                 pending: List[List[QueuedPodGroupInfo]]) -> None:
        first = pack[0]
        # Claims already accepted into this session (all members' PVCs):
        # collect_pack rejects groups re-using any of them — the kernel's
        # per-landing attach count assumes distinct claims, like the host's
        # distinct-claim NodeVolumeLimits count.
        self._session_claims = {
            c for m in first.members for c in self._claims_of(m.pod)}
        # Gang resumes stay exact-signature: the neutral erasure targets
        # plain-pod namespace sweeps, not group entities.
        sd = self._open_session(fw, first.members[0].pod, neutral_ok=False)
        sig, aux_shape, node_names = sd.sig, sd.aux_shape, sd.node_names
        inflight, ok_rows, dirty_rows = sd.inflight, sd.ok_rows, sd.dirty_rows
        stages = self.stages
        invalidated = False

        def collect_pack(took) -> List[QueuedPodGroupInfo]:
            groups: List[QueuedPodGroupInfo] = []
            total = 0
            while True:
                nxt = self._pop()
                if nxt is None:
                    break
                if isinstance(nxt, QueuedPodGroupInfo):
                    gfw, gsig = self._gang_device_eligible(
                        nxt, session_claims=self._session_claims,
                        session_aux_shape=aux_shape)
                    if (gfw is fw and gsig == sig
                            and total + len(nxt.members) <= self.max_batch):
                        groups.append(nxt)
                        total += len(nxt.members)
                        took(len(nxt.members))
                        self._session_claims.update(
                            c for m in nxt.members
                            for c in self._claims_of(m.pod))
                        continue
                self._holdover = nxt
                break
            return groups

        while True:
            while not invalidated and len(inflight) < PIPELINE_DEPTH:
                if sd.patch_pending:
                    if inflight:
                        break  # retire dispatched packs before patching
                    if not self._note_session_events(sd, busy=False):
                        invalidated = True
                        break
                if pack is None:
                    with self._pop_stage() as took:
                        pack = collect_pack(took) or None
                    if pack is None:
                        break
                    pending.append(pack)
                self._dispatch_next(
                    sd, pack, sum(len(g.members) for g in pack))
                pack = None
            if not inflight:
                break
            groups, res = self._retire_oldest(sd)
            if (invalidated or self.state_unwinds != sd.start_unwinds
                    or not self._note_session_events(sd, busy=True)):
                invalidated = True
                self._to_host_path(groups)
                if groups in pending:
                    pending.remove(groups)
                continue
            with stages.stage("host.commit", groups=len(groups)):
                i = 0
                for g in groups:
                    ms = self._sorted_members(g)
                    rows = res[0, i:i + len(ms)]
                    self.next_start_node_index = int(res[1, i + len(ms) - 1])
                    i += len(ms)
                    if invalidated or (rows < 0).any():
                        # Some member infeasible (or a prior group diverged):
                        # every row this group DID take is charged dirty (the
                        # carry placed them), and the exact host group cycle
                        # owns the entity (diagnosis, PodGroupPostFilter).
                        for r in rows:
                            if r >= 0:
                                dirty_rows.append(int(r))
                        self._to_host_path([g])
                        invalidated = True
                        continue
                    if not self._commit_gang_group(fw, g, ms, rows, node_names,
                                                   ok_rows, dirty_rows):
                        invalidated = True  # a member's host commit rejected a
                        # placement the carry already applied
                    if (self.state_unwinds != sd.start_unwinds
                            or not self._note_session_events(sd, busy=True)):
                        invalidated = True
                        sd.start_seq = self.cluster_event_seq
                        sd.start_unwinds = self.state_unwinds
            self._note_after_flush(sd)
            if groups in pending:
                pending.remove(groups)  # fully handled: out of crash recovery

        if pack:
            self._to_host_path(pack)
            if pack in pending:
                pending.remove(pack)

        self._close_session(sd, invalidated, "gang_session_invalidated")

    def _commit_gang_group(self, fw: Framework, qgpi: QueuedPodGroupInfo,
                           members: List[QueuedPodInfo], rows, node_names,
                           ok_rows: List[int], dirty_rows: List[int]) -> bool:
        """All members feasible on device: run the group commit exactly as
        schedule_pod_group's tail (assume into cache, reserve → permit →
        binding cycle per member, group bookkeeping). Returns False when any
        member's host commit rejected its placement — the device carry has
        that placement applied, so the caller must invalidate."""
        self.attempts += 1
        committed = 0
        attempted_uids = set()
        for m, r in zip(members, rows):
            attempted_uids.add(m.pod.uid)
            node = node_names[int(r)]
            m.pod.node_name = node
            self.cache.assume_pod(m.pod, m.pod_info)
            if self._commit_group_member(fw, m, CycleState(),
                                         ScheduleResult(suggested_host=node)):
                committed += 1
                ok_rows.append(int(r))
                self.device_scheduled += 1
            else:
                dirty_rows.append(int(r))
        _t_store = _time.perf_counter()
        group_key = (qgpi.group.namespace, qgpi.group.name)
        self.queue.clear_group_members(group_key, attempted_uids)
        self.queue.done(qgpi.uid)
        self.metrics.store_schedule_results_duration.observe(
            _time.perf_counter() - _t_store)
        self.metrics.podgroup_schedule_attempts.inc(
            "scheduled" if committed else "unschedulable")
        return committed == len(members)

    # -- placement-gang device evaluation ----------------------------------

    @staticmethod
    def _placement_plan_restriction_invariant(plan) -> bool:
        """True when the plan can be evaluated per-placement on device.
        Topology-SPREAD tables are: the host oracle computes them over the
        restricted list (cache.py assume_placement), and
        _placement_spread_overrides rebuilds each placement's restricted
        tables from the plan's per-node columns. Host-only:
        inter-pod-affinity tables (term matches against restricted pod sets)
        and image-locality (its spread discount divides by the restricted
        node count). Static row-local terms (fit, balance, taints,
        node-affinity preference) restrict exactly."""
        f = plan.features
        return (f.anti_axis.shape[0] == 0 and f.aff_axis.shape[0] == 0
                and f.ipa_axis.shape[0] == 0 and not plan.has_ipa_base
                and not bool(np.asarray(f.il_score).any()))

    def _placement_spread_overrides(self, plan, placements, index):
        """Per-placement restricted spread tables (the device analogue of
        running calPreFilterState / initPreScoreState over
        assume_placement's node list): scatter-add the plan's per-node
        match-count columns over each placement's rows. Returns the
        spread_overrides tuple for ops/kernel.py schedule_placements, or
        None when the plan carries no spread features."""
        f = plan.features
        c1p, c2p = f.dns_axis.shape[0], f.sa_axis.shape[0]
        if c1p == 0 and c2p == 0:
            return None
        vmax = plan.vmax
        p_pad = _pow2_pad(len(placements))
        n = len(self.snapshot.node_info_list)
        dns_axis = np.asarray(f.dns_axis)
        sa_axis = np.asarray(f.sa_axis)
        dns_counts = np.zeros((p_pad, c1p, vmax), np.int32)
        dns_dom = np.zeros((p_pad, c1p, vmax), bool)
        dns_forced0 = np.ones((p_pad, c1p), np.int32)  # pad rows: min 0
        sa_counts = np.zeros((p_pad, c2p, vmax), np.int32)
        sa_wq = np.zeros((p_pad, c2p), np.int64)
        nc1 = 0 if plan.dns_node_counts is None else plan.dns_node_counts.shape[0]
        nc2 = 0 if plan.sa_node_counts is None else plan.sa_node_counts.shape[0]
        for pi, placement in enumerate(placements):
            rows = np.array([r for name in placement.node_names
                             if (r := index.get(name)) is not None and r < n],
                            np.int64)
            for ci in range(nc1):
                vids = self.mirror.h_topo[dns_axis[ci], rows]
                elig = plan.dns_node_elig[ci, rows]
                ev = vids[elig]
                np.add.at(dns_counts[pi, ci], ev,
                          plan.dns_node_counts[ci, rows][elig])
                dns_dom[pi, ci, ev] = True
                nd = np.unique(ev).size
                md = plan.dns_min_domains[ci]
                dns_forced0[pi, ci] = 1 if (nd == 0 or (
                    md is not None and nd < md)) else 0
            for ci in range(nc2):
                vids = self.mirror.h_topo[sa_axis[ci], rows]
                live = plan.sa_node_live[rows]
                lv = vids[live]
                np.add.at(sa_counts[pi, ci], lv,
                          plan.sa_node_counts[ci, rows][live])
                size = (int(live.sum()) if plan.sa_hostname_axis[ci]
                        else np.unique(lv).size)
                sa_wq[pi, ci] = int(round(math.log(size + 2) * 1024))
        return (jnp.asarray(dns_counts), jnp.asarray(dns_dom),
                jnp.asarray(dns_forced0), jnp.asarray(sa_counts),
                jnp.asarray(sa_wq))

    def _evaluate_placements(self, fw: Framework, pg_state, group, members,
                             placements, start_index: int):
        """Stacked device evaluation of ALL candidate placements in one
        kernel call (ops/kernel.py schedule_placements) — the TPU form of
        the per-placement simulation loop. Falls back to the host loop when
        any member or the plan is outside the device ring."""

        host_loop = partial(super()._evaluate_placements, fw, pg_state, group,
                            members, placements, start_index)
        if not self.device_enabled or self.queue.nominator.has_nominated_pods():
            return host_loop()
        p0 = members[0].pod
        sig = fw.sign_pod(p0)
        if sig is None or any(
                fw.sign_pod(m.pod) != sig
                or self._batch_supported_memo(m.pod, fw) is not None
                or self._device_unsupported_profile(fw, m.pod) is not None
                or self._narrows(m.pod)  # the host loop, as before
                # claim-carrying members: host sims (no intra-sim claim dedup)
                or any(v.pvc_name for v in m.pod.volumes)
                for m in members):
            return host_loop()
        # Plan cache across group cycles: restriction-invariant, port-free
        # plans depend only on NODE state + the pod spec — our own commits
        # between cycles only move per-node aggregates, which flow through
        # the mirror's dirty-row scatter, NOT the feature tables. A stream
        # of identical gangs (the perf shape) then builds features once.
        ckey = (id(fw), sig, len(members), self.cluster_event_seq,
                self.mirror.np_cap)
        plan = self._placement_plan_cache.get(ckey)
        if plan is not None:
            self._sync_mirror()
            state = self.mirror.flush()  # resident stays mesh-committed
        else:
            try:
                state, plan = self.build_plan(fw, p0, len(members))
            except Unsupported:
                return host_loop()
            if not self._placement_plan_restriction_invariant(plan):
                return host_loop()
            # Spread-carrying plans are NOT cached across group cycles: the
            # per-node match-count columns change with every commit of a
            # matching pod, unlike the node-state aggregates that flow
            # through the mirror's dirty rows.
            self._placement_plan_cache.clear()
            if not (plan.port_selfblock or plan.has_aux
                    or plan.dns_node_counts is not None
                    or plan.sa_node_counts is not None):
                self._placement_plan_cache[
                    (id(fw), sig, len(members), self.cluster_event_seq,
                     self.mirror.np_cap)] = plan

        index = self._snapshot_rows()
        npc = self.mirror.np_cap
        # Pad the placement axis to a pow2 tier so XLA compiles once per
        # (placement tier, batch tier), not once per candidate count.
        p_pad = _pow2_pad(len(placements))
        # Mask cache: candidate placements for one topology key are identical
        # across a stream of identical groups (same domains, same rows).
        mkey = (self.cluster_event_seq, p_pad, npc,
                tuple(tuple(p.node_names) for p in placements))
        masks_dev = self._placement_mask_cache.get(mkey)
        if masks_dev is None:
            masks = np.zeros((p_pad, npc), bool)
            for pi, placement in enumerate(placements):
                for name in placement.node_names:
                    row = index.get(name)
                    if row is not None:
                        masks[pi, row] = True
            masks_dev = jnp.asarray(masks)
            self._placement_mask_cache.clear()
            self._placement_mask_cache[mkey] = masks_dev
        _t_pe = _time.perf_counter()
        res = np.asarray(schedule_placements(
            state, plan.features, plan.batch_pad, plan.fit_strategy,
            plan.vmax, masks_dev,
            n_active=np.int32(len(members)),
            has_pns=plan.has_pns, has_na_pref=plan.has_na_pref,
            port_selfblock=plan.port_selfblock,
            has_aux=plan.has_aux,
            spread_overrides=self._placement_spread_overrides(
                plan, placements, index)))  # [P, 2, B]
        self.placement_device_evals += 1
        self.metrics.placement_evaluations.inc(
            "device", value=len(placements))
        self.metrics.placement_evaluation_duration.observe(
            _time.perf_counter() - _t_pe)

        node_names = [ni.name for ni in self.snapshot.node_info_list]
        candidates = []
        for pi, placement in enumerate(placements):
            rows = res[pi, 0, :len(members)]
            placed = [(m, int(r)) for m, r in zip(members, rows) if r >= 0]
            failed = len(members) - len(placed)
            progress = PlacementProgress(len(placed), failed, len(members))
            if not placed or not fw.run_placement_feasible_plugins(
                    pg_state, group, progress).is_success():
                continue
            # Placement-eligible members carry no stateful-plugin simulation
            # data — the explicit volume/claim gate above keeps PVC members
            # off this path (batch_supported itself ACCEPTS bound-PVC pods;
            # do not remove that gate without establishing fresh-CycleState
            # parity for the placement commit) — so a fresh CycleState is
            # exactly what the host simulation would have produced for them.
            assignment = {m.pod.uid: (node_names[r], CycleState())
                          for m, r in placed}
            pga = PodGroupAssignments(
                placement,
                proposed=[(m.pod, assignment[m.pod.uid][0]) for m in members
                          if m.pod.uid in assignment],
                nodes=[self.snapshot.get(n) for n in placement.node_names])
            candidates.append((placement, assignment, pga))
        return candidates

    # -- resilience: device→host fallback + circuit breaker ----------------

    def _note_device_failure(self, exc: BaseException, where: str) -> None:
        """One unexpected device-path exception: log it, count it, charge
        the breaker, and discard every piece of device-resident state the
        failure may have poisoned (every holder registered in
        `_device_holders`). The caller reroutes the affected work to the
        host Evaluator."""
        reason = type(exc).__name__
        _log.error("device path failed in %s (%s: %s) — falling back to the "
                   "host path", where, reason, exc, exc_info=True)
        self.metrics.device_path_fallback.inc(reason)
        # Fallbacks sample at 100% (forced process context): a flight-
        # recorder dump of the span ring around this instant is exactly the
        # forensic artifact the breaker incidents need.
        self.tracer.record("device.fallback", self.tracer.proc_ctx(),
                           where=where, reason=reason)
        _spans.request_dump("device_fallback")
        opened = self.device_breaker.record_failure()
        if opened:
            _log.error(
                "device-path circuit breaker OPEN after %d consecutive "
                "failures; host path pinned for %.1fs",
                self.device_breaker.consecutive_failures,
                self.device_breaker.cooldown)
        self.metrics.device_breaker_state.set(
            0.0 if self.device_breaker.allows() else 1.0)
        for drop in self._device_holders.values():
            drop()
        self.metrics.batch_cache_flushed.inc("device_path_failure")
        self._after_flush = True

    def _note_device_success(self) -> None:
        self.device_breaker.record_success()
        self.metrics.device_breaker_state.set(0.0)

    def _note_bind_conflict(self, message: str, pod=None, node: str = "") -> None:
        """Bind-409 (sync unwind or async dispatcher error): beyond the
        base accounting, invalidate the score hint for the conflicted NODE
        only — the winner's commit re-encodes the row through the journal
        (docs/PERF.md hint-cache freshness contract). An async failure of
        any kind has already taken back the optimistic hint hit
        (_note_async_bind_lost)."""
        super()._note_bind_conflict(message, pod, node)
        if node:
            self._hints.note_conflict(node)

    def _note_own_bind_confirm(self, new) -> None:
        """The bind settled: drop the optimistic-hit take-back tag from the
        SCHEDULER's assumed object (the watch copy replaces it in the cache
        right after) — a later requeue of that object must not erase a hit
        that really bound."""
        st = self.cache.pod_states.get(new.uid)
        if st is not None:
            st.pod.__dict__.pop("_hint_bound", None)

    def _recover_qpi(self, qpi) -> None:
        """Host-path one entity stranded by a mid-session device failure.
        Pods the session already committed (bound or assumed onto a node)
        are done — re-running them would double-place; everything else gets
        the exact host cycle."""
        members = getattr(qpi, "members", None)
        bindings = getattr(self.clientset, "bindings", None) or {}
        if members is None:
            pod = qpi.pod
            if pod.node_name or pod.uid in bindings:
                self.queue.done(pod.uid)
                return
            self.host_path_pods += 1
            self.process_one(qpi)
        else:
            remaining = [m for m in members
                         if not (m.pod.node_name or m.pod.uid in bindings)]
            if not remaining:
                # _commit_gang_group finished this group before the crash
                # (it already cleared members + queue bookkeeping): re-running
                # the group cycle would double-place every member.
                return
            if len(remaining) < len(members):
                # Crash mid-gang-commit: some members are already bound.
                # Rerun the group cycle over the UNBOUND tail only — the
                # bound members are real cluster load now, and re-placing
                # them would double-count.
                qpi.members = remaining
            self.host_path_pods += len(remaining)
            self.process_one(qpi)

    # -- a nominated pod's own node, first and alone ------------------------

    def _run_nominated(self, fw: Framework, batch: "_Batch") -> bool:
        """evaluateNominatedNode (schedule_one.go:722) on the device path,
        for a head that holds a nomination: its nominated node alone, by a
        batch of one of the pod's own scheduling program whose plan is over
        that row only (``KeptPlan.derive`` ``rows``: the narrowing a
        PreFilterResult gets, to one node). The fit there counts the
        other nominations of equal or higher priority (the plan's nominated
        lane, which leaves the pod's own out), nothing is scored, and the
        start index stays where it was: findNodesThatFitPod returns before
        it advances it. True: the pod is dealt with,
        bound there with its nomination cleared (``_commit``). False: the
        node is gone or no longer takes the pod, nothing was changed, and
        the caller runs the ordinary cycle with the pod at the head of its
        batch. The stage ``nominated.eval`` and
        ``scheduler_nominated_evaluations_total{outcome}`` say which."""
        with self.stages.stage("nominated.eval", batch.sampled,
                               engine="device") as st:
            try:
                outcome = self._evaluate_nominated_node(fw, batch[0], st)
            except Unsupported:
                outcome = "fell_through"
            st.say(outcome=outcome)
        self.metrics.nominated_evaluations.inc(outcome)
        # a bind the apiserver refused was unwound and requeued by _commit:
        # the pod is dealt with, and no ordinary cycle follows either
        return outcome != "fell_through"

    def _evaluate_nominated_node(self, fw: Framework, qpi: QueuedPodInfo,
                                 st) -> str:
        """``bound``, ``fell_through`` or ``bind_refused``; the open
        ``nominated.eval`` stage `st` is told where the plan came from
        (``plan`` = ``kept`` / ``built``)."""
        pod = qpi.pod
        self._sync_mirror()
        row = self._snapshot_rows().get(pod.nominated_node_name)
        if row is None:
            return "fell_through"  # the node left: the ordinary cycle
        start = self.next_start_node_index
        state, plan, how = self._preemptor_plan(
            fw, pod, self.max_batch, "nominated", only_row=row)
        st.say(plan=how)
        attrs = self._dispatch_attrs(plan, 1, 0)
        with self.stages.stage("device.dispatch", **attrs):
            results, _carry = self._dispatch(state, plan, 1, None)
        self._count_dispatch(attrs)
        with self.stages.stage("device.wait", batch=1, seq=attrs["seq"]):
            res = np.asarray(results)
        self._note_device_success()
        if int(res[0, 0]) != 0:  # the plan's one row
            return "fell_through"
        with self.stages.stage("host.commit", batch=1, tail="single"):
            bound = self._commit(fw, qpi, pod.nominated_node_name)
        self.metrics.commit_pods.inc("single")
        # findNodesThatFitPod returns before it advances the index
        self.next_start_node_index = start
        return "bound" if bound else "bind_refused"

    # -- device preemption dry run -----------------------------------------

    def device_dry_run_preemption(self, fw: Framework, state, pod,
                                  node_to_status, num_candidates: int,
                                  start: int):
        """Batched DryRunPreemption (ops/kernel.py dry_run_preemption): every
        candidate node's minimal victim set in ONE kernel call, replacing the
        host Evaluator's per-node simulation loop (preemption.go:425).
        Returns rotation-ordered, capped [Candidate] — or None when the
        preemptor (or cluster) needs the exact host dry run: topology-coupled
        features change with victim removal (spread counts, affinity terms,
        freed host ports, freed attach room), which the static-filter + fit
        arithmetic kernel doesn't model. The SELECTED candidate is
        host-verified by the caller (plugins/preemption.py post_filter)."""
        if not self.device_enabled or not self.device_breaker.allows():
            return None
        if self._resources_only_block(pod) is not None:
            return None
        if self._device_unsupported_profile(fw, pod) is not None:
            return None
        try:
            return self._device_dry_run_preemption(
                fw, pod, node_to_status, num_candidates, start)
        except Unsupported:
            return None
        except Exception as e:  # noqa: BLE001 - crash-proof fallback
            # A shape error (victim tensors at one r_slots width, the plan
            # at another) lands here: one count, one breaker charge, and the
            # host Evaluator reruns the dry run exactly — never a crashed
            # PostFilter cycle.
            self._note_device_failure(e, "preemption_dry_run")
            return None

    def _device_dry_run_preemption(self, fw: Framework, pod, node_to_status,
                                   num_candidates: int, start: int):
        # The parts are clocked only for a postfilter.preempt stage that is
        # open around this call and listened to (StageLedger.heard); for
        # nobody, every reading is float(), 0.0.
        st = self.stages.heard("postfilter.preempt")
        clock = _time.perf_counter if st is not None else float
        t0 = clock()
        self._sync_mirror()
        nodes = self.snapshot.node_info_list
        if any(ni.pods_with_required_anti_affinity for ni in nodes):
            # Removing an anti-carrying victim could clear exist_anti, which
            # the kernel treats as static.
            return None
        # The arrays are the holder's own and the next call patches them in
        # place. That is safe because this method fetches the what-if's
        # answer (np.asarray(on_device)) before it returns: no dispatch that
        # reads them is in flight by then (the CPU backend's jnp.asarray may
        # alias host memory instead of copying it).
        victims = self._victims
        built = victims.build(pod, self.snapshot)
        self.metrics.preemption_victim_rows.inc(
            "rebuilt", value=victims.rebuilt)
        self.metrics.preemption_victim_rows.inc(
            "kept", value=len(nodes) - victims.rebuilt)
        if built is None:
            return None
        vic_req, vic_valid, potential = built
        t_victims = clock()
        sent = self.mirror.transfers.total()
        dstate, plan, how = self._preemptor_plan(fw, pod, 1, "dry_run")
        sent = int(self.mirror.transfers.total() - sent)
        if vic_req.shape[2] != self.mirror.r_slots:
            # build_plan interned the preemptor's never-seen scalar slots
            # AFTER the victim tensors were built, growing the mirror's
            # resource tier. The grown slots name
            # resources no victim carries, so zero-padding vic_req to the
            # plan's width is exact — without it the kernel's
            # `state.req_r - sum_vic` raises a shape error.
            grown = np.zeros(
                (vic_req.shape[0], vic_req.shape[1], self.mirror.r_slots),
                np.int64)
            grown[:, :, :vic_req.shape[2]] = vic_req
            vic_req = grown
        if self._fault_hook is not None:
            self._fault_hook("preempt")
        # The plan's nominated lane (what the nominated pods of equal or
        # higher priority hold, this preemptor's own nomination left out)
        # counts in every fit of the what-if; zeros of the same shape where
        # nobody is nominated, so both are one compiled program.
        f = plan.features
        if not plan.has_nom:
            nom_req, nom_pods = self._empty_nom_lane()
            f = f._replace(nom_req=nom_req, nom_pods=nom_pods)
        t_plan = clock()
        on_device = dry_run_preemption(
            dstate, f, jnp.asarray(vic_req), jnp.asarray(vic_valid),
            vic_valid.shape[1])
        t_dispatch = clock()
        res = np.asarray(on_device)
        t_fetch = clock()
        if st is not None:
            # the stage's parts, and the shapes the kernel's cost is reckoned
            # from (rows, victim slots, resource slots)
            st.say(victims_ms=round(1e3 * (t_victims - t0), 3),
                   plan_ms=round(1e3 * (t_plan - t_victims), 3), plan=how,
                   transfers=sent,
                   dispatch_ms=round(1e3 * (t_dispatch - t_plan), 3),
                   fetch_ms=round(1e3 * (t_fetch - t_dispatch), 3),
                   victim_rows_rebuilt=victims.rebuilt,
                   rows=int(vic_valid.shape[0]), k=int(vic_valid.shape[1]),
                   r=int(vic_req.shape[2]),
                   nom_rows=len({row for row, _ in
                                 self._nominated_lane(pod) or ()}))
        self.preemption_device_evals += 1
        self._note_device_success()
        feasible, vmask = res[:, 0], res[:, 1:]
        n = len(nodes)
        out = []
        for i in range(n):
            r = (start + i) % n
            st = node_to_status.get(nodes[r].name)
            if st is not None and st.code == UNSCHEDULABLE_AND_UNRESOLVABLE:
                continue  # nodesWherePreemptionMightHelp
            if not feasible[r]:
                continue
            victims = [pi for j, pi in enumerate(potential[r]) if vmask[r, j]]
            out.append(Candidate(node_name=nodes[r].name, victims=victims))
            if len(out) >= num_candidates:
                break
        return out

    # -- device dispatch ---------------------------------------------------

    def _profile_weights(self, fw: Framework) -> Tuple[int, int, int, int, int, int, int]:
        w = {p.name: weight for p, weight in fw.score_plugins}
        return (
            w.get("TaintToleration", 0),
            w.get("NodeResourcesFit", 0),
            w.get("PodTopologySpread", 0),
            w.get("InterPodAffinity", 0),
            w.get("NodeResourcesBalancedAllocation", 0),
            w.get("NodeAffinity", 0),
            w.get("ImageLocality", 0),
        )

    def _profile_filters(self, fw: Framework) -> Tuple[bool, bool, bool, bool, bool]:
        names = {p.name for p in fw.filter_plugins}
        return (
            "NodeName" in names,
            "NodeUnschedulable" in names,
            "TaintToleration" in names,
            "NodeAffinity" in names,
            "NodeResourcesFit" in names,
        )

    def _nominated_device_block(self, fw: Framework, pod) -> Optional[str]:
        """Why `pod` cannot ride the device while nominations exist (None =
        the nominated LANE covers it). The lane models pass-1 of the two-pass
        filter (runtime/framework.go:1275,1300-1317) for RESOURCES only:
        nominated pods' requests/counts tighten the fit filter on their
        nominated rows. Features where a nominated pod interacts beyond
        resources — topology domain counts, affinity terms, host ports,
        counted volume/claim constraints — take the host path, as does a pod
        whose own filters a nominated pod's spec could reject (a nominated
        pod carrying required anti-affinity)."""
        nom = self.queue.nominator
        if not nom.has_nominated_pods():
            return None
        reason = self._resources_only_block(pod)
        if reason is not None:
            return f"nominated pods with {reason}"
        for pi in nom.all_nominated_pod_infos():
            if pi.required_anti_affinity_terms:
                return "nominated pod carries required anti-affinity"
        return None

    @staticmethod
    def _resources_only_block(pod) -> Optional[str]:
        """Why `pod`'s filter outcome depends on more than per-node resource
        arithmetic + static per-batch masks. Shared by the nominated lane and
        the preemption dry-run kernel: both model OTHER pods' effects (a
        nomination counted in, a victim removed) as pure request/count
        deltas, which is only exact when the pod carries none of these."""
        if pod.topology_spread_constraints:
            return "spread constraints"
        aff = pod.affinity
        if aff is not None and (aff.pod_affinity or aff.pod_anti_affinity):
            return "pod affinity"
        if pod.host_ports():
            return "host ports"
        if any(v.pvc_name for v in pod.volumes) or getattr(
                pod, "resource_claims", None):
            return "counted claims"
        return None

    def _snapshot_rows(self) -> dict:
        """Node name -> row of the snapshot's node_info_list (call AFTER
        update_snapshot): the snapshot's own index where it covers the
        list, else made from it."""
        index = self.snapshot._index
        nodes = self.snapshot.node_info_list
        if len(index) != len(nodes):
            index = {ni.name: i for i, ni in enumerate(nodes)}
        return index

    def _nominated_lane(self, pod) -> Optional[list]:
        """[(snapshot row, PodInfo)] for the lane: nominated pods with
        priority >= the batch pod's, on rows present in the snapshot.
        Call AFTER update_snapshot (rows index node_info_list)."""
        nom = self.queue.nominator
        if not nom.has_nominated_pods():
            return None
        index = self._snapshot_rows()
        out = []
        for node_name, pis in nom._node_to_pods.items():
            row = index.get(node_name)
            if row is None:
                continue
            for pi in pis:
                if pi.pod.priority >= pod.priority and pi.pod.uid != pod.uid:
                    out.append((row, pi))
        return out or None

    def _device_unsupported_profile(self, fw: Framework, pod) -> Optional[str]:
        """PTS/IPA are always enforced by the kernel when the pod carries the
        feature; if the profile disables the plugin, take the host path."""
        names = {p.name for p in fw.filter_plugins}
        if pod.topology_spread_constraints and "PodTopologySpread" not in names:
            return "spread constraints without PodTopologySpread plugin"
        aff = pod.affinity
        if aff is not None and (aff.pod_affinity or aff.pod_anti_affinity) \
                and "InterPodAffinity" not in names:
            return "pod affinity without InterPodAffinity plugin"
        pts = fw.plugin("PodTopologySpread")
        if pts is not None and getattr(pts, "default_constraints", ()) \
                and not pod.topology_spread_constraints:
            return "plugin-level default spread constraints"
        if fw.plugin("DynamicResources") is not None:
            req = pod.resource_request()
            if req.scalar_resources and any(
                    dc.extended_resource_name in req.scalar_resources
                    for dc in self.clientset.device_classes.values()
                    if dc.extended_resource_name):
                # Extended resources backed by DRA: the kernel's fit math
                # would treat them as plain node scalars, but the plugin may
                # satisfy them from ResourceSlices instead.
                return "extended resources backed by DRA"
        return None

    def _sync_mirror(self) -> None:
        """The snapshot refreshed from the cache and the mirror's staging
        rows synced to it, the resident copy committed to the mesh's
        shardings (or none): what every reader of either starts from."""
        self.cache.update_snapshot(self.snapshot)
        if self.mesh is not None:
            self.mirror.commit_shardings(mesh_state_shardings(self.mesh))
        else:
            self.mirror.commit_shardings(None)
        self.mirror.sync(self.snapshot.node_info_list)

    def _narrows(self, pod) -> bool:
        """The pod's NodeAffinity PreFilterResult narrows its cycle
        (plugins/basic.py NodeAffinity.narrowed_node_names), so its
        sessions plan over the named nodes' rows only. Memoized on the
        template's shared holder, as its signature is: a clone never
        mutates its spec."""
        if pod.affinity is None:
            return False
        shared = pod.__dict__.get("_sig_shared")
        if shared is not None and "_narrows" in shared:
            return shared["_narrows"]
        out = NodeAffinity.narrowed_node_names(pod) is not None
        if shared is not None:
            shared["_narrows"] = out
        return out

    def _plan_rows(self, pod, only_row: Optional[int] = None):
        """The snapshot rows a plan for `pod` is over, or None for all of
        them: ``only_row`` alone (a nominated pod's own node), else the
        nodes its PreFilterResult names (ops/features.py narrowed_rows).
        Call AFTER `_sync_mirror`."""
        if only_row is not None:
            return (only_row,)
        if not self._narrows(pod):
            return None
        return narrowed_rows(pod, self._snapshot_rows())

    def _narrowed(self, entry: KeptPlan, pod, rows, batch_size: int,
                  lane=None):
        """(device state, plan) over `rows` only, derived from `entry`'s
        plan over every row (KeptPlan.derive): the staging rows uploaded as
        a state of their own (NodeStateMirror.rows_state), placed as any
        state and plan are. `lane`: the nominated lane's requests where the
        caller has them (`lane_requests`)."""
        if lane is None:
            lane = lane_requests(self.mirror, self._nominated_lane(pod))
        shards = mesh_shard_count(self.mesh) if self.mesh is not None else 1
        plan = entry.derive(
            self.mirror, self.snapshot.num_nodes(), batch_size=batch_size,
            start_index=self.next_start_node_index, nom_reqs=lane,
            rows=rows, shards=shards,
            percentage_of_nodes_to_score=self.percentage_of_nodes_to_score)
        state = self.mirror.rows_state(
            padded_rows(rows, plan.plan_rows), len(rows))
        if self.mesh is not None:
            state = shard_node_state(state, self.mesh)
            plan.features = shard_features(plan.features, self.mesh)
        return state, plan

    def build_plan(self, fw: Framework, pod, batch_size: int,
                   only_row: Optional[int] = None):
        """(device_state, BatchPlan) for `batch_size` pods like `pod`, as a
        session of them would plan: over every row of the snapshot
        (`_build_full_plan`), or, where the pod's PreFilterResult narrows
        or ``only_row`` names the one snapshot row the plan may land on (a
        nominated pod's own node), over those rows only (`_narrowed`). Also
        the graft/bench entry's way to produce kernel inputs."""
        state, plan = self._build_full_plan(fw, pod, batch_size)
        rows = self._plan_rows(pod, only_row)
        if rows is None:
            return state, plan
        return self._narrowed(
            KeptPlan(plan, self.cluster_event_seq, None), pod, rows,
            batch_size)

    def _build_full_plan(self, fw: Framework, pod, batch_size: int):
        """Snapshot → mirror sync → batch feature build → device flush, over
        every row of the snapshot. Returns (device_state, BatchPlan).

        Mesh-first: under a mesh the mirror's RESIDENT copy is committed to
        mesh_state_shardings, so flush() uploads host staging straight to
        the sharded placement and later dirty scatters / delta patches ride
        pinned jits on the resident itself — no per-session single-device
        copy + device_put round-trip of the whole state."""
        self._sync_mirror()
        ipa = fw.plugin("InterPodAffinity")
        dra_enabled, dra_in_use = self._dra_ctx(fw)
        plan = build_batch(
            pod,
            batch_size=batch_size,
            mirror=self.mirror,
            snapshot=self.snapshot,
            ns_labels_fn=self.cache.namespace_labels,
            percentage_of_nodes_to_score=self.percentage_of_nodes_to_score,
            start_index=self.next_start_node_index,
            weights=self._profile_weights(fw),
            filters_on=self._profile_filters(fw),
            extra_filters={
                name: name in {p.name for p in fw.filter_plugins}
                for name in ("NodePorts", "NodeDeclaredFeatures")
            },
            hard_pod_affinity_weight=getattr(ipa, "hard_pod_affinity_weight", 1),
            ignore_preferred_terms_of_existing_pods=getattr(
                ipa, "ignore_preferred_terms_of_existing_pods", False),
            fit_plugin=fw.plugin("NodeResourcesFit"),
            clientset=self.clientset, pvc_refs=self.cache.pvc_refs,
            limited_drivers=self.limited_drivers(),
            dra_enabled=dra_enabled,
            dra_in_use=dra_in_use,
            nominated=self._nominated_lane(pod),
            stages=self.stages,
        )
        self._count_ipa(plan)
        state = self.mirror.flush()  # committed to the mesh placement
        if self.mesh is not None:
            plan.features = shard_features(plan.features, self.mesh)
        return state, plan

    # -- the keeper of built plans -------------------------------------------
    #
    # `self._plans`: template key -> KeptPlan. A built plan is asked for
    # again by a session that starts where its template's last clean session
    # ended (the entry's tail; at most one lives) and by a preemptor that
    # derives from it (an entry with a guard; at most _KEPT_PLANS). Both go
    # one way: `_kept_plan` (look it up; ask the journal, once, whether it
    # outlived it; drop or use it), then `_build_kept_plan` if it did not.

    def _template_key(self, fw: Framework, pod, sig, aux_shape,
                      neutral_ok: bool = True) -> tuple:
        """What a built plan is kept under: the pod's signature, the neutral
        (namespace-erased) one where the pod is eligible, the profile, the
        counted-constraint shape and the claims' version."""
        nsig = self._neutral_sig(fw, pod, sig) if neutral_ok else None
        mode = ("neutral", nsig) if nsig is not None else ("exact", sig)
        return mode + (id(fw), aux_shape,
                       getattr(self.clientset, "resource_claims_rv", 0))

    def _kept_plan_guard(self) -> tuple:
        """What a kept plan holds that neither its key nor a journal event
        says: the shapes its arrays were built at, the mesh they are placed
        on, the sample size behind `to_find`, and the count of bound pods
        that carry inter-pod terms (this scheduler's own binds are not
        journalled, the carry holds them; one of a pod with terms moves
        `exist_anti` / `ipa_base` of a plan that has neither, and moves this
        count, as it gates `_neutral_sig`)."""
        return plan_shape(self.mirror) + (
            self.mesh, self.percentage_of_nodes_to_score,
            self.cache.affinity_pod_refs)

    def _kept_plan(self, key, tail: bool):
        """(entry, events, classification): the template's entry where it
        holds what the caller needs (``tail``: a session's tail; else a
        plan to derive from) and outlived the journal's events since (the
        tail's end; else the plan's last use): they classify under
        `_classify_delta`, the one rule, and a plan to derive from kept its
        guard. Else the entry is None and what was voided is dropped."""
        entry = self._plans.get(key)
        if entry is None or (entry.tail_seq if tail else entry.guard) is None:
            return None, (), None
        events, cls = self._classify_since(
            entry.tail_seq if tail else entry.seq, entry.plan)
        if cls is None or not (
                tail or entry.guard == self._kept_plan_guard()):
            self._drop_kept(key, entry, tail)
            entry = None
        return entry, events, cls

    def _drop_kept(self, key, entry: KeptPlan, tail: bool) -> None:
        """The entry's tail (``tail``), or its guard, goes: a plan that is
        not to be derived from any more stays only where a live tail needs
        it. An entry left with neither leaves the map."""
        if tail:
            entry.drop_tail()
        else:
            entry.guard = None
        if entry.guard is None and entry.tail_seq is None:
            del self._plans[key]

    def _build_kept_plan(self, fw: Framework, pod, key, batch_size: int):
        """(device state, entry) of a full build for `pod`'s template
        (`_build_full_plan`, over every row), kept under `key` to derive from
        where the pod's filters read other pods by their requests alone
        (`_resources_only_block`, the precondition of both sites that
        derive): nothing a pod brings to a node moves such a plan's
        features. Else (or with no key: an unsignable pod; or where a live
        tail's plan holds the key) the entry is the caller's alone. Nobody
        writes to a built plan: the session's own object is kept."""
        state, plan = self._build_full_plan(fw, pod, batch_size)
        plans = self._plans
        held = plans.get(key)
        if key is None or self._resources_only_block(pod) is not None or (
                held is not None and held.guard is None):
            return state, KeptPlan(plan, self.cluster_event_seq, None)
        plans.pop(key, None)
        derivable = [k for k, e in plans.items() if e.guard is not None]
        if len(derivable) >= _KEPT_PLANS:  # the oldest leaves
            self._drop_kept(derivable[0], plans[derivable[0]], tail=False)
        entry = plans[key] = KeptPlan(plan, self.cluster_event_seq,
                                      self._kept_plan_guard())
        return state, entry

    def _claim_tail(self, keys, priority: int):
        """A session starts: the one tail that may live is this session's
        to resume, or it is dropped. Returns (its key, "") where a session
        of one of `keys` left it with `attempts`, `state_unwinds` and the
        nomination key as they are now; else (None, why a full build
        follows): ``first`` (no tail lived), ``other_pod`` (another
        template's, profile's or attempt count's), ``nomination`` (only
        the nominations moved: the plan's nominated lane is stale)."""
        mine, cause = None, "first"
        for key, entry in list(self._plans.items()):
            if entry.tail_seq is None:
                continue
            cause = "other_pod"
            if key in keys and (entry.attempts, entry.state_unwinds) == (
                    self.attempts, self.state_unwinds):
                if entry.nom_key == self._nom_resume_key(priority):
                    mine, cause = key, ""
                    continue
                cause = "nomination"
            self._drop_kept(key, entry, tail=True)
        return mine, cause

    def _hand_back_tail(self, sd: "_SessionDelta") -> None:
        """A clean session's end state, for the template's next session to
        resume from: under the neutral (namespace-erased) signature when
        eligible, so label/namespace-only-different sessions chain. The
        template's entry takes it where it holds this session's plan; else
        an entry of its own does, from which nobody derives."""
        key = self._template_key(sd.fw, sd.pod, sd.sig, sd.aux_shape,
                                 sd.neutral_ok)
        entry = self._plans.get(key)
        if entry is None or entry.plan is not sd.plan:
            entry = self._plans[key] = KeptPlan(
                sd.plan, self.cluster_event_seq, None)
        entry.state, entry.carry = sd.state, sd.carry
        entry.node_names = sd.node_names
        entry.tail_seq = self.cluster_event_seq
        entry.attempts, entry.state_unwinds = self.attempts, self.state_unwinds
        entry.nom_key = self._nom_resume_key(sd.pod.priority)

    def _preemptor_plan(self, fw: Framework, pod, batch_size: int, site: str,
                        only_row: Optional[int] = None):
        """(device state, plan, ``kept`` | ``built``) for ONE pod of a
        template that was planned for before: the what-if of its preemption
        (`site` ``dry_run``) and, once it is nominated, the evaluation of
        its own node (``nominated``, with ``only_row``: the plan and its
        state are over that row alone, `_narrowed`, and the mirror's flush
        is left to whoever next needs the whole state). Call AFTER
        `_sync_mirror`. The template's kept plan holds while the events
        since classify (`_kept_plan`): a `pod_local` plan and plain pods'
        events dirty mirror rows, never features, nor does a node update
        (taints, allocatable); the rest `KeptPlan.derive` makes again. The
        device state is the mirror's flush, as in `build_plan`: the dirtied
        rows are scattered, the truth the what-if reads. Every call counts
        in `scheduler_preemptor_plan_total{site, how}`; session starts keep
        their own counters."""
        sig = fw.sign_pod(pod)
        key = (self._template_key(fw, pod, sig, self._aux_shape(pod))
               if sig is not None else None)
        # the lane's scalar slots before the guard is read: a never-seen
        # one grows r_slots, and with it the guard (so does a bound pod's,
        # which the mirror's sync interns)
        lane = lane_requests(self.mirror, self._nominated_lane(pod))
        entry, _events, _cls = self._kept_plan(key, tail=False)
        how = "built" if entry is None else "kept"
        self.metrics.preemptor_plans.inc(site, how)
        if entry is None:
            # (a plan that is not one to keep is still derived from, once)
            state, entry = self._build_kept_plan(fw, pod, key, batch_size)
        else:
            entry.seq = self.cluster_event_seq
            state = self.mirror.flush() if only_row is None else None
        if only_row is not None:
            return self._narrowed(entry, pod, (only_row,), batch_size,
                                  lane) + (how,)
        plan = entry.derive(
            self.mirror, self.snapshot.num_nodes(), batch_size=batch_size,
            start_index=self.next_start_node_index, nom_reqs=lane)
        if self.mesh is not None:
            plan.features = shard_features(plan.features, self.mesh)
        return state, plan, how

    def _count_ipa(self, plan) -> None:
        """What a built plan's required inter-pod term tables cost
        (scheduler_plan_ipa_terms_total; the `plan.ipa` span carries the same
        two numbers), and whether its anti filter has something to refuse,
        by BatchPlan.anti_rowlocal (scheduler_plan_anti_lane_total: true is
        the lap path; false shared domains, or only existing pods' terms)."""
        if plan.ipa_matches:
            self.metrics.plan_ipa_terms.inc("matches", value=plan.ipa_matches)
            self.metrics.plan_ipa_terms.inc("term_pods",
                                            value=plan.ipa_term_pods)
        if plan.ipa_pods_walked:
            # the score-table walk (`plan.ipa_score` carries the same two)
            self.metrics.plan_ipa_terms.inc("score_matches",
                                            value=plan.ipa_score_matches)
            self.metrics.plan_ipa_terms.inc("pods_walked",
                                            value=plan.ipa_pods_walked)
        if plan.anti_lane:
            self.metrics.plan_anti_lane.inc(
                "true" if plan.anti_rowlocal else "false")

    def warm_for(self, pod, nominated: bool = False) -> None:
        """Compile the kernel shapes a workload of `pod`-shaped pods will hit,
        WITHOUT scheduling anything: a dispatch with n_active=0 runs the
        call's prologue and a loop of zero trips. Measuring harnesses call this so
        XLA compilation lands outside the measured window. Warms both the
        fresh-carry and chained-carry traces.

        The warm calls MUST be call-signature-identical to the session's
        dispatch (run_device_session) — `carry_in=None` passed explicitly is
        a DIFFERENT kwargs pytree than omitting the kwarg, and a mismatch
        recompiles (~1 min) inside the measured window. Sessions always plan
        with self.max_batch, so that is the only batch_pad tier to warm."""
        fw = self.framework_for_pod(pod)
        if batch_supported(pod, self.snapshot,
                           fit_plugin=fw.plugin("NodeResourcesFit")) is not None:
            return
        state, plan = self.build_plan(fw, pod, self.max_batch)

        def warm(p, dispatch=partial(self._dispatch, count=False)):
            # The live call path itself, uncounted: shard_map_dispatches
            # says how many LIVE dispatches rode the sharded lap.
            _res, carry = dispatch(state, p, 0, None)
            res, _ = dispatch(state, p, 0, carry)
            np.asarray(res)  # block until compiled + executed

        warm(plan)
        if self._shard_map_fn(plan) is not None:
            # The live dispatch rides the shard_map lap path — but a
            # mid-workload row_local flip (an anti-affinity pod lands and
            # exist_anti goes nonzero, or a node-tier regrow breaks shard
            # divisibility) drops later sessions onto the GSPMD
            # schedule_batch fallback. Warm that trace too, or the flip
            # puts its ~1min XLA compile inside the measured window (the
            # same hazard as the anti_rowlocal fallback below).
            warm(plan, self._gspmd_dispatch)
        if plan.anti_rowlocal:
            # anti_rowlocal is topology-derived (all anti axes singleton) and
            # can flip to False mid-workload (e.g. churn adds a node sharing a
            # hostname-like value): warm the conservative fallback trace too
            # so the flip can't put a compile inside the measured window.
            warm(dataclasses.replace(plan, anti_rowlocal=False))

        def warm_with_a_lane(p):
            # Preemption workloads flip the nominated lane on mid-run (the
            # first nomination would otherwise compile inside the measured
            # window): warm the has_nom variant with an empty lane — shapes
            # and statics are identical to the live nominated plan.
            if not p.has_nom:
                nom_req, nom_pods = self._empty_nom_lane(p.plan_rows)
                nf = p.features._replace(nom_req=nom_req, nom_pods=nom_pods)
                warm(dataclasses.replace(p, features=nf, has_nom=True))

        if nominated:
            warm_with_a_lane(plan)
            if self.snapshot.num_nodes():
                # The nominated retry's own program: a plan over one row
                # (_evaluate_nominated_node), with and without a lane.
                state, one = self.build_plan(fw, pod, self.max_batch,
                                             only_row=0)
                warm(one)
                warm_with_a_lane(one)

    def _empty_nom_lane(self, rows: Optional[int] = None):
        """A nominated lane that holds nothing, at the live lane's shapes
        (`rows` of them: the mirror's, or a narrowed plan's) and (under a
        mesh) committed shardings, which jit keys on: shard_features puts
        the lane on the node axis. Kept per shape."""
        key = (rows or self.mirror.np_cap, self.mirror.r_slots, self.mesh)
        if self._empty_nom_key != key:
            nom_req = jnp.zeros(key[:2], jnp.int64)
            nom_pods = jnp.zeros(key[0], jnp.int32)
            if self.mesh is not None:
                nom_req = jax.device_put(
                    nom_req, NamedSharding(self.mesh, P("nodes", None)))
                nom_pods = jax.device_put(
                    nom_pods, NamedSharding(self.mesh, P("nodes")))
            self._empty_nom_key, self._empty_nom = key, (nom_req, nom_pods)
        return self._empty_nom

    def warm_for_preemption(self, pod) -> None:
        """Compile the dry-run program a preemptor shaped like ``pod`` will
        meet, at the victim width the cluster has now, WITHOUT evicting
        anybody: the what-if itself, its answer dropped. Measuring harnesses
        call it beside ``warm_for(pod, nominated=True)`` (the scheduling
        program of the failed attempt and of the nominated retry, with and
        without a lane), so no compile lands inside the measured window."""
        fw = self.framework_for_pod(pod)
        evals = self.preemption_device_evals
        self.device_dry_run_preemption(fw, None, pod, {}, 1, 0)
        self.preemption_device_evals = evals

    def warm_for_placements(self, pod, group_size: int,
                            n_placements: int) -> None:
        """Compile the stacked placement-evaluation kernel for the tiers a
        topology-constrained gang workload will hit (inert n_active=0
        dispatch), so XLA compilation lands outside the measured window —
        the placement analogue of warm_for."""
        fw = self.framework_for_pod(pod)
        if self._batch_supported_memo(pod, fw) is not None:
            return
        try:
            state, plan = self.build_plan(fw, pod, group_size)
        except Unsupported:
            return
        if not self._placement_plan_restriction_invariant(plan):
            return
        p_pad = _pow2_pad(max(1, n_placements))
        masks = jnp.zeros((p_pad, self.mirror.np_cap), bool)
        overrides = None
        f = plan.features
        if f.dns_axis.shape[0] or f.sa_axis.shape[0]:
            # Warm the spread-override trace with empty tables of the live
            # shapes (pad lanes are inert at n_active=0).
            overrides = (
                jnp.zeros((p_pad, f.dns_axis.shape[0], plan.vmax), jnp.int32),
                jnp.zeros((p_pad, f.dns_axis.shape[0], plan.vmax), bool),
                jnp.ones((p_pad, f.dns_axis.shape[0]), jnp.int32),
                jnp.zeros((p_pad, f.sa_axis.shape[0], plan.vmax), jnp.int32),
                jnp.zeros((p_pad, f.sa_axis.shape[0]), jnp.int64),
            )
        res = schedule_placements(
            state, plan.features, plan.batch_pad, plan.fit_strategy,
            plan.vmax, masks, n_active=np.int32(0),
            has_pns=plan.has_pns, has_na_pref=plan.has_na_pref,
            port_selfblock=plan.port_selfblock, has_aux=plan.has_aux,
            spread_overrides=overrides)
        np.asarray(res)

    def _shard_map_fn(self, plan):
        """The explicit-collectives lap kernel for this plan, or None when
        the GSPMD-compiled schedule_batch owns the dispatch. Under a mesh a
        plan that rides the lap (BatchPlan.rides_lap) and is row-local
        (BatchPlan.row_local) takes shard_map: per-shard work is provably
        local and the per-lap collectives are two small exchanges, where
        GSPMD infers about twice as many (collective counts of compiled
        programs, a CPU dry run — not a speed)."""
        if self.mesh is None or not (plan.rides_lap and plan.row_local):
            return None
        if plan.plan_rows % mesh_shard_count(self.mesh):
            return None  # node tier not divisible across shards
        return sharded_lap_schedule(self.mesh, plan.batch_pad,
                                    plan.fit_strategy, plan.vmax)

    def _dispatch(self, state, plan, n_active: int, carry, count=True):
        """The ONLY kernel call site. Every dispatch — warm or live — must
        be call-signature-identical (kwarg set included: static kwargs are
        part of jit's cache-key pytree structure), or the warmed trace
        misses and a ~1min XLA compile lands inside the measured window.
        The path choice (shard_map lap vs GSPMD schedule_batch) is a pure
        function of (mesh, plan), so it is constant for a session's
        lifetime and warm_for warms the same path the live session runs
        (with `count=False`: not a live dispatch)."""
        if self._fault_hook is not None:
            self._fault_hook("dispatch")
        fn = self._shard_map_fn(plan)
        if fn is not None:
            self.shard_map_dispatches += count
            return fn(state, plan.features, np.int32(n_active), carry)
        return self._gspmd_dispatch(state, plan, n_active, carry)

    def _dispatch_attrs(self, plan, n_active: int, depth: int) -> dict:
        """What the `device.dispatch` stage of this scheduler's next
        dispatch opens with: the plan's own account of it
        (BatchPlan.dispatch_attrs), its ordinal in this scheduler's life
        (`seq`: the session keeps it beside the batch, and the
        `device.wait` that retires the batch opens with the same), and the
        pipeline depth it found (`inflight`)."""
        self.dispatch_seq += 1
        attrs = plan.dispatch_attrs(n_active)
        attrs["seq"] = self.dispatch_seq
        attrs["inflight"] = depth
        return attrs

    def _count_dispatch(self, attrs: dict) -> None:
        """One live dispatch into the registry, from what its stage said of
        it (BatchPlan.dispatch_attrs): which engine placed it and, for the
        scans, the steps it ran beside those its padded width would have
        cost a fixed-length scan."""
        m = self.metrics
        m.device_batches.inc(attrs["engine"])
        steps = attrs.get("steps")
        if steps is not None:
            m.device_scan_steps.inc("run", value=steps)
            m.device_scan_steps.inc("skipped",
                                    value=attrs["batch_pad"] - steps)
        m.batch_attempts.inc("dispatched")
        m.batch_size.observe(attrs["batch"])

    def _gspmd_dispatch(self, state, plan, n_active: int, carry,
                        call=schedule_batch):
        """The GSPMD-compiled schedule_batch call — one kwargs set shared
        by the live fallback dispatch, warm_for's fallback warming (a
        differing kwarg pytree would be a separate jit cache entry) and
        collective_counts' lowering (``call``)."""
        return call(
            state, plan.features, plan.batch_pad, plan.fit_strategy,
            plan.vmax, n_active=np.int32(n_active), carry_in=carry,
            has_pns=plan.has_pns, has_ipa_base=plan.has_ipa_base,
            anti_rowlocal=plan.anti_rowlocal, has_na_pref=plan.has_na_pref,
            port_selfblock=plan.port_selfblock, has_aux=plan.has_aux,
            has_nom=plan.has_nom)

    def collective_counts(self, pod, batch_size: Optional[int] = None):
        """Compile-time per-step collective profile of the EXACT dispatch a
        `pod`-shaped session runs (ici/dcn split via
        parallel/mesh.py collective_report), or None off-mesh: the count
        tests/test_sharded_mesh.py pins (the row-local shard_map path
        at-or-below the GSPMD baseline per step)."""
        if self.mesh is None:
            return None
        fw = self.framework_for_pod(pod)
        bs = batch_size or self.max_batch
        state, plan = self.build_plan(fw, pod, bs)
        fn = self._shard_map_fn(plan)
        if fn is not None:
            lowered = fn.lower(state, plan.features, np.int32(bs), None)
            path = "shard_map"
        else:
            lowered = self._gspmd_dispatch(state, plan, bs, None,
                                           call=schedule_batch.lower)
            path = "gspmd"
        n_hosts, per_host = mesh_host_split(self.mesh)
        report = collective_report(lowered.compile().as_text(),
                                   n_hosts, per_host)
        report["path"] = path
        return report

    # -- device session ----------------------------------------------------
    #
    # A *session* is a run of same-signature batches chained on device: the
    # ScanCarry returned by batch N is passed straight back as batch N+1's
    # carry_in (no feature rebuild, no state re-upload), and the host commits
    # batch N's pods while the device computes batch N+1 — the TPU-era form
    # of the reference's scheduling/binding-cycle overlap
    # (schedule_one.go:141 go runBindingCycle). The session ends when the
    # queue yields something incompatible, a commit diverges from the host
    # oracle, or any external cluster event arrives
    # (Scheduler.cluster_event_seq).

    def _nom_resume_key(self, priority: int):
        """Nomination component of a session's tail (KeptPlan.nom_key): the
        set version plus — only when a lane is live — the priority threshold
        the plan was built with (an empty nominator makes priority
        irrelevant)."""
        nom = self.queue.nominator
        return (nom.version, priority if nom.has_nominated_pods() else None)

    # -- incremental session resume (typed event journal) -------------------
    #
    # The journal (core/cache.py EventJournal) records what each bump of
    # cluster_event_seq WAS, so a session can classify the intervening
    # events against its plan and patch exactly the rows they dirtied —
    # mirror staging, resident device state, and the live carry — then keep
    # (or resume) the session with the pipeline full. An event that does
    # not classify is a full rebuild / an invalidation.

    def _count_rebuild(self, kind: str) -> None:
        if kind == "full":
            self.plan_rebuilds_full += 1
        elif kind == "delta":
            self.plan_rebuilds_delta += 1
        else:
            self.plan_rebuilds_resume += 1
        # plane label: mesh full rebuilds are the cost the delta patches
        # exist to avoid (a sharded teardown re-uploads the whole state).
        self.metrics.plan_rebuild_total.inc(
            kind, "mesh" if self.mesh is not None else "single")

    def _neutral_sig(self, fw: Framework, pod, sig):
        """Namespace/label-erased session signature, or None when ineligible.

        The IPA and PTS Sign plugins fold (labels, namespace) into every
        pod's signature because affinity terms and spread selectors read
        them — which splits e.g. per-namespace pod sets (the *WithNSSelector
        init phase) into one session per namespace even though every pod
        builds the IDENTICAL plan. When the pod carries no affinity/spread
        machinery, no volumes or claims (namespaced PVC keys), and NO pod in
        the cluster carries affinity terms (cache.affinity_pod_refs — live
        truth, unlike the possibly-stale snapshot sublists), labels and
        namespace are scheduling-inert: erase them so pods differing only
        there share one session, one plan, and one chained carry.

        The erased tuple is pure spec (memoized on the template-shared
        signature holder, so a namespace sweep of N clones erases once);
        only the cluster-side affinity gate is live state."""
        if sig is None or self.cache.affinity_pod_refs:
            return None
        shared = pod.__dict__.get("_sig_shared")
        # node_name rides the key exactly as sign_pod's own memo does (it is
        # the one signed field mutated in place).
        key = ("_nsig", id(fw), pod.node_name)
        if shared is not None and key in shared:
            return shared[key]
        aff = pod.affinity
        if (pod.topology_spread_constraints or pod.volumes
                or getattr(pod, "resource_claims", None)
                or (aff is not None
                    and (aff.pod_affinity or aff.pod_anti_affinity))):
            out = None
        else:
            out = tuple(
                (name, part[2:] if name in ("InterPodAffinity",
                                            "PodTopologySpread") else part)
                for name, part in sig)
        if shared is not None:
            shared[key] = out
        return out

    def _classify_delta(self, events, plan):
        """Map journal events to the feature blocks they dirty under `plan`.
        Returns (level, dirty node names, pod_only): 'benign' (nothing
        node-side moved), 'safe' (row patches whose events only enlarge
        feasibility — in-flight device results stay committable), 'strict'
        (row patches that may shrink feasibility: applicable with an empty
        pipeline, or while busy when pod_only and the bind path re-validates
        capacity) — or None when any event needs the full rebuild. pod_only:
        every dirtying event was a plain-pod row event (no node update)."""
        level = 0
        names = set()
        pod_only = True
        for ev in events:
            if ev.kind == EV_QUEUE:
                continue
            if ev.kind == EV_NAMESPACE:
                # Namespace labels feed ONLY affinity namespaceSelector
                # matching: inert while no term exists on either side.
                if plan.pod_local and self.cache.affinity_pod_refs == 0:
                    continue
                return None
            if ev.kind in (EV_POD_ADD, EV_POD_REMOVE, EV_POD_UPDATE):
                # plan.pod_local: a pod on node n can only dirty row n's
                # resource aggregates (no count table could have counted
                # it); ev.pod_plain: the pod brings no terms that could
                # flip exist_anti/ipa_base from their compiled-empty state.
                if not (plan.pod_local and ev.pod_plain):
                    return None
                if ev.pod_ports and plan.port_selfblock:
                    return None  # used_ports moved under a port-aware plan
            elif ev.kind == EV_NODE_UPDATE:
                if not plan.pod_local:
                    return None  # honor-policy spread tables read taints
                pod_only = False
            else:
                return None
            names.add(ev.key)
            level = max(level, 1 if ev.shrink else 2)
        return ("benign", "safe", "strict")[level], names, pod_only

    def _classify_since(self, seq: int, plan):
        """(events, classification) of what the journal holds since `seq`
        under `plan`: the one answer to whether a kept plan outlived them.
        The events are None where the journal no longer reaches back to
        `seq`, the classification None there and wherever `_classify_delta`
        says so."""
        events = self.journal.since(seq)
        return events, (self._classify_delta(events, plan)
                        if events is not None else None)

    def _note_session_events(self, sd: _SessionDelta, busy: bool) -> bool:
        """The ONE journal-consumption protocol both session kinds run at
        their invalidation checks. `sd` is the session's mutable view
        (_SessionDelta); updated in place. Returns True when the session
        stays valid — benign advance, patch applied, or patch deferred
        until the pipeline drains — False when it must invalidate. `busy` =
        dispatched-but-uncommitted device results exist."""
        if self.cluster_event_seq == sd.start_seq and not sd.patch_pending:
            return True
        with self.stages.stage("inbox.drain"):  # the journal's half of it
            return self._consume_session_events(sd, busy)

    def _consume_session_events(self, sd: _SessionDelta, busy: bool) -> bool:
        _events, cls = self._classify_since(sd.start_seq, sd.plan)
        if cls is None:
            return False
        level, names, pod_only = cls
        if not names:
            sd.start_seq = self.cluster_event_seq
            sd.patch_pending = False
            return True
        if busy:
            if level == "strict" and not (
                    pod_only and self.bind_capacity_validated):
                return False  # in-flight results may no longer fit
            if pod_only and self.bind_capacity_validated:
                # Strict POD rows under a capacity-validating bind path (the
                # shard plane): a foreign scheduler's bind may have consumed
                # room an in-flight result counts on, but the binding
                # subresource re-validates committed usage per node, so the
                # worst case is a 409 → conflict requeue — never an
                # overcommitted node. Patch the carry/state NOW, with the
                # pipeline still full: draining first (the conservative
                # deferral below) serializes every shard against its peers'
                # bind bursts — the ping-pong that held a 2-shard plane
                # under a 1-shard one. The patched rows are charged dirty
                # (_SessionDelta.busy_patch_rows) so session-end adoption
                # re-encodes them from post-commit truth.
                if self._apply_delta_patch(sd, names, busy=True):
                    row_of = self._session_row_of[1]
                    sd.busy_patch_rows.extend(
                        row_of[nm] for nm in names if nm in row_of)
                    sd.start_seq = self.cluster_event_seq
                    sd.patch_pending = False
                    self._count_rebuild("delta")
                    return True
            # Deferral: commit in-flight as-is, patch once the pipeline
            # drains — shrink-only ('safe') events only enlarged
            # feasibility, and a failed busy patch falls back here. Strict
            # NODE events (taint/alloc shrink) still invalidate above:
            # nothing re-validates taints at bind time.
            sd.patch_pending = True
            return True
        if not self._apply_delta_patch(sd, names):
            return False
        sd.start_seq = self.cluster_event_seq
        sd.patch_pending = False
        self._count_rebuild("delta")
        return True

    def _apply_delta_patch(self, sd: _SessionDelta, names,
                           busy: bool = False) -> bool:
        """Patch the journal's dirty rows into mirror staging, the resident
        device state, and the session carry (`sd`'s, rebound to the
        result). False when the patch can't apply — the caller's
        full-rebuild fallback recovers from every False.

        Mesh sessions patch EVERY classifiable kind — POD-event aggregates
        (pod_add/pod_remove/pod_update) included, the events that dominate
        churn workloads: the row scatter and the carry re-eval run through
        jits pinned to the session's committed shardings
        (mesh_state_shardings / patch_carry_rows_pinned), so the patched
        pytrees keep the exact placement the session kernel's traces key
        on, and the stale state/carry buffers are DONATED into the patch
        jits (reused in place) when no dispatched batch still reads them
        (`busy`)."""
        if not names:
            return True
        if sd.plan.rows is not None:
            # A narrowed session's rows are not the mirror's: it ends, and
            # the template's next session builds from the patched truth.
            return False
        with self.stages.stage("plan.patch", rows=len(names)):
            patched = self._patch_rows(sd.plan, sd.node_names, names,
                                       sd.state, sd.carry, busy)
        if patched is not None:
            sd.state, sd.carry = patched
        return patched is not None

    def _patch_rows(self, plan, node_names, names, state, carry, busy: bool):
        row_of = self._session_row_of
        if row_of is None or row_of[0] is not node_names:
            row_of = (node_names, {n: i for i, n in enumerate(node_names)})
            self._session_row_of = row_of
        updates = []
        for nm in names:
            row = row_of[1].get(nm)
            ni = self.cache.nodes.get(nm)
            if row is None or ni is None or ni.node is None:
                return None  # row set changed shape: structural after all
            updates.append((row, ni))
        if self.mesh is not None:
            new_state = self.mirror.patch_rows(
                updates, sharded_state=state,
                out_shardings=mesh_state_shardings(self.mesh),
                donate=not busy)
        else:
            new_state = self.mirror.patch_rows(updates)
        if new_state is None:
            return None
        rows = sorted({r for r, _ in updates})
        if not plan.has_pns:
            if (self.mirror.h_taint_eff[rows]
                    == EFFECT_PREFER_NO_SCHEDULE).any():
                # The plan compiled the no-PreferNoSchedule fast path;
                # staging is already patched, so the full rebuild (which
                # recomputes has_pns) resumes from truth.
                return None
        if carry is not None:
            tier = patch_tier(len(rows))
            prows = rows + [rows[-1]] * (tier - len(rows))
            patch_fn = (patch_carry_rows_pinned if self.mesh is not None
                        else patch_carry_rows)
            carry = patch_fn(
                new_state, plan.features, carry,
                jnp.asarray(np.asarray(prows, np.int32)),
                jnp.asarray(self.mirror.h_req_r[prows]),
                jnp.asarray(self.mirror.h_nonzero[prows]),
                jnp.asarray(self.mirror.h_pod_count[prows]),
                fit_strategy=plan.fit_strategy, has_nom=plan.has_nom)
        self.delta_dirty_rows += len(rows)
        self.metrics.plan_rebuild_dirty_rows.inc(value=len(rows))
        return new_state, carry

    def _resume_or_rebuild(self, sd: "_SessionDelta") -> str:
        """Session-start plan acquisition: the tail the template's last
        clean session handed back, as it is (``resume``) or row-patched by
        the journal's events since (``delta``), else a full build
        (``full``). Fills `sd` with the plan, its rows' node names, the
        device state and the carry (None after a full build)."""
        fw, pod = sd.fw, sd.pod
        _t_hint = _time.perf_counter()
        filed = self._template_key(fw, pod, sd.sig, sd.aux_shape)
        exact = self._template_key(fw, pod, sd.sig, sd.aux_shape, False)
        mine, cause = self._claim_tail(
            (exact, filed) if sd.neutral_ok else (exact,), pod.priority)
        entry, events, cls = self._kept_plan(mine, tail=True)
        kind = "full"
        if entry is not None:
            sd.plan, sd.node_names = entry.plan, entry.node_names
            sd.state, sd.carry = entry.state, entry.carry
            ended = entry.tail_seq
            # taken: this session resumes from it, or nobody does
            self._drop_kept(mine, entry, tail=True)
            if ended == self.cluster_event_seq:
                kind = "resume"
            else:
                # No pipeline is in flight at session start: every level
                # (benign/safe/strict) may patch here.
                _level, names, _pod_only = cls
                if self._apply_delta_patch(sd, names):
                    kind = "delta"
                else:
                    cause = "patch_failed"
        elif mine is not None:  # the journal voided it
            if events is None:
                cause = "journal_overrun"
            elif any(ev.kind == EV_STRUCTURAL for ev in events):
                cause = "structural"
            else:
                cause = "unpatchable"
        # get_node_hint_duration (runtime/batch.go GetNodeHint analogue):
        # the batch-reuse lookup is the session-resume key check.
        self.metrics.get_node_hint_duration.observe(
            _time.perf_counter() - _t_hint)
        if kind == "full":
            sd.state, entry = self._build_kept_plan(fw, pod, filed,
                                                    self.max_batch)
            sd.plan, sd.carry = entry.plan, None
            sd.node_names = [ni.name for ni in self.snapshot.node_info_list]
            rows = self._plan_rows(pod)
            if rows is not None:
                # The PreFilterResult narrows: the session runs over the
                # named nodes' rows only. It hands back no tail (a session
                # of the template always starts here) and installs no hint.
                sd.state, sd.plan = self._narrowed(entry, pod, rows,
                                                   self.max_batch)
                sd.node_names = [sd.node_names[r] for r in rows]
            self.metrics.plan_rebuild_cause.inc(cause)
        self.plan_build_cause = cause if kind == "full" else ""
        self._count_rebuild(kind)
        return kind

    def limited_drivers(self) -> frozenset:
        rv = getattr(self.clientset, "csi_nodes_rv", 0)
        if rv != self._limited_drivers_n:
            self._limited_drivers = frozenset(
                d for cn in self.clientset.csi_nodes.values()
                for d in cn.driver_limits)
            self._limited_drivers_n = rv
        return self._limited_drivers

    def _dra_ctx(self, fw: Framework):
        """(dra_enabled, in_use) for eligibility/plan builds: claims are
        scheduling-relevant only when the profile runs DynamicResources."""
        dr = fw.plugin("DynamicResources")
        if dr is None:
            return False, None
        return True, dr._in_use()

    def _claims_of(self, pod) -> list:
        return [f"{pod.namespace}/{v.pvc_name}"
                for v in pod.volumes if v.pvc_name]

    def _claim_shape(self, pod):
        names = getattr(pod, "resource_claims", ()) or ()
        if not names:
            return None
        claim = self.clientset.resource_claims.get(
            f"{pod.namespace}/{names[0]}")
        if claim is None or len(claim.requests) != 1:
            return ("?",)
        r = claim.requests[0]
        return (r.device_class, r.count,
                tuple(sorted(r.selectors.items())), r.expression)

    def _aux_shape(self, pod):
        """The counted-constraint shape a plan models for this pod: the
        volume attach (driver, inc) AND the DRA claim shape. Every session
        member must share it — a mixed batch would run the head's aux math
        against members with different (or no) counted constraints. Plain
        pods (the >13k pods/s path) answer without the volume walk."""
        if not pod.volumes and not getattr(pod, "resource_claims", None):
            return (None, None)
        _r, vol_d, vol_inc = volume_device_support(
            pod, self.clientset, pvc_refs=self.cache.pvc_refs,
            limited_drivers=self.limited_drivers())
        return ((vol_d, vol_inc) if vol_d else None, self._claim_shape(pod))

    def _batch_supported_memo(self, pod, fw: Framework,
                              as_head: bool = False):
        """batch_supported with the verdict memoized on the pod's shared
        template-signature holder (clone_from_template invariant: clones
        never mutate spec), so a 50k-pod workload computes it once, not 50k
        times. The one per-INSTANCE field read here —
        nominated_node_name — is checked outside the memo: a pod that holds
        a nomination joins nobody's batch (its node is evaluated first and
        alone), it only ever heads one (``as_head``: _collect_batch, which
        hands it to _run_nominated)."""
        if pod.nominated_node_name and not as_head:
            return "nominated pod heads a batch of its own"
        shared = pod.__dict__.get("_sig_shared")
        # PVC/claim verdicts depend on live claim/PV state — never memoized.
        live = (shared is None or any(v.pvc_name for v in pod.volumes)
                or getattr(pod, "resource_claims", None))
        key = ("_bsup", id(fw))
        if not live and key in shared:
            return shared[key]
        supported = partial(
            batch_supported, pod, self.snapshot,
            fit_plugin=fw.plugin("NodeResourcesFit"),
            ba_plugin=fw.plugin("NodeResourcesBalancedAllocation"),
            clientset=self.clientset, pvc_refs=self.cache.pvc_refs,
            limited_drivers=self.limited_drivers())
        if live:
            dra_enabled, dra_in_use = self._dra_ctx(fw)
            return supported(dra_enabled=dra_enabled, dra_in_use=dra_in_use,
                             session_claims=self._session_claims)
        shared[key] = supported()
        return shared[key]

    def _session_compatible(self, head: QueuedPodInfo, fw: Framework, sig) -> bool:
        if isinstance(head, QueuedPodGroupInfo):
            return False
        if (getattr(self, "_session_nom_priority", None) is not None
                and head.pod.priority != self._session_nom_priority):
            return False  # nominated lane is priority-thresholded
        if not (head.pod.scheduler_name in self.profiles
                and self.framework_for_pod(head.pod) is fw):
            return False
        psig = fw.sign_pod(head.pod)
        sig_ok = psig == sig
        if not sig_ok and psig is not None \
                and self._session_neutral_sig is not None:
            # Label/namespace-only signature difference: join the session
            # when the pod's namespace-erased signature matches and the
            # cluster still has no affinity-carrying pods (_neutral_sig
            # re-checks the live gate) — per-namespace pod sweeps then ride
            # ONE session instead of one per namespace.
            sig_ok = self._neutral_sig(fw, head.pod, psig) \
                == self._session_neutral_sig
        if not (sig_ok
                # Signatures only cover the Sign plugins; a member with a
                # feature outside the kernel (unbound volumes, DRA claims)
                # shares the head's signature but must NOT ride the device —
                # it would silently skip that feature's filters.
                and self._batch_supported_memo(head.pod, fw) is None):
            return False
        if self._aux_shape(head.pod) != getattr(
                self, "_session_aux_shape", None):
            # The plan's aux decrement models ONE counted-constraint shape
            # (volume attach or claim); a member with a different (or no)
            # constraint must not share the batch.
            return False
        claims = self._claims_of(head.pod)
        dra_claims = [f"dra:{head.pod.namespace}/{n}"
                      for n in getattr(head.pod, "resource_claims", ()) or ()]
        if claims or dra_claims:
            # A claim already used by a pod accepted into this session must
            # not be counted twice by the kernel's per-landing attach math.
            if any(c in self._session_claims for c in claims + dra_claims):
                return False
            self._session_claims.update(claims)
            self._session_claims.update(dra_claims)
        return True

    def _refill(self, batch: "_Batch", fw: Framework, sig,
                took) -> "_Batch":
        """Fill ``batch`` up to max_batch with the run the queue hands out
        next (PriorityQueue.pop_run): the maximal prefix of its pop order
        that the session accepts. The entity that ends the run goes to the
        holdover slot, popped and in flight; a deleting or already placed
        pod is settled and dropped, as `_pop` does.

        A pod joins on the session template's verdict, without
        `_session_compatible`, where what can be observed says it must get
        the head's answers: the head's own shared signature holder (a clone
        of the same template: one signature, one memoized batch_supported),
        its priority and scheduler name, and nothing per pod that any of
        the checks reads (a nomination, a node name, volumes, claims).
        Every other entity takes `_session_compatible` as the head's
        successors always did. The pop's records are written in one pass
        after the run, on the run's one clock reading: `queue.wait` for all
        of it, a sampled pod's two rows and its place in the batch; the open
        `queue.pop` stage is told what the refill ``took`` (`_pop_stage`)."""
        holder, priority, scheduler_name = self._session_template
        queue = self.queue
        skip, compatible = self._skip_pod_schedule, self._session_compatible
        on_template = 0

        def accept(qpi) -> Optional[bool]:
            nonlocal on_template
            if type(qpi) is QueuedPodInfo:
                pod = qpi.pod_info.pod
                if skip(pod):
                    return None
                if (pod.__dict__.get("_sig_shared") is holder
                        and pod.priority == priority
                        and pod.scheduler_name == scheduler_name
                        and not pod.nominated_node_name
                        and not pod.node_name
                        and not pod.volumes and not pod.resource_claims):
                    on_template += 1
                    return True
            return compatible(qpi, fw, sig)

        run: List = []
        held, self._holdover = self._holdover, None
        if held is not None:
            ok = accept(held)
            if ok:
                run.append(held)
            elif ok is not None:
                self._holdover = held
                return batch
        more, refused, now = queue.pop_run(
            self.max_batch - len(batch) - len(run), accept)
        self._holdover = refused
        run += more
        tracer = self.tracer if self.tracer.enabled else None
        if type(refused) is QueuedPodInfo:
            # the holdover's wait ends at its pop, as a batch member's does
            self.record_queue_wait(
                refused, tracer and tracer.context_for(refused.pod.uid))
        if tracer is not None:
            context_for = tracer.context_for
            wall_pop = _time.time()
            for at, qpi in enumerate(run, len(batch)):
                if type(qpi) is not QueuedPodInfo:
                    continue  # a group entity records as one, elsewhere
                ctx = context_for(qpi.pod_info.pod.uid)
                if ctx is None:
                    continue
                if "_qwait_recorded" not in qpi.__dict__:
                    self.trace_queue_wait(
                        qpi, ctx, queue_wait(qpi, now), wall_pop)
                batch.sampled.append(ctx)
                batch.sampled_at.append(at)
        waits: List[float] = []
        for qpi in run:
            if (type(qpi) is QueuedPodInfo
                    and "_qwait_recorded" not in qpi.__dict__):
                qpi._qwait_recorded = True
                waits.append(queue_wait(qpi, now))
        self.metrics.pod_stage_duration.observe_many(waits, "queue.wait")
        batch += run
        took(len(run), on_template,
             len(run) if self._session_narrows else 0)
        return batch

    def _collect_session_batch(self, fw: Framework, sig,
                               took) -> List[QueuedPodInfo]:
        """Pop up to max_batch pods matching the session signature; an
        incompatible entity goes to the holdover slot and ends the refill."""
        return self._refill(_Batch(), fw, sig, took)

    def run_device_session(self, fw: Framework, first_batch: List[QueuedPodInfo]) -> None:
        """A session of plain pods from `first_batch` on (`_run_session`)."""
        self._run_session(self._run_device_session, fw, first_batch,
                          "device_session")

    def _run_session(self, ladder, fw: Framework, first, where: str) -> None:
        """The crash-proof frame round a session ladder. An unexpected
        device failure mid-session (kernel shape error, dispatch fault,
        poisoned carry) must not strand what the session popped: every
        batch not yet fully committed (`pending`; `first` is in it BEFORE
        the plan is built) reruns on the host path, the mirror invalidates,
        the breaker is charged. A template the device cannot plan for
        (Unsupported, as the session opens) sends `first` to the host."""
        pending = [first]
        try:
            ladder(fw, first, pending)
        except Unsupported:
            self.metrics.device_path_fallback.inc("unsupported")
            self._to_host_path(first)
        except Exception as e:  # noqa: BLE001 - device→host fallback
            self._note_device_failure(e, where)
            for b in pending:
                for qpi in b:
                    self._recover_qpi(qpi)

    def _to_host_path(self, entities) -> None:
        """Entities popped for the device take the exact host cycle, a
        group as one, its members counted."""
        for qpi in entities:
            self.host_path_pods += len(getattr(qpi, "members", ()) or (1,))
            self.process_one(qpi)

    def _run_device_session(self, fw: Framework,
                            first_batch: List[QueuedPodInfo],
                            pending: List[List[QueuedPodInfo]]) -> None:
        # Plan acquisition latency: the extension-point histogram gets
        # EVERY session (p50/p99 truth); sampled pods get plan.build spans
        # tagged with the acquisition kind (full/delta/resume).
        sd = self._open_session(fw, first_batch[0].pod, True,
                                first_batch.sampled, "DevicePlan",
                                batch=len(first_batch))
        sig, node_names = sd.sig, sd.node_names
        inflight, ok_rows, dirty_rows = sd.inflight, sd.ok_rows, sd.dirty_rows
        stages = self.stages
        start_nom = self.queue.nominator.version
        invalidated = False
        batch: Optional[List[QueuedPodInfo]] = first_batch

        while True:
            # Refill the dispatch pipeline (depth-bounded): dispatch is
            # async — these calls enqueue device work and return immediately.
            while not invalidated and len(inflight) < PIPELINE_DEPTH:
                if sd.patch_pending:
                    if inflight:
                        break  # retire dispatched work before patching
                    if not self._note_session_events(sd, busy=False):
                        invalidated = True
                        break
                if batch is None:
                    if self.cluster_events_parked:
                        # A node was added, changed or removed under the
                        # backlog: no refill. What is in flight retires and
                        # the session ends as one that ran dry (adopted,
                        # resumable), so the turn that follows replays the
                        # event with an empty pipeline.
                        break
                    with self._pop_stage() as took:
                        batch = self._collect_session_batch(
                            fw, sig, took) or None
                    if batch is None and self._event_inbox:
                        # A concurrent client (threaded watch feed) may have
                        # parked pod-add events while this session ran: drain
                        # them HERE so a creation burst doesn't end the
                        # session early, up to the first parked cluster
                        # event (held for the next turn, as above). Events
                        # raised on this thread patch the live plan+carry
                        # when the journal classifies them, and invalidate
                        # the session when it can't.
                        self.drain_event_inbox(hold_cluster_events=True)
                        if not self._note_session_events(
                                sd, busy=bool(inflight)):
                            invalidated = True
                        elif sd.patch_pending:
                            continue  # patch (or drain) before collecting
                        else:
                            with self._pop_stage() as took:
                                batch = self._collect_session_batch(
                                    fw, sig, took) or None
                    if batch is None:
                        break
                    pending.append(batch)
                self._dispatch_next(sd, batch, len(batch), batch.sampled)
                batch = None
            if not inflight:
                break
            # Retire the oldest batch: block on its results (the device is
            # already computing the NEXT batch), then run the host tail.
            b = inflight[0][0]
            b, res = self._retire_oldest(sd, b.sampled, "DeviceWait",
                                         batch=len(b))
            if not invalidated:
                run = self._batch_tail_run(b, res, fw)
                with stages.stage("host.commit", b.sampled, "HostCommit",
                                  batch=len(b),
                                  tail=("single" if not run else "batch"
                                        if run == len(b) else "mixed")):
                    invalidated = self._commit_batch(
                        b, res, fw, node_names, ok_rows, dirty_rows, run)
                self._note_after_flush(sd)
                if not invalidated and (
                        self.state_unwinds != sd.start_unwinds
                        or self.queue.nominator.version != start_nom
                        or not self._note_session_events(
                            sd, busy=bool(inflight))):
                    invalidated = True
                    sd.start_seq = self.cluster_event_seq
                    sd.start_unwinds = self.state_unwinds
                    start_nom = self.queue.nominator.version
            else:
                # A previous batch diverged: every later device choice is
                # stale. Rerun the pods and charge their rows dirty.
                for i, qpi in enumerate(b):
                    self._rerun_after_divergence(fw, qpi, int(res[0, i]),
                                                 dirty_rows)
            if b in pending:
                pending.remove(b)  # fully handled: out of crash recovery

        if batch:  # popped but never dispatched (invalidated mid-refill)
            self._to_host_path(batch)
            if batch in pending:
                pending.remove(batch)

        # The hint (the cross-cycle OpportunisticBatch save): after a clean
        # end the carry IS the kernel's sorted-score truth for the next
        # identical pod.
        self._close_session(sd, invalidated, "session_invalidated",
                            install_hint=True)

    # -- the frame round a device session, shared by both ladders -----------

    def _open_session(self, fw: Framework, pod, neutral_ok: bool,
                      sampled=(), point: str = "", **attrs) -> _SessionDelta:
        """A session opens for `pod`'s template: `plan.build` (the caller's
        span contexts, extension point and attrs) round the acquisition,
        which says `kind`, `cause`, `transfers`, `node_shapes` and how many
        mirror rows it brought in line whole and by column, `rows_encoded`
        and `rows_by_column` (a profiler event's stats).
        ``neutral_ok``: sessions of the template's namespace-erased
        signature chain (plain pods only)."""
        sig = fw.sign_pod(pod)
        # Signatures cover only the Sign plugins — NOT volumes/claims, whose
        # counted-constraint shape changes the PLAN (aux_room semantics). A
        # resume must match the aux shape too, or a claim-template session
        # could chain onto a volume session's attach-room plan (fuzz-caught).
        sd = _SessionDelta(
            fw, pod, sig,
            self._neutral_sig(fw, pod, sig) if neutral_ok else None,
            neutral_ok, self._aux_shape(pod))
        transfers, rows = self.mirror.transfers, self.mirror.rows
        with self.stages.stage("plan.build", sampled, point, **attrs) as st:
            sent = transfers.total()
            encoded, by_column = rows.value("encoded"), rows.value("by_column")
            kind = self._resume_or_rebuild(sd)
            sd.built = {"kind": kind, "cause": self.plan_build_cause}
            # the cluster's allocatable shapes as the mirror's census has
            # them (kept row by row where a row is encoded: no pass here)
            shapes = len(self.mirror.shapes)
            self.metrics.plan_node_shapes.set(shapes)
            st.say(**sd.built, **sd.plan.narrowed_attrs(),
                   transfers=int(transfers.total() - sent),
                   node_shapes=shapes,
                   rows_encoded=int(rows.value("encoded") - encoded),
                   rows_by_column=int(rows.value("by_column") - by_column))
        sd.start_seq = self.cluster_event_seq
        sd.start_unwinds = self.state_unwinds
        return sd

    def _dispatch_next(self, sd: _SessionDelta, entities, n: int,
                       sampled=()) -> None:
        """`n` pods of `entities` (a batch, or a pack of groups) go to the
        device, chained on the session's carry. The device→host copy starts
        NOW: the fetch overlaps the host commit loop of the batch before."""
        attrs = self._dispatch_attrs(sd.plan, n, len(sd.inflight))
        with self.stages.stage("device.dispatch", sampled, **attrs):
            results, sd.carry = self._dispatch(sd.state, sd.plan, n, sd.carry)
            results.copy_to_host_async()
        self._count_dispatch(attrs)
        if sd.plan.rows is not None:  # a session narrows by PreFilterResult
            self.metrics.prefilter_narrowed_pods.inc("device", value=float(n))
        sd.inflight.append((entities, results, attrs["seq"]))
        self._note_inflight(sd)

    def _retire_oldest(self, sd: _SessionDelta, sampled=(), point: str = "",
                       **attrs):
        """(entities, results on the host) of the oldest dispatch in
        flight: `device.wait` (with its `seq`) round the one fetch."""
        entities, results, seq = sd.inflight.pop(0)
        self._note_inflight(sd)
        with self.stages.stage("device.wait", sampled, point, **attrs,
                               seq=seq):
            return entities, np.asarray(results)

    def _note_inflight(self, sd: _SessionDelta) -> None:
        self.stages.inflight = depth = len(sd.inflight)
        self.metrics.goroutines.set(float(depth), "device_dispatch")

    def _note_after_flush(self, sd: _SessionDelta) -> None:
        """The first batch (or pack) committed after a flush: its pods were
        scheduled from a fresh (non-chained) evaluation."""
        if self._after_flush:
            self.metrics.pod_scheduled_after_flush.inc(
                value=len(sd.ok_rows))
            self._after_flush = False

    def _close_session(self, sd: _SessionDelta, invalidated: bool,
                       flushed: str, install_hint: bool = False) -> None:
        """A session closes: `plan.adopt`, opened with its session's build,
        says `rows_adopted` and `snapshot_refreshed` as it ends.
        An invalidated session's carry charged host-diverged placements, so
        staging is the authority again: a full re-encode + upload, counted
        as a flush (`flushed`). A clean one keeps the device state resident
        (the final carry holds every placement: the next flush uploads
        nothing), hands its tail back and may install the score hint; the
        mirror adopts the rows the session landed on from the LIVE cache, as
        a row patch reads them, and nothing here refreshes the snapshot: the
        cache keeps its dirty rows for the next reader's own refresh
        (`_sync_mirror`, the host cycle's, the group paths'). A
        clean session over a narrowed row set leaves the resident state as
        it is: its carry holds other rows than the mirror's, so the rows it
        landed on go the ordinary way (their NodeInfo generations moved:
        the next sync brings them in line, the next flush scatters them, one
        row a pinned node), and nobody resumes or is served from it."""
        with self.stages.stage("plan.adopt", **sd.built) as st:
            seen, adopted = self.snapshot.generation, 0
            dirty_rows = sd.dirty_rows + sd.busy_patch_rows  # re-encoded
            if invalidated:
                self.mirror.invalidate()
                self.metrics.batch_cache_flushed.inc(flushed)
                self._after_flush = True
            elif sd.plan.rows is None:
                carry = sd.carry
                adopted = self.mirror.adopt(
                    self.cache.nodes, sd.ok_rows, carry.req_r, carry.nonzero,
                    carry.pod_count, dirty_rows=dirty_rows)
                if not dirty_rows:
                    self._hand_back_tail(sd)
                    if install_hint and self._hints.enabled and hint_eligible(
                            sd.plan, sd.aux_shape, sd.pod, self.extenders,
                            self.queue.nominator,
                            self.cache.affinity_pod_refs):
                        self._hints.install(sd.fw, sd.pod, sd.sig, sd.nsig,
                                            sd.plan, sd.node_names, carry)
            st.say(rows_adopted=adopted,
                   snapshot_refreshed=int(self.snapshot.generation != seen))
        # The session ran to completion (invalidation included — that is a
        # NORMAL end, not a device failure): a half-open breaker closes.
        self._note_device_success()

    def _batch_tail_run(self, b, res, fw) -> int:
        """How many pods of a retired batch, from its first on, the batch
        tail commits (``_commit_run``); the pods after them take ``_commit``
        one by one. Decided from what can be observed as the batch retires:
        binds leave on the loop's own thread (the dispatcher's inline mode),
        to a clientset with the bulk verb; the profile's tail is the lean
        one (``_commit_fast_eligible``, no extenders); and the run ends at
        the first pod the device could not place, that claims devices or
        that belongs to a gang."""
        if (self.api_dispatcher.mode != "inline" or self.extenders
                or not self._commit_fast_eligible(fw)
                or getattr(self.clientset, "bind_many", None) is None):
            return 0
        for i, row in enumerate(res[0, :len(b)].tolist()):
            pod = b[i].pod
            if row < 0 or pod.pod_group or pod.resource_claims:
                return i
        return len(b)

    def _commit_run(self, b, run: int, rows, fw, node_names,
                    ok_rows, dirty_rows) -> Tuple[int, bool]:
        """The batch tail: the first ``run`` pods of a retired batch,
        committed in passes over the run instead of one ``_commit`` a pod,
        with ``_commit``'s lean tail's outcome for every pod. Returns how
        many pods of the batch it dealt with and whether the session must
        invalidate.

        Assume all (one cache call), bind all in one request
        (``DefaultBinder.bind_run``; this scheduler's handler confirms its
        own binds by the short way while it is open, core/scheduler.py
        ``_pod_events``), settle all. A refused bind ends it: the pods the
        request bound are settled, the refused one is unwound as ``_commit``
        unwinds it, the pods the request never reached are un-assumed and
        left to ``_commit_batch``'s invalidated branch, where the per-pod
        tail sends them too."""
        pairs = []
        assume = []
        for i in range(run):
            qpi = b[i]
            pod = qpi.pod
            pod.node_name = node_name = node_names[rows[i]]
            pairs.append((pod, node_name))
            assume.append((pod, qpi.pod_info))
        self.cache.assume_pods(assume)
        confirms = self._own_confirms = []
        try:
            results = fw.bind_plugins[0].bind_run(pairs)
        finally:
            self._own_confirms = None
            self.metrics.event_handling_duration.observe_many(
                confirms, "pod")
        bound_at = self.now()
        answered = len(results)
        self.attempts += answered
        self.metrics.commit_pods.inc("batch", value=float(answered))
        if results.count(None) == run:
            self._settle_run(b, range(run), pairs, bound_at)
            ok_rows.extend(rows[:run])
            return run, False
        self._settle_run(
            b, [i for i in range(answered) if results[i] is None], pairs,
            bound_at)
        for i in range(answered, run):  # never reached: as if never assumed
            pod = b[i].pod
            self.cache.forget_pod(pod)
            pod.node_name = ""
        for i in range(answered):
            if results[i] is None:
                ok_rows.append(rows[i])
                continue
            self._unwind_binding(fw, CycleState(), b[i], pairs[i][1],
                                 results[i])
            self.queue.done(b[i].pod.uid)
            dirty_rows.append(rows[i])
        return answered, True

    def _settle_run(self, b, bound, pairs, bound_at: float) -> None:
        """Pods ``bound`` (places in the batch) were bound by a request that
        returned at ``bound_at`` on the scheduler's clock: what ``_commit``'s
        lean tail does after a bind that succeeded, for all of them."""
        if not bound:
            return
        cache = self.cache
        if cache.assumed_pods:  # a confirm is still to come: arm the expiry
            for i in bound:
                cache.finish_binding(b[i].pod)
        nom = self.queue.nominator
        if nom._pod_to_node:
            for i in bound:
                nom.delete_nominated_pod(b[i].pod)
        n = len(bound)
        self.scheduled += n
        self.device_scheduled += n
        # observe_bound for every pod, its series ending as the request did
        e2e = {}
        for i in bound:
            start = getattr(b[i], "enqueued_at", None)
            if start is not None:
                e2e[i] = max(0.0, bound_at - start)
        self.metrics.e2e_scheduling_duration.observe_many(
            list(e2e.values()))
        tr = self.tracer
        if b.sampled and tr.enabled:
            wall = _time.time()
            for i, ctx in zip(b.sampled_at, b.sampled):
                if i in e2e:
                    tr.record("pod.e2e", ctx, e2e[i], node=pairs[i][1],
                              attempts=b[i].attempts, start=wall - e2e[i])
        eventf = self.recorder.eventf
        done = self.queue.done
        for i in bound:
            pod, node_name = pairs[i]
            eventf(pod.namespace + "/" + pod.name, "Normal", "Scheduled",
                   ("Successfully assigned %s/%s to %s",
                    (pod.namespace, pod.name, node_name)))
            done(pod.uid)

    def _commit_batch(self, b, res, fw, node_names, ok_rows, dirty_rows,
                      run: int = 0) -> bool:
        """Host tail for one retired batch: its first ``run`` pods
        (``_batch_tail_run``) by the batch tail, the others by ``_commit``,
        one by one. Returns True when the session must invalidate
        (host/device divergence or host-path interleaving)."""
        n = len(b)
        rows = res[0, :n].tolist()
        starts = res[1, :n].tolist()
        first, invalidated = 0, False
        if run:
            first, invalidated = self._commit_run(
                b, run, rows, fw, node_names, ok_rows, dirty_rows)
            if first:
                self.next_start_node_index = starts[first - 1]
        single = 0
        for i in range(first, n):
            qpi = b[i]
            row = rows[i]
            self.next_start_node_index = starts[i]
            if invalidated:
                self._rerun_after_divergence(fw, qpi, row, dirty_rows)
                continue
            if row < 0:
                if self._fail_from_memo(fw, qpi):
                    # Identical pod, identical state, known terminal outcome:
                    # park it with the memoized diagnosis. No state mutated,
                    # so the session carry stays valid — an unschedulable
                    # FLOOD (10k hopeless pods + churn) must not tear down
                    # the measured pods' session per flood pod.
                    continue
                if self._fail_with_vector_diagnosis(fw, qpi):
                    # Exact Diagnosis built from the mirror arrays (numpy)
                    # instead of a 0.3s pure-Python cluster scan; when the
                    # PostFilter made no nomination, no state moved and the
                    # session continues.
                    if qpi.pod.nominated_node_name or qpi.pod.node_name:
                        invalidated = True
                    else:
                        self._memoize_failure(fw, qpi)
                    continue
                # Infeasible on device: rerun on the host path for the exact
                # FitError diagnosis. The host attempt may mutate state
                # (preemption nomination), so the session cannot continue on
                # the chained carry.
                self.host_path_pods += 1
                self.process_one(qpi)
                self._memoize_failure(fw, qpi)
                invalidated = True
                continue
            single += 1
            if self._commit(fw, qpi, node_names[row]):
                ok_rows.append(row)
            else:
                # Host rejected what the device applied in its carry.
                dirty_rows.append(row)
                invalidated = True
        if single:
            self.metrics.commit_pods.inc("single", value=float(single))
        return invalidated

    def _rerun_after_divergence(self, fw: Framework, qpi: QueuedPodInfo,
                                row: int, dirty_rows: List[int]) -> None:
        """A pod whose device answer is stale: an earlier pod of its batch,
        or of a batch ahead of it in the pipeline, moved state the carry
        does not hold. Where the device had placed it (``row``) the row is
        charged dirty and the host cycle places it anew. Where the device
        had found it no node (several preemptors behind one another: the
        first one's nomination is what moved the state), the diagnosis is
        made anew from the mirror's staging arrays and the nominations as
        they stand NOW (``_fail_with_vector_diagnosis`` answers only if
        every node still fails), so its PostFilter meets what the host
        cycle's would, without the host cycle's walk."""
        if row >= 0:
            dirty_rows.append(row)
        elif self._fail_with_vector_diagnosis(fw, qpi):
            return
        self.host_path_pods += 1
        self.process_one(qpi)

    def _fail_state_key(self, fw: Framework, pod) -> tuple:
        """Everything a scheduling outcome can depend on, versioned: the pod
        spec (signature), priority (no Sign plugin covers it, but PostFilter
        preemption eligibility does — a higher-priority pod with an identical
        signature may succeed where the memoized pod could not), external
        cluster changes, our own binds, and the nomination SET (sessions may
        run WITH a nominated lane; a changed set changes two-pass filter
        outcomes, so the memo keys on Nominator.version)."""
        return (fw.sign_pod(pod), pod.priority, self.cluster_event_seq,
                self.scheduled, self.state_unwinds,
                self.queue.nominator.version)

    def _fail_from_memo(self, fw: Framework, qpi: QueuedPodInfo) -> bool:
        """An identical pod was already host-diagnosed unschedulable against
        this exact state with NO side effects (no nomination, no preemption):
        the rerun would reproduce the same diagnosis, so park the pod from
        the memo. Keeps the device session alive through unschedulable
        floods (Unschedulable/5kNodes perf contract), including floods of
        MULTIPLE alternating signatures (keyed LRU, not a single slot)."""
        memo = self._fail_memo.get(self._fail_state_key(fw, qpi.pod))
        if memo is None:
            return False
        plugins, message = memo
        self.attempts += 1
        qpi.unschedulable_plugins |= plugins
        self.handle_scheduling_failure(fw, qpi, Status.unschedulable(message), None)
        self.queue.done(qpi.pod.uid)
        self.metrics.schedule_attempts.inc("unschedulable", fw.profile_name)
        return True

    def _fail_with_vector_diagnosis(self, fw: Framework, qpi: QueuedPodInfo) -> bool:
        """Build the FitError diagnosis for a device-infeasible pod from the
        mirror's staging arrays and run the standard fit-error tail
        (PostFilter/preemption included). Returns False when the pod's
        feature set needs the exact host rerun (topology features)."""

        if self._nominated_device_block(fw, qpi.pod) is not None:
            # A nomination that touches this pod by more than its requests
            # (_nominated_device_block): the vectorized diagnosis models the
            # two-pass filter for the resource fit only, and the exact host
            # rerun owns the Diagnosis.
            return False
        t0 = _time.perf_counter()
        self._sync_mirror()
        diag = diagnose_unschedulable(
            qpi.pod, self.mirror, self.snapshot, fw,
            nominated=self._nominated_lane(qpi.pod),
            rows=self._plan_rows(qpi.pod))
        if diag is None:
            return False
        self.attempts += 1
        fe = FitError(qpi.pod, self.snapshot.num_nodes(), diag)
        self.handle_fit_error(fw, CycleState(), qpi, fe, t0)
        return True

    def _memoize_failure(self, fw: Framework, qpi: QueuedPodInfo) -> None:
        """Record the host diagnosis IF the attempt was terminal and
        side-effect-free (keyed on the post-attempt state). State-moving
        attempts (bind/nomination) change the key components (scheduled /
        cluster_event_seq / nominated flag), so stale entries can never be
        served — eviction is purely a memory bound."""
        pod = qpi.pod
        if pod.node_name or pod.nominated_node_name:
            return  # scheduled after all, or nominated: state moved
        if len(self._fail_memo) >= self._fail_memo_cap:
            self._fail_memo.pop(next(iter(self._fail_memo)))
        self._fail_memo[self._fail_state_key(fw, pod)] = (
            frozenset(qpi.unschedulable_plugins),
            f"0/{self.snapshot.num_nodes()} nodes are available",
        )

    def _commit_fast_eligible(self, fw: Framework) -> bool:
        """True when this profile's commit tail collapses to assume+bind for
        non-gang device pods: every Reserve/PreBind/PostBind plugin acts only
        through CycleState it wrote in PreFilter/Filter (state_driven_tail —
        device pods carry a fresh empty state, so those runs are no-ops by
        construction), Permit plugins act only on gang members, and binding
        goes through the single DefaultBinder."""
        ok = self._fast_tail.get(id(fw))
        if ok is None:
            ok = (
                all(getattr(p, "state_driven_tail", False)
                    for p in fw.reserve_plugins)
                and all(getattr(p, "state_driven_tail", False)
                        for p in fw.pre_bind_plugins)
                and all(getattr(p, "gang_only", False)
                        for p in fw.permit_plugins)
                and not fw.post_bind_plugins
                and len(fw.bind_plugins) == 1
                and isinstance(fw.bind_plugins[0], DefaultBinder)
            )
            self._fast_tail[id(fw)] = ok
        return ok

    _EMPTY_STATE = CycleState()  # shared by the stateless fast commits

    def _commit(self, fw: Framework, qpi: QueuedPodInfo, node_name: str) -> bool:
        """assume → reserve → permit → binding cycle (the unchanged host tail
        of the scheduling cycle, schedule_one.go:315 onward). Returns False
        when the host rejected the placement (carry divergence)."""
        pod = qpi.pod
        self.attempts += 1
        dra_state = None
        if getattr(pod, "resource_claims", None):
            dr = fw.plugin("DynamicResources")
            if dr is not None:
                # The kernel decided the NODE via the free-matching-device
                # aux count; the host picks the actual devices by running
                # the plugin's allocation on that one node (the full
                # per-node Filter, restricted to the winner). A miss means
                # the carry diverged from live device state.
                dra_state = CycleState()
                # its own refresh: a resumed session made none, and the
                # session before it no longer ends in one
                self.cache.update_snapshot(self.snapshot)
                ni = self.snapshot.get(node_name)
                _r, st = dr.pre_filter(dra_state, pod,
                                       [ni] if ni is not None else [])
                if st.is_success() and ni is not None:
                    st = dr.filter(dra_state, pod, ni)
                if ni is None or not st.is_success():
                    self.host_path_pods += 1
                    self.process_one(qpi)
                    return False
        if (dra_state is None and not pod.pod_group and not self.extenders
                and self._commit_fast_eligible(fw)):
            # Lean tail: identical observable semantics to the full path
            # below for this plugin shape (the skipped plugin runs are
            # provably no-ops on an empty CycleState), ~2x cheaper. The one
            # per-pod tail: every pod of the hint walk, of the thread mode
            # and of a batch the batch tail refuses (_batch_tail_run) pays
            # it; a retired batch that qualifies is committed in passes
            # (_commit_run), to the same outcome.
            pod.node_name = node_name
            self.cache.assume_pod(pod, qpi.pod_info)
            st = fw.bind_plugins[0].bind(
                TPUScheduler._EMPTY_STATE, pod, node_name)
            if st.queued:
                # Thread-mode dispatcher: assumed until the apiserver's
                # acknowledgement is drained (_settle_bind does the tail
                # below then, with the acknowledgement's instant).
                self._unsettled[pod.uid] = QueuedBind(
                    fw, TPUScheduler._EMPTY_STATE, qpi, node_name,
                    device=True)
                self.queue.done(pod.uid)
                return True
            if st.is_success():
                self.cache.finish_binding(pod)
                nom = self.queue.nominator
                if nom._pod_to_node:
                    nom.delete_nominated_pod(pod)
                self.scheduled += 1
                self.observe_bound(qpi, node_name)
                self.recorder.eventf(
                    pod.namespace + "/" + pod.name, "Normal", "Scheduled",
                    ("Successfully assigned %s/%s to %s",
                     (pod.namespace, pod.name, node_name)))
                self.device_scheduled += 1
                self.queue.done(pod.uid)
                return True
            self._unwind_binding(fw, CycleState(), qpi, node_name, st)
            self.queue.done(pod.uid)
            return False
        state = dra_state if dra_state is not None else CycleState()
        pod.node_name = node_name
        self.cache.assume_pod(pod, qpi.pod_info)
        if fw.reserve_plugins:  # guard: this tail runs once per pod at >10k/s
            st = fw.run_reserve_plugins_reserve(state, pod, node_name)
            if not st.is_success():
                fw.run_reserve_plugins_unreserve(state, pod, node_name)
                self.cache.forget_pod(pod)
                pod.node_name = ""
                self.handle_scheduling_failure(fw, qpi, st, None)
                self.queue.done(pod.uid)
                return False
        st = fw.run_permit_plugins(state, pod, node_name) if fw.permit_plugins \
            else _OK_STATUS
        if st.is_rejected():
            fw.run_reserve_plugins_unreserve(state, pod, node_name)
            self.cache.forget_pod(pod)
            pod.node_name = ""
            self.handle_scheduling_failure(fw, qpi, st, None)
            self.queue.done(pod.uid)
            return False
        if st.code == WAIT:
            # WaitOnPermit (framework.go:2097): park exactly as process_one
            # does — the pod stays assumed on the node, so the device carry
            # remains correct (no divergence).
            self.park_waiting_pod(
                fw, state, qpi, ScheduleResult(suggested_host=node_name))
            self.queue.done(pod.uid)
            # Not counted in device_scheduled yet: the bind outcome is only
            # known when the waiter is allowed/rejected.
            return True
        if not self.run_binding_cycle(fw, state, qpi, ScheduleResult(suggested_host=node_name)):
            self.queue.done(pod.uid)
            return False  # bind failed and unwound
        rec = self._unsettled.get(pod.uid)
        if rec is not None:
            rec.device = True  # counted when the bind settles
        else:
            self.device_scheduled += 1
        self.queue.done(pod.uid)
        return True

    def _settle_bind(self, pod, acked_at: float):
        rec = super()._settle_bind(pod, acked_at)
        if rec is not None:
            if rec.device:
                self.device_scheduled += 1
            # Acknowledged: no failure can follow, so the optimistic hint
            # hit stands (see _walk_hint).
            pod.__dict__.pop("_hint_bound", None)
        return rec

    def _note_async_bind_lost(self, pod) -> None:
        """The optimistic hint hit was counted when the bind was queued;
        the pod will be counted again when it actually binds."""
        if pod.__dict__.pop("_hint_bound", False):
            self.hint_hits = max(0, self.hint_hits - 1)

    # -- score-hint fast path (models/score_hints.py) ----------------------

    def _try_hint_binds(self) -> int:
        """Bind a run of identical replicas host-side off the live score
        hint — the steady-state execution model for deployment-shaped
        traffic: per pod, a cheap validate (journal replay + counters) and
        the kernel's own selection math in numpy, then the existing commit
        tail (bulk-binding path included). Any miss — signature, validation,
        infeasibility — parks the entity in the holdover slot and returns,
        so the normal batch path owns it. Returns pods bound."""
        if self._hints.entry is None:
            return 0
        with self.stages.stage("hint.walk"):
            return self._walk_hint()

    def _walk_hint(self) -> int:
        hints = self._hints
        stages = self.stages
        clock = _time.perf_counter
        bound = 0
        handled = 0
        while True:
            if bound and bound % 64 == 0:
                # Settle the queued binds the apiserver has answered, and
                # surface the failed ones (409 → per-node hint
                # invalidation), while the walk runs.
                self.process_async_api_errors()
            # Per pod the walk's parts are leaves of the table (clock reads,
            # no profiler annotation): queue.pop, hint.validate, host.commit.
            _t0 = clock()
            qpi = self._pop()
            if qpi is None:
                if self._event_inbox:
                    # Concurrent creators park pod-adds in the inbox
                    # (queue-only events): drain so a creation burst does
                    # not end the hint run early — the session refill seam.
                    self.drain_event_inbox()
                    _t0 = clock()
                    qpi = self._pop()
                if qpi is None:
                    break
            stages.leaf("queue.pop", clock() - _t0)
            if (isinstance(qpi, (QueuedPodGroupInfo,
                                 QueuedCompositeGroupInfo))
                    or qpi.pod.scheduler_name not in self.profiles):
                self._holdover = qpi
                break
            fw = self.framework_for_pod(qpi.pod)
            _t0 = clock()
            served = hints.serve(fw, qpi.pod)
            if served is not None:
                entry, kind = served
                row, evaluated = entry.select(self.next_start_node_index)
            # Misses pay validation too (a stale-entry journal replay is
            # the EXPENSIVE path) — the histogram must see them.
            _tv = clock() - _t0
            self.metrics.hint_validation_duration.observe(_tv)
            stages.leaf("hint.validate", _tv)
            if served is None:
                self._holdover = qpi
                break
            if row < 0:
                # No feasible node under the hint: the normal path owns the
                # exact diagnosis (FitError / PostFilter) — fall through.
                hints._miss("infeasible")
                self._holdover = qpi
                break
            node = entry.node_names[row]
            with stages.stage("host.commit", annotate=False):
                committed = self._commit(fw, qpi, node)
            hints.note_own_attempt(node if committed else "", entry)
            handled += 1
            if not committed:
                # A sync 409 already blocked the row via _note_bind_conflict
                # (the pod re-enters through requeue_conflict); any other
                # rejection moved state the next serve() fences. Either way
                # the attempt was hint-path work — report it handled so the
                # surviving hint keeps the NEXT replica off the device.
                break
            entry.apply(row)
            self.next_start_node_index = (
                self.next_start_node_index % entry.num + evaluated) % entry.num
            bound += 1
            if qpi.pod.uid not in self.waiting_pods:
                # Hits count BINDS only. A Permit-WAIT park returns True
                # from _commit with the pod assumed-but-unbound — the
                # walker must apply the placement (it occupies the node),
                # but the hit waits for a real bind (a rejected/expired
                # waiter unwinds through state_unwinds, killing the hint).
                hints._hit(kind)
                if qpi.pod.uid in self.cache.assumed_pods:
                    # Still assumed ⇒ the bind is queued or its own event
                    # has not come back yet (the in-process store confirms
                    # inside _commit and never reaches here). Tag the pod
                    # so a later async failure takes this hit back — hint_hits
                    # must never exceed pods actually bound, or HintHitRate
                    # reads > 1.0 on exactly the contended runs where it
                    # matters. The tag is dropped when the bind settles
                    # (_settle_bind) or its own event confirms it
                    # (_note_own_bind_confirm): after that, a later life of
                    # the same object must not erase a real hit.
                    qpi.pod.__dict__["_hint_bound"] = True
            if hints.entry is not entry:
                break  # invalidated mid-loop (conflict burst)
        return handled

    # -- run loop ----------------------------------------------------------

    def _cycle(self) -> bool:
        if not self.device_enabled:
            return super()._cycle()  # TPUBatchScheduling gate off
        if not self.device_breaker.allows():
            # Breaker open: the host Evaluator owns every cycle until the
            # cool-down elapses (then ONE probe session runs half-open).
            # The device path's holdover slot (an entity popped by a session
            # refill but never dispatched) MUST drain here — the host
            # schedule_one only pops the queue and would strand it forever.
            if self._holdover is not None:
                qpi, self._holdover = self._holdover, None
                self._to_host_path([qpi])
                return True
            return super()._cycle()
        self.process_async_api_errors()
        # Score-hint fast path FIRST: while a fresh hint matches the queue
        # head, identical replicas bind in a host-only loop with zero
        # device dispatches; the first miss falls through to the batch
        # path below (the popped entity waits in the holdover slot).
        if self._hints.entry is not None and self._try_hint_binds():
            return True
        with self._pop_stage() as took:
            fw, batch, fallback_reason = self._collect_batch(took)
        if not batch:
            return False
        if fallback_reason is _GANG_SESSION:
            self.run_gang_device_session(fw, batch[0])
            return True
        nominated = fallback_reason is _NOMINATED
        if nominated:
            fallback_reason = None
        if fallback_reason is None:
            fallback_reason = self._device_unsupported_profile(
                fw, batch[0].pod)
        if fallback_reason is None and nominated \
                and self._run_nominated(fw, batch):
            return True
        if fallback_reason is not None:
            for qpi in batch:
                self.host_path_pods += 1
                self.process_one(qpi)
            return True
        self.run_device_session(fw, batch)
        return True
