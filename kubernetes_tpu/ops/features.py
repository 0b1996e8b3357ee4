"""Per-batch pod feature extraction for the device kernel.

A *batch* is a row-block of consecutive same-signature pending pods (identical
scheduling-relevant spec — the generalization of the reference's
OpportunisticBatching pod signatures, runtime/batch.go:33, to true kernel
batching per SURVEY.md §2.4). Because every pod in the batch is identical, the
expensive O(all-pods) PreFilter aggregations (PodTopologySpread
filtering.go:241 calPreFilterState, InterPodAffinity filtering.go:287) are
computed ONCE here on the host, and the *sequential* inter-pod dependency —
each assignment shifting the counts the next pod sees — runs entirely on
device inside the kernel's loop carry (ops/kernel.py).

Everything here mirrors the host-oracle plugin semantics exactly; equivalence
is enforced by tests/test_device_equivalence.py.
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass, replace
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax.numpy as jnp

from ..api import resource as res
from ..api.types import (
    DO_NOT_SCHEDULE,
    HONOR,
    LABEL_HOSTNAME,
    NO_SCHEDULE,
    SCHEDULE_ANYWAY,
    Pod,
    Taint,
    find_matching_untolerated_taint,
)
from ..core.node_info import NodeInfo, PodInfo
from ..core.scheduler import num_feasible_nodes_to_find
from ..plugins.basic import NodeAffinity, NodeUnschedulable
from ..plugins.helpers import compile_terms
from ..plugins.podtopologyspread import (
    _compile_constraints,
    _count_pods_matching,
    PodTopologySpread,
)
from .codebook import EFFECT_IDS, EFFECT_PREFER_NO_SCHEDULE, OP_EQUAL, OP_EXISTS
from .device_state import BASE_RESOURCES, DeviceNodeState, NodeStateMirror

_UNSCHED_TAINT = Taint(key=NodeUnschedulable.TAINT_KEY, effect=NO_SCHEDULE)

DEFAULT_BA_RESOURCES = (res.CPU, res.MEMORY)


def _pow2(n: int, floor: int = 1) -> int:
    if n <= 0:
        return 0
    c = floor
    while c < n:
        c *= 2
    return c


class BatchFeatures(NamedTuple):
    """Dynamic (traced) inputs to the batch kernel. All count tables are
    [*, VMAX]; VMAX and every leading dimension are padded to power-of-two
    tiers so jit recompiles are bounded (SURVEY.md §7 'capacity tiers')."""

    # resources
    request: jnp.ndarray          # [R] i64
    nz_request: jnp.ndarray       # [2] i64 (cpu/mem with non-zero defaults)
    has_request: jnp.ndarray      # i64 scalar (0 => all-zero request)
    ba_skip: jnp.ndarray          # i64 scalar (BalancedAllocation PreScore skip)
    # tolerations (pad eff = -1 rows never tolerate)
    tol_key: jnp.ndarray          # [LT] i32
    tol_val: jnp.ndarray          # [LT] i32
    tol_eff: jnp.ndarray          # [LT] i32
    tol_op: jnp.ndarray           # [LT] i32
    # cheap filters
    node_name_id: jnp.ndarray     # i32 (0 = unset)
    tolerates_unsched: jnp.ndarray  # i32
    # Full node-selector + required-node-affinity verdict per node, evaluated
    # host-side with the oracle semantics (matchExpressions In/NotIn/Exists/
    # DoesNotExist/Gt/Lt AND matchFields metadata.name — node_affinity.go
    # Filter). Static per batch: node labels cannot change mid-session.
    sel_match: jnp.ndarray        # [NP] bool
    # Extra host-evaluated static filters folded into static_ok: NodePorts
    # conflicts vs existing pods (nodeports.go Fits) and NodeDeclaredFeatures
    # (fork plugin). Placement-dependent port self-conflicts ride the carry's
    # `blocked` vector instead (BatchPlan.port_selfblock).
    extra_ok: jnp.ndarray         # [NP] bool
    # Static additive / normalized score inputs.
    il_score: jnp.ndarray         # [NP] i64 ImageLocality score (0-100, no norm)
    na_raw: jnp.ndarray           # [NP] i64 preferred-node-affinity raw sum
    # PodTopologySpread DoNotSchedule
    dns_axis: jnp.ndarray         # [C1] i32 axis row in state.topo
    dns_active: jnp.ndarray       # [C1] i32 (0 = padding row, never rejects)
    dns_max_skew: jnp.ndarray     # [C1] i64
    dns_self: jnp.ndarray         # [C1] i32 selector matches the batch pod itself
    dns_forced0: jnp.ndarray      # [C1] i32 min-match forced to 0 (minDomains)
    dns_honor_aff: jnp.ndarray    # [C1] i32 nodeAffinityPolicy == Honor
    dns_honor_taints: jnp.ndarray  # [C1] i32 nodeTaintsPolicy == Honor
    dns_counts: jnp.ndarray       # [C1, V] i32
    dns_dom: jnp.ndarray          # [C1, V] bool eligible-domain mask
    # PodTopologySpread ScheduleAnyway
    sa_axis: jnp.ndarray          # [C2] i32
    sa_wq: jnp.ndarray            # [C2] i64 round(log(size+2)*1024)
    sa_skew: jnp.ndarray          # [C2] i64
    sa_self: jnp.ndarray          # [C2] i32
    sa_counts: jnp.ndarray        # [C2, V] i32
    # InterPodAffinity required
    anti_axis: jnp.ndarray        # [A1] i32
    anti_self: jnp.ndarray        # [A1] i32
    anti_counts: jnp.ndarray      # [A1, V] i32 (own anti ∪ landed contributions)
    exist_anti: jnp.ndarray       # [NP] i32 existing pods' anti-affinity hits
    aff_axis: jnp.ndarray         # [A2] i32
    aff_self: jnp.ndarray         # [A2] i32
    aff_active: jnp.ndarray       # [A2] i32 (0 = padding row, auto-pass)
    aff_counts: jnp.ndarray       # [A2, V] i32
    aff_own_all: jnp.ndarray      # i32 incoming matches all its own terms
    # InterPodAffinity scoring
    ipa_base: jnp.ndarray         # [NP] i64
    ipa_axis: jnp.ndarray         # [KD] i32
    ipa_wland: jnp.ndarray        # [KD] i64 score delta per landing at axis value
    # Fit / BalancedAllocation scoring config
    fit_slots: jnp.ndarray        # [FR] i32 resource slot per scored resource
    fit_weights: jnp.ndarray      # [FR] i64
    # plugin weights: [tt, fit, pts, ipa, ba, na, il]
    weights: jnp.ndarray          # [7] i64
    # filter enablement from the profile's filter plugin set:
    # [NodeName, NodeUnschedulable, TaintToleration, NodeAffinity, NodeResourcesFit]
    enable: jnp.ndarray           # [5] i32
    # Counted row-local auxiliary constraint (CSI attach limits,
    # nodevolumelimits/csi.go): room left per node for the batch's limited
    # driver; each landing consumes aux_inc units of its row's room.
    aux_room: jnp.ndarray         # [NP] i32 (BIG = unconstrained)
    aux_inc: jnp.ndarray          # i32 scalar (0 = no aux constraint)
    # Nominated-pod lane (runtime/framework.go:1275 two-pass filter, pass 1):
    # per-node request/count totals of preemption-nominated pods with
    # priority >= the batch pod's — the FIT FILTER counts them as if running
    # (pass 1 is strictly tighter than pass 2 for resources, so one pass
    # suffices); scores ignore them, exactly like the host. Shape [0]/[0, R]
    # when the plan has no nominated lane (has_nom=False).
    nom_req: jnp.ndarray          # [NP or 0, R] i64
    nom_pods: jnp.ndarray         # [NP or 0] i32
    # sampling / loop
    num_nodes: jnp.ndarray        # i32
    start_index: jnp.ndarray      # i32
    to_find: jnp.ndarray          # i32


@dataclass
class BatchPlan:
    """A built batch: kernel inputs + host bookkeeping."""

    features: BatchFeatures
    batch_pad: int                # result width (>= len(pods)): a tier, not a trip count
    fit_strategy: int             # 0 = LeastAllocated, 1 = MostAllocated
    vmax: int
    # Host-known batch facts passed as static jit args so the kernel can drop
    # dead score reductions from the scan body (ops/kernel.py fast paths).
    has_pns: bool = True          # any PreferNoSchedule taint staged
    has_ipa_base: bool = True     # a landing axis, or any nonzero base score
    # Every required anti-affinity term is keyed to a singleton-per-node
    # topology axis (kubernetes.io/hostname-like): a landing can only block
    # its own row, so the kernel's lap-vectorized path stays exact.
    anti_rowlocal: bool = False
    # Pod carries preferred node-affinity terms (na_raw nonzero possible):
    # adds a kept-set normalization, disabling the carried-score fast path.
    has_na_pref: bool = False
    # Pod requests host ports: a landing occupies them, so the landed row
    # blocks itself for the rest of the session (identical pods always
    # port-conflict with each other) — row-local, lap-path compatible.
    port_selfblock: bool = False
    # Counted aux constraint live (CSI attach limits) — row-local,
    # lap-path compatible.
    has_aux: bool = False
    # Nominated-pod lane live: the fit filter subtracts nom_req/nom_pods
    # (static per plan; any nomination add/delete invalidates the session
    # via Nominator.version).
    has_nom: bool = False
    # No pod-derived lane anywhere in the plan: every width ops/kernel.py
    # `coupling` reads is zero, and (what only the values can say) no
    # nonzero ipa_base and no existing-pod anti-affinity hit in exist_anti.
    # A pod arriving on / leaving node n then dirties ONLY row n's resource
    # aggregates — the precondition for the event-journal delta patch
    # (models/tpu_scheduler.py _classify_delta).
    pod_local: bool = False
    # Host bookkeeping of the required inter-pod term tables (the stage
    # `plan.ipa`): the kernel's anti filter has something to refuse (the
    # pod's own required anti terms, or existing pods' hits in exist_anti),
    # and what the tables cost to build.
    anti_lane: bool = False
    ipa_matches: int = 0          # `term.matches` evaluations
    ipa_term_pods: int = 0        # existing pods with a required anti term
    # The same of the score-table walk (the stage `plan.ipa_score`): its
    # `term.matches` evaluations and the pods it matched terms against;
    # both 0 where the walk met no term to match.
    ipa_score_matches: int = 0
    ipa_pods_walked: int = 0
    # A plan over a narrowed row set (KeptPlan.derive `rows`): the snapshot
    # rows its rows stand for, in snapshot order (the nodes a NodeAffinity
    # PreFilterResult names, or a nominated pod's own node); row i of the
    # plan, of its device state and of its results is snapshot row rows[i].
    # None: the plan's rows are the snapshot's.
    rows: Optional[tuple] = None

    @property
    def coupling(self):
        """ops/kernel.py `coupling` of this plan: what schedule_batch
        derives at trace time from the same shapes and flags."""
        from .kernel import coupling
        return coupling(self.features, self.batch_pad, has_pns=self.has_pns,
                        has_ipa_base=self.has_ipa_base,
                        anti_rowlocal=self.anti_rowlocal,
                        has_na_pref=self.has_na_pref)

    @property
    def engine(self) -> str:
        """Which of schedule_batch's engines places this plan, as
        scheduler_device_batches_total{engine} names them."""
        c = self.coupling
        if c.lap:
            return "lap"
        return "scan_carried" if c.scores_carried else "scan_normalised"

    def dispatch_attrs(self, n_active: int) -> dict:
        """What a `device.dispatch` stage says of a dispatch of `n_active`
        pods on this plan. The scan loops `steps` = n_active times over a
        result buffer `batch_pad` wide; how many laps the lap kernel takes
        only the device knows."""
        engine = self.engine
        attrs = {"batch": n_active, "engine": engine,
                 "batch_pad": self.batch_pad}
        if engine != "lap":
            attrs["steps"] = n_active
        attrs.update(self.narrowed_attrs())
        return attrs

    def narrowed_attrs(self) -> dict:
        """What a narrowed plan says of itself on `plan.build` and
        `device.dispatch`: `narrowed_rows`, the nodes it may land on, and
        `plan_rows`, the padded rows its arrays hold; nothing on a plan
        over the snapshot's own rows."""
        if self.rows is None:
            return {}
        return {"narrowed_rows": len(self.rows), "plan_rows": self.plan_rows}

    @property
    def plan_rows(self) -> int:
        """The rows the plan's per-row arrays hold, padding included."""
        return int(self.features.sel_match.shape[0])

    @property
    def rides_lap(self) -> bool:
        """schedule_batch places this plan with the lap kernel, not the
        scan."""
        return self.coupling.lap

    @property
    def row_local(self) -> bool:
        """True when a landing changes feasibility AND scores only at its
        own landed row, through the fit lanes alone: `pod_local` (so
        feasibility is incremental with no anti lane at all), scores
        carried, and none of the per-row lanes only the single-device lap
        carries (nominated pods, host-port self-block, counted aux). The
        precondition for the explicit shard_map lap kernel
        (parallel/mesh.py sharded_lap_schedule — per-shard work is provably
        local, collectives are two small per-lap exchanges) and, with the
        same math host-side, for the score-hint walk
        (models/score_hints.py)."""
        return (self.pod_local and self.coupling.scores_carried
                and not (self.has_nom or self.port_selfblock
                         or self.has_aux))

    # Host-side per-node topology-spread columns (numpy, NOT shipped to the
    # kernel): per-constraint per-node matching-pod counts + domain
    # eligibility. schedule_placements rebuilds each candidate placement's
    # RESTRICTED count tables from these (the host oracle computes its
    # PreFilter state over the placement-restricted node list —
    # core/cache.py assume_placement), lifting the old no-spread
    # restriction invariant. None when the plan has no spread features.
    dns_node_counts: Optional[object] = None   # np [C1, n] i32
    dns_node_elig: Optional[object] = None     # np [C1, n] bool (key+policies)
    dns_min_domains: Optional[object] = None   # list[Optional[int]] per C1 row
    sa_node_counts: Optional[object] = None    # np [C2, n] i32
    sa_node_live: Optional[object] = None      # np [n] bool (~sa_ignored)
    sa_hostname_axis: Optional[object] = None  # list[bool] per C2 row
    sa_max_skew: Optional[object] = None       # list[int] per C2 row


class Unsupported(Exception):
    """Pod uses a feature outside the device kernel's coverage — the caller
    must take the host path (SURVEY.md §7.4 'sequential fallback')."""


ZONE_KEYS = ("topology.kubernetes.io/zone", "topology.kubernetes.io/region",
             "failure-domain.beta.kubernetes.io/zone",
             "failure-domain.beta.kubernetes.io/region")


def volume_device_support(pod: Pod, clientset, pvc_refs=None,
                          limited_drivers=frozenset()):
    """Device eligibility for a pod's PVC-backed volumes. Returns
    (reason, limited_driver, inc): reason is None when the volumes impose
    either NO per-node constraint (bound PV, no node affinity, no zone
    labels, not RWOP, unshared claim) or exactly one counted CSI
    attach-limit constraint — which the kernel models as the aux counted
    row-local resource (limited_driver/inc feed build_batch's aux vectors).

    Parity argument: under these conditions the volume plugins' Filter
    verdicts are all-pass except NodeVolumeLimits, whose distinct-claim
    count over unshared fresh claims equals the kernel's per-landing count
    (plugins/volumes.py NodeVolumeLimits.filter)."""
    from ..api.storage import RWOP

    names = [v.pvc_name for v in pod.volumes if v.pvc_name]
    if not names:
        return None, "", 0
    if clientset is None:
        return "pvc-backed volumes", "", 0
    driver_incs: Dict[str, int] = {}
    for name in names:
        key = f"{pod.namespace}/{name}"
        pvc = clientset.pvcs.get(key)
        if pvc is None or not pvc.volume_name:
            return "unbound pvc", "", 0
        if RWOP in pvc.access_modes:
            return "rwop pvc", "", 0
        if pvc_refs is not None and pvc_refs.get(key, 0) > 0:
            return "shared pvc", "", 0
        pv = clientset.pvs.get(pvc.volume_name)
        if pv is None:
            return "missing pv", "", 0
        if pv.node_affinity is not None:
            return "pv node affinity", "", 0
        if any(k in pv.labels for k in ZONE_KEYS):
            return "pv zone labels", "", 0
        driver = pv.csi_driver
        if not driver:
            sc = clientset.storage_classes.get(pvc.storage_class)
            driver = sc.provisioner if sc is not None else ""
        if driver and driver in limited_drivers:
            driver_incs[driver] = driver_incs.get(driver, 0) + 1
    if len(driver_incs) > 1:
        return "multiple attach-limited drivers", "", 0
    if driver_incs:
        d, inc = next(iter(driver_incs.items()))
        return None, d, inc
    return None, "", 0


def dra_device_support(pod: Pod, clientset, dra_in_use=None,
                       session_claims=None):
    """Device eligibility for a pod's resource claims: returns
    (reason, shape, inc). Eligible when the pod has exactly ONE unallocated,
    unreserved, unshared claim with ONE request — the claim-template shape.
    The kernel then models the node's FREE MATCHING DEVICE count as the
    counted aux resource; the host commit picks the actual devices on the
    chosen node only (plugins/dynamicresources.py filter, restricted to one
    node). `shape` keys session compatibility: every member of a batch must
    request identically or the per-landing decrement is wrong."""
    names = list(getattr(pod, "resource_claims", ()) or ())
    if not names:
        return None, None, 0
    if clientset is None or len(names) != 1:
        return "dynamic resource claims", None, 0
    key = f"{pod.namespace}/{names[0]}"
    claim = clientset.resource_claims.get(key)
    if claim is None:
        return "resource claim not found", None, 0
    if claim.allocated or claim.reserved_for:
        return "allocated resource claim", None, 0
    if getattr(clientset, "has_consuming_devices", False):
        # Devices that consume node allocatable add a second constraint
        # dimension the aux count cannot model (the plugin's
        # _check_node_allocatable).
        return "node-allocatable-consuming devices", None, 0
    if session_claims is not None and f"dra:{key}" in session_claims:
        return "claim shared within session", None, 0
    if len(claim.requests) != 1:
        return "multi-request claim", None, 0
    r = claim.requests[0]
    shape = (r.device_class, r.count, tuple(sorted(r.selectors.items())),
             r.expression)
    return None, shape, int(r.count)


def count_free_matching_devices(clientset, node_name: str, shape,
                                dra_in_use) -> int:
    """Free devices on `node_name` matching the session's claim shape —
    the aux_room source for DRA batches (mirror of
    plugins/dynamicresources.py filter's per-device predicate)."""
    from ..plugins.dynamicresources import DynamicResources

    device_class, _count, sel_items, expression = shape
    sel = dict(sel_items)
    if device_class:
        dc = clientset.device_classes.get(device_class)
        if dc is not None:
            sel.update(dc.selectors)
    matcher = _compiled_expr(expression) if expression else None
    n = 0
    for sl in clientset.resource_slices.get(node_name, ()):
        for dev in sl.devices:
            if (node_name, sl.driver, dev.name) in dra_in_use:
                continue
            if not all(dev.attributes.get(k) == v for k, v in sel.items()):
                continue
            if matcher is not None and not matcher(dev, sl.driver):
                continue
            n += 1
    return n


from functools import lru_cache


@lru_cache(maxsize=256)
def _compiled_expr(expression: str):
    """Compiled device-selector cache (expression strings are the whole
    input to compilation; bounded so long-lived processes with many claim
    shapes can't grow it without limit)."""
    from ..api.dra import compile_device_expression
    return compile_device_expression(expression)


def batch_supported(pod: Pod, snapshot, fit_plugin=None, ba_plugin=None,
                    clientset=None, pvc_refs=None,
                    limited_drivers=frozenset(),
                    dra_enabled=False, dra_in_use=None, session_claims=None,
                    _volume_verdict=None) -> Optional[str]:
    """Returns a reason string when the pod needs the host path, else None.

    Host ports, node-affinity expressions (required AND preferred), image
    locality, NodeDeclaredFeatures, and bound-PVC volumes (incl. one
    counted CSI attach limit) are covered on device via host-evaluated
    static per-node vectors (sel_match / extra_ok / na_raw / il_score /
    aux_room) — only genuinely stateful host machinery (unbound volume
    binding, DRA allocation) still falls back. Neither a nomination on the
    pod nor a NodeAffinity PreFilterResult (every required term pins
    metadata.name) is a reason: both plan over a narrowed row set
    (`KeptPlan.derive` `rows`), the nominated pod's own node first and
    alone by a batch of one (models/tpu_scheduler.py
    _evaluate_nominated_node; it never joins another pod's batch,
    _batch_supported_memo), the pinned pods' named nodes by ordinary
    sessions, over which the kernel's own rotation is the host's rotation
    over the narrowed list (`narrowed_rows`). Only a pin that names nobody
    (`In` with no values) stays on the host: its PreFilter rejects the pod
    before the start index is read, which a dispatch would move."""
    if NodeAffinity.narrowed_node_names(pod) == set():
        return "node-affinity names no node"
    reason, vol_d, vol_inc = (_volume_verdict if _volume_verdict is not None
                              else volume_device_support(
                                  pod, clientset, pvc_refs=pvc_refs,
                                  limited_drivers=limited_drivers))
    if reason is not None:
        return reason
    if getattr(pod, "resource_claims", None):
        if not dra_enabled:
            # Profile has no DynamicResources plugin: claims are inert for
            # scheduling (host semantics) — the pod batches as plain.
            pass
        else:
            dreason, _shape, dinc = dra_device_support(
                pod, clientset, dra_in_use=dra_in_use,
                session_claims=session_claims)
            if dreason is not None:
                return dreason
            if dinc and (vol_d and vol_inc):
                return "volume and DRA counted constraints together"
    if fit_plugin is not None and fit_plugin.scoring_strategy not in ("LeastAllocated", "MostAllocated"):
        return "requestedToCapacityRatio strategy"
    if ba_plugin is not None and tuple(
            spec["name"] for spec in ba_plugin.resources) != DEFAULT_BA_RESOURCES:
        return "balanced-allocation custom resources"
    return None


def _resource_vec(mirror: NodeStateMirror, r: "res.Resource") -> np.ndarray:
    out = np.zeros(mirror.r_slots, np.int64)
    out[0] = r.milli_cpu
    out[1] = r.memory
    out[2] = r.ephemeral_storage
    for name, amount in r.scalar_resources.items():
        out[mirror.scalar_slot(name)] = amount
    return out


def build_batch(
    pod: Pod,
    batch_size: int,
    mirror: NodeStateMirror,
    snapshot,
    ns_labels_fn=None,
    *,
    percentage_of_nodes_to_score: int = 0,
    start_index: int = 0,
    weights: Tuple[int, ...] = (3, 1, 2, 2, 1, 2, 1),
    filters_on: Tuple[bool, bool, bool, bool, bool] = (True, True, True, True, True),
    extra_filters: Optional[Dict[str, bool]] = None,
    hard_pod_affinity_weight: int = 1,
    ignore_preferred_terms_of_existing_pods: bool = False,
    fit_plugin=None,
    clientset=None,
    pvc_refs=None,
    limited_drivers=frozenset(),
    dra_enabled=False,
    dra_in_use=None,
    nominated=None,
    stages=None,
) -> BatchPlan:
    """Build kernel inputs for a batch of `batch_size` pods identical to `pod`.

    `mirror` must already be synced to `snapshot`. Raises Unsupported for
    feature combinations the kernel does not cover.

    `stages`: the caller's StageLedger (core/spans.py). The build of the
    required inter-pod term tables is its stage `plan.ipa`, opened only
    where there is a term to evaluate (the pod's own, or an existing pod's
    required anti-affinity), so a cluster without any never shows it. The
    walk that builds the InterPodAffinity score tables (`ipa_base`: every
    pod against the incoming pod's preferred terms, each existing pod's
    preferred terms against the incoming pod) is its stage `plan.ipa_score`,
    opened at the first pod that brings that walk a term to match.

    `nominated`: [(node_row, PodInfo)] of preemption-nominated pods with
    priority >= the batch pod's, pre-filtered by the caller (the device
    gate guarantees the batch pod carries no feature a nominated pod could
    interact with beyond resources — models/tpu_scheduler.py
    _nominated_device_block).

    The plan is over every row of the snapshot, whatever the pod's
    PreFilterResult says: a plan over a narrowed row set is derived from it
    (`KeptPlan.derive` `rows`), never built.
    """
    verdict = volume_device_support(
        pod, clientset, pvc_refs=pvc_refs, limited_drivers=limited_drivers)
    reason = batch_supported(pod, snapshot, fit_plugin=fit_plugin,
                             clientset=clientset, pvc_refs=pvc_refs,
                             limited_drivers=limited_drivers,
                             dra_enabled=dra_enabled, dra_in_use=dra_in_use,
                             _volume_verdict=verdict)
    if reason:
        raise Unsupported(reason)
    _vr, aux_driver, aux_inc_n = verdict
    dra_shape = None
    if dra_enabled and getattr(pod, "resource_claims", None):
        _dr, dra_shape, dra_inc = dra_device_support(
            pod, clientset, dra_in_use=dra_in_use)
        if dra_shape is not None and dra_inc:
            aux_driver, aux_inc_n = "", 0  # volume aux unused with DRA aux

    nodes: List[NodeInfo] = snapshot.node_info_list
    n = len(nodes)
    i32, i64 = np.int32, np.int64

    # -- resources (slot interning only; vectors are built after the
    # re-sync point, since interning can grow the slot capacity) -----------
    req = pod.resource_request()
    for name in req.scalar_resources:
        mirror.scalar_slot(name)
    # Nominated pods' scalar slots intern HERE, before the re-sync point —
    # a grow later would orphan every feature vector already built at the
    # old r_slots width.
    nom_reqs = lane_requests(mirror, nominated)
    if fit_plugin is not None:
        specs = fit_plugin.resources
        strategy = {"LeastAllocated": 0, "MostAllocated": 1}[fit_plugin.scoring_strategy]
    else:
        specs = ({"name": res.CPU, "weight": 1}, {"name": res.MEMORY, "weight": 1})
        strategy = 0
    for spec in specs:
        if spec["name"] not in (res.CPU, res.MEMORY, res.EPHEMERAL_STORAGE, res.PODS):
            mirror.scalar_slot(spec["name"])
    has_request = i64(0 if req.is_zero() else 1)
    ba_skip = i64(1 if (req.milli_cpu == 0 and req.memory == 0) else 0)

    # -- tolerations ------------------------------------------------------
    tols = pod.tolerations
    lt = _pow2(len(tols))
    tol_key = np.zeros(lt, i32)
    tol_val = np.zeros(lt, i32)
    tol_eff = np.full(lt, -1, i32)  # pad: never tolerates
    tol_op = np.zeros(lt, i32)
    for j, t in enumerate(tols):
        tol_key[j] = mirror.keys.intern(t.key)
        tol_val[j] = mirror.vals.intern(t.value)
        tol_eff[j] = EFFECT_IDS.get(t.effect, 0)
        tol_op[j] = OP_EXISTS if t.operator == "Exists" else OP_EQUAL
    tolerates_unsched = i32(
        1 if any(t.tolerates(_UNSCHED_TAINT) for t in tols) else 0)

    # -- cheap filters ----------------------------------------------------
    node_name_id = i32(mirror.names.lookup(pod.node_name) if pod.node_name else 0)
    if pod.node_name and node_name_id == -1:
        # Requested node not in the snapshot: no node can match.
        node_name_id = i32(-2)

    # Host-side per-node predicates reused by the topology aggregations below
    # (identical to the plugin oracles' helpers). sel_match_host carries the
    # FULL node-selector + required-node-affinity semantics and is shipped to
    # the kernel verbatim — affinity matchExpressions/matchFields need no
    # device re-implementation because they are static per batch.
    sel_match_host = [pod.required_node_selector_matches(ni.node) for ni in nodes]
    taint_ok_host = [
        find_matching_untolerated_taint(ni.node.taints, tols) is None for ni in nodes
    ]

    extra = extra_filters or {}

    # -- extra static filters: NodeDeclaredFeatures + NodePorts -------------
    extra_ok_host = np.ones(len(nodes), bool)
    req_feats = [s.strip() for s in pod.annotations.get(
        "features.k8s.io/required", "").split(",") if s.strip()]
    if req_feats and extra.get("NodeDeclaredFeatures", True):
        for r_i, ni in enumerate(nodes):
            declared = ni.node.declared_features if ni.node else {}
            if not all(declared.get(ft, False) for ft in req_feats):
                extra_ok_host[r_i] = False
    ports = pod.host_ports()
    port_selfblock = False
    if ports and extra.get("NodePorts", True):
        # Identical pods always conflict with their own ports, so a landing
        # blocks its row (kernel carry `blocked`); existing-pod conflicts are
        # static — evaluated with the host plugin's own predicate.
        from ..plugins.basic import host_ports_conflict
        port_selfblock = True
        for r_i, ni in enumerate(nodes):
            if host_ports_conflict(ports, ni.used_ports):
                extra_ok_host[r_i] = False

    # -- ImageLocality static score (imagelocality.go scaledImageScore) -----
    il_host = None
    if weights[6] and any(c.image for c in pod.containers):
        from ..plugins.basic import ImageLocality
        total_nodes = max(1, len(nodes))
        il_host = np.zeros(len(nodes), np.int64)
        for r_i, ni in enumerate(nodes):
            il_host[r_i] = ImageLocality.scaled_score(
                pod, ni, snapshot.image_num_nodes, total_nodes)

    # -- preferred node affinity raw score (node_affinity.go Score) ---------
    na_host = None
    has_na_pref = False
    na_spec = pod.affinity.node_affinity if pod.affinity else None
    if na_spec is not None and na_spec.preferred and weights[5]:
        has_na_pref = True
        na_host = np.zeros(len(nodes), np.int64)
        for r_i, ni in enumerate(nodes):
            t = 0
            for pref in na_spec.preferred:
                if pref.preference.matches(ni.node):
                    t += pref.weight
            na_host[r_i] = t

    # -- PodTopologySpread ------------------------------------------------
    dns = _compile_constraints(pod, DO_NOT_SCHEDULE)
    sa = _compile_constraints(pod, SCHEDULE_ANYWAY)
    for c in dns + sa:
        mirror.ensure_axis(c.topology_key)

    # -- InterPodAffinity terms -------------------------------------------
    pi = PodInfo.of(pod)
    aff_terms = compile_terms(pi.required_affinity_terms, pod)
    anti_terms = compile_terms(pi.required_anti_affinity_terms, pod)
    pref_aff = [(w.weight, t) for w, t in
                ((w, compile_terms((w.term,), pod)[0]) for w in pi.preferred_affinity_terms)]
    pref_anti = [(w.weight, t) for w, t in
                 ((w, compile_terms((w.term,), pod)[0]) for w in pi.preferred_anti_affinity_terms)]
    for t in list(aff_terms) + list(anti_terms):
        mirror.ensure_axis(t.topology_key)
    for _, t in pref_aff + pref_anti:
        mirror.ensure_axis(t.topology_key)
    # Existing pods' terms introduce axes too; collect before building tables.
    existing_term_cache: Dict[str, tuple] = {}

    def existing_terms(epi: PodInfo, which: str):
        ck = (epi.pod.uid, which)
        terms = existing_term_cache.get(ck)
        if terms is None:
            raw = getattr(epi, which)
            terms = compile_terms(raw, epi.pod)
            existing_term_cache[ck] = terms
        return terms

    for ni in nodes:
        for epi in ni.pods_with_affinity:
            for which in ("required_anti_affinity_terms", "required_affinity_terms",
                          "preferred_affinity_terms", "preferred_anti_affinity_terms"):
                raw = getattr(epi, which)
                for item in raw:
                    key = item.term.topology_key if hasattr(item, "term") else item.topology_key
                    mirror.ensure_axis(key)

    if mirror._full_flush:
        # New axes or capacity tiers were registered: rows must re-encode
        # before any vid/slot gathers below.
        mirror.sync(nodes)

    npc = mirror.np_cap
    request = _resource_vec(mirror, req)
    nz_request = np.array(
        [req.milli_cpu or NodeInfo.DEFAULT_MILLI_CPU,
         req.memory or NodeInfo.DEFAULT_MEMORY], i64)

    vmax = _plan_vmax(mirror)

    # ---- DNS tables ------------------------------------------------------
    c1 = _pow2(len(dns))
    dns_axis = np.zeros(c1, i32)
    dns_active = np.zeros(c1, i32)            # pad rows: inert
    dns_max_skew = np.full(c1, 1 << 40, i64)  # pad: never rejects
    dns_self = np.zeros(c1, i32)
    dns_forced0 = np.ones(c1, i32)            # pad: min 0
    dns_honor_aff = np.zeros(c1, i32)
    dns_honor_taints = np.zeros(c1, i32)
    dns_counts = np.zeros((c1, vmax), i32)
    dns_dom = np.zeros((c1, vmax), bool)
    dns_node_counts = np.zeros((len(dns), n), i32) if dns else None
    dns_node_elig = np.zeros((len(dns), n), bool) if dns else None
    dns_min_domains = [c.min_domains for c in dns] if dns else None
    for ci, c in enumerate(dns):
        ax = mirror.axes[c.topology_key]
        dns_axis[ci] = ax.index
        dns_active[ci] = 1
        dns_max_skew[ci] = c.max_skew
        dns_self[ci] = 1 if c.selector.matches(pod.labels) else 0
        dns_honor_aff[ci] = 1 if c.node_affinity_policy == HONOR else 0
        dns_honor_taints[ci] = 1 if c.node_taints_policy == HONOR else 0
        vids = mirror.h_topo[ax.index]
        n_domains = set()
        for r_i, ni in enumerate(nodes):
            node = ni.node
            if c.topology_key not in node.labels:
                continue
            if dns_honor_aff[ci] and not sel_match_host[r_i]:
                continue
            if dns_honor_taints[ci] and not taint_ok_host[r_i]:
                continue
            vid = vids[r_i]
            dns_dom[ci, vid] = True
            n_domains.add(vid)
            cnt = _count_pods_matching(ni, c.selector, pod.namespace)
            dns_counts[ci, vid] += cnt
            dns_node_counts[ci, r_i] = cnt
            dns_node_elig[ci, r_i] = True
        forced = c.min_domains is not None and len(n_domains) < c.min_domains
        dns_forced0[ci] = 1 if (forced or not n_domains) else 0

    # ---- SA tables -------------------------------------------------------
    c2 = _pow2(len(sa))
    sa_axis = np.zeros(c2, i32)
    sa_wq = np.zeros(c2, i64)
    sa_skew = np.ones(c2, i64)
    sa_self = np.zeros(c2, i32)
    sa_counts = np.zeros((c2, vmax), i32)
    sa_node_counts = np.zeros((len(sa), n), i32) if sa else None
    sa_node_live = None
    sa_hostname_axis = [c.topology_key == LABEL_HOSTNAME for c in sa] if sa else None
    sa_max_skew_l = [int(c.max_skew) for c in sa] if sa else None
    if sa:
        # scoring.go initPreScoreState: a node is ignored when it misses any
        # constraint's topology key or fails the pod's required node affinity.
        sa_ignored = [
            (not all(c.topology_key in ni.node.labels for c in sa)) or not sel_match_host[r_i]
            for r_i, ni in enumerate(nodes)
        ]
        sa_node_live = ~np.asarray(sa_ignored, bool)
        for ci, c in enumerate(sa):
            ax = mirror.axes[c.topology_key]
            sa_axis[ci] = ax.index
            sa_skew[ci] = c.max_skew
            sa_self[ci] = 1 if c.selector.matches(pod.labels) else 0
            vids = mirror.h_topo[ax.index]
            domains = set()
            size_hostname = 0
            for r_i, ni in enumerate(nodes):
                if sa_ignored[r_i]:
                    continue
                vid = vids[r_i]
                cnt = _count_pods_matching(ni, c.selector, pod.namespace)
                sa_counts[ci, vid] += cnt
                sa_node_counts[ci, r_i] = cnt
                domains.add(vid)
                size_hostname += 1
            if c.topology_key == LABEL_HOSTNAME:
                size = size_hostname
            else:
                size = len(domains)
            sa_wq[ci] = int(round(math.log(size + 2) * 1024))

    # ---- IPA required tables --------------------------------------------
    a1 = _pow2(len(anti_terms))
    anti_axis = np.zeros(a1, i32)
    anti_self = np.zeros(a1, i32)
    anti_counts = np.zeros((a1, vmax), i32)
    a2 = _pow2(len(aff_terms))
    aff_axis = np.zeros(a2, i32)
    aff_self = np.zeros(a2, i32)
    aff_active = np.zeros(a2, i32)
    aff_counts = np.zeros((a2, vmax), i32)
    exist_anti = np.zeros(npc, i32)
    anti_rowlocal = bool(anti_terms)
    # What the tables cost the host: `term.matches` evaluations, and the
    # existing pods that carry a required anti-affinity term.
    ipa_matches = 0
    ipa_term_pods = 0
    ipa_live = bool(aff_terms or anti_terms
                    or snapshot.have_pods_with_required_anti_affinity_list)
    with (stages.stage("plan.ipa") if stages is not None and ipa_live
          else contextlib.nullcontext()) as ipa_stage:
        for ti, t in enumerate(anti_terms):
            ax = mirror.axes[t.topology_key]
            anti_axis[ti] = ax.index
            anti_self[ti] = 1 if t.matches(pod, ns_labels_fn) else 0
            if anti_rowlocal:
                vids = mirror.h_topo[ax.index, :n]
                nz = vids[vids > 0]
                if nz.size and np.bincount(nz).max() > 1:
                    anti_rowlocal = False  # shared domains: cross-window coupling
        for ti, t in enumerate(aff_terms):
            aff_axis[ti] = mirror.axes[t.topology_key].index
            aff_self[ti] = 1 if t.matches(pod, ns_labels_fn) else 0
            aff_active[ti] = 1
        aff_own_all = i32(1 if aff_terms and aff_self[:len(aff_terms)].all()
                          else 0)
        ipa_matches += len(anti_terms) + len(aff_terms)

        # Existing pods' required anti-affinity vs the incoming pod
        # (filtering.go:217-241) — accumulated per (axis, value) then
        # broadcast to a per-row hit count.
        exist_pairs: Dict[Tuple[int, int], int] = {}
        for r_i, ni in enumerate(nodes):
            if not ni.pods_with_required_anti_affinity:
                continue
            node = ni.node
            ipa_term_pods += len(ni.pods_with_required_anti_affinity)
            for epi in ni.pods_with_required_anti_affinity:
                for term in existing_terms(epi, "required_anti_affinity_terms"):
                    tp_val = node.labels.get(term.topology_key)
                    if tp_val is None:
                        continue
                    ipa_matches += 1
                    if term.matches(pod, ns_labels_fn):
                        ax = mirror.axes[term.topology_key]
                        key = (ax.index, ax.lookup_value(tp_val))
                        exist_pairs[key] = exist_pairs.get(key, 0) + 1
        for (ax_i, vid), cnt in exist_pairs.items():
            if cnt > 0 and vid >= 0:
                exist_anti[:n] += (mirror.h_topo[ax_i, :n] == vid).astype(i32)

        # Incoming pod's required terms vs all existing pods
        # (filtering.go:247-284).
        if aff_terms or anti_terms:
            for r_i, ni in enumerate(nodes):
                if not ni.pods:
                    continue
                for epi in ni.pods:
                    ep = epi.pod
                    for ti, term in enumerate(aff_terms):
                        vid = mirror.h_topo[mirror.axes[term.topology_key].index, r_i]
                        if vid > 0:
                            ipa_matches += 1
                            if term.matches(ep, ns_labels_fn):
                                aff_counts[ti, vid] += 1
                    for ti, term in enumerate(anti_terms):
                        vid = mirror.h_topo[mirror.axes[term.topology_key].index, r_i]
                        if vid > 0:
                            ipa_matches += 1
                            if term.matches(ep, ns_labels_fn):
                                anti_counts[ti, vid] += 1
        if ipa_stage is not None:
            ipa_stage.attrs.update(matches=ipa_matches, term_pods=ipa_term_pods)

    # ---- IPA scoring -----------------------------------------------------
    # Base per-node preferred-term score (scoring.go PreScore accumulation),
    # plus per-axis landing deltas for batch-internal contributions.
    topology_score: Dict[str, Dict[str, int]] = {}

    def _add_score(tp_key: str, tp_val: str, w: int) -> None:
        if w == 0:
            return
        topology_score.setdefault(tp_key, {})
        topology_score[tp_key][tp_val] = topology_score[tp_key].get(tp_val, 0) + w

    has_pref = bool(pref_aff or pref_anti)
    scan_nodes = nodes if has_pref else snapshot.have_pods_with_affinity_list
    # What the walk costs the host: `term.matches` evaluations and the pods
    # it matches them against. Its stage `plan.ipa_score` opens at the first
    # pod that brings a term to match (a preferred term of the incoming pod:
    # the first pod; else the first existing pod with a term the score
    # reads), so a cluster whose pods carry none never shows it.
    score_matches = 0
    pods_walked = 0
    term_met = False
    score_stage = None
    with contextlib.ExitStack() as walk:
        for ni in scan_nodes:
            node = ni.node
            if node is None:
                continue
            pods_iter = ni.pods if has_pref else ni.pods_with_affinity
            for epi in pods_iter:
                if not term_met:
                    if not (has_pref
                            or (hard_pod_affinity_weight > 0
                                and epi.required_affinity_terms)
                            or (not ignore_preferred_terms_of_existing_pods
                                and (epi.preferred_affinity_terms
                                     or epi.preferred_anti_affinity_terms))):
                        continue
                    term_met = True
                    if stages is not None:
                        score_stage = walk.enter_context(
                            stages.stage("plan.ipa_score"))
                ep = epi.pod
                pods_walked += 1
                for weight, term in pref_aff:
                    tp_val = node.labels.get(term.topology_key)
                    if tp_val is not None:
                        score_matches += 1
                        if term.matches(ep, ns_labels_fn):
                            _add_score(term.topology_key, tp_val, weight)
                for weight, term in pref_anti:
                    tp_val = node.labels.get(term.topology_key)
                    if tp_val is not None:
                        score_matches += 1
                        if term.matches(ep, ns_labels_fn):
                            _add_score(term.topology_key, tp_val, -weight)
                if hard_pod_affinity_weight > 0:
                    for term in existing_terms(epi, "required_affinity_terms"):
                        tp_val = node.labels.get(term.topology_key)
                        if tp_val is not None:
                            score_matches += 1
                            if term.matches(pod, ns_labels_fn):
                                _add_score(term.topology_key, tp_val,
                                           hard_pod_affinity_weight)
                if not ignore_preferred_terms_of_existing_pods:
                    for sign, weighted in ((1, epi.preferred_affinity_terms),
                                           (-1, epi.preferred_anti_affinity_terms)):
                        for wt in weighted:
                            term = compile_terms((wt.term,), ep)[0]
                            tp_val = node.labels.get(term.topology_key)
                            if tp_val is not None:
                                score_matches += 1
                                if term.matches(pod, ns_labels_fn):
                                    _add_score(term.topology_key, tp_val,
                                               sign * wt.weight)
        if score_stage is not None:
            score_stage.attrs.update(matches=score_matches,
                                     pods_walked=pods_walked)

    ipa_base = np.zeros(npc, i64)
    for tp_key, vals in topology_score.items():
        ax = mirror.axes.get(tp_key)
        if ax is None:
            continue  # key only on deleted nodes; no live node can match
        col = np.zeros(vmax, i64)
        for v, w in vals.items():
            vid = ax.lookup_value(v)
            if vid >= 0:
                col[vid] = w
        ipa_base[:n] += col[np.clip(mirror.h_topo[ax.index, :n], 0, vmax - 1)]
        ipa_base[:n][mirror.h_topo[ax.index, :n] == 0] -= col[0]  # absent key adds nothing

    # Landing deltas: contributions a landed batch pod makes to the *next*
    # batch pod's topology_score, aggregated per axis. Both directions of each
    # preferred term apply for identical pods (pre_score's a/c loops).
    land: Dict[int, int] = {}
    mult = 1 if ignore_preferred_terms_of_existing_pods else 2
    for weight, term in pref_aff:
        if term.matches(pod, ns_labels_fn):
            ax_i = mirror.axes[term.topology_key].index
            land[ax_i] = land.get(ax_i, 0) + weight * mult
    for weight, term in pref_anti:
        if term.matches(pod, ns_labels_fn):
            ax_i = mirror.axes[term.topology_key].index
            land[ax_i] = land.get(ax_i, 0) - weight * mult
    if hard_pod_affinity_weight > 0:
        for term in aff_terms:
            if term.matches(pod, ns_labels_fn):
                ax_i = mirror.axes[term.topology_key].index
                land[ax_i] = land.get(ax_i, 0) + hard_pod_affinity_weight
    kd = _pow2(len(land))
    ipa_axis = np.zeros(kd, i32)
    ipa_wland = np.zeros(kd, i64)
    for j, (ax_i, w) in enumerate(sorted(land.items())):
        ipa_axis[j] = ax_i
        ipa_wland[j] = w

    # ---- Fit scoring config (slots pre-interned above) ------------------
    fr = _pow2(len(specs))
    fit_slots = np.zeros(fr, i32)
    fit_weights = np.zeros(fr, i64)  # pad weight 0: excluded
    slot_of = {res.CPU: 0, res.MEMORY: 1, res.EPHEMERAL_STORAGE: 2}
    for j, spec in enumerate(specs):
        name = spec["name"]
        fit_slots[j] = slot_of.get(name, mirror.scalar_slot(name) if name not in slot_of else 0)
        fit_weights[j] = spec.get("weight", 1)

    to_find = num_feasible_nodes_to_find(n, percentage_of_nodes_to_score)

    # ---- nominated-pod lane (two-pass filter pass 1, resources only) -----
    # (scalar slots were interned at the top of build_batch, before re-sync)
    has_nom = bool(nominated)
    nom_req, nom_pods = _lane_arrays(mirror, nom_reqs)

    # ---- counted aux constraint: CSI attach room / DRA free devices ------
    AUX_BIG = (1 << 30)
    aux_room = np.full(npc, AUX_BIG, i32)
    has_aux_flag = False
    if dra_shape is not None:
        iu = dra_in_use if dra_in_use is not None else set()
        for r_i, ni in enumerate(nodes):
            aux_room[r_i] = count_free_matching_devices(
                clientset, ni.name, dra_shape, iu)
        aux_inc_n = dra_shape[1]
        has_aux_flag = True
    if aux_driver and aux_inc_n:
        driver_of: Dict[str, Optional[str]] = {}

        def _claim_driver(key: str) -> Optional[str]:
            d = driver_of.get(key)
            if d is None and key not in driver_of:
                pvc = clientset.pvcs.get(key)
                d = None
                if pvc is not None:
                    pv = clientset.pvs.get(pvc.volume_name) if pvc.volume_name else None
                    if pv is not None and pv.csi_driver:
                        d = pv.csi_driver
                    else:
                        sc = clientset.storage_classes.get(pvc.storage_class)
                        d = sc.provisioner if sc is not None else None
                driver_of[key] = d
            return driver_of.get(key)

        for r_i, ni in enumerate(nodes):
            cn = clientset.csi_nodes.get(ni.name)
            limit = cn.driver_limits.get(aux_driver) if cn is not None else None
            if limit is None:
                continue
            existing = sum(1 for key in ni.pvc_ref_counts
                           if _claim_driver(key) == aux_driver)
            aux_room[r_i] = max(0, limit - existing)

    # every field in ONE transfer (NodeStateMirror.upload)
    feats = BatchFeatures(**mirror.upload("features", dict(
        request=request, nz_request=nz_request,
        has_request=has_request, ba_skip=ba_skip,
        tol_key=tol_key, tol_val=tol_val, tol_eff=tol_eff, tol_op=tol_op,
        node_name_id=node_name_id,
        tolerates_unsched=tolerates_unsched,
        sel_match=_pad_bool(sel_match_host, npc),
        extra_ok=_pad_bool(extra_ok_host, npc, default=True),
        il_score=_pad_i64(il_host, npc), na_raw=_pad_i64(na_host, npc),
        dns_axis=dns_axis, dns_active=dns_active, dns_max_skew=dns_max_skew,
        dns_self=dns_self, dns_forced0=dns_forced0,
        dns_honor_aff=dns_honor_aff, dns_honor_taints=dns_honor_taints,
        dns_counts=dns_counts, dns_dom=dns_dom,
        sa_axis=sa_axis, sa_wq=sa_wq, sa_skew=sa_skew, sa_self=sa_self,
        sa_counts=sa_counts,
        anti_axis=anti_axis, anti_self=anti_self, anti_counts=anti_counts,
        exist_anti=exist_anti,
        aff_axis=aff_axis, aff_self=aff_self, aff_active=aff_active,
        aff_counts=aff_counts, aff_own_all=aff_own_all,
        ipa_base=ipa_base, ipa_axis=ipa_axis, ipa_wland=ipa_wland,
        fit_slots=fit_slots, fit_weights=fit_weights,
        weights=np.array(weights, i64),
        enable=np.array([1 if b else 0 for b in filters_on], i32),
        aux_room=aux_room, aux_inc=np.int32(aux_inc_n),
        nom_req=nom_req, nom_pods=nom_pods,
        num_nodes=np.int32(n),
        start_index=np.int32(start_index % max(1, n)),
        to_find=np.int32(to_find),
    )))
    return BatchPlan(
        features=feats,
        batch_pad=_batch_tier(batch_size),
        fit_strategy=strategy,
        vmax=vmax,
        has_pns=_has_pns(mirror, n),
        # With a landing axis the kernel reads ipa_base whatever this says
        # (`if KD or has_ipa_base`), so the flag follows the axis: an empty
        # cluster's plan and a loaded one's are then one compiled program,
        # not two that differ in a static argument alone.
        has_ipa_base=bool(kd) or bool((ipa_base != 0).any()),
        pod_local=bool(c1 == 0 and c2 == 0 and a1 == 0 and a2 == 0
                       and kd == 0 and not (ipa_base != 0).any()
                       and not (exist_anti != 0).any()),
        anti_rowlocal=anti_rowlocal,
        has_na_pref=has_na_pref,
        port_selfblock=port_selfblock,
        has_aux=has_aux_flag or bool(aux_driver and aux_inc_n),
        has_nom=has_nom,
        anti_lane=bool(anti_terms) or bool((exist_anti != 0).any()),
        ipa_matches=ipa_matches,
        ipa_term_pods=ipa_term_pods,
        ipa_score_matches=score_matches,
        ipa_pods_walked=pods_walked,
        dns_node_counts=dns_node_counts,
        dns_node_elig=dns_node_elig,
        dns_min_domains=dns_min_domains,
        sa_node_counts=sa_node_counts,
        sa_node_live=sa_node_live,
        sa_hostname_axis=sa_hostname_axis,
        sa_max_skew=sa_max_skew_l,
    )


def _pad_bool(vals, npc: int, default: bool = False) -> np.ndarray:
    out = np.full(npc, default, bool)
    if vals is not None:
        out[:len(vals)] = vals
    return out


def _pad_i64(vals, npc: int) -> np.ndarray:
    out = np.zeros(npc, np.int64)
    if vals is not None:
        out[:len(vals)] = vals
    return out


def lane_requests(mirror: NodeStateMirror, nominated) -> list:
    """[(row, request)] of the nominated lane's pods, their scalar slots
    interned: a slot that grows `r_slots` must do so before any vector of
    that width is built, or kept."""
    nom_reqs = [(row, npi.pod.resource_request())
                for row, npi in (nominated or ())]
    for _row, r in nom_reqs:
        for name in r.scalar_resources:
            mirror.scalar_slot(name)
    return nom_reqs


def _lane_arrays(mirror: NodeStateMirror, nom_reqs) -> tuple:
    """(nom_req, nom_pods) of the nominated lane: per row what its nominated
    pods ask and how many they are; zero rows where nobody is nominated."""
    if not nom_reqs:
        return (np.zeros((0, mirror.r_slots), np.int64),
                np.zeros(0, np.int32))
    nom_req = np.zeros((mirror.np_cap, mirror.r_slots), np.int64)
    nom_pods = np.zeros(mirror.np_cap, np.int32)
    for row, r in nom_reqs:
        nom_req[row] += _resource_vec(mirror, r)
        nom_pods[row] += 1
    return nom_req, nom_pods


# The BatchFeatures fields that hold one value a node row (the fields
# parallel/mesh.py shards over the node axis; tests/test_narrowed_plan.py
# holds the two lists equal): what a plan over a narrowed row set gathers.
ROW_FIELDS = ("sel_match", "extra_ok", "il_score", "na_raw", "exist_anti",
              "ipa_base", "aux_room")
# The smallest row tier of a narrowed plan: the mirror's own smallest.
NARROW_FLOOR = 64


def narrowed_rows(pod: Pod, row_of: Dict[str, int]) -> Optional[List[int]]:
    """The snapshot rows a NodeAffinity PreFilterResult narrows `pod`'s cycle
    to, in snapshot order (the order the host walks the narrowed list in,
    core/scheduler.py find_nodes_that_fit_pod), or None where it does not
    narrow. `row_of`: node name -> snapshot row. A named node that is not
    in the snapshot has no row; a pin to nodes that are all gone gives []."""
    names = NodeAffinity.narrowed_node_names(pod)
    if names is None:
        return None
    return sorted(row_of[nm] for nm in names if nm in row_of)


def narrow_width(m: int, shards: int = 1) -> int:
    """The padded row count of a plan narrowed to `m` rows: a power of two
    from NARROW_FLOOR, a multiple of the mesh's `shards`."""
    width = _pow2(max(m, shards), NARROW_FLOOR)
    return -(-width // shards) * shards


def _plan_vmax(mirror: NodeStateMirror) -> int:
    """The width of a plan's count tables: the tier of the most values any
    topology axis of the mirror holds."""
    return _pow2(max((len(ax.values) for ax in mirror.axes.values()),
                     default=1) + 1, 64)


def _has_pns(mirror: NodeStateMirror, n: int) -> bool:
    return bool((mirror.h_taint_eff[:n] == EFFECT_PREFER_NO_SCHEDULE).any())


def _batch_tier(n: int) -> int:
    """Coarse tiers of the result buffer's width: each distinct tier is a
    separate XLA compile (~1 min on first use), so bound them to {8, 64,
    512, 1024, ...}. The width costs no steps: the kernels loop over the
    pods a dispatch holds (`n_active`), and the host slices the rest off."""
    if n <= 8:
        return 8
    if n <= 64:
        return 64
    return _pow2(n, 512)


def plan_shape(mirror: NodeStateMirror) -> tuple:
    """What of the mirror sizes a plan's arrays: a plan built at one shape
    is no plan at another."""
    return mirror.np_cap, mirror.r_slots, _plan_vmax(mirror)


@dataclass
class KeptPlan:
    """A built plan kept for its pod template (models/tpu_scheduler.py, "the
    keeper of built plans"), for the two kinds of caller that plan for a
    template planned for before.

    A preemptor (`_preemptor_plan`: the preemption what-if, and a nominated
    pod's own node) derives from it. `plan` is what `build_batch` gave, over
    every row of the snapshot; `seq` the journal's sequence up to which it
    is known to hold; `guard` what the key does not say and no event
    announces (the owner's to compose, with `plan_shape` in it), or None:
    nobody may derive from a plan kept for its tail alone. What a kept plan
    cannot hold is derived again at every use (`derive`): the nominated
    lane, the narrowed row set (``rows``: a nominated pod's own node, or
    the nodes a PreFilterResult names, with the sample and the start index
    over them), the start index, the width of the results, `has_pns`.
    Everything else in it is the template's and the nodes' own (labels,
    images, declared features), which the events that keep it valid do not
    touch.

    A session (`_resume_or_rebuild`) resumes from its **tail**, what the
    template's last clean session left: the device `state` and `carry` it
    ended on, the `node_names` of their rows, the journal's sequence at its
    end (`tail_seq`, None without a tail; not `seq`, which a preemptor's use
    advances, while the session's row patch must see every event since its
    end), and what must be as it was for the carry to chain on: `attempts`,
    `state_unwinds`, the nomination key `nom_key`."""

    plan: BatchPlan
    seq: int
    guard: Optional[tuple]
    state: Optional[DeviceNodeState] = None
    carry: object = None
    node_names: Optional[List[str]] = None
    tail_seq: Optional[int] = None
    attempts: int = 0
    state_unwinds: int = 0
    nom_key: Optional[tuple] = None
    _host_rows: Optional[dict] = None  # the plan's ROW_FIELDS, on the host

    def drop_tail(self) -> None:
        self.state = self.carry = self.node_names = self.tail_seq = None

    def derive(self, mirror: NodeStateMirror, n: int, *, batch_size: int,
               start_index: int, nom_reqs, rows=None, shards: int = 1,
               percentage_of_nodes_to_score: int = 0) -> BatchPlan:
        """The plan `build_batch` would give now for `batch_size` pods of the
        template, with `nom_reqs` (`lane_requests`) the nominated lane: one
        transfer (`NodeStateMirror.upload`) and no pass over the nodes. The
        mirror is synced to the `n` nodes. A node update may lie behind the
        plan (taints,
        allocatable or the unschedulable flag of a row; labels, images and
        declared features intact): of the plan only `has_pns` reads those
        rows, and the synced mirror says it again as `build_batch` would.

        ``rows``: the snapshot rows the batch may land on, in snapshot order
        (`narrowed_rows`, or a nominated pod's one node). The plan is then
        over THOSE rows only, padded to `narrow_width`: every per-row
        feature gathered (the count tables stay the whole cluster's, as the
        host's PreFilter state does), `num_nodes` their count, the sample
        `num_feasible_nodes_to_find` of it and the start index modulo it, so
        the kernel's rotation over its rows IS the host's rotation over the
        narrowed list (core/scheduler.py find_nodes_that_pass_filters), and
        a batch costs the device the rows that can matter. Its device state
        is `NodeStateMirror.rows_state` of the same rows."""
        feats = self.plan.features
        nom_req, nom_pods = _lane_arrays(mirror, nom_reqs)
        again = {}
        if rows is not None:
            rows = tuple(rows)
            n = len(rows)
            idx = padded_rows(rows, narrow_width(n, shards))
            if self._host_rows is None:
                self._host_rows = {name: np.asarray(getattr(feats, name))
                                   for name in ROW_FIELDS}
            again = {name: a[idx] for name, a in self._host_rows.items()}
            # the padding rows fail the static mask, whatever row 0 says
            again["extra_ok"] &= np.arange(len(idx)) < n
            if nom_reqs:
                nom_req, nom_pods = nom_req[idx], nom_pods[idx]
            again["num_nodes"] = np.int32(n)
            again["to_find"] = np.int32(num_feasible_nodes_to_find(
                n, percentage_of_nodes_to_score))
        again.update(nom_req=nom_req, nom_pods=nom_pods,
                     start_index=np.int32(start_index % max(1, n)))
        again = mirror.upload("derive", again)
        return replace(self.plan, features=feats._replace(**again),
                       has_nom=bool(nom_reqs),
                       has_pns=_has_pns(mirror, mirror.num_nodes),
                       batch_pad=_batch_tier(batch_size), rows=rows)


def padded_rows(rows, width: int) -> np.ndarray:
    """`rows` as a gather index `width` long: the padding repeats row 0
    (every staging array holds it), and is refused by `extra_ok` and by the
    state's `valid`."""
    idx = np.zeros(width, np.int64)
    idx[:len(rows)] = rows
    return idx


PREEMPT_K_CAP = 256  # victims-per-node tier ceiling (recompile guard)


def _reprieve_order(pi: PodInfo):
    return -pi.pod.priority, pi.pod.creation_ts


class PreemptionVictims:
    """Victim tensors for the dry-run kernel: per node, every lower-priority
    pod in MoreImportantPod reprieve order (higher priority first, then
    earlier start — preemption.go:480-520 / the host Evaluator's sort).

    They are state that follows the snapshot by its generations, as the
    mirror's rows do (`NodeStateMirror._sync_rows`): per row the (node name,
    `NodeInfo.generation`) it was derived from is kept, and a call derives
    again only the rows whose pair differs. Every mutation of a NodeInfo
    bumps its generation and `cache.update_snapshot` re-clones only nodes
    whose generation advanced, so an unchanged pair means the row's `ni.pods`
    holds the same PodInfos in the same order. What the pair does not cover
    is the key (the preemptor's priority, `np_cap`, `r_slots`): a change of
    it drops everything, and the build from nothing is the same per-row code
    with every row stale."""

    def __init__(self, mirror: NodeStateMirror):
        self.mirror = mirror
        self.rebuilt = 0    # rows the last call derived again
        self.drop()

    def drop(self) -> None:
        self._key = None
        self._names: List[str] = []
        self._gens: List[int] = []
        self._potential: List[list] = []
        self._vic_req = self._vic_valid = None

    def build(self, pod: Pod, snapshot):
        """(vic_req [npc, K, R] i64, vic_valid [npc, K] bool, potential [n]
        list-of-PodInfo in the same order) or None when no node holds a
        victim or some node exceeds the K cap (host path owns it). The
        arrays and the list are the holder's own, patched in place by the
        next call: the caller is done with them before it returns."""
        try:
            return self._build(pod, snapshot.node_info_list)
        except BaseException:
            self.drop()     # half a walk is no state to patch from
            raise

    def _build(self, pod: Pod, nodes):
        mirror = self.mirror
        prio = pod.priority
        key = (prio, mirror.np_cap, mirror.r_slots)
        if key != self._key:
            self.drop()
            self._key = key
        names, gens, potential = self._names, self._gens, self._potential
        n = len(nodes)
        stale = []
        for i, ni in enumerate(nodes):
            if i < len(names) and names[i] == ni.name \
                    and gens[i] == ni.generation:
                continue
            pis = [pi for pi in ni.pods if pi.pod.priority < prio]
            pis.sort(key=_reprieve_order)
            if i < len(names):
                names[i], gens[i], potential[i] = ni.name, ni.generation, pis
            else:
                names.append(ni.name)
                gens.append(ni.generation)
                potential.append(pis)
            stale.append(i)
        self.rebuilt = len(stale)
        # shrink: the tail rows hold nobody now
        emptied = stale + list(range(n, len(names)))
        del names[n:], gens[n:], potential[n:]
        kmax = max(map(len, potential), default=0)
        if kmax == 0 or kmax > PREEMPT_K_CAP:
            self.drop()
            return None
        # Intern every victim scalar-resource slot BEFORE touching the arrays
        # (interning can grow r_slots; the caller's build_plan re-syncs the
        # mirror after): at a new width everything starts again.
        reqs = [[pi.pod.resource_request() for pi in potential[i]]
                for i in stale]
        for rs in reqs:
            for r in rs:
                for name in r.scalar_resources:
                    mirror.scalar_slot(name)
        if mirror.r_slots != key[2]:
            return self._build(pod, nodes)
        # k, and with it the shapes and the compiled program, is what a build
        # from nothing gives: a kept row holds at most kmax <= k victims, so
        # crossing a tier is a copy of the columns both widths have.
        k = _pow2(kmax, 8)
        vic_req, vic_valid = self._vic_req, self._vic_valid
        if vic_valid is None or vic_valid.shape[1] != k:
            self._vic_req = np.zeros((mirror.np_cap, k, key[2]), np.int64)
            self._vic_valid = np.zeros((mirror.np_cap, k), bool)
            if vic_valid is not None:
                m = min(k, vic_valid.shape[1])
                self._vic_req[:, :m] = vic_req[:, :m]
                self._vic_valid[:, :m] = vic_valid[:, :m]
            vic_req, vic_valid = self._vic_req, self._vic_valid
        vic_req[emptied] = 0
        vic_valid[emptied] = False
        for r_i, rs in zip(stale, reqs):
            for j, r in enumerate(rs):
                vic_req[r_i, j] = _resource_vec(mirror, r)
            vic_valid[r_i, :len(rs)] = True
        return vic_req, vic_valid, potential


def build_preemption_victims(pod: Pod, snapshot, mirror: NodeStateMirror):
    """The victim tensors built from nothing: `PreemptionVictims` with every
    row stale."""
    return PreemptionVictims(mirror).build(pod, snapshot)


def diagnose_unschedulable(pod: Pod, mirror: NodeStateMirror, snapshot,
                           fw, nominated=None, rows=None) -> Optional["object"]:
    """Per-node failure Diagnosis for a pod the device found infeasible
    EVERYWHERE — vectorized over the mirror's staging arrays instead of the
    pure-Python per-node filter loop (which costs ~0.3s at 5k nodes and used
    to run once per hopeless pod; the Unschedulable-flood workloads pay it
    hundreds of times).

    Covers pods whose filters are all static per batch (no topology spread /
    pod affinity — those return None and take the exact host rerun). The
    verdict codes and plugin attributions match the host plugins in profile
    filter order; messages are the plugins' standard texts.

    `nominated`: the plan's nominated lane, [(node_row, PodInfo)] as
    build_batch takes it (pods of equal or higher priority nominated to a
    row, the pod's own nomination left out). Their requests and count join
    what the row holds in the resource fit, which is pass one of the
    two-pass filter; a row that fails there has that status on the host too,
    and one that passes it passes pass two (the caller's gate,
    _nominated_device_block, keeps away every pod a nominated pod touches
    by more than its requests).

    `rows`: the snapshot rows a PreFilterResult narrowed the pod's cycle to
    (`narrowed_rows`), or None. The host evaluates the narrowed list only,
    so only those nodes have a status; a pin to nodes that are all gone
    fails with no status at all (and no plugin to blame), as the host's
    walk over an empty list does.
    """
    if (pod.topology_spread_constraints
            or (pod.affinity is not None
                and (pod.affinity.pod_affinity or pod.affinity.pod_anti_affinity))):
        return None
    from ..core.framework import Diagnosis, Status

    nodes: List[NodeInfo] = snapshot.node_info_list
    if len(nodes) == 0:
        return None
    # `at`: the staging rows the statuses are for, all of them or the
    # narrowed ones; `nodes` and `n` follow it.
    at = slice(0, len(nodes))
    if rows is not None:
        if not rows:
            return Diagnosis()
        at = np.asarray(rows)
        nodes = [nodes[r] for r in rows]
    n = len(nodes)
    names = {p.name for p in fw.filter_plugins}

    # (plugin, unresolvable, fails[n] bool, message) in profile filter order.
    checks: List[Tuple[str, bool, np.ndarray, str]] = []

    if "NodeName" in names and pod.node_name:
        fails = np.array([ni.name != pod.node_name for ni in nodes])
        checks.append(("NodeName", True, fails,
                       "node(s) didn't match the requested node name"))
    if "NodeUnschedulable" in names:
        unsched = mirror.h_unsched[at].copy()
        if any(t.tolerates(_UNSCHED_TAINT) for t in pod.tolerations):
            unsched[:] = False
        checks.append(("NodeUnschedulable", True, unsched,
                       "node(s) were unschedulable"))
    if "TaintToleration" in names:
        tainted_rows = (mirror.h_taint_eff[at] != 0).any(axis=1)
        fails = np.zeros(n, bool)
        for r_i in np.nonzero(tainted_rows)[0]:
            fails[r_i] = find_matching_untolerated_taint(
                nodes[r_i].node.taints, pod.tolerations) is not None
        checks.append(("TaintToleration", True, fails,
                       "node(s) had untolerated taint(s)"))
    if "NodeAffinity" in names and (
            pod.node_selector or (pod.affinity and pod.affinity.node_affinity
                                  and pod.affinity.node_affinity.required)):
        fails = np.array([not pod.required_node_selector_matches(ni.node)
                          for ni in nodes])
        checks.append(("NodeAffinity", True, fails,
                       "node(s) didn't match Pod's node affinity/selector"))
    ports = pod.host_ports()
    if "NodePorts" in names and ports:
        from ..plugins.basic import host_ports_conflict
        fails = np.array([host_ports_conflict(ports, ni.used_ports)
                          for ni in nodes])
        checks.append(("NodePorts", False, fails,
                       "node(s) didn't have free ports for the requested pod ports"))
    if "NodeResourcesFit" in names:
        req = pod.resource_request()
        req_vec = _resource_vec(mirror, req)
        alloc = mirror.h_alloc_r[at]
        used = mirror.h_req_r
        count = mirror.h_pod_count
        if nominated:
            used, count = used.copy(), count.copy()
            for row, npi in nominated:
                used[row] += _resource_vec(mirror, npi.pod.resource_request())
                count[row] += 1
        used, count = used[at], count[at]
        pos = req_vec > 0
        insufficient = (req_vec[None, :] > (alloc - used)) & pos[None, :]
        over_capacity = (req_vec[None, :] > alloc) & pos[None, :]
        pods_full = (count + 1) > mirror.h_alloc_pods[at]
        # Unresolvable when the request exceeds allocatable outright
        # (fit.go fitsRequest Unresolvable flag) — preemption can't help.
        checks.append(("NodeResourcesFit", True,
                       over_capacity.any(axis=1),
                       "Insufficient resources (request exceeds allocatable)"))
        checks.append(("NodeResourcesFit", False,
                       insufficient.any(axis=1) | pods_full,
                       "Insufficient resources"))
    if "NodeDeclaredFeatures" in names:
        feats = [s.strip() for s in pod.annotations.get(
            "features.k8s.io/required", "").split(",") if s.strip()]
        if feats:
            fails = np.array([
                not all((ni.node.declared_features if ni.node else {}).get(ft, False)
                        for ft in feats) for ni in nodes])
            checks.append(("NodeDeclaredFeatures", False, fails,
                           "node(s) didn't declare required features"))

    if not checks:
        return None
    fail_stack = np.stack([c[2] for c in checks])          # [C, n]
    any_fail = fail_stack.any(axis=0)
    if not any_fail.all():
        return None  # some node passes every static filter: not our case
    first = np.argmax(fail_stack, axis=0)                  # first failing check
    diag = Diagnosis()
    statuses = {}
    for ci, (plugin, unresolvable, _f, msg) in enumerate(checks):
        statuses[ci] = (Status.unresolvable(msg) if unresolvable
                        else Status.unschedulable(msg))
        statuses[ci].plugin = plugin
        diag.unschedulable_plugins.add(plugin)
    # Only plugins that actually rejected somewhere count.
    rejected_plugins = {checks[ci][0] for ci in set(first.tolist())}
    diag.unschedulable_plugins &= rejected_plugins
    for r_i, ni in enumerate(nodes):
        diag.node_to_status[ni.name] = statuses[int(first[r_i])]
    return diag
