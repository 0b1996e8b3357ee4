"""The batch scheduling kernel: the whole Filter→Score hot path
(schedule_one.go findNodesThatFitPod :630 / prioritizeNodes :945) as ONE
jit-compiled dense pods×nodes evaluation, with the greedy sequential
assignment loop running on device: "the scan", a `lax.while_loop` of one
trip a pod the call holds (`n_active`), not one a column of its padded width.

Replaces the reference's per-node goroutine fan-out
(parallelize/parallelism.go:28 Parallelizer, 16 goroutines, √n chunks) with
vectorized masks over the node axis, and the reference's per-pod scheduling
cycles with a scan whose carry holds exactly the state one pod's placement
changes for the next pod: per-node requested vectors, per-domain topology
match counts, and inter-pod-affinity count tables.

Performance shape (measured on a TPU v5e; PERF.md section 5 has the traces):
a scan step is a chain of program events, each a fused elementwise pass or a
reduction over the node axis, which the chip runs one after another, and
what a step costs beyond its events' own time lies between them, where a
value crosses to the scalar side and back. Until PR 49 a step
was 65 events and a scalar detour (48.5 us: 21.9 us inside the events, 26.6
us between them: the row index decoded from the reduction, ten one-row
reads, the scores of one row on the scalar core, seven one-row writes); it
is 22 events and 26.5 us now (21.9 inside, 4.6 between), the normalising
scan 58 events and 51.7 us (21.2 + 30.5) before, 21 and 29.4 (22.7 + 6.7)
now (PR 49's traces of 1,024 chained steps at 8,192 rows). So the scan body
is written to keep the COUNT of events and the length of their chain down,
not the instruction count —
- the landed row never leaves the vector side: the packed selection key is
  unique a row, so the rows that hold the best key ARE the landed row, as a
  mask; everything a landing changes is a masked elementwise update over the
  node axis or a reduction under the mask, and the loop body holds no dynamic
  slice, dynamic update, gather or scatter at all (tests/test_kernel_aot.py).
  The row's index is decoded for the results only;
- the landed row's resource-derived values are re-evaluated at LANES columns,
  not at one scalar row and not over all rows: the rows fold into
  [NP / LANES, LANES], the row's inputs come down its column under the mask,
  one evaluation runs over the columns (`_fit_scores`), the row takes its
  column's result back. Requested lanes are kept as the call found them plus
  the request times the pods a row took since (`landed_n`), so no [NP, R]
  tensor is written a step;
- per-step domain-count lookups ride the carry as per-NODE projections
  (mnum/scnt/acnt/fcnt/dproj) updated with elementwise compares against the
  landed row's topology value, instead of take_along_axis gathers (a TPU
  gather serializes); the count tables move by a compare against the same
  value along their own axis;
- reductions that read the same rows are written side by side (the compiler
  makes sibling reductions one event; a stacked one costs an event more to
  take its lanes apart), sums over a plan's few lanes are elementwise adds
  (`_sum_lanes`), and what the NEXT step reads of the carry (the feasibility
  mask where a landing reaches whole domains, the prefix count, its two
  heads) is made at the step's tail, beside the updates it reads: events
  fuse within an iteration, never across the loop's edge;
- the prefix count of the feasibility mask is one int8 matrix product with a
  triangle of ones over the folded rows (`_prefix_count`), exact in int32,
  where a cumulative sum is five events;
- no general integer `//` or `%` runs inside a scan or lap step (XLA:TPU
  expands an int64 one into a 64-step long division, ~1,900 scalar
  instructions): every quotient has a bound (a score 0..100, a fraction
  below 10^6, a window index up to LAP_MAX) and `_bounded_divmod` reaches it
  exactly in that many compare-subtract steps; the rotation wraps are one
  compare each and the selection key unpacks with a mask;
- batches whose score vector cannot change except at the landed row carry
  the total score; batches with no cross-window coupling at all take the
  lap-vectorized path (_lap_schedule) which places L pods per iteration.

Semantics parity (bit-exact vs the host oracle, enforced by
tests/test_device_equivalence.py):
- feasibility: NodeName, NodeUnschedulable, TaintToleration,
  node_selector, NodeResourcesFit (fit.go:710 fitsRequest),
  PodTopologySpread DoNotSchedule skew test (filtering.go:358),
  InterPodAffinity required terms incl. the bootstrap case
  (filtering.go:368-426);
- adaptive sampling + rotation: numFeasibleNodesToFind truncation and
  nextStartNodeIndex advance (schedule_one.go:779-892) are emulated with a
  rotation-order cumulative count, so the device picks the IDENTICAL node the
  sequential host loop would;
- scoring: TaintToleration (×3), NodeResourcesFit LeastAllocated/MostAllocated
  (×1), BalancedAllocation integer-quantized (×1), PodTopologySpread
  ScheduleAnyway (×2), InterPodAffinity (×2), each normalized over the kept
  (sampled feasible) set exactly as runtime/framework.go:1526-1582 does;
- selection: max total score, ties broken by first position in rotation order
  (the host's deterministic-tie mode; the reference randomizes ties,
  schedule_one.go selectHost).

Pallas note (evaluated, deliberately not used): a hand-written Pallas kernel
could fuse the lap loop's iterations and pin the node tensors in VMEM
(5k x 8 i64 ~ 320KB — fits), saving per-iteration dispatch + HBM traffic.
It loses on two hard constraints: (1) the scheduler's score math is
SPECIFIED in exact int64 arithmetic so host and device agree bit-for-bit
(memory quantities alone exceed int32), and Pallas-TPU's int64 support is
poor — rescaling to int32 domains would change integer-division results and
break the equivalence contract; (2) the op mix is masked elementwise +
small reductions with no matmul — the MXU is idle either way and XLA
already fuses the VPU work, so the ceiling is per-op issue latency, which
the lap/scan restructuring (few dependent stages) addresses directly.
"""

from __future__ import annotations

import functools
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .codebook import (
    EFFECT_NO_EXECUTE,
    EFFECT_NO_SCHEDULE,
    EFFECT_PREFER_NO_SCHEDULE,
    OP_EXISTS,
)
from .device_state import DeviceNodeState
from .features import BatchFeatures, _pow2
from ..plugins.interpodaffinity import float_shortfalls  # after .features: core first

MAX_NODE_SCORE = 100
_BIG = jnp.int32(1 << 30)
_INF64 = jnp.int64(1 << 60)


class ScanCarry(NamedTuple):
    """The kernel's dynamic state. Returned by schedule_batch and accepted
    back as `carry_in`, so consecutive same-signature batches CHAIN on device
    with no host roundtrip or feature rebuild between them — the device-
    resident generalization of keeping the snapshot incremental
    (cache.go:206): in steady state the only state changes are the batch's
    own placements, which the carry already holds."""

    req_r: jnp.ndarray        # [NP, R] i64 requested per node
    nonzero: jnp.ndarray      # [NP, 2] i64 non-zero-default cpu/mem
    pod_count: jnp.ndarray    # [NP]    i32
    fit_ok: jnp.ndarray       # [NP]    bool
    fit_sc: jnp.ndarray       # [NP]    i64
    ba: jnp.ndarray           # [NP]    i64
    dns_counts: jnp.ndarray   # [C1, V] i32
    sa_counts: jnp.ndarray    # [C2, V] i32
    anti_counts: jnp.ndarray  # [A1, V] i32
    aff_counts: jnp.ndarray   # [A2, V] i32
    ipa_delta: jnp.ndarray    # [KD, V] i64
    start: jnp.ndarray        # i32 rotation index
    blocked: jnp.ndarray      # [NP] bool rows self-blocked by a landing (ports)
    aux_cnt: jnp.ndarray      # [NP] i32 aux units consumed by landings (CSI)


def entry_name(name: str):
    """Pin a jitted entry's program name: `jit_<name>` is what a profiler
    trace and XLA's module table call the program, and the trace reduction
    finds the scheduling programs by `jit_schedule_batch*`. Pinned here, a
    rename of the Python function cannot move it."""
    def pin(fn):
        fn.__name__ = fn.__qualname__ = name
        return fn
    return pin


def _tolerates(f: BatchFeatures, taint_key, taint_val, taint_eff):
    """tolerated[n, t] — any toleration row matches the taint
    (component-helpers ToleratesTaint, api/types.py Toleration.tolerates)."""
    tk = f.tol_key[None, None, :]
    tv = f.tol_val[None, None, :]
    te = f.tol_eff[None, None, :]
    to = f.tol_op[None, None, :]
    k = taint_key[:, :, None]
    v = taint_val[:, :, None]
    e = taint_eff[:, :, None]
    eff_ok = (te == 0) | (te == e)
    key_ok = (tk == 0) | (tk == k)
    val_ok = (to == OP_EXISTS) | (tv == v)
    return eff_ok & key_ok & val_ok  # [N, T, L]


def _static_masks(state: DeviceNodeState, f: BatchFeatures):
    """Per-batch node predicates that no assignment can change."""
    # taints
    m = _tolerates(f, state.taint_key, state.taint_val, state.taint_eff)
    tolerated = m.any(axis=2) if f.tol_key.shape[0] else jnp.zeros(state.taint_key.shape, bool)
    sched_relevant = (state.taint_eff == EFFECT_NO_SCHEDULE) | (
        state.taint_eff == EFFECT_NO_EXECUTE)
    taint_ok = ~(sched_relevant & ~tolerated).any(axis=1)  # [N]
    # PreferNoSchedule score counts (taint_toleration.go:182-194)
    pns_tol_ok = (f.tol_eff == 0) | (f.tol_eff == EFFECT_PREFER_NO_SCHEDULE)
    if f.tol_key.shape[0]:
        pns_tolerated = (m & pns_tol_ok[None, None, :]).any(axis=2)
    else:
        pns_tolerated = jnp.zeros(state.taint_key.shape, bool)
    pns_cnt = ((state.taint_eff == EFFECT_PREFER_NO_SCHEDULE) & ~pns_tolerated).sum(
        axis=1).astype(jnp.int64)  # [N]
    # Full node-selector + required-node-affinity verdict, host-evaluated
    # (static per batch — ops/features.py sel_match).
    sel_ok = f.sel_match
    # cheap gates
    name_ok = (f.node_name_id == 0) | (state.name_id == f.node_name_id)
    unsched_ok = ~state.unsched | (f.tolerates_unsched == 1)
    exist_anti_ok = f.exist_anti == 0
    # Profile filter enablement (a disabled filter plugin never rejects).
    name_ok |= f.enable[0] == 0
    unsched_ok |= f.enable[1] == 0
    taint_ok |= f.enable[2] == 0
    sel_ok |= f.enable[3] == 0
    return taint_ok, pns_cnt, sel_ok, name_ok, unsched_ok, exist_anti_ok


# Bits of a quotient that is a node score (0..MAX_NODE_SCORE) and of one that
# is a fraction in millionths (below SCALE): facts of the arithmetic, not of
# a configuration.
_SCORE_BITS = 7
_SCALE = 1_000_000
_SCALE_BITS = 20


def _bounded_divmod(n, d, bits: int):
    """Exact (n // d, n % d) for n >= 0, d >= 1 and a quotient below
    2**bits, as `bits` compare-subtract steps. XLA:TPU expands a general
    int64 `//` into 64 such steps (~1,900 instructions for one scalar
    division); every quotient the kernels need has a far smaller bound.

    Total: a quotient at or over the bound saturates at 2**bits - 1 (the
    remainder is then >= d), n < 0 gives (0, n). Rows outside the kept set
    carry such inputs and are masked by the caller. `d << (bits - 1)` must
    fit the dtype: memory bytes up to 2**41 at 20 bits do."""
    q = jnp.int32(0)
    r = n
    for k in range(bits - 1, -1, -1):
        less = r - (d << k)
        ge = less >= 0
        r = jnp.where(ge, less, r)
        q = (q << 1) | ge.astype(jnp.int32)
    return q.astype(n.dtype), r


def _bounded_div(n, d, bits: int):
    return _bounded_divmod(n, d, bits)[0]


def _truncated_percent(a, b):
    """InterPodAffinity's NormalizeScore as scoring.go computes it,
    `int64(100 * (float64(a) / float64(b)))` for 0 <= a <= b, with no float:
    the floor, less one where the quotient is exact and one of the whole
    percentages that the float form falls short of
    (plugins/interpodaffinity.py `float_shortfalls`: 29, 57, 58). Rows
    outside the kept set carry other inputs and are masked by the caller."""
    q, r = _bounded_divmod(MAX_NODE_SCORE * a, b, _SCORE_BITS)
    short = _any_lanes([q == k for k in float_shortfalls()])
    return q - ((r == 0) & short).astype(q.dtype)


def _wrap(x, num):
    """x % num for 0 <= x < 2 * num."""
    return jnp.where(x >= num, x - num, x)


def _unwrap(x, num):
    """x % num for -num <= x < num."""
    return jnp.where(x < 0, x + num, x)


def _prefix_count(mask):
    """Inclusive running count of a [NP] mask along the rows, exact: the rows
    fold into columns, one int8 matrix product with a triangle of ones
    counts along each fold (int32 accumulation), and the folds before a fold
    add their totals."""
    n = mask.shape[0]
    w = min(n, 128)
    folds = n // w
    tri = (jnp.arange(w, dtype=jnp.int32)[:, None]
           <= jnp.arange(w, dtype=jnp.int32)[None, :]).astype(jnp.int8)
    within = lax.dot(mask.reshape(folds, w).astype(jnp.int8), tri,
                     preferred_element_type=jnp.int32)
    fold = jnp.arange(folds, dtype=jnp.int32)
    before = jnp.where(fold[None, :] < fold[:, None], within[:, -1][None, :], 0
                       ).sum(axis=1, dtype=jnp.int32)
    return (within + before[:, None]).reshape(n)


def _sum_lanes(x):
    """x.sum(axis=0) of a [C, NP] stack of a few lanes (C is a plan's count
    of constraints or terms), as C - 1 elementwise adds: it fuses with what
    makes the lanes and what reads the sum, where a reduction is a program
    event of its own."""
    return functools.reduce(jnp.add, list(x))


def _any_lanes(x):
    """x.any(axis=0) of such a stack of masks, elementwise."""
    return functools.reduce(jnp.logical_or, list(x))


def _normalize_default_reverse(raw, mx):
    """default_normalize_score(max=100, reverse=True); mx precomputed over
    the kept set (one lane of the step's batched reduction)."""
    return jnp.where(
        mx > 0,
        MAX_NODE_SCORE - _bounded_div(MAX_NODE_SCORE * raw, jnp.maximum(mx, 1),
                                      _SCORE_BITS),
        jnp.int64(MAX_NODE_SCORE))


def _resource_eval(f: BatchFeatures, fit_strategy: int,
                   alloc_r, alloc_pods, req_r, nonzero, pod_count,
                   nom_r=None, nom_p=None):
    """Fit filter (fit.go:710) + LeastAllocated/MostAllocated score +
    integer-quantized BalancedAllocation for any leading shape (all nodes
    before the scan and in every lap; the rows a delta patch names). Inside
    the scan these values only change at the row a pod landed on, so the
    scan carries them and feeds `_fit_scores` that row's columns itself.

    `nom_r`/`nom_p` (the nominated-pod lane): pass-1 of the two-pass filter
    (runtime/framework.go:1300-1317) counts nominated pods' requests/count
    against the FILTER only — scores stay pass-2 (real pods), exactly as the
    host computes them."""
    eff_count = pod_count if nom_p is None else pod_count + nom_p
    avail = alloc_r - req_r if nom_r is None else alloc_r - req_r - nom_r
    viol = ((f.request > 0) & (f.request > avail)).any(axis=-1)
    slots = []
    for j in range(f.fit_slots.shape[0]):
        slot = f.fit_slots[j]
        slots.append((slot, f.fit_weights[j], jnp.take(alloc_r, slot, axis=-1),
                      jnp.take(req_r, slot, axis=-1) + f.request[slot]))
    return _fit_scores(
        fit_strategy, eff_count, alloc_pods, viol,
        nonzero[..., 0] + f.nz_request[0], nonzero[..., 1] + f.nz_request[1],
        slots, alloc_r[..., 0], alloc_r[..., 1],
        f.has_request, f.enable[4], f.ba_skip)


def _fit_scores(fit_strategy: int, eff_count, alloc_pods, viol, used0, used1,
                slots, a_cpu, a_mem, has_request, fit_on, ba_skip):
    """`_resource_eval` past its reads along the resource axis: elementwise
    over whatever leading shape its operands share. `viol`: some requested
    resource exceeds what is left; `used0` / `used1`: non-zero cpu / memory
    with this pod; `slots`: per scored resource (slot, weight, allocatable,
    requested with this pod); `a_cpu` / `a_mem`: allocatable cpu / memory.
    The scan's step feeds it the landed row's columns directly."""
    pods_ok = (eff_count + 1).astype(jnp.int64) <= alloc_pods
    fit_ok = (pods_ok & (~viol | (has_request == 0))) | (fit_on == 0)
    fit_num = jnp.zeros_like(used0)
    fit_den = jnp.zeros_like(used0)
    for slot, w, alloc, used_slot in slots:
        used = jnp.where(slot == 0, used0, jnp.where(slot == 1, used1, used_slot))
        if fit_strategy == 0:  # LeastAllocated
            part = jnp.where((alloc > 0) & (used <= alloc), alloc - used, 0)
        else:  # MostAllocated
            part = jnp.where(alloc > 0, jnp.minimum(used, alloc), 0)
        rscore = _bounded_div(part * MAX_NODE_SCORE, jnp.maximum(alloc, 1),
                              _SCORE_BITS)
        fit_num = fit_num + jnp.where(alloc > 0, rscore * w, 0)
        fit_den = fit_den + jnp.where(alloc > 0, w, 0)
    fit_sc = jnp.where(fit_den > 0,
                       _bounded_div(fit_num, jnp.maximum(fit_den, 1), _SCORE_BITS), 0)
    SCALE = jnp.int64(_SCALE)

    def millionths(used, alloc):
        # min(used * SCALE // alloc, SCALE): the min IS `used >= alloc`,
        # and below it the quotient is under SCALE.
        return jnp.where(used >= alloc, SCALE,
                         _bounded_div(used * SCALE, jnp.maximum(alloc, 1), _SCALE_BITS))

    q_cpu = millionths(used0, a_cpu)
    q_mem = millionths(used1, a_mem)
    both = (a_cpu > 0) & (a_mem > 0)
    ba_val = jnp.where(both,
                       _bounded_div(MAX_NODE_SCORE * SCALE - 50 * jnp.abs(q_cpu - q_mem),
                                    SCALE, _SCORE_BITS),
                       jnp.int64(MAX_NODE_SCORE))
    ba = jnp.where(ba_skip == 1, 0, ba_val)
    return fit_ok, fit_sc, ba


# The largest batch_pad that stays on the scan whatever its coupling
# (ops/features.py _batch_tier: the gang-sized tiers 8 and 64). A scan step
# is 21-22 program events over [NP] lanes, 26-29 us at 8,192 rows (PERF.md
# section 5; 58-65 events and 48-52 us until PR 49) against the lap's
# [LAP_MAX, NP] window tensors, and a 4-member gang gets no lap parallelism anyway (with
# truncation inactive every window spans the whole rotation, L=1).
SCAN_MAX_BATCH = 64


class Coupling(NamedTuple):
    """How far one landing reaches in a built batch: which engine may place
    it is read from here and nowhere else (schedule_batch below at trace
    time; BatchPlan.rides_lap and .row_local on the host, and through them
    the sharded lap, the score-hint walk and warm_for)."""

    # Feasibility can change only at the landed row.
    incremental_feas: bool
    # The total score can change only at the landed row: it rides the carry
    # instead of being recomputed.
    scores_carried: bool
    # Both, in a batch past the scan's tiers: _lap_schedule places a whole
    # lap of pods per iteration.
    lap: bool


def coupling(f: BatchFeatures, batch_pad: int, *, has_pns: bool,
             has_ipa_base: bool, anti_rowlocal: bool,
             has_na_pref: bool) -> Coupling:
    """The coupling facts of a batch from its lane widths (only the shapes
    of `f` are read, so a tracer or a sharded pytree serves) and the four
    static flags that bear on them. DNS skew and required-affinity counts
    couple whole domains, but a required ANTI term on a singleton axis
    (hostname: `anti_rowlocal`) only ever blocks the landed row itself;
    every kept-set normalization (soft spread, preferred inter-pod terms and
    their base score, PreferNoSchedule counts, preferred node affinity)
    makes each score depend on the whole window."""
    C1 = f.dns_axis.shape[0]
    C2 = f.sa_axis.shape[0]
    A1 = f.anti_axis.shape[0]
    A2 = f.aff_axis.shape[0]
    KD = f.ipa_axis.shape[0]
    incremental_feas = C1 == 0 and A2 == 0 and (A1 == 0 or anti_rowlocal)
    scores_carried = (C2 == 0 and KD == 0 and not has_pns
                      and not has_ipa_base and not has_na_pref)
    return Coupling(incremental_feas, scores_carried,
                    incremental_feas and scores_carried
                    and batch_pad > SCAN_MAX_BATCH)


@partial(jax.jit, static_argnames=("batch_pad", "fit_strategy", "vmax",
                                   "has_pns", "has_ipa_base", "anti_rowlocal",
                                   "has_na_pref", "port_selfblock", "has_aux",
                                   "has_nom"),
         donate_argnames=("carry_in",))
@entry_name("schedule_batch")
def schedule_batch(
    state: DeviceNodeState,
    f: BatchFeatures,
    batch_pad: int,
    fit_strategy: int,
    vmax: int,
    n_active: Optional[jnp.ndarray] = None,
    carry_in: Optional[ScanCarry] = None,
    has_pns: bool = True,
    has_ipa_base: bool = True,
    anti_rowlocal: bool = False,
    has_na_pref: bool = False,
    port_selfblock: bool = False,
    has_aux: bool = False,
    has_nom: bool = False,
) -> Tuple[jnp.ndarray, ScanCarry]:
    """Greedy-assign `n_active` identical pods, at most `batch_pad`: both
    engines loop over the pods the call holds, so `batch_pad` is the static
    width of the results and costs no steps.

    Returns (results, carry) where results is the stacked [2, B] array of
    (chosen row or -1, start_index_after) — one array so the host fetches
    with a single transfer; slice results[:, :n_active] (past it the scan
    leaves its initial -1). Passing the returned
    ScanCarry back as `carry_in` chains the NEXT batch of identical pods
    without re-uploading features or node state (dispatch pipelining: the
    host commits batch N while the device computes batch N+1 — the TPU-era
    form of schedule_one.go:141's async binding-cycle overlap).

    `has_pns` / `has_ipa_base` / `anti_rowlocal` are host-known batch facts
    (any PreferNoSchedule taints staged; any nonzero preferred-affinity base
    score; every required anti-affinity term keyed to a singleton-per-node
    topology axis, i.e. kubernetes.io/hostname-like). They let the kernel
    drop dead score reductions and — when a placement can only affect its own
    landed row — take the lap-vectorized path."""
    NP = state.valid.shape[0]
    C1 = f.dns_axis.shape[0]
    C2 = f.sa_axis.shape[0]
    A1 = f.anti_axis.shape[0]
    A2 = f.aff_axis.shape[0]
    KD = f.ipa_axis.shape[0]
    R = f.request.shape[0]
    idx = jnp.arange(NP, dtype=jnp.int32)
    num = jnp.maximum(f.num_nodes, 1)
    # Radix of the packed (score, rotation) selection key: a power of two,
    # so the rotation comes back out with a mask.
    RADIX = _pow2(NP)
    # Columns the rows fold into for the landed row's one evaluation (`step`).
    LANES = min(NP, 128)

    incremental_feas, scores_carried, static_scores = coupling(
        f, batch_pad, has_pns=has_pns, has_ipa_base=has_ipa_base,
        anti_rowlocal=anti_rowlocal, has_na_pref=has_na_pref)

    with jax.named_scope("taints"):
        taint_ok, pns_cnt, sel_ok, name_ok, unsched_ok, exist_anti_ok = _static_masks(state, f)

    # Static topology vid gathers [C, NP].
    dns_vid = state.topo[f.dns_axis] if C1 else jnp.zeros((0, NP), jnp.int32)
    sa_vid = state.topo[f.sa_axis] if C2 else jnp.zeros((0, NP), jnp.int32)
    anti_vid = state.topo[f.anti_axis] if A1 else jnp.zeros((0, NP), jnp.int32)
    aff_vid = state.topo[f.aff_axis] if A2 else jnp.zeros((0, NP), jnp.int32)
    ipa_vid = state.topo[f.ipa_axis] if KD else jnp.zeros((0, NP), jnp.int32)

    # DNS eligibility for count updates (node_eligible, filtering.go AddPod).
    if C1:
        dns_elig = (dns_vid > 0)
        dns_elig &= jnp.where(f.dns_honor_aff[:, None] == 1, sel_ok[None, :], True)
        dns_elig &= jnp.where(f.dns_honor_taints[:, None] == 1, taint_ok[None, :], True)
    else:
        dns_elig = jnp.zeros((0, NP), bool)
    # SA ignored nodes (scoring.go initPreScoreState).
    if C2:
        sa_ignored = ~(sa_vid > 0).all(axis=0) | ~sel_ok
    else:
        sa_ignored = jnp.zeros(NP, bool)
    # Bootstrap only applies on nodes carrying every requested topology key
    # (satisfyPodAffinity checks key presence before the no-matches-anywhere
    # case, filtering.go:398-426). Static per batch.
    if A2:
        aff_has_keys = ((f.aff_active[:, None] == 0) | (aff_vid > 0)).all(axis=0)
    else:
        aff_has_keys = jnp.ones(NP, bool)

    static_ok = (state.valid & name_ok & unsched_ok & taint_ok & sel_ok
                 & exist_anti_ok & f.extra_ok)

    w_tt, w_fit, w_pts, w_ipa, w_ba, w_na, w_il = (f.weights[i] for i in range(7))
    # ImageLocality has no normalization: a static additive score term that
    # rides every path (including carried totals — landings can't change it).
    il_term = w_il * f.il_score

    n_act = jnp.int32(batch_pad) if n_active is None else n_active.astype(jnp.int32)

    # What a landing tells every lane family, lane by lane in the carry's
    # order: the rows whose landing counts there (None: every row) and each
    # row's topology value.
    landing_lanes = (
        [(dns_elig[c], dns_vid[c]) for c in range(C1)]
        + [(~sa_ignored, sa_vid[c]) for c in range(C2)]
        + [(None, vid[c]) for vid in (anti_vid, aff_vid, ipa_vid)
           for c in range(vid.shape[0])])

    def feasibility_proj(fit_ok, dns_counts, mnum, acnt, fcnt, aff_total,
                         blocked, aux_cnt):
        """Per-node ok mask from the dynamic filters
        (findNodesThatPassFilters; PTS skew filtering.go:318-362, IPA
        required filtering.go:368-426, counted CSI attach room), reading
        the carried per-node projections — no gathers on the critical
        path."""
        ok = static_ok & fit_ok & (idx < num)
        if port_selfblock:
            ok &= ~blocked
        if has_aux:
            ok &= aux_cnt + f.aux_inc <= f.aux_room
        with jax.named_scope("spread_lanes"):
            if C1:
                # All-int32 skew math (counts are pods-per-domain, far below 2^31;
                # int64 vector ops cost ~2x in the per-op-latency regime).
                min_match = jnp.where(f.dns_dom, dns_counts, _BIG).min(axis=1)  # [C1]
                min_match = jnp.where(f.dns_forced0 == 1, 0, min_match)
                skew_bad = (mnum + f.dns_self[:, None] - min_match[:, None]
                            ) > jnp.minimum(f.dns_max_skew, _BIG)[:, None]
                dns_reject = (f.dns_active[:, None] == 1) & (~(dns_vid > 0) | skew_bad)
                ok &= ~_any_lanes(dns_reject)
        if A1:
            ok &= ~_any_lanes((anti_vid > 0) & (acnt > 0))
        if A2:
            term_ok = (f.aff_active[:, None] == 0) | ((aff_vid > 0) & (fcnt > 0))
            bootstrap = (aff_total == 0) & (f.aff_own_all == 1) & aff_has_keys
            ok &= ~_any_lanes(~term_ok) | bootstrap
        return ok

    def prefix_heads(F, start):
        """The prefix sum's last value and its value before `start`, as
        reductions, not reads at an address (F never falls along the rows,
        so its maximum is its last value; no row is -1, so `start` 0 reads
        0). Made at a step's tail for the next step's ranks."""
        return jnp.max(F), jnp.max(jnp.where(idx == start - 1, F, 0))

    def step(carry):
        (landed_n, fit_ok, fit_sc, ba,
         dns_counts, sa_counts, anti_counts, aff_counts, ipa_delta, start,
         blocked, aux_cnt, okd, F, total_feas, f_start, total,
         mnum, scnt, acnt, fcnt, dproj, aff_total, t, out) = carry
        # True at every trip of the loop below; a step past n_act (the tests'
        # fixed-length reference runs them) lands nothing, moves no start.
        active = t < n_act

        with jax.named_scope("select"):
            # ---- sampling truncation + rotation (schedule_one.go:779-892) -----
            # Gather-free formulation: rank[row] = #feasible rows at rotation
            # positions <= rot(row), from the row-order prefix-sum with wrap
            # adjustment (feasible count in [start..row] resp. wrapped); the
            # prefix count's two heads ride the carry (`prefix_heads`).
            rank = jnp.where(idx >= start, F - f_start, F + total_feas - f_start)
            kept = okd & (rank <= f.to_find)
            rot_of_row = _unwrap(idx - start, num)             # row -> rotation pos

        with jax.named_scope("score_normalise"):
            # ---- reductions: maxes side by side (mins ride negated), which the
            # compiler runs as one event where they read the same rows ------
            # the window-boundary rotation (evaluated)
            bound_lane = jnp.where(okd & (rank == f.to_find),
                                   num - 1 - rot_of_row, 0)
            if scores_carried:
                # total is already known: boundary + packed selection key
                # (max-score-then-min-rotation; scores non-negative) are ONE
                # reduction round.
                key = total * RADIX + (jnp.int32(RADIX - 1) - rot_of_row)
                best_key = jnp.max(jnp.where(kept, key, -1))
                evaluated = (num - jnp.max(bound_lane)).astype(jnp.int32)
            else:
                lanes = []
                if has_pns:
                    lanes.append(jnp.where(kept, pns_cnt, 0))              # mx_pns
                if C2:
                    raw_sa = _sum_lanes(scnt.astype(jnp.int64) * f.sa_wq[:, None] +
                                        (f.sa_skew[:, None] - 1) * 1024)
                    live = kept & ~sa_ignored
                    lanes.append(jnp.where(live, raw_sa, 0))               # mx_sa
                    lanes.append(jnp.where(live, -raw_sa, -_INF64))        # -mn_sa
                if KD or has_ipa_base:
                    raw_ipa = f.ipa_base
                    if KD:
                        raw_ipa = raw_ipa + _sum_lanes(dproj)
                    lanes.append(jnp.where(kept, raw_ipa, -_INF64))        # mx_ipa
                    lanes.append(jnp.where(kept, -raw_ipa, -_INF64))       # -mn_ipa
                if has_na_pref:
                    lanes.append(jnp.where(kept, f.na_raw, 0))             # mx_na
                red = [jnp.max(lane) for lane in lanes]
                evaluated = (num - jnp.max(bound_lane)).astype(jnp.int32)
                li = 0
                # ---- score assembly (runtime/framework.go:1526-1582) ----------
                if has_pns:
                    tt = _normalize_default_reverse(pns_cnt, red[li]); li += 1
                else:
                    tt = jnp.int64(MAX_NODE_SCORE)
                if C2:
                    mx, mn = red[li], -red[li + 1]; li += 2
                    norm = jnp.where(
                        mx > 0,
                        _bounded_div(MAX_NODE_SCORE * (mx + jnp.minimum(mn, mx) - raw_sa),
                                     jnp.maximum(mx, 1), _SCORE_BITS),
                        jnp.int64(MAX_NODE_SCORE))
                    pts = jnp.where(sa_ignored, 0, norm)
                else:
                    pts = jnp.int64(0)
                if KD or has_ipa_base:
                    mx_i, mn_i = red[li], -red[li + 1]; li += 2
                    diff = mx_i - mn_i
                    ipa = jnp.where(diff > 0,
                                    _truncated_percent(raw_ipa - mn_i,
                                                       jnp.maximum(diff, 1)), 0)
                else:
                    ipa = jnp.int64(0)
                if has_na_pref:
                    # default_normalize_score(max=100, reverse=False): raw*100//mx
                    # over the kept set; all-zero raws stay zero.
                    mx_na = red[li]; li += 1
                    na = jnp.where(mx_na > 0,
                                   _bounded_div(MAX_NODE_SCORE * f.na_raw,
                                                jnp.maximum(mx_na, 1), _SCORE_BITS), 0)
                else:
                    na = jnp.int64(0)
                total = (w_tt * tt + w_fit * fit_sc + w_ba * ba + w_pts * pts
                         + w_ipa * ipa + w_na * na + il_term)
                # second reduction round: packed selection over the fresh scores
                key = total * RADIX + (jnp.int32(RADIX - 1) - rot_of_row)
                best_key = jnp.max(jnp.where(kept, key, -1))
        with jax.named_scope("select"):
            any_kept = (best_key >= 0) & active
            # The key is unique a row (the rotation is a permutation), so the
            # rows that hold the best one ARE the landed row, as a mask; no
            # row holds -1, so nothing is hit where nothing was kept.
            hit = kept & (key == best_key) & active
            # The row's index is a value for `out` (and one compare below),
            # never an address.
            chosen_rot = jnp.int32(RADIX - 1) - (best_key & (RADIX - 1)).astype(jnp.int32)
            chosen = jnp.where(any_kept, _wrap(start + chosen_rot, num), -1).astype(jnp.int32)

        with jax.named_scope("carry_update"):
            # ---- carry updates: everything a landing changes is a masked
            # elementwise update over the node axis, or a reduction under
            # the mask (inert when nothing was kept: `hit` is then empty) ----
            h32 = hit.astype(jnp.int32)
            landed_n = landed_n + h32
            with jax.named_scope("resource_fit"):
                # Re-evaluate ONLY the landed row's resource-derived values,
                # without leaving the vector side: the rows are folded into
                # LANES columns, the landed row's inputs come down its column
                # under the mask (every other column reads zeros), one
                # LANES-wide evaluation runs, and the row takes its column's
                # result back. A row's requested lanes are the call's own
                # (`fit_cols`) plus the request times the pods it took since.
                hit2 = hit.reshape(NP // LANES, LANES)
                col = dict(zip(fit_rows, jnp.where(hit2[None], fit_cols, 0).sum(axis=1)))
                took = jnp.where(hit2, landed_n.reshape(hit2.shape), 0).sum(
                    axis=0, dtype=jnp.int64)
                left = [col["alloc", j] - col["req", j] - took * request[j]
                        - (col["nom", j] if has_nom else 0) for j in range(R)]
                r_ok, r_fit, r_ba = _fit_scores(
                    fit_strategy,
                    col["pods"] + took + (col["nom_pods"] if has_nom else 0),
                    col["room"],
                    _any_lanes([(request[j] > 0) & (request[j] > left[j])
                               for j in range(R)]),
                    col["nz", 0] + (took + 1) * nz_request[0],
                    col["nz", 1] + (took + 1) * nz_request[1],
                    [(slot, w, col["slot_alloc", j],
                      col["slot_req", j] + (took + 1) * slot_request)
                     for j, (slot, w, slot_request) in enumerate(fit_slots)],
                    col["alloc", 0], col["alloc", 1], f.has_request, fit_on,
                    f.ba_skip)

                def to_rows(x):
                    return jnp.broadcast_to(x[None, :], hit2.shape).reshape(NP)

                r_ok, r_fit, r_ba = to_rows(r_ok), to_rows(r_fit), to_rows(r_ba)
            fit_ok = jnp.where(hit, r_ok, fit_ok)
            fit_sc = jnp.where(hit, r_fit, fit_sc)
            ba = jnp.where(hit, r_ba, ba)
            if port_selfblock:
                blocked = blocked | hit
            if has_aux:
                aux_cnt = aux_cnt + f.aux_inc * h32
            # What the landed row says to every other row, as sums under the
            # mask side by side (one row at most is hit, so a sum is its
            # value): its topology value on every lane, 0 where the landing
            # counts for nothing there (no value, ineligible, nothing
            # landed), and whether it stays feasible.
            lanes = [jnp.where(hit if counts is None else hit & counts, vid, 0)
                     for counts, vid in landing_lanes]
            if incremental_feas:
                # Feasibility flips only at the landed row: each row as it
                # would stand after a landing on it (its own count of a
                # row-local anti term moves by the pod's own weight there;
                # the tables themselves move below, after this reduction).
                own = acnt + f.anti_self[:, None] * (anti_vid > 0) if A1 else acnt
                new_ok = feasibility_proj(r_ok, dns_counts, mnum, own, fcnt,
                                          aff_total, blocked, aux_cnt)
                lanes.append(jnp.where(
                    hit, new_ok.astype(jnp.int32) - okd.astype(jnp.int32), 0))
            landed = iter([lane.sum(dtype=jnp.int32) for lane in lanes])

            def land(counts, proj, vid, weight):
                """One lane family after the landing: its count table and
                its per-node projection, both by compares against the landed
                row's value on each of its lanes."""
                lv = jnp.stack([next(landed) for _ in range(vid.shape[0])])
                upd = weight * (lv > 0)
                values = jnp.arange(counts.shape[1], dtype=jnp.int32)
                counts = counts + jnp.where(values[None, :] == lv[:, None],
                                            upd[:, None], 0)
                proj = proj + upd[:, None] * (vid == lv[:, None])
                return counts, proj, upd

            with jax.named_scope("spread_lanes"):
                if C1:
                    dns_counts, mnum, _ = land(dns_counts, mnum, dns_vid, f.dns_self)
                if C2:
                    sa_counts, scnt, _ = land(sa_counts, scnt, sa_vid, f.sa_self)
            if A1:
                anti_counts, acnt, _ = land(anti_counts, acnt, anti_vid, f.anti_self)
            if A2:
                aff_counts, fcnt, upd = land(aff_counts, fcnt, aff_vid, f.aff_self)
                aff_total = aff_total + upd.sum(dtype=aff_total.dtype)
            if KD:
                ipa_delta, dproj, _ = land(ipa_delta, dproj, ipa_vid, f.ipa_wland)
            if incremental_feas:
                # patch okd and shift the prefix-sum tail by the delta
                # (replaces the full cumsum)
                okd = jnp.where(hit, new_ok, okd)
                F = F + jnp.where(idx >= chosen, next(landed), 0)
            else:
                with jax.named_scope("feasibility"):
                    # A landing reaches whole domains: the next step's mask
                    # and prefix sum, made here, beside the updates they
                    # read (a step's tail fuses; a loop's edge does not).
                    okd = feasibility_proj(fit_ok, dns_counts, mnum, acnt, fcnt,
                                           aff_total, blocked, aux_cnt)
                    F = _prefix_count(okd)                     # inclusive, row order
            if scores_carried:
                total = jnp.where(
                    hit,
                    w_tt * jnp.int64(MAX_NODE_SCORE) + w_fit * r_fit + w_ba * r_ba
                    + il_term,
                    total)
            start = jnp.where(active, _wrap(start + evaluated, num), start).astype(jnp.int32)
            # Results accumulate in the CARRY via a one-hot masked write at
            # the int32 step counter `t`, which also rides the carry and is
            # the loop's only counter: an s64 one (a lax.scan's own, in x64
            # mode, indexing the dynamic_update_slice of its ys-stacking) is
            # what this environment's XLA miscompiles under GSPMD —
            # compare(s64, s32) after spmd-partitioning, the ROADMAP open
            # item. The elementwise write is also exact under vmap (the cells
            # axis), where a batched-index update slice is not.
            out = jnp.where(jnp.arange(batch_pad, dtype=jnp.int32) == t,
                            (chosen + 1).astype(out.dtype) * RADIX + start, out)

        new_carry = (landed_n, fit_ok, fit_sc, ba,
                     dns_counts, sa_counts, anti_counts, aff_counts,
                     ipa_delta, start, blocked, aux_cnt, okd, F,
                     *prefix_heads(F, start), total,
                     mnum, scnt, acnt, fcnt, dproj, aff_total,
                     t + jnp.int32(1), out)
        return new_carry

    if carry_in is None:
        fit_ok0, fit_sc0, ba0 = _resource_eval(
            f, fit_strategy, state.alloc_r, state.alloc_pods,
            state.req_r, state.nonzero, state.pod_count,
            nom_r=f.nom_req if has_nom else None,
            nom_p=f.nom_pods if has_nom else None)
        ipa_delta0 = jnp.zeros((KD, vmax), jnp.int64)
        ext0 = ScanCarry(state.req_r, state.nonzero, state.pod_count,
                         fit_ok0, fit_sc0, ba0,
                         f.dns_counts, f.sa_counts, f.anti_counts,
                         f.aff_counts, ipa_delta0, f.start_index,
                         jnp.zeros(NP, bool), jnp.zeros(NP, jnp.int32))
    else:
        ext0 = carry_in
    if static_scores:
        return _lap_schedule(state, f, batch_pad, fit_strategy,
                             ext0, static_ok, n_act, idx, num,
                             w_tt, w_fit, w_ba, il_term, anti_vid,
                             port_selfblock, has_aux, has_nom)
    # Per-node projections of the count tables (one gather per table per
    # CALL, kept elementwise-fresh by the scan) + okd/F seeds. Index dtype
    # is uniformly int32 — see the scatter-dtype note in `step`.
    i32v = jnp.int32
    mnum0 = (jnp.take_along_axis(ext0.dns_counts, dns_vid.astype(i32v), axis=1)
             if C1 else jnp.zeros((0, NP), jnp.int32))
    scnt0 = (jnp.take_along_axis(ext0.sa_counts, sa_vid.astype(i32v), axis=1)
             if C2 else jnp.zeros((0, NP), jnp.int32))
    acnt0 = (jnp.take_along_axis(ext0.anti_counts, anti_vid.astype(i32v), axis=1)
             if A1 else jnp.zeros((0, NP), jnp.int32))
    fcnt0 = (jnp.take_along_axis(ext0.aff_counts, aff_vid.astype(i32v), axis=1)
             if A2 else jnp.zeros((0, NP), jnp.int32))
    if KD:
        d0 = jnp.take_along_axis(ext0.ipa_delta, ipa_vid.astype(i32v), axis=1)
        dproj0 = d0 * jnp.where(ipa_vid > 0, 1, 0)
    else:
        dproj0 = jnp.zeros((0, NP), jnp.int64)
    aff_total0 = (ext0.aff_counts * (f.aff_active[:, None] == 1)).sum()
    okd0 = feasibility_proj(ext0.fit_ok, ext0.dns_counts, mnum0, acnt0,
                            fcnt0, aff_total0, ext0.blocked, ext0.aux_cnt)
    F0 = _prefix_count(okd0)
    if scores_carried:
        total0 = (w_tt * jnp.int64(MAX_NODE_SCORE) + w_fit * ext0.fit_sc
                  + w_ba * ext0.ba + il_term)
    else:
        total0 = jnp.zeros(NP, jnp.int64)
    # A step's result, the chosen row (or -1) and the start index after it,
    # packed into one word of the narrowest type that holds both.
    out0 = jnp.full(batch_pad, -1,
                    jnp.int32 if (NP + 1) * RADIX < 2 ** 31 else jnp.int64)
    # The landed row's resource lanes by name, one below the other for the
    # step's one reduction under the mask and each folded into LANES columns:
    # allocatable, requested and non-zero requested as the call found them,
    # pod room and count, the nominated lane, and allocatable and requested
    # of each scored resource. What the evaluation reads of the pod itself
    # goes in as scalars.
    fit_slots = [(f.fit_slots[j], f.fit_weights[j], f.request[f.fit_slots[j]])
                 for j in range(f.fit_slots.shape[0])]
    fit_rows = {}
    for j in range(R):
        fit_rows["alloc", j] = state.alloc_r[:, j]
        fit_rows["req", j] = ext0.req_r[:, j]
        if has_nom:
            fit_rows["nom", j] = f.nom_req[:, j]
    fit_rows["nz", 0], fit_rows["nz", 1] = ext0.nonzero[:, 0], ext0.nonzero[:, 1]
    fit_rows["room"] = state.alloc_pods
    fit_rows["pods"] = ext0.pod_count.astype(jnp.int64)
    if has_nom:
        fit_rows["nom_pods"] = f.nom_pods.astype(jnp.int64)
    for j, (slot, _, _) in enumerate(fit_slots):
        fit_rows["slot_alloc", j] = jnp.take(state.alloc_r, slot, axis=-1)
        fit_rows["slot_req", j] = jnp.take(ext0.req_r, slot, axis=-1)
    # The stack is this call's OWN copy of those lanes, made before the loop
    # and kept opaque: the carry it returns is reckoned from the copy, so
    # nothing of `state` is read once the results can be fetched. (On the
    # CPU backend the host moves on as soon as it has them, and a carry
    # reckoned from `state` after the loop was seen to count a landing
    # twice: ROADMAP D20.)
    fit_cols = lax.optimization_barrier(
        jnp.stack(list(fit_rows.values())).reshape(-1, NP // LANES, LANES))
    request = [f.request[j] for j in range(R)]
    nz_request = [f.nz_request[0], f.nz_request[1]]
    fit_on = f.enable[4]
    carry0 = (jnp.zeros(NP, jnp.int32),) + tuple(ext0)[3:] + (
        okd0, F0, *prefix_heads(F0, ext0.start), total0,
                            mnum0, scnt0, acnt0, fcnt0, dproj0, aff_total0,
                            jnp.int32(0), out0)
    # The loop stops at the pods it holds: `n_act` trips, not `batch_pad`.
    # `t` (carry[-2]) starts as an unbatched int32 0 and only ever adds 1, so
    # the predicate stays one scalar under vmap, and a replicated one under
    # shard_map / GSPMD; `out` past `n_act` keeps its initial -1.
    final = lax.while_loop(lambda c: c[-2] < n_act, step, carry0)
    # chosen+starts stacked into ONE array: the host fetches results with a
    # single device→host transfer. The final ScanCarry rides back
    # (device-resident) so the host can
    # chain the next batch (carry_in) and keep the mirror resident
    # (NodeStateMirror.adopt) instead of re-uploading — the device-side
    # analogue of the incremental snapshot.
    took = final[0]
    took64 = took.astype(jnp.int64)[:, None]
    found = dict(zip(fit_rows, fit_cols.reshape(-1, NP)))
    out = final[-1]
    results = jnp.where(out[None, :] < 0, -1, jnp.stack(
        [(out >> RADIX.bit_length() - 1) - 1, out & (RADIX - 1)])).astype(jnp.int32)
    return results, ScanCarry(
        jnp.stack([found["req", j] for j in range(R)], axis=1)
        + took64 * f.request[None, :],
        jnp.stack([found["nz", 0], found["nz", 1]], axis=1)
        + took64 * f.nz_request[None, :],
        found["pods"].astype(jnp.int32) + took, *final[1:12])


@partial(jax.jit, static_argnames=("fit_strategy", "has_nom"))
@entry_name("patch_carry_rows")
def patch_carry_rows(
    state: DeviceNodeState,
    f: BatchFeatures,
    carry: ScanCarry,
    idx: jnp.ndarray,        # [K] i32 rows to patch (pow2-padded, dups OK)
    req_rows: jnp.ndarray,   # [K, R] i64 post-event requested aggregates
    nz_rows: jnp.ndarray,    # [K, 2] i64
    cnt_rows: jnp.ndarray,   # [K] i32
    fit_strategy: int = 0,
    has_nom: bool = False,
) -> ScanCarry:
    """Event-delta patch of a live session carry: install the post-event
    per-node aggregates for the journal's dirty rows and re-evaluate ONLY
    those rows' resource-derived values — the carry-side analogue of the
    mirror's dirty-row scatter. Valid only for pod-local plans (no count
    tables to touch); taint/allocatable changes ride the separately patched
    `state`, whose rows this reads. Duplicate padded indices write identical
    values, so the pow2 index tier is exact."""
    ok, sc, ba = _resource_eval(
        f, fit_strategy, state.alloc_r[idx], state.alloc_pods[idx],
        req_rows, nz_rows, cnt_rows,
        nom_r=f.nom_req[idx] if has_nom else None,
        nom_p=f.nom_pods[idx] if has_nom else None)
    return carry._replace(
        req_r=carry.req_r.at[idx].set(req_rows),
        nonzero=carry.nonzero.at[idx].set(nz_rows),
        pod_count=carry.pod_count.at[idx].set(cnt_rows),
        fit_ok=carry.fit_ok.at[idx].set(ok),
        fit_sc=carry.fit_sc.at[idx].set(sc),
        ba=carry.ba.at[idx].set(ba))


# One jit per (carry sharding set, statics): a mesh session's carry shardings
# are stable for the session's lifetime, so this stays a handful of entries.
_CARRY_PATCH_PINNED_CACHE: dict = {}


def patch_carry_rows_pinned(
    state: DeviceNodeState,
    f: BatchFeatures,
    carry: ScanCarry,
    idx: jnp.ndarray,
    req_rows: jnp.ndarray,
    nz_rows: jnp.ndarray,
    cnt_rows: jnp.ndarray,
    fit_strategy: int = 0,
    has_nom: bool = False,
) -> ScanCarry:
    """patch_carry_rows with out_shardings pinned to the live carry's OWN
    committed shardings, and the stale carry DONATED into the patch (its
    buffers are dead the moment the call returns — every caller rebinds
    its reference to the result, so the patched carry reuses the old
    carry's device memory instead of allocating a sharded copy per patch
    wave). A mesh session's chained-carry kernel trace keys on the carry's
    placement; the patch must hand back the identical placement or the
    next dispatch retraces — the exact failure mode that kept mesh
    sessions on the full-rebuild path (ROADMAP: delta resume under a
    sharded mesh)."""
    out = ScanCarry(*[x.sharding for x in carry])
    key = (out, fit_strategy, has_nom)
    fn = _CARRY_PATCH_PINNED_CACHE.get(key)
    if fn is None:
        fn = jax.jit(
            partial(patch_carry_rows.__wrapped__,
                    fit_strategy=fit_strategy, has_nom=has_nom),
            out_shardings=out, donate_argnums=(2,))
        _CARRY_PATCH_PINNED_CACHE[key] = fn
    return fn(state, f, carry, idx, req_rows, nz_rows, cnt_rows)


@partial(jax.jit, static_argnames=("batch_pad", "fit_strategy", "vmax",
                                   "has_pns", "has_na_pref",
                                   "port_selfblock", "has_aux"))
@entry_name("schedule_placements")
def schedule_placements(
    state: DeviceNodeState,
    f: BatchFeatures,
    batch_pad: int,
    fit_strategy: int,
    vmax: int,
    masks: jnp.ndarray,          # [P, NP] bool candidate-placement row masks
    n_active: Optional[jnp.ndarray] = None,
    has_pns: bool = True,
    has_na_pref: bool = False,
    port_selfblock: bool = False,
    has_aux: bool = False,
    spread_overrides: Optional[Tuple] = None,
) -> jnp.ndarray:
    """Evaluate a pod group against P candidate placements IN PARALLEL — the
    device form of podGroupSchedulingPlacementAlgorithm's per-placement
    simulation loop (schedule_one_podgroup.go:971): each lane restricts the
    node universe to one placement's rows and runs the full greedy member
    assignment from the CURRENT cluster state (fresh carry — simulations
    never contaminate the resident state). Returns the stacked [P, 2, B]
    results; the host gates lanes with PlacementFeasible and scores the
    survivors (findBestPodGroupPlacement :1173).

    Placement simulations evaluate their whole candidate (no adaptive
    truncation) from rotation origin 0 — the host oracle uses the identical
    spec (core/scheduler.py _evaluate_placement), making host and device
    placement evaluation bit-identical for eligible plans (no
    inter-pod-affinity / image terms; see models/tpu_scheduler.py
    _placement_plan_restriction_invariant).

    `spread_overrides` lifts the no-topology-spread restriction: the host
    oracle computes its PreFilter spread state over the placement-RESTRICTED
    node list (cache.py assume_placement), so each lane gets its own
    restricted count tables — a (dns_counts [P,C1,V], dns_dom [P,C1,V],
    dns_forced0 [P,C1], sa_counts [P,C2,V], sa_wq [P,C2]) tuple built by
    models/tpu_scheduler.py _placement_spread_overrides."""

    def run_lane(f2):
        results, _carry = schedule_batch.__wrapped__(
            state, f2, batch_pad, fit_strategy, vmax,
            n_active=n_active, carry_in=None,
            has_pns=has_pns, has_ipa_base=False, anti_rowlocal=False,
            has_na_pref=has_na_pref, port_selfblock=port_selfblock,
            has_aux=has_aux)
        return results

    if spread_overrides is None:
        def one(mask):
            return run_lane(f._replace(
                extra_ok=f.extra_ok & mask,
                start_index=jnp.int32(0),
                to_find=f.num_nodes,
            ))

        return jax.vmap(one)(masks)

    def one_sp(mask, dns_counts, dns_dom, dns_forced0, sa_counts, sa_wq):
        return run_lane(f._replace(
            extra_ok=f.extra_ok & mask,
            start_index=jnp.int32(0),
            to_find=f.num_nodes,
            dns_counts=dns_counts, dns_dom=dns_dom, dns_forced0=dns_forced0,
            sa_counts=sa_counts, sa_wq=sa_wq,
        ))

    return jax.vmap(one_sp)(masks, *spread_overrides)


@partial(jax.jit, static_argnames=("k",))
@entry_name("dry_run_preemption")
def dry_run_preemption(
    state: DeviceNodeState,
    f: BatchFeatures,
    vic_req: jnp.ndarray,    # [NP, K, R] i64 victim requests, MoreImportantPod order
    vic_valid: jnp.ndarray,  # [NP, K] bool
    k: int,
) -> jnp.ndarray:
    """Batched DryRunPreemption (preemption.go:425 SelectVictimsOnNode for
    every candidate node in ONE dense what-if — SURVEY §7.7's 'natural second
    TPU kernel').

    Per node: remove all lower-priority pods (columns of vic_req), check the
    preemptor fits; then reprieve victims most-important-first (the host's
    MoreImportantPod order, pre-sorted into the K axis), keeping each victim
    whose re-addition still leaves the preemptor feasible. The preemptor's
    non-resource filters are static per node (the device gate excludes
    topology-coupled preemptors and clusters with anti-affinity pods), so
    the per-victim feasibility check reduces to the fit arithmetic of
    _resource_eval — bit-identical to the host oracle's filter verdicts.

    The nominated lane of the preemptor's plan (`f.nom_req` / `f.nom_pods`:
    per row, what the nominated pods of equal or higher priority hold there,
    the preemptor's own nomination left out) counts in every fit of the
    what-if, as the host's two-pass filter counts it (SelectVictimsOnNode →
    RunFilterPluginsWithNominatedPods). The scheduler hands the lane at
    full width always, zeros where nobody is nominated, so an empty and a
    filled lane are ONE compiled program; features whose lane has no rows
    (a plan built without one, handed in as it is) are read as no lane.

    Returns one stacked bool array [NP, 1+K] (a single device→host fetch):
    column 0 = feasible (non-empty minimal victim set), columns 1..K = the
    victim mask; scores/PDBs/selection stay host-side
    (pickOneNodeForPreemption, preemption.go:286)."""
    NP = state.valid.shape[0]
    idx = jnp.arange(NP, dtype=jnp.int32)
    num = jnp.maximum(f.num_nodes, 1)
    taint_ok, _pns, sel_ok, name_ok, unsched_ok, exist_anti_ok = _static_masks(state, f)
    static_ok = (state.valid & name_ok & unsched_ok & taint_ok & sel_ok
                 & exist_anti_ok & f.extra_ok & (idx < num))

    has_lane = f.nom_req.shape[0] == NP
    nom_req = f.nom_req if has_lane else None
    nom_pods = f.nom_pods if has_lane else None

    n_pot = vic_valid.sum(axis=1).astype(jnp.int32)          # [NP]
    sum_vic = (vic_req * vic_valid[:, :, None]).sum(axis=1)  # [NP, R]
    base_req = state.req_r - sum_vic
    cnt0 = state.pod_count - n_pot

    def fit(req_r, pod_cnt):
        # The scheduling kernel's exact fit filter; scores are dead code
        # under jit (XLA eliminates them). The nominated lane counts, as in
        # the host dry run's two-pass filter (Evaluator.dry_run_on_node).
        ok, _sc, _ba = _resource_eval(
            f, 0, state.alloc_r, state.alloc_pods, req_r,
            jnp.zeros_like(req_r[..., :2]), pod_cnt,
            nom_r=nom_req, nom_p=nom_pods)
        return ok

    feasible0 = static_ok & fit(base_req, cnt0) & (n_pot > 0)

    def step(carry, i):
        kept_req, kept_cnt = carry
        vr = vic_req[:, i]                                   # [NP, R]
        valid = vic_valid[:, i]                              # [NP]
        keep = valid & feasible0 & fit(base_req + kept_req + vr,
                                       cnt0 + kept_cnt + 1)
        kept_req = kept_req + vr * keep[:, None]
        kept_cnt = kept_cnt + keep.astype(jnp.int32)
        return (kept_req, kept_cnt), valid & feasible0 & ~keep

    (_kr, _kc), victims_t = lax.scan(
        step, (jnp.zeros_like(sum_vic), jnp.zeros(NP, jnp.int32)),
        jnp.arange(k, dtype=jnp.int32))
    victim_mask = jnp.moveaxis(victims_t, 0, 1)              # [NP, K]
    feasible = feasible0 & victim_mask.any(axis=1)
    return jnp.concatenate([feasible[:, None], victim_mask], axis=1)


# Max pods placed per lap iteration (bounds the segment tensors; L_full =
# floor(total_feasible / to_find) never exceeds ~20 for the reference's
# adaptive percentage formula, schedule_one.go:866, but custom
# percentageOfNodesToScore can push it higher — the lap's quotients saturate
# above LAP_MAX and the excess windows spill to later laps).
LAP_MAX = 32
_LAP_BITS = LAP_MAX.bit_length()  # a window index saturates above LAP_MAX


def _lap_schedule(state, f, batch_pad, fit_strategy, ext0,
                  static_ok, n_act, idx, num, w_tt, w_fit, w_ba, il_term,
                  anti_vid, port_selfblock, has_aux, has_nom=False):
    """Lap-vectorized greedy assignment for the static-score case.

    Key fact: with adaptive sampling live (schedule_one.go:866-892), pod i
    examines the window holding the first `to_find` feasible nodes after its
    start index, and pod i+1's window begins where pod i's ended. Windows of
    consecutive pods are therefore DISJOINT until the rotation laps the
    cluster — and with no cross-window topology coupling, a placement changes
    scores and feasibility only at its own landed row, which later windows in
    the same lap never see. So all `L = total_feasible // to_find` pods of
    one lap are independent: one segmented argmax places them all. The
    sequential scan (1 pod/step) collapses to ~B·to_find/N steps — at 5k
    nodes the 1024-pod batch runs in ~100 lap iterations of which each does
    ONE pass over the node tensors. This is the TPU-shaped replacement for
    the goroutine pool: maximal vector work per sequential dependency, not
    per worker.

    Required anti-affinity terms on singleton axes (hostname) ride this path
    too: a landing only blocks its own row, which later windows never
    examine; `anti_counts` is refreshed per lap from the placements."""
    NP = state.valid.shape[0]
    RADIX = _pow2(NP)  # of the packed selection key, as in schedule_batch
    A1 = anti_vid.shape[0]
    tf = jnp.maximum(f.to_find, 1)
    B = batch_pad
    SEG = LAP_MAX + 1  # window segments + 1 dump lane

    lanes = jnp.arange(LAP_MAX, dtype=jnp.int32)             # [LAP_MAX]

    def cond(c):
        return c[0] < n_act

    def body(c):
        (done, req_r, nonzero, pod_count, anti_counts, blocked, aux_cnt,
         start, out) = c
        with jax.named_scope("resource_fit"):
            # Dense per-lap recompute (no scatters/gathers — TPU scatters
            # serialize per index, so one-hot masked vector ops win):
            fit_ok, fit_sc, ba = _resource_eval(
                f, fit_strategy, state.alloc_r, state.alloc_pods,
                req_r, nonzero, pod_count,
                nom_r=f.nom_req if has_nom else None,
                nom_p=f.nom_pods if has_nom else None)
        with jax.named_scope("feasibility"):
            okd = static_ok & fit_ok & (idx < num)
            if port_selfblock:
                okd &= ~blocked
            if has_aux:
                okd &= aux_cnt + f.aux_inc <= f.aux_room
            if A1:
                acnt = jnp.take_along_axis(anti_counts, anti_vid.astype(jnp.int32), axis=1)
                okd &= ~((anti_vid > 0) & (acnt > 0)).any(axis=0)
            F = jnp.cumsum(okd.astype(jnp.int32))
        with jax.named_scope("score_normalise"):
            total = (w_tt * jnp.int64(MAX_NODE_SCORE) + w_fit * fit_sc
                     + w_ba * ba + il_term)
        with jax.named_scope("select"):
            total_feas = F[-1]
            f_start = jnp.where(start > 0, F[jnp.maximum(start - 1, 0)], 0)
            rank = jnp.where(idx >= start, F - f_start, F + total_feas - f_start)
            rot = _unwrap(idx - start, num)
            l_full = _bounded_div(total_feas, tf, _LAP_BITS)
            L = jnp.clip(jnp.minimum(l_full, n_act - done), 1, LAP_MAX)
            # window of each feasible row; singleton window 0 when sampling
            # truncation is inactive (total_feas <= to_find ⇒ all rows rank<=tf).
            # One division serves the window and its end boundary:
            # rank = w * tf + rem + 1, so rank % tf == 0 is rem == tf - 1 and
            # then rank // tf - 1 == w. Windows at or past LAP_MAX saturate
            # into the dump lane either way.
            w, rem = _bounded_divmod(rank - 1, tf, _LAP_BITS)
            w = jnp.minimum(w, LAP_MAX)
            seg = jnp.where(okd & (w < L), w, LAP_MAX)           # [NP]
            in_w = seg[None, :] == lanes[:, None]                # [LAP_MAX, NP]
            # max-score-then-min-rotation packed argmax per window
            key = total * RADIX + (jnp.int32(RADIX - 1) - rot)
            key_w = jnp.max(jnp.where(in_w, key[None, :], -1), axis=1)
            has_w = (lanes < L) & (key_w >= 0)
            rot_w = jnp.int32(RADIX - 1) - (key_w & (RADIX - 1)).astype(jnp.int32)
            row_w = jnp.where(has_w, _wrap(start + rot_w, num), -1).astype(jnp.int32)
            # window end boundaries: the row with feasible rank (w+1)*to_find is
            # the last one examined for window w (numFeasibleNodesToFind cut);
            # empty ⇒ the window ran to the end of the rotation (evaluated=num).
            is_b = okd & (rem == tf - 1)
            seg_b = jnp.where(is_b, w, LAP_MAX)
            in_b = seg_b[None, :] == lanes[:, None]
            ev_w = jnp.min(jnp.where(in_b, rot[None, :] + 1, num), axis=1)  # [LAP_MAX]
            # per-pod cumulative start: start_after lane w = boundary of its window
            start_w = _wrap(start + ev_w, num)                    # [LAP_MAX]
        with jax.named_scope("carry_update"):
            # ---- apply the L placements (windows are disjoint ⇒ each row gets
            # at most one pod: a one-hot sum over lanes is an exact update) -----
            chosen_1h = (idx[None, :] == row_w[:, None]) & has_w[:, None]
            cnt = chosen_1h.any(axis=0)                           # [NP] bool
            c64 = cnt.astype(jnp.int64)
            req_r = req_r + f.request[None, :] * c64[:, None]
            nonzero = nonzero + f.nz_request[None, :] * c64[:, None]
            pod_count = pod_count + cnt.astype(jnp.int32)
            if port_selfblock:
                blocked |= cnt
            if has_aux:
                aux_cnt = aux_cnt + f.aux_inc * cnt.astype(jnp.int32)
            if A1:
                # hostname-anti landings: +self at each landed row's own value
                # (duplicate vids cannot occur — the axis is singleton-per-node).
                rr = jnp.maximum(row_w, 0)
                upd = (f.anti_self[:, None] * (anti_vid[:, rr] > 0).astype(jnp.int32)
                       * has_w[None, :].astype(jnp.int32))        # [A1, LAP_MAX]
                anti_counts = anti_counts.at[
                    jnp.arange(A1, dtype=jnp.int32)[:, None],
                    anti_vid[:, rr]].add(upd)
            # ---- emit results (positions >= n_act are sliced off by the host) -
            chosen_w = jnp.where(has_w, row_w, -1)
            block = jnp.stack([chosen_w, start_w.astype(jnp.int32)])  # [2, LAP_MAX]
            out = lax.dynamic_update_slice(out, block, (jnp.int32(0), done))
            start = start_w[jnp.maximum(L - 1, 0)]
        return (done + L, req_r, nonzero, pod_count, anti_counts, blocked,
                aux_cnt, start, out)

    out0 = jnp.full((2, B + LAP_MAX), -1, jnp.int32)
    c0 = (jnp.int32(0), ext0.req_r, ext0.nonzero, ext0.pod_count,
          ext0.anti_counts, ext0.blocked, ext0.aux_cnt, ext0.start, out0)
    (done, req_r, nonzero, pod_count, anti_counts, blocked, aux_cnt, start,
     out) = lax.while_loop(cond, body, c0)
    # The carry's fit_ok seeds the next chained batch of the SAME plan, so
    # it keeps the nominated lane (a changed nomination set never chains —
    # Nominator.version invalidates the session).
    fit_ok, fit_sc, ba = _resource_eval(
        f, fit_strategy, state.alloc_r, state.alloc_pods,
        req_r, nonzero, pod_count,
        nom_r=f.nom_req if has_nom else None,
        nom_p=f.nom_pods if has_nom else None)
    carry = ScanCarry(req_r, nonzero, pod_count, fit_ok, fit_sc, ba,
                      ext0.dns_counts, ext0.sa_counts, anti_counts,
                      ext0.aff_counts, ext0.ipa_delta, start, blocked,
                      aux_cnt)
    return out[:, :B], carry
