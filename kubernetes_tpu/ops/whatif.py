"""What-if rescore of BOUND pods — the descheduler's scoring core.

The scheduler answers "where should this pending pod land?"; the
descheduler asks the inverse: "for a pod already bound, does a strictly
better row exist?". Both questions share one arithmetic — ops/kernel.py's
`_resource_eval` fit filter + LeastAllocated + integer-quantized
BalancedAllocation — and this module evaluates it as ONE dense
candidate-pods × nodes matrix, with each candidate's own usage
subtracted from its source row first (the move vacates it).

Two implementations, bit-identical by construction:

- ``whatif_scores(batch)`` — a numpy host walker with zero device
  requirements (the controller-process default: no jax import, no
  compile wait in a 250ms reconcile tick);
- ``whatif_scores(batch, device=True)`` — a jax.jit mirror of the same
  int64 formulas, shape-padded so a steady descheduler tick reuses one
  compiled executable. The descheduler is its own OS process, so this
  is a SECOND JAX process beside the scheduler: a chip belongs to one
  process, hence CPU (JAX_PLATFORMS=cpu, what shard/harness.py _env gives
  every spawned controller) or a chip of its own — never the scheduler's.

Bit-parity is load-bearing, not cosmetic: a standby descheduler
re-deriving a dead ACTIVE's plan — possibly on different hardware —
must mint the SAME ``uid@node`` move set, or the exactly-once eviction
ledger stops absorbing the replay. tests/test_descheduler.py fuzzes the
two paths against each other on hint-eligible shapes.

Every integer division below runs on non-negative numerators (guards
mirror `_resource_eval`'s `where` clauses), where numpy's and XLA's
int64 ``//`` agree exactly — and agree with the kernel, which since PR 25
computes the same floor quotients without ``//`` (ops/kernel.py
`_bounded_divmod`: compare-subtract steps to each quotient's bound,
because XLA:TPU expands a general int64 ``//`` into 64 of them). The
forms differ, the integers do not; tests/test_kernel_division.py holds
the kernel's to Python's ``//``.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..api.types import find_matching_untolerated_taint
from ..core.node_info import NodeInfo

MAX_NODE_SCORE = 100
BA_SCALE = 1_000_000

# Resource slot layout — the NodeStateMirror row convention
# (ops/device_state.py): [cpu_milli, memory, ephemeral_storage, *scalars].
SLOT_CPU = 0
SLOT_MEMORY = 1
SLOT_EPHEMERAL = 2
BASE_RESOURCES = 3


class WhatIfBatch(NamedTuple):
    """One dense candidates × nodes what-if problem (all int64/bool numpy).

    Node rows use the mirror's encoding; ``mask[p, n]`` folds the
    host-evaluated static gates (row validity, taint toleration) so both
    score paths consume one shared feasibility plane and parity reduces
    to the fit/BA arithmetic alone.
    """

    alloc_r: np.ndarray      # [N, R] allocatable per slot
    alloc_pods: np.ndarray   # [N]    allocatable pod count
    req_r: np.ndarray        # [N, R] requested per slot (bound pods)
    nonzero: np.ndarray      # [N, 2] non-zero-default cpu/mem aggregate
    pod_count: np.ndarray    # [N]    bound pods per node
    request: np.ndarray      # [P, R] candidate request vector
    nz_request: np.ndarray   # [P, 2] candidate non-zero cpu/mem
    src: np.ndarray          # [P]    candidate's current row index
    mask: np.ndarray         # [P, N] landing eligibility

    @property
    def n_pods(self) -> int:
        return int(self.request.shape[0])

    @property
    def n_nodes(self) -> int:
        return int(self.alloc_r.shape[0])


def _resource_vec(r, slots: Dict[str, int], out: np.ndarray) -> None:
    out[SLOT_CPU] = r.milli_cpu
    out[SLOT_MEMORY] = r.memory
    out[SLOT_EPHEMERAL] = r.ephemeral_storage
    for name, amount in r.scalar_resources.items():
        out[slots[name]] = amount


def encode_batch(node_infos: Sequence[NodeInfo],
                 candidates: Sequence[object]) -> WhatIfBatch:
    """Encode a snapshot + candidate pod list into one WhatIfBatch.

    Rows follow NodeStateMirror's slot layout with the scalar-slot map
    rebuilt per batch (a descheduler tick is a fresh snapshot; there is
    no cross-tick device residency to preserve on the host path). The
    taint gate is evaluated here once and folded into ``mask`` — shared
    verbatim by both score paths.
    """
    slots: Dict[str, int] = {}
    for ni in node_infos:
        for name in ni.allocatable.scalar_resources:
            slots.setdefault(name, BASE_RESOURCES + len(slots))
    for pod in candidates:
        for name in pod.resource_request().scalar_resources:
            slots.setdefault(name, BASE_RESOURCES + len(slots))
    R = BASE_RESOURCES + len(slots)
    N, P = len(node_infos), len(candidates)
    alloc_r = np.zeros((N, R), np.int64)
    alloc_pods = np.zeros(N, np.int64)
    req_r = np.zeros((N, R), np.int64)
    nonzero = np.zeros((N, 2), np.int64)
    pod_count = np.zeros(N, np.int64)
    by_name = {ni.name: i for i, ni in enumerate(node_infos)}
    for i, ni in enumerate(node_infos):
        _resource_vec(ni.allocatable, slots, alloc_r[i])
        alloc_pods[i] = ni.allocatable.allowed_pod_number
        _resource_vec(ni.requested, slots, req_r[i])
        nonzero[i, 0] = ni.non_zero_requested.milli_cpu
        nonzero[i, 1] = ni.non_zero_requested.memory
        pod_count[i] = len(ni.pods)
    request = np.zeros((P, R), np.int64)
    nz_request = np.zeros((P, 2), np.int64)
    src = np.zeros(P, np.int64)
    mask = np.zeros((P, N), bool)
    for p, pod in enumerate(candidates):
        req = pod.resource_request()
        _resource_vec(req, slots, request[p])
        nz_request[p, 0] = req.milli_cpu or NodeInfo.DEFAULT_MILLI_CPU
        nz_request[p, 1] = req.memory or NodeInfo.DEFAULT_MEMORY
        src[p] = by_name.get(pod.node_name, 0)
        for i, ni in enumerate(node_infos):
            node = ni.node
            if node is None or getattr(node, "unschedulable", False):
                continue
            if find_matching_untolerated_taint(
                    node.taints, pod.tolerations) is not None:
                continue
            mask[p, i] = True
    return WhatIfBatch(alloc_r, alloc_pods, req_r, nonzero, pod_count,
                       request, nz_request, src, mask)


def _score_host(b: WhatIfBatch) -> Tuple[np.ndarray, np.ndarray]:
    """`_resource_eval` (fit filter + LeastAllocated + BalancedAllocation,
    default profile weights) on the vacated state, pure numpy int64."""
    P, N = b.n_pods, b.n_nodes
    vacate = np.zeros((P, N), np.int64)
    vacate[np.arange(P), b.src] = 1
    req_r = b.req_r[None, :, :] - vacate[:, :, None] * b.request[:, None, :]
    nonzero = (b.nonzero[None, :, :]
               - vacate[:, :, None] * b.nz_request[:, None, :])
    pod_count = b.pod_count[None, :] - vacate
    alloc_r = np.broadcast_to(b.alloc_r[None, :, :], req_r.shape)
    # fit filter (fit.go:710)
    pods_ok = pod_count + 1 <= b.alloc_pods[None, :]
    avail = alloc_r - req_r
    req = b.request[:, None, :]
    viol = ((req > 0) & (req > avail)).any(axis=-1)
    fit_ok = pods_ok & ~viol & b.mask
    used0 = nonzero[..., 0] + b.nz_request[:, 0, None]
    used1 = nonzero[..., 1] + b.nz_request[:, 1, None]
    # LeastAllocated over (cpu, memory), weight 1 each (default profile)
    fit_num = np.zeros_like(used0)
    fit_den = np.zeros_like(used0)
    for slot, used in ((SLOT_CPU, used0), (SLOT_MEMORY, used1)):
        alloc = alloc_r[..., slot]
        rscore = np.where(
            (alloc > 0) & (used <= alloc),
            (alloc - used) * MAX_NODE_SCORE // np.maximum(alloc, 1), 0)
        fit_num = fit_num + np.where(alloc > 0, rscore, 0)
        fit_den = fit_den + np.where(alloc > 0, 1, 0)
    fit_sc = np.where(fit_den > 0, fit_num // np.maximum(fit_den, 1), 0)
    # integer-quantized BalancedAllocation
    a_cpu = alloc_r[..., SLOT_CPU]
    a_mem = alloc_r[..., SLOT_MEMORY]
    q_cpu = np.minimum(used0 * BA_SCALE // np.maximum(a_cpu, 1), BA_SCALE)
    q_mem = np.minimum(used1 * BA_SCALE // np.maximum(a_mem, 1), BA_SCALE)
    both = (a_cpu > 0) & (a_mem > 0)
    ba = np.where(both,
                  (MAX_NODE_SCORE * BA_SCALE
                   - 50 * np.abs(q_cpu - q_mem)) // BA_SCALE,
                  np.int64(MAX_NODE_SCORE))
    return fit_ok, (fit_sc + ba).astype(np.int64)


# -- device mirror ----------------------------------------------------------

_jit_cache: dict = {}


def _pow2(n: int, floor: int = 8) -> int:
    out = floor
    while out < n:
        out *= 2
    return out


def _device_fn():
    """Lazily build (and cache) the jitted mirror. jax is imported only
    here — a host-walker descheduler process never pays the import."""
    fn = _jit_cache.get("fn")
    if fn is not None:
        return fn
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_x64", True)

    def score(alloc_r, alloc_pods, req_r0, nonzero0, pod_count0,
              request, nz_request, src, mask):
        P = request.shape[0]
        vacate = jnp.zeros(mask.shape, jnp.int64).at[
            jnp.arange(P, dtype=jnp.int32), src].set(1)
        req_r = req_r0[None, :, :] - vacate[:, :, None] * request[:, None, :]
        nonzero = (nonzero0[None, :, :]
                   - vacate[:, :, None] * nz_request[:, None, :])
        pod_count = pod_count0[None, :] - vacate
        alloc = alloc_r[None, :, :]
        pods_ok = pod_count + 1 <= alloc_pods[None, :]
        req = request[:, None, :]
        viol = ((req > 0) & (req > alloc - req_r)).any(axis=-1)
        fit_ok = pods_ok & ~viol & mask
        used0 = nonzero[..., 0] + nz_request[:, 0, None]
        used1 = nonzero[..., 1] + nz_request[:, 1, None]
        fit_num = jnp.zeros_like(used0)
        fit_den = jnp.zeros_like(used0)
        for slot, used in ((SLOT_CPU, used0), (SLOT_MEMORY, used1)):
            a = alloc[..., slot]
            rscore = jnp.where(
                (a > 0) & (used <= a),
                (a - used) * MAX_NODE_SCORE // jnp.maximum(a, 1), 0)
            fit_num = fit_num + jnp.where(a > 0, rscore, 0)
            fit_den = fit_den + jnp.where(a > 0, 1, 0)
        fit_sc = jnp.where(fit_den > 0,
                           fit_num // jnp.maximum(fit_den, 1), 0)
        a_cpu = alloc[..., SLOT_CPU]
        a_mem = alloc[..., SLOT_MEMORY]
        q_cpu = jnp.minimum(used0 * BA_SCALE // jnp.maximum(a_cpu, 1),
                            BA_SCALE)
        q_mem = jnp.minimum(used1 * BA_SCALE // jnp.maximum(a_mem, 1),
                            BA_SCALE)
        both = (a_cpu > 0) & (a_mem > 0)
        ba = jnp.where(both,
                       (MAX_NODE_SCORE * BA_SCALE
                        - 50 * jnp.abs(q_cpu - q_mem)) // BA_SCALE,
                       jnp.int64(MAX_NODE_SCORE))
        return fit_ok, (fit_sc + ba).astype(jnp.int64)

    fn = _jit_cache["fn"] = jax.jit(score)
    return fn


def _score_device(b: WhatIfBatch) -> Tuple[np.ndarray, np.ndarray]:
    """Pad to power-of-two tiers (one executable per steady tick) and
    dispatch the jitted mirror; slice the pads back off on the host."""
    fn = _device_fn()
    P, N = b.n_pods, b.n_nodes
    PP, NP_ = _pow2(max(P, 1)), _pow2(max(N, 1))

    def pad(a, shape):
        out = np.zeros(shape, a.dtype)
        out[tuple(slice(0, s) for s in a.shape)] = a
        return out

    R = b.alloc_r.shape[1]
    fit_ok, score = fn(
        pad(b.alloc_r, (NP_, R)), pad(b.alloc_pods, (NP_,)),
        pad(b.req_r, (NP_, R)), pad(b.nonzero, (NP_, 2)),
        pad(b.pod_count, (NP_,)), pad(b.request, (PP, R)),
        pad(b.nz_request, (PP, 2)), pad(b.src, (PP,)),
        pad(b.mask, (PP, NP_)))
    return (np.asarray(fit_ok)[:P, :N], np.asarray(score)[:P, :N])


def whatif_scores(batch: WhatIfBatch,
                  device: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    """Score the batch: returns ``(fit_ok [P, N] bool, score [P, N] i64)``
    with ``score = fit_sc + ba`` (0..200). ``device=True`` dispatches the
    jitted mirror (bit-identical); default walks on the host."""
    if batch.n_pods == 0 or batch.n_nodes == 0:
        shape = (batch.n_pods, batch.n_nodes)
        return np.zeros(shape, bool), np.zeros(shape, np.int64)
    if device:
        return _score_device(batch)
    return _score_host(batch)


class Move(NamedTuple):
    pod_index: int        # index into the candidate list
    src: int              # current row
    dst: int              # best landing row
    improvement: int      # score(dst) - score(src); >= 1 when src unfit


def best_moves(batch: WhatIfBatch, fit_ok: np.ndarray,
               score: np.ndarray) -> List[Optional[Move]]:
    """Pick each candidate's best strictly-different landing row.

    Deterministic: ties break to the LOWEST row index (numpy argmax
    first-occurrence), so two managers scoring the same snapshot plan
    the same move set — the exactly-once replay contract. A candidate
    whose source row no longer fits it (drift shrank the node under a
    bound pod) scores its current seat as ``current - 1``, so a
    merely-equal landing row still registers a positive improvement.
    """
    out: List[Optional[Move]] = []
    P = batch.n_pods
    for p in range(P):
        row_ok = fit_ok[p].copy()
        s = int(batch.src[p])
        cur_fit = bool(row_ok[s])
        cur = int(score[p, s]) if cur_fit else int(score[p, s]) - 1
        row_ok[s] = False
        if not row_ok.any():
            out.append(None)
            continue
        masked = np.where(row_ok, score[p], np.int64(-1))
        dst = int(masked.argmax())
        out.append(Move(p, s, dst, int(masked[dst]) - cur))
    return out
