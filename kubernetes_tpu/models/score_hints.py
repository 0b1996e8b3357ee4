"""Signature-keyed score-hint fast path: bind identical replicas without a
device dispatch.

The reference's opportunistic batching (KEP-5598, framework/runtime/batch.go
OpportunisticBatch) caches the previous cycle's sorted score list keyed by
pod signature so the next identical pod gets a node hint and skips
filter/score entirely. This module is that cache's TPU-era form: when a
device session ends cleanly, the session's final carry — per-node requested
aggregates plus the carried fit/balance score vector, i.e. the kernel's OWN
sorted-score truth — is persisted host-side, keyed by the session's exact
AND namespace-erased neutral signature and stamped with the
cluster_event_seq it reflects. The next identical pod then walks the hinted
score vector entirely on the host: a numpy replica of the kernel's
scores_carried/incremental_feas selection (the only shapes eligible — see
``hint_eligible``) picks the SAME node the kernel would, the pod binds
through the existing commit tail (bulk-binding path included), and the
walker applies the placement to its own row state — a host-only bind loop
with the device reserved for novel signatures.

Exactness contract: hint placements must be bit-identical to the
always-dispatch oracle. That holds because eligibility is restricted to
plans where the kernel itself proves the total score row-local
(``scores_carried``: no spread/IPA/NA-pref normalization, no
PreferNoSchedule counts) and feasibility row-local (``incremental_feas``
with no anti/affinity axes at all — ``BatchPlan.pod_local``), so the walk
is the kernel's scan step with the dead lanes removed: same int64 fit/BA
integers (ops/kernel.py _resource_eval reaches each quotient by bounded
compare-subtract steps, this walk by numpy's ``//``: both are the exact
floor, tests/test_kernel_division.py), same adaptive-sampling
truncation and rotation (schedule_one.go:779-892 emulation), same
max-score-then-min-rotation packed selection.

Freshness is event-driven, not TTL-driven (the journal decides which hints
survive — core/cache.py EventJournal):

    event kind          hint survival
    ------------------  ------------------------------------------------
    queue               free (nothing node-side moved)
    namespace           free while no affinity-term pod exists
    pod_add/remove/upd  plain pod: re-encode that ROW from cache truth
                        (and unblock a 409-blocked row); terms: killed
    node_update         re-validate that ROW's taints/alloc/unschedulable
                        (labels/images intact by the kind's contract);
                        a PreferNoSchedule taint kills the hint (the plan
                        compiled the no-PNS fast path). This row is how
                        the node-lifecycle controller's unreachable taint
                        (controllers/node_lifecycle.py NoSchedule ladder
                        step) reaches the fast path: the taint PUT fans a
                        MODIFIED node event, the journal records
                        node_update, and the tainted node's hint row dies
                        here — zero lifecycle-specific device code
    structural/other    killed
    journal gap         killed (anything may have changed)

Out-of-journal state moves are fenced by counters the serve path checks:
any scheduling attempt the walker did not make itself (``attempts``), any
cache unwind (``state_unwinds``), any nomination change
(``Nominator.version``), and the cluster-wide 0→1 affinity-pod transition
(``cache.affinity_pod_refs`` — mirroring the watch plane's selector gate)
all invalidate. A bind-409 invalidates the hinted NODE only: the row is
blocked until the winner's commit re-encodes it through the journal.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from ..api.types import PREFER_NO_SCHEDULE
from ..core.cache import (EV_NAMESPACE, EV_NODE_UPDATE, EV_POD_ADD,
                          EV_POD_REMOVE, EV_POD_UPDATE, EV_QUEUE)
# The kernel's own lap bound: windows of consecutive pods are disjoint
# until the rotation laps the cluster — ONE shared constant, so the host
# walk's batching can never silently diverge from the device lap's.
from ..ops.kernel import LAP_MAX as _LAP_MAX

MAX_NODE_SCORE = 100
_BA_SCALE = 1_000_000
# Live hints kept, one per pod signature. Two: a rollout alternates the
# old and the new replica shape through one queue.
HINT_LRU_SLOTS = 2


def hint_eligible(plan, aux_shape, head_pod, extenders,
                  nominator, affinity_pod_refs: int) -> bool:
    """Can a clean session of this shape seed a score hint? The plan must
    be row-local (BatchPlan.row_local: the walk replicates exactly that
    fast path), and the host-side state the walk does not model must be
    absent: counted claims, extenders, nominated pods, and any live
    affinity-term pod (cluster-wide disable — the 0→1 transition mirrors
    the watch plane's selector gate). Mesh sessions are eligible too: the
    install fetches the per-node aggregates/score vector from the SHARDED
    carry via one device→host gather at clean session end — sharded and
    single-device carries are bit-identical (integer arithmetic), so the
    walk stays oracle-exact."""
    return (plan.row_local
            and aux_shape == (None, None)
            and not head_pod.volumes
            and not getattr(head_pod, "resource_claims", None)
            and not extenders
            and not nominator.has_nominated_pods()
            and affinity_pod_refs == 0)


class HintEntry:
    """One live hint: the per-node walk state for one pod signature."""

    __slots__ = (
        "keys", "fw_id", "pod", "node_names", "row_of",
        "NP", "num", "to_find",
        # pod-spec facts (ints / small np vectors)
        "request", "nz_request", "has_request", "ba_skip",
        "fit_slots", "fit_weights", "fit_strategy",
        "w_tt", "w_fit", "w_ba", "w_il", "tolerates_unsched", "enable",
        # per-node state (np arrays, entry-owned copies)
        "alloc_r", "alloc_pods", "req_r", "nonzero", "pod_count",
        "static_ok", "fit_ok", "fit_sc", "ba", "total", "ok", "blocked",
        "il_score", "sel_ok", "extra_ok", "name_ok", "valid", "_idx",
        # freshness watermarks
        "seq", "attempts", "unwinds", "nom_version",
        # scalar-slot interning view (read-only; a slot the map lacks
        # cannot affect this plan — its request is zero)
        "scalar_slots",
        # batched-walk state (ROADMAP 12a): precomputed (row, evaluated,
        # expected_start) placements for the rest of the current LAP —
        # adaptive-sampling windows of consecutive pods are disjoint, so
        # one cumsum serves up to total_feas//to_find pods. Any row
        # mutation that is NOT the served head's own apply() clears it.
        "_pending", "lap_walks",
    )

    # -- construction -------------------------------------------------------

    @classmethod
    def from_session(cls, sched, fw, head_pod, sig, nsig, plan, node_names,
                     carry) -> "HintEntry":
        """Capture the session's end state. `carry` is the final ScanCarry —
        its req_r/nonzero/pod_count/fit_ok/fit_sc/ba ARE the kernel's
        post-commit truth, so copying them (one device→host fetch) makes
        the walk bit-identical to what the next dispatch would compute."""
        e = cls()
        e.keys = {("exact", sig)}
        if nsig is not None:
            e.keys.add(("neutral", nsig))
        e.fw_id = id(fw)
        e.pod = head_pod
        e.node_names = list(node_names)
        e.row_of = {n: i for i, n in enumerate(node_names)}
        f = plan.features
        mirror = sched.mirror
        e.NP = int(mirror.np_cap)
        e.num = max(int(np.asarray(f.num_nodes)), 1)
        e.to_find = int(np.asarray(f.to_find))
        e._idx = np.arange(e.NP, dtype=np.int64)
        # pod-spec facts
        e.request = np.asarray(f.request).astype(np.int64)
        e.nz_request = np.asarray(f.nz_request).astype(np.int64)
        e.has_request = int(np.asarray(f.has_request))
        e.ba_skip = int(np.asarray(f.ba_skip))
        e.fit_slots = np.asarray(f.fit_slots).astype(np.int64)
        e.fit_weights = np.asarray(f.fit_weights).astype(np.int64)
        e.fit_strategy = int(plan.fit_strategy)
        w = np.asarray(f.weights)
        e.w_tt, e.w_fit, e.w_ba, e.w_il = (
            int(w[0]), int(w[1]), int(w[4]), int(w[6]))
        e.tolerates_unsched = int(np.asarray(f.tolerates_unsched))
        e.enable = tuple(int(x) for x in np.asarray(f.enable))
        e.scalar_slots = mirror.scalar_slots
        # per-node dynamic state: the carry's own arrays (post-commit
        # truth). ONE device→host gather for all six lanes — under a mesh
        # the carry is sharded across chips and per-leaf np.asarray would
        # pay a separate cross-device gather each (ROADMAP 12d).
        import jax
        req_r, nonzero, pod_count, fit_ok, fit_sc, ba = jax.device_get(
            (carry.req_r, carry.nonzero, carry.pod_count,
             carry.fit_ok, carry.fit_sc, carry.ba))
        e.req_r = np.asarray(req_r).astype(np.int64).copy()
        e.nonzero = np.asarray(nonzero).astype(np.int64).copy()
        e.pod_count = np.asarray(pod_count).astype(np.int64).copy()
        e.fit_ok = np.asarray(fit_ok).astype(bool).copy()
        e.fit_sc = np.asarray(fit_sc).astype(np.int64).copy()
        e.ba = np.asarray(ba).astype(np.int64).copy()
        # per-node static state (mirror staging is in line after adopt())
        e.alloc_r = mirror.h_alloc_r.astype(np.int64).copy()
        e.alloc_pods = mirror.h_alloc_pods.astype(np.int64).copy()
        e.il_score = np.asarray(f.il_score).astype(np.int64)
        e.sel_ok = np.asarray(f.sel_match).astype(bool)
        e.extra_ok = np.asarray(f.extra_ok).astype(bool)
        e.valid = mirror.h_valid.copy() & (e._idx < e.num)
        nid = int(np.asarray(f.node_name_id))
        e.name_ok = ((nid == 0) | (mirror.h_name_id == nid)
                     | (e.enable[0] == 0))
        e.static_ok = e.valid & e.name_ok & e.sel_ok_effective() \
            & e.extra_ok & e._taint_unsched_ok(mirror, f)
        e.blocked = np.zeros(e.NP, bool)
        e.total = (e.w_tt * MAX_NODE_SCORE + e.w_fit * e.fit_sc
                   + e.w_ba * e.ba + e.w_il * e.il_score)
        e.ok = e.static_ok & e.fit_ok & ~e.blocked
        # freshness watermarks
        e.seq = sched.cluster_event_seq
        e.attempts = sched.attempts
        e.unwinds = sched.state_unwinds
        e.nom_version = sched.queue.nominator.version
        e._pending = []
        e.lap_walks = 0
        return e

    def sel_ok_effective(self) -> np.ndarray:
        return self.sel_ok | (self.enable[3] == 0)

    def _taint_unsched_ok(self, mirror, f) -> np.ndarray:
        """Vectorized _static_masks taint + unschedulable verdicts over the
        staging arrays (ops/kernel.py semantics, numpy)."""
        from ..ops.codebook import (EFFECT_NO_EXECUTE, EFFECT_NO_SCHEDULE,
                                    OP_EXISTS)
        tk = np.asarray(f.tol_key)
        tv = np.asarray(f.tol_val)
        te = np.asarray(f.tol_eff)
        to = np.asarray(f.tol_op)
        k = mirror.h_taint_key[:, :, None]
        v = mirror.h_taint_val[:, :, None]
        ef = mirror.h_taint_eff[:, :, None]
        if tk.shape[0]:
            eff_ok = (te[None, None, :] == 0) | (te[None, None, :] == ef)
            key_ok = (tk[None, None, :] == 0) | (tk[None, None, :] == k)
            val_ok = (to[None, None, :] == OP_EXISTS) | (tv[None, None, :] == v)
            tolerated = (eff_ok & key_ok & val_ok).any(axis=2)
        else:
            tolerated = np.zeros(mirror.h_taint_key.shape, bool)
        relevant = ((mirror.h_taint_eff == EFFECT_NO_SCHEDULE)
                    | (mirror.h_taint_eff == EFFECT_NO_EXECUTE))
        taint_ok = ~(relevant & ~tolerated).any(axis=1) | (self.enable[2] == 0)
        unsched_ok = (~mirror.h_unsched | (self.tolerates_unsched == 1)
                      | (self.enable[1] == 0))
        return taint_ok & unsched_ok

    # -- the walk (the kernel's scores_carried scan step, host-side) --------

    def select(self, start: int) -> Tuple[int, int]:
        """One pod's selection against the current walk state: returns
        (row or -1, evaluated) where `evaluated` advances the rotation
        exactly as the kernel's window-boundary reduction does.

        Batched walk (ROADMAP 12a): when adaptive-sampling truncation is
        live (total_feas // to_find >= 2), consecutive pods examine
        DISJOINT windows — the kernel's own lap-vectorization fact
        (ops/kernel.py _lap_schedule) — so ONE cumsum pass segments up to
        a lap of placements and the per-pod cost drops to ~1/L of a full
        walk (the per-pod numpy cumsum over np_cap rows was ~200µs/pod at
        5k nodes). Served placements pop off `_pending`; any row mutation
        other than the served head's own apply() clears it. Bit-exact:
        window w's selection reads only rows later windows never touch."""
        num, NP, to_find = self.num, self.NP, self.to_find
        start = start % num
        if self._pending:
            if self._pending[0][2] == start:
                row, evaluated, _ = self._pending.pop(0)
                return row, evaluated
            self._pending = []  # rotation moved outside the walk: recompute
        ok = self.ok
        F = np.cumsum(ok, dtype=np.int64)
        total_feas = int(F[-1])
        idx = self._idx
        f_start = int(F[start - 1]) if start > 0 else 0
        rank = np.where(idx >= start, F - f_start,
                        F + total_feas - f_start)
        rot = (idx - start) % num
        if total_feas:
            # Lap attempt FIRST: when it serves, the single-pod boundary
            # reduction below is never needed (the lap carries its own
            # per-window evaluated values).
            tf = max(to_find, 1)
            L = min(total_feas // tf, _LAP_MAX)
            if L >= 2:
                got = self._lap_select(start, ok, rank, rot, int(L), tf,
                                       num, NP)
                if got is not None:
                    return got
        boundary = ok & (rank == to_find)
        mx = int(np.max(np.where(boundary, num - 1 - rot, 0))) \
            if NP else 0
        evaluated = num - mx
        if not total_feas:
            return -1, evaluated
        kept = ok & (rank <= to_find)
        key = np.where(kept, self.total * NP + (NP - 1 - rot), -1)
        best = int(key.max())
        if best < 0:
            return -1, evaluated
        chosen_rot = (NP - 1) - (best % NP)
        return (start + chosen_rot) % num, evaluated

    def _lap_select(self, start, ok, rank, rot, L, tf, num, NP):
        """Segment the feasible rotation into L disjoint sampling windows
        (window w = feasible ranks (w·tf, (w+1)·tf]) and pick each
        window's max-score-then-min-rotation key in ONE vectorized pass —
        the numpy restatement of the kernel lap's segmented argmax. Every
        window holds exactly tf feasible rows, so its boundary row
        (rank == (w+1)·tf) exists and the per-window `evaluated` is the
        boundary-to-boundary rotation span, exactly the scan's per-pod
        advance. Returns the first (row, evaluated) and stashes the rest
        on `_pending`, or None to fall back to the single-pod path."""
        key = np.where(ok, self.total * NP + (NP - 1 - rot), -1)
        w = np.zeros_like(rank)
        np.floor_divide(rank - 1, tf, out=w, where=ok)
        sel = ok & (w < L)
        best = np.full(L, -1, np.int64)
        np.maximum.at(best, w[sel], key[sel])
        is_b = ok & (rank % tf == 0) & (rank >= tf) & (rank // tf <= L)
        # Sentinel num+1: a genuine boundary at the LAST rotation slot is
        # ev == num (the scan's evaluated=num full-wrap case) and must be
        # kept; only a truly boundary-less window exceeds it.
        ev_abs = np.full(L, num + 1, np.int64)
        np.minimum.at(ev_abs, rank[is_b] // tf - 1, rot[is_b] + 1)
        entries = []
        cur, prev_abs = start, 0
        for wi in range(L):
            k = int(best[wi])
            if k < 0 or ev_abs[wi] > num:
                break  # defensive: empty / unbounded window ends the lap
            row = (start + (NP - 1 - k % NP)) % num
            entries.append((int(row), int(ev_abs[wi]) - prev_abs, cur))
            prev_abs = int(ev_abs[wi])
            cur = (start + prev_abs) % num
        if not entries:
            return None
        self.lap_walks += 1
        row, evaluated, _ = entries.pop(0)
        self._pending = entries
        return row, evaluated

    def apply(self, row: int) -> None:
        """Commit one placement into the walk state (the scan's carry
        update restricted to the landed row)."""
        self.req_r[row] += self.request
        self.nonzero[row] += self.nz_request
        self.pod_count[row] += 1
        self._reval_row(row)

    # -- row re-evaluation (ops/kernel.py _resource_eval, one row; on the
    # host a real int64 `//` is one instruction, so it stays `//`) ----------

    def _reval_row(self, row: int) -> None:
        alloc = self.alloc_r[row]
        pods_ok = int(self.pod_count[row]) + 1 <= int(self.alloc_pods[row])
        avail = alloc - self.req_r[row]
        viol = bool(((self.request > 0) & (self.request > avail)).any())
        fit_ok = ((pods_ok and (not viol or self.has_request == 0))
                  or self.enable[4] == 0)
        used0 = int(self.nonzero[row, 0]) + int(self.nz_request[0])
        used1 = int(self.nonzero[row, 1]) + int(self.nz_request[1])
        num_ = den = 0
        for j in range(self.fit_slots.shape[0]):
            slot = int(self.fit_slots[j])
            wj = int(self.fit_weights[j])
            a = int(alloc[slot])
            if slot == 0:
                used = used0
            elif slot == 1:
                used = used1
            else:
                used = int(self.req_r[row, slot]) + int(self.request[slot])
            if self.fit_strategy == 0:  # LeastAllocated
                rscore = ((a - used) * MAX_NODE_SCORE // max(a, 1)
                          if (a > 0 and used <= a) else 0)
            else:  # MostAllocated
                rscore = (min(used, a) * MAX_NODE_SCORE // max(a, 1)
                          if a > 0 else 0)
            if a > 0:
                num_ += rscore * wj
                den += wj
        fit_sc = num_ // max(den, 1) if den > 0 else 0
        a_cpu, a_mem = int(alloc[0]), int(alloc[1])
        q_cpu = min(used0 * _BA_SCALE // max(a_cpu, 1), _BA_SCALE)
        q_mem = min(used1 * _BA_SCALE // max(a_mem, 1), _BA_SCALE)
        if self.ba_skip == 1:
            ba = 0
        elif a_cpu > 0 and a_mem > 0:
            ba = (MAX_NODE_SCORE * _BA_SCALE
                  - 50 * abs(q_cpu - q_mem)) // _BA_SCALE
        else:
            ba = MAX_NODE_SCORE
        self.fit_ok[row] = fit_ok
        self.fit_sc[row] = fit_sc
        self.ba[row] = ba
        self.total[row] = (self.w_tt * MAX_NODE_SCORE + self.w_fit * fit_sc
                           + self.w_ba * ba + self.w_il * int(self.il_score[row]))
        self.ok[row] = (bool(self.static_ok[row]) and fit_ok
                        and not self.blocked[row])

    def _reval_rows(self, rows: slice) -> None:
        """``_reval_row`` over a run of rows in one pass of int64 array
        arithmetic: the same integers to the bit (numpy's ``//`` is the
        floor Python's is; a lane whose guard is false is computed and
        discarded, never read; int64 as in the kernel and ``from_session``,
        where the scalar form's Python ints would only part from it past
        8 TiB of memory on a node). For the caller that holds a session, where
        ``_reval_row`` is for the one that holds a row: a one-row call of
        this costs more than the scalar form, so both stay, and
        tests/test_hint_reval_rows.py holds them to each other."""
        alloc = self.alloc_r[rows]
        req_r = self.req_r[rows]
        request = self.request
        if self.enable[4] == 0:
            fit_ok = True
        else:
            fit_ok = self.pod_count[rows] + 1 <= self.alloc_pods[rows]
            if self.has_request != 0:
                fit_ok &= ~((request > 0)
                            & (request > alloc - req_r)).any(axis=1)
        used = self.nonzero[rows] + self.nz_request
        num_ = den = 0
        for slot, wj in zip(self.fit_slots.tolist(),
                            self.fit_weights.tolist()):
            a = alloc[:, slot]
            u = used[:, slot] if slot < 2 else req_r[:, slot] + request[slot]
            held = a > 0
            if self.fit_strategy == 0:  # LeastAllocated
                rscore = np.where(
                    held & (u <= a),
                    (a - u) * MAX_NODE_SCORE // np.maximum(a, 1), 0)
            else:  # MostAllocated
                rscore = np.where(
                    held,
                    np.minimum(u, a) * MAX_NODE_SCORE // np.maximum(a, 1), 0)
            num_ = num_ + rscore * wj
            den = den + held * wj
        fit_sc = np.where(den > 0, num_ // np.maximum(den, 1), 0)
        if self.ba_skip == 1:
            ba = 0
        else:
            a_cpu, a_mem = alloc[:, 0], alloc[:, 1]
            q_cpu = np.minimum(
                used[:, 0] * _BA_SCALE // np.maximum(a_cpu, 1), _BA_SCALE)
            q_mem = np.minimum(
                used[:, 1] * _BA_SCALE // np.maximum(a_mem, 1), _BA_SCALE)
            ba = np.where(
                (a_cpu > 0) & (a_mem > 0),
                (MAX_NODE_SCORE * _BA_SCALE
                 - 50 * np.abs(q_cpu - q_mem)) // _BA_SCALE,
                MAX_NODE_SCORE)
        self.fit_ok[rows] = fit_ok
        self.fit_sc[rows] = fit_sc
        self.ba[rows] = ba
        self.total[rows] = (self.w_tt * MAX_NODE_SCORE + self.w_fit * fit_sc
                            + self.w_ba * ba
                            + self.w_il * self.il_score[rows])
        self.ok[rows] = self.static_ok[rows] & fit_ok & ~self.blocked[rows]

    # -- event-driven freshness (the journal replay) ------------------------

    def block_row(self, node: str) -> bool:
        """Bind-409: the hint's view of this node understates committed
        usage — exclude the row until a journal pod event re-encodes it
        from cache truth (the winner's commit arrives as exactly that)."""
        row = self.row_of.get(node)
        if row is None:
            return False
        self.blocked[row] = True
        self.ok[row] = False
        self._pending = []  # feasibility shrank outside the walk
        return True

    def _resource_vec(self, r) -> np.ndarray:
        """Entry-width resource vector. Scalar resources the interning map
        lacks are ignored: this plan's request for them is zero by
        construction, so they cannot move its fit filter or scores."""
        out = np.zeros(self.req_r.shape[1], np.int64)
        out[0] = r.milli_cpu
        out[1] = r.memory
        out[2] = r.ephemeral_storage
        for name, amount in r.scalar_resources.items():
            slot = self.scalar_slots.get(name)
            if slot is not None and slot < out.shape[0]:
                out[slot] = amount
        return out

    def _reencode_pod_row(self, cache, key: str,
                          unblock: bool = True) -> Optional[str]:
        row = self.row_of.get(key)
        ni = cache.nodes.get(key)
        if row is None or ni is None or ni.node is None:
            return "structural"  # row set changed shape after all
        self.req_r[row] = self._resource_vec(ni.requested)
        self.nonzero[row, 0] = ni.non_zero_requested.milli_cpu
        self.nonzero[row, 1] = ni.non_zero_requested.memory
        self.pod_count[row] = len(ni.pods)
        if unblock:
            # Journal truth (the 409 winner's commit arrives as exactly
            # this event) releases a conflict block. A SIBLING entry's own
            # bind (note_own_attempt cross-feed) must NOT: the winner's
            # watch copy may not have landed in the cache yet, so the row
            # would understate committed usage all over again.
            self.blocked[row] = False
        self._reval_row(row)
        self._pending = []  # a row moved outside the walk: re-segment
        return None

    def resync_rows(self, fresh: "HintEntry") -> bool:
        """Absorb a device session this entry did not watch (own binds are
        journal-benign, so there is no event stream to replay): take EVERY
        row's dynamic pod state from `fresh`, the entry captured from that
        session's final carry, and re-evaluate every row once
        (``_reval_rows``). The session ended clean, so the carry's
        req_r/nonzero/pod_count ARE cache truth for every row, the ones it
        placed on and the ones it left alone: what ``fresh`` itself serves
        from. This entry keeps its own alloc_r and static verdicts (the
        journal re-validates a row of those at its next serve) and its
        blocked rows (a 409 block must outlive a sibling's session).

        Three lane copies and one array evaluation, whatever the session
        placed; paid at every clean session end while two pod templates
        take turns (it was a scalar pass over every row by name, 72 ms of
        a 93 ms adoption at 5,000 nodes; PERF.md, PR 41). False when this entry's rows are not the session's (a node
        came or went since it was captured, or a capacity tier grew): the
        journal's structural record would end it at its next serve anyway,
        so the caller drops it now."""
        n = len(self.node_names)
        if (self.req_r.shape != fresh.req_r.shape
                or self.node_names != fresh.node_names):
            return False
        self.req_r[:n] = fresh.req_r[:n]
        self.nonzero[:n] = fresh.nonzero[:n]
        self.pod_count[:n] = fresh.pod_count[:n]
        self._reval_rows(slice(0, n))
        self._pending = []  # rows moved outside the walk: re-segment
        return True

    def _revalidate_node_row(self, cache, key: str) -> Optional[str]:
        """EV_NODE_UPDATE: taints/allocatable/unschedulable moved on one
        row (labels/images/declared-features intact by the event kind's
        contract, so sel/extra/name verdicts stay valid)."""
        row = self.row_of.get(key)
        ni = cache.nodes.get(key)
        if row is None or ni is None or ni.node is None:
            return "structural"
        node = ni.node
        if any(t.effect == PREFER_NO_SCHEDULE for t in node.taints):
            # The plan compiled the no-PNS fast path (has_pns=False); the
            # oracle would now score PreferNoSchedule counts.
            return "pns_taint"
        tols = self.pod.tolerations
        taint_ok = (self.enable[2] == 0) or all(
            any(tol.tolerates(t) for tol in tols)
            for t in node.taints if t.effect != PREFER_NO_SCHEDULE)
        unsched_ok = ((not node.unschedulable)
                      or self.tolerates_unsched == 1
                      or self.enable[1] == 0)
        self.static_ok[row] = (bool(self.valid[row])
                               and bool(self.name_ok[row])
                               and bool(self.sel_ok_effective()[row])
                               and bool(self.extra_ok[row])
                               and taint_ok and unsched_ok)
        self.alloc_r[row] = self._resource_vec(ni.allocatable)
        self.alloc_pods[row] = ni.allocatable.allowed_pod_number
        self._reval_row(row)
        self._pending = []  # a row moved outside the walk: re-segment
        return None

    def consume(self, sched, events) -> Optional[str]:
        """Replay journal events into the walk state. Returns None when the
        hint survives (rows patched as needed) or the invalidation reason."""
        cache = sched.cache
        for ev in events:
            if ev.kind == EV_QUEUE:
                continue
            if ev.kind == EV_NAMESPACE:
                if cache.affinity_pod_refs == 0:
                    continue  # namespace labels feed only affinity selectors
                return "namespace"
            if ev.kind in (EV_POD_ADD, EV_POD_REMOVE, EV_POD_UPDATE):
                if not ev.pod_plain:
                    return "pod_terms"
                reason = self._reencode_pod_row(cache, ev.key)
                if reason:
                    return reason
            elif ev.kind == EV_NODE_UPDATE:
                reason = self._revalidate_node_row(cache, ev.key)
                if reason:
                    return reason
            else:
                return ev.kind  # structural / other
        return None


class ScoreHintCache:
    """The scheduler's live hints + serve/install/invalidate protocol.
    Counters live on the scheduler (WINDOW_COUNTERS surface); labeled
    series on its SchedulerMetrics.

    The cache is a small signature-keyed LRU (``HINT_LRU_SLOTS``, MRU
    first): alternating deployment waves — two replica shapes interleaving
    through one queue — keep BOTH shapes on the host path instead of
    thrashing a single slot. Coherence across
    entries is push-based, not journal-based, because own binds are
    deliberately journal-benign: every own attempt bumps EVERY live
    entry's attempt watermark, and a committed bind re-encodes the landed
    node's row on the non-serving entries from cache truth
    (``note_own_attempt``), so a sibling's placements can never make an
    entry serve a stale row. A clean device session of one shape is pushed
    whole: at its install every entry of another shape takes all rows' pod
    state from the fresh carry in one array pass (``resync_rows``), or is
    dropped where its rows are not the session's."""

    def __init__(self, sched, enabled: bool = True):
        self.sched = sched
        # Off only in a scheduler with no device path, and in the tests'
        # always-dispatch oracle (`_hints.enabled = False`).
        self.enabled = enabled
        self.entries: list = []  # HintEntry, MRU first

    @property
    def entry(self) -> Optional[HintEntry]:
        """The MRU entry or None — the 'is a hint live at all' view the
        scheduler's fast-path gates read."""
        return self.entries[0] if self.entries else None

    @entry.setter
    def entry(self, value: Optional[HintEntry]) -> None:
        self.entries = [] if value is None else [value]

    # -- counters -----------------------------------------------------------

    def _miss(self, reason: str) -> None:
        self.sched.hint_misses += 1
        self.sched.metrics.hint_cache_misses.inc(reason)

    def _hit(self, kind: str) -> None:
        self.sched.hint_hits += 1
        self.sched.metrics.hint_cache_hits.inc(kind)

    def _drop(self, e: HintEntry, reason: str) -> None:
        self.entries.remove(e)
        self.sched.hint_invalidations += 1
        self.sched.metrics.hint_cache_invalidations.inc(reason)

    def invalidate(self, reason: str) -> None:
        while self.entries:
            self._drop(self.entries[-1], reason)

    # -- lifecycle ----------------------------------------------------------

    def install(self, fw, head_pod, sig, nsig, plan, node_names,
                carry) -> None:
        if not self.enabled:
            return
        e = HintEntry.from_session(
            self.sched, fw, head_pod, sig, nsig, plan, node_names, carry)
        # Same-signature slots are superseded in place (the fresh carry IS
        # the newer truth for that shape); a genuinely new shape pushes the
        # coldest entry out. Surviving siblings ABSORB the device session
        # that just ended — its attempts bump and its committed placements
        # (every row's pod state from the fresh carry, which is cache truth
        # after a clean end: resync_rows) — or the attempts fence would read
        # every sibling as foreign next serve and alternating shapes would
        # thrash the cache one install per pod. unwinds/nomination fences
        # are deliberately NOT absorbed: a session that moved those leaves
        # the sibling stale, and the fence catches it.
        kept = []
        for x in self.entries:
            if x.keys & e.keys:
                continue
            if x.resync_rows(e):
                x.attempts = self.sched.attempts
                kept.append(x)
            else:
                self.sched.hint_invalidations += 1
                self.sched.metrics.hint_cache_invalidations.inc(
                    "cross_reencode")
        rows = sum(len(x.node_names) for x in kept)
        if kept:
            absorbed = self.sched.metrics.hint_sibling_absorbed
            absorbed.inc("siblings", value=len(kept))
            absorbed.inc("rows", value=rows)
        st = self.sched.stages.heard("plan.adopt")
        if st is not None:
            st.say(siblings=len(kept), sibling_rows=rows)
        self.entries = [e] + kept
        while len(self.entries) > HINT_LRU_SLOTS:
            self._drop(self.entries[-1], "lru_evict")

    def note_conflict(self, node: str) -> None:
        """Bind-409 on `node`: invalidate EVERY entry's view of that node
        ONLY. The conflict's unwind (forget_pod) is absorbed — its entire
        effect is on the blocked rows, which re-encode from cache truth
        when the winner's commit lands through the journal. An entry whose
        row set does not cover the node cannot absorb and is dropped."""
        for e in list(self.entries):
            if e.block_row(node):
                e.unwinds += 1
                self.sched.hint_invalidations += 1
                self.sched.metrics.hint_cache_invalidations.inc(
                    "bind_conflict")
            else:
                self._drop(e, "bind_conflict")

    def note_own_attempt(self, node: str = "",
                         served: Optional[HintEntry] = None) -> None:
        """One walker attempt just ran: absorb the scheduler attempt-
        counter bump on EVERY live entry (all watermarks stay current —
        without this, one entry serving would read as a foreign attempt to
        its siblings and evict them). A committed bind passes the landed
        `node`: non-serving entries re-encode that row from cache truth
        (the assumed pod is already in it) WITHOUT unblocking — a 409
        block must outlive a sibling's bind. A failed attempt passes
        node="" (the 409 path already blocked the row via note_conflict)."""
        if not self.entries:
            return
        cache = self.sched.cache
        for e in list(self.entries):
            e.attempts += 1
            if e is served or not node:
                continue
            if e._reencode_pod_row(cache, node, unblock=False) is not None:
                # The sibling's row set does not cover the landed node —
                # its world no longer matches the cluster's shape.
                self._drop(e, "cross_reencode")

    # -- serve --------------------------------------------------------------

    def serve(self, fw, pod) -> Optional[Tuple[HintEntry, str]]:
        """Validate the signature-matched entry against `pod` and the
        world; returns (entry, hit kind) when the hint path may bind this
        pod, else None (counted as a miss; stale entries are dropped +
        counted as invalidations). A served entry moves to the LRU head."""
        if not self.enabled:
            # The oracle's switch (`_hints.enabled = False`) must hold on
            # a WARM scheduler too: live entries installed before the flip
            # may not keep serving, or the dispatch-only baseline is
            # silently invalid.
            self.entries = []
            return None
        s = self.sched
        if not self.entries:
            self._miss("empty")
            return None
        if s.cache.affinity_pod_refs:
            # 0→1 affinity-pod transition: hints disabled cluster-wide
            # (labels/namespaces just became scheduling-relevant).
            self.invalidate("affinity_transition")
            self._miss("affinity_gate")
            return None
        sig = fw.sign_pod(pod)
        if sig is None:
            self._miss("unsignable")
            return None
        same_fw = [x for x in self.entries if id(fw) == x.fw_id]
        if not same_fw:
            self._miss("profile")
            return None
        # Exact key beats neutral ACROSS entries (single-entry semantics —
        # both keys lived on one entry — carried to the LRU); MRU order
        # breaks ties within a kind.
        e = kind = None
        for x in same_fw:
            if ("exact", sig) in x.keys:
                e, kind = x, "exact"
                break
        if e is None:
            nsig = s._neutral_sig(fw, pod, sig)
            for x in same_fw:
                if nsig is not None and ("neutral", nsig) in x.keys:
                    e, kind = x, "neutral"
                    break
        if e is None:
            self._miss("signature")
            return None
        if pod.volumes or getattr(pod, "resource_claims", None):
            self._miss("claims")
            return None
        if s._batch_supported_memo(pod, fw) is not None:
            self._miss("unsupported")
            return None
        if s.extenders and any(x.is_interested(pod) for x in s.extenders):
            self._miss("extender")
            return None
        if s.queue.nominator.version != e.nom_version:
            self._drop(e, "nomination")
            self._miss("stale")
            return None
        if s.attempts != e.attempts:
            # A scheduling attempt the walker did not make (host path,
            # device session, fall-through) moved cache state the journal
            # does not record (own binds are deliberately benign there —
            # sibling-entry serves are absorbed by note_own_attempt, so
            # only a genuinely foreign attempt lands here).
            self._drop(e, "foreign_attempt")
            self._miss("stale")
            return None
        if s.state_unwinds != e.unwinds:
            self._drop(e, "state_unwind")
            self._miss("stale")
            return None
        if s.cluster_event_seq != e.seq:
            events = s.journal.since(e.seq)
            if events is None:
                self._drop(e, "journal_gap")
                self._miss("stale")
                return None
            reason = e.consume(s, events)
            if reason is not None:
                self._drop(e, reason)
                self._miss("stale")
                return None
            e.seq = s.cluster_event_seq
        if self.entries[0] is not e:
            self.entries.remove(e)
            self.entries.insert(0, e)
        return e, kind
