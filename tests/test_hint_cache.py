"""Score-hint fast path (models/score_hints.py): churn-equivalence fuzz.

The hint cache binds identical replicas host-side with ZERO device
dispatches (ISSUE 12; KEP-5598 OpportunisticBatch, cross-cycle). The repo's
core invariant applies to it unchanged: hint-path placements must be
BIT-IDENTICAL to the always-dispatch oracle, under randomized journal event
streams interleaved with hint binds — node taint/allocatable churn, bound-
pod deletes, namespace sweeps, unschedulable floods, the 0→1 affinity-pod
transition (hints disabled cluster-wide, mirroring the watch plane's
selector gate), bind-409 single-node invalidation, and shard adoption
mid-stream. The hit counter is asserted > 0 throughout: equivalence with
the hint path demonstrably ENGAGED, not silently fallen through.

Also here: the requeue_conflict enqueued_at regression (conflict retries
must not restart the scheduler_e2e_scheduling_duration_seconds clock).
"""

import copy
import random

import pytest

from kubernetes_tpu.core.framework import Status
from kubernetes_tpu.core.scheduler import Scheduler
from kubernetes_tpu.models.tpu_scheduler import TPUScheduler
from kubernetes_tpu.testing.wrappers import make_node, make_pod


def _node(name, cpu=8, taint=None, pods=110):
    b = (make_node().name(name)
         .capacity({"cpu": cpu, "memory": "32Gi", "pods": pods})
         .zone(f"zone-{len(name) % 3}"))
    if taint:
        b = b.taint(*taint)
    return b.obj()


def _pod(name, ns="default", cpu="200m", labels=None, anti=None):
    b = make_pod().name(name).namespace(ns).req({"cpu": cpu,
                                                 "memory": "128Mi"})
    if labels:
        b = b.labels(dict(labels))
    if anti:
        b = b.pod_affinity("kubernetes.io/hostname", anti, anti=True)
    return b.obj()


def _pair(n_nodes=24, max_batch=64, oracle_hints=False, journal_cap=None):
    """(always-dispatch oracle, hint-enabled device scheduler) over
    identical clusters. The oracle is a TPUScheduler with the hint cache
    disabled — the exact code path every pod takes today. mesh=None keeps
    this suite on the single-device plane; mesh sessions install hints
    from the sharded carry too (one device→host gather — ROADMAP 12d,
    TestMeshAndLapWalk)."""
    oracle = TPUScheduler(max_batch=max_batch, mesh=None)
    oracle._hints.enabled = oracle_hints
    dev = TPUScheduler(max_batch=max_batch, mesh=None)
    assert dev._hints.enabled
    for s in (oracle, dev):
        if journal_cap:     # before the first record: a journal never shrinks
            s.journal.cap = journal_cap
        for i in range(n_nodes):
            s.clientset.create_node(_node(f"node-{i}"))
    return oracle, dev


def _assignments(s):
    return {f"{p.namespace}/{p.name}": p.node_name
            for p in s.clientset.pods.values()}


def _both(a, b, fn):
    fn(a)
    fn(b)
    a.run_until_idle()
    b.run_until_idle()


def _assert_identical(oracle, dev, ctx=""):
    ao, ad = _assignments(oracle), _assignments(dev)
    diffs = {k: (ao[k], ad.get(k)) for k in ao if ao[k] != ad.get(k)}
    assert not diffs, f"hint/oracle divergence {ctx}: {diffs}"


class TestHintFastPath:
    def test_identical_replicas_bind_without_dispatch(self):
        """The headline shape: after one seeding session, every identical
        replica binds via the hint — hit counter moves, dispatch counter
        does not, placements match the always-dispatch oracle."""
        oracle, dev = _pair()
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"seed-{i}")) for i in range(8)])
        assert dev._hints.entry is not None
        b0 = dev.device_batches
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"rep-{i}")) for i in range(40)])
        _assert_identical(oracle, dev)
        assert dev.hint_hits >= 40
        assert dev.device_batches == b0, "hint path still dispatched"
        assert dev.metrics.hint_cache_hits.value("exact") >= 40
        assert dev.metrics.hint_validation_duration.count() >= 40

    def test_neutral_signature_shares_hint_across_namespaces(self):
        """Replicas differing only in namespace/labels ride ONE hint (the
        namespace-erased neutral signature, PR 3's collapse)."""
        oracle, dev = _pair()
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"seed-{i}", ns="ns-a")) for i in range(6)])
        b0 = dev.device_batches
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"rep-{i}", ns=f"ns-{i % 5}",
                 labels={"app": f"dep-{i % 5}"})) for i in range(30)])
        _assert_identical(oracle, dev)
        assert dev.device_batches == b0
        assert dev.metrics.hint_cache_hits.value("neutral") > 0

    def test_infeasible_replica_falls_through_with_exact_diagnosis(self):
        """Capacity exhaustion mid-run: the hint walk reports -1 and the
        pod falls through to the normal path for the oracle's diagnosis;
        outcomes stay identical."""
        oracle, dev = _pair(n_nodes=3)
        # 3 nodes x 8 cpu; 2000m pods -> 12 fit, the rest are unschedulable.
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"seed-{i}", cpu="2000m")) for i in range(4)])
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"rep-{i}", cpu="2000m")) for i in range(12)])
        _assert_identical(oracle, dev)
        assert dev.hint_hits > 0
        assert dev.metrics.hint_cache_misses.value("infeasible") > 0
        # the unschedulable tail parked identically on both sides
        assert (len(oracle.queue.unschedulable)
                == len(dev.queue.unschedulable) > 0)


class TestHintFreshness:
    """The event-kind → hint-survival matrix (docs/PERF.md)."""

    def test_node_update_dirties_one_row_hint_survives(self):
        """A NoSchedule taint toggling on one node is an EV_NODE_UPDATE:
        the hint re-validates that ROW and keeps serving — no full
        invalidation, placements still oracle-identical."""
        oracle, dev = _pair()
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"seed-{i}")) for i in range(8)])
        assert dev._hints.entry is not None
        for rnd in range(3):
            def taint_step(s, rnd=rnd):
                s.clientset.update_node(_node(
                    f"node-{rnd}", taint=("maint", "", "NoSchedule")))
                for i in range(10):
                    s.clientset.create_pod(_pod(f"r{rnd}-{i}"))
            _both(oracle, dev, taint_step)
            def lift_step(s, rnd=rnd):
                s.clientset.update_node(_node(f"node-{rnd}"))
                for i in range(4):
                    s.clientset.create_pod(_pod(f"l{rnd}-{i}"))
            _both(oracle, dev, lift_step)
        _assert_identical(oracle, dev)
        assert dev._hints.entry is not None, "node_update killed the hint"
        assert dev.hint_hits >= 40

    def test_bound_pod_delete_reencodes_row(self):
        oracle, dev = _pair()
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"seed-{i}", cpu="1500m")) for i in range(10)])
        for rnd in range(3):
            def step(s, rnd=rnd):
                vs = sorted((p for p in s.clientset.pods.values()
                             if p.node_name), key=lambda p: p.name)
                s.clientset.delete_pod(vs[rnd])
                for i in range(6):
                    s.clientset.create_pod(_pod(f"r{rnd}-{i}", cpu="1500m"))
            _both(oracle, dev, step)
        _assert_identical(oracle, dev)
        assert dev.hint_hits > 0
        assert dev._hints.entry is not None

    def test_pns_taint_kills_hint(self):
        """A PreferNoSchedule taint appearing means the compiled no-PNS
        score path no longer matches the oracle: the hint must die and the
        normal path take over (still oracle-identical)."""
        oracle, dev = _pair()
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"seed-{i}")) for i in range(6)])
        assert dev._hints.entry is not None
        def step(s):
            s.clientset.update_node(_node(
                "node-1", taint=("soft", "", "PreferNoSchedule")))
            for i in range(10):
                s.clientset.create_pod(_pod(f"r-{i}"))
        _both(oracle, dev, step)
        _assert_identical(oracle, dev)
        assert dev.metrics.hint_cache_invalidations.value("pns_taint") == 1

    def test_structural_event_kills_hint(self):
        oracle, dev = _pair()
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"seed-{i}")) for i in range(6)])
        assert dev._hints.entry is not None
        def step(s):
            s.clientset.create_node(_node("node-new"))
            for i in range(10):
                s.clientset.create_pod(_pod(f"r-{i}"))
        _both(oracle, dev, step)
        _assert_identical(oracle, dev)
        assert dev.metrics.hint_cache_invalidations.value("structural") == 1

    def test_affinity_transition_disables_hints_cluster_wide(self):
        """0→1 affinity-pod transition: once ANY affinity-term pod is
        placed, labels/namespaces are scheduling-relevant — hints are
        disabled cluster-wide (the watch plane's selector-gate shape) and
        no new hint installs until the count drops back to zero."""
        oracle, dev = _pair()
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"seed-{i}", labels={"app": "web"})) for i in range(6)])
        assert dev._hints.entry is not None
        def step(s):
            s.clientset.create_pod(_pod("anti-0", labels={"app": "web"},
                                        anti={"app": "web"}))
            for i in range(10):
                s.clientset.create_pod(_pod(f"r-{i}", labels={"app": "web"}))
        _both(oracle, dev, step)
        _assert_identical(oracle, dev)
        assert dev._hints.entry is None
        assert dev.cache.affinity_pod_refs > 0
        # sessions while refs > 0 must NOT reinstall
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"r2-{i}", labels={"app": "web"})) for i in range(6)])
        assert dev._hints.entry is None
        _assert_identical(oracle, dev)

    def test_journal_gap_kills_hint(self):
        oracle, dev = _pair()
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"seed-{i}")) for i in range(6)])
        assert dev._hints.entry is not None
        # Overflow the journal window with queue-only records, then pop a
        # replica: since() returns None -> journal_gap invalidation.
        for _ in range(dev.journal.cap + 8):
            dev._record_event("queue", "x")
            oracle._record_event("queue", "x")
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"r-{i}")) for i in range(8)])
        _assert_identical(oracle, dev)
        assert dev.metrics.hint_cache_invalidations.value("journal_gap") == 1

    def test_foreign_attempt_kills_hint(self):
        """A pod the walker did not bind (different signature -> device
        session) moves state the journal does not record: the attempts
        fence must invalidate before the next hint serve."""
        oracle, dev = _pair()
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"seed-{i}")) for i in range(6)])
        entry0 = dev._hints.entry
        assert entry0 is not None
        def step(s):
            s.clientset.create_pod(_pod("big-0", cpu="900m"))
        _both(oracle, dev, step)
        # the big pod's own session replaced (or will replace) the entry;
        # serving the stale one must have been fenced, not reused
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"r-{i}")) for i in range(8)])
        _assert_identical(oracle, dev)


class TestBindConflict409:
    def test_conflict_invalidates_single_node_only(self):
        """A bind-409 blocks the hinted NODE; the hint survives, the loser
        re-enters through requeue_conflict, and the next identical pod
        picks a different node host-side."""
        _oracle, dev = _pair(n_nodes=8)
        for i in range(6):
            dev.clientset.create_pod(_pod(f"seed-{i}"))
        dev.run_until_idle()
        entry = dev._hints.entry
        assert entry is not None
        fw = next(iter(dev.profiles.values()))
        binder = fw.bind_plugins[0]
        real_bind = binder.bind
        fails = {"n": 0}
        def flaky_bind(state, pod, node_name, _rb=real_bind):
            if fails["n"] == 0:
                fails["n"] += 1
                st = Status.error(f"bind conflict: OutOfCapacity on "
                                  f"{node_name}")
                st.conflict = True
                flaky_bind.node = node_name
                return st
            return _rb(state, pod, node_name)
        binder.bind = flaky_bind
        try:
            for i in range(6):
                dev.clientset.create_pod(_pod(f"rep-{i}"))
            dev.run_until_idle()
            for _ in range(10):
                dev.process_async_api_errors()
                dev.run_until_idle()
        finally:
            binder.bind = real_bind
            dev.run_until_idle()
        # single-node invalidation: the entry survived, the conflicted row
        # is blocked, and later replicas still rode the hint
        assert dev._hints.entry is entry
        row = entry.row_of[flaky_bind.node]
        assert entry.blocked[row]
        assert not entry.ok[row]
        assert dev.metrics.hint_cache_invalidations.value(
            "bind_conflict") == 1
        assert dev.bind_conflicts == 1
        # every replica is bound exactly once despite the conflict
        bound = [p for p in dev.clientset.pods.values()
                 if p.name.startswith("rep-") and p.node_name]
        assert len(bound) == 6

    def test_async_conflict_takes_back_the_hint_hit(self):
        """Thread-mode binds commit optimistically: a LATER async 409 must
        take the counted hit back (hint_hits would otherwise exceed pods
        actually bound, HintHitRate > 1.0 on contended runs) — while a
        CONFIRMED bind settles the tag, so a later unrelated conflict for
        the same object never erases a real hit."""
        _oracle, dev = _pair(n_nodes=8)
        for i in range(6):
            dev.clientset.create_pod(_pod(f"seed-{i}"))
        dev.run_until_idle()
        p = _pod("rep-0")
        dev.clientset.create_pod(p)
        dev.run_until_idle()
        assert dev.hint_hits == 1
        node = p.node_name
        # the inline FakeClientset confirm settled the optimistic tag
        assert "_hint_bound" not in p.__dict__, "confirm left the tag live"

        class _E(Exception):
            code = 409

            def read(self):
                return b'{"error": "AlreadyBound"}'

        # a LATER conflict in this object's next life must NOT take back
        # the settled hit
        dev.handle.on_async_bind_error(p, _E())
        assert dev.hint_hits == 1, "settled hit was erased"
        # an UNSETTLED optimistic hit (409 arrives before any confirm —
        # the real async-conflict interleaving) is taken back
        p.__dict__["_hint_bound"] = True
        dev.handle.on_async_bind_error(p, _E())
        assert dev.hint_hits == 0, "async 409 left the optimistic hit"
        entry = dev._hints.entry
        assert entry is not None and entry.blocked[entry.row_of[node]]

    def test_permit_wait_park_is_not_a_hint_hit(self):
        """_commit returns True for a Permit-WAIT park, but the pod is
        assumed-unbound: the walker applies the placement (it occupies the
        node) WITHOUT counting a hit — hits count binds only."""
        from kubernetes_tpu.core.framework import OK, Status, WAIT
        from kubernetes_tpu.core.registry import build_framework

        class ParkNamed:
            name = "ParkNamed"

            def permit(self, state, pod, node_name):
                if pod.name == "waitme":
                    return Status(WAIT, ("parked",), self.name)
                return OK

        def factory(h):
            fw = build_framework(h)
            fw.permit_plugins.append(ParkNamed())
            return {"default-scheduler": fw}

        dev = TPUScheduler(max_batch=64, mesh=None,
                           profile_factory=factory)
        for i in range(8):
            dev.clientset.create_node(_node(f"node-{i}"))
        for i in range(6):
            dev.clientset.create_pod(_pod(f"seed-{i}"))
        dev.run_until_idle()
        assert dev._hints.entry is not None
        hits0 = dev.hint_hits
        dev.clientset.create_pod(_pod("waitme"))
        dev.run_until_idle()
        assert len(dev.waiting_pods) == 1
        assert dev.hint_hits == hits0, "a parked (unbound) pod was a hit"
        # the walker applied the park: allowing it binds on the hinted node
        uid = next(iter(dev.waiting_pods))
        assert dev.allow_waiting_pod(uid)
        bound = next(p for p in dev.clientset.pods.values()
                     if p.name == "waitme")
        assert bound.node_name

    def test_disabling_hints_stops_a_warm_entry(self):
        """The A/B seam (`_hints.enabled = False` after a wave installed
        an entry) must actually force the dispatch-only baseline."""
        _oracle, dev = _pair()
        for i in range(6):
            dev.clientset.create_pod(_pod(f"seed-{i}"))
        dev.run_until_idle()
        assert dev._hints.entry is not None
        dev._hints.enabled = False
        b0 = dev.device_batches
        for i in range(8):
            dev.clientset.create_pod(_pod(f"rep-{i}"))
        dev.run_until_idle()
        assert dev.hint_hits == 0, "disabled hint cache still served"
        assert dev._hints.entry is None
        assert dev.device_batches > b0, "replicas did not dispatch"

    def test_pod_event_on_blocked_row_unblocks_it(self):
        _oracle, dev = _pair(n_nodes=8)
        for i in range(6):
            dev.clientset.create_pod(_pod(f"seed-{i}"))
        dev.run_until_idle()
        entry = dev._hints.entry
        assert entry is not None
        node = entry.node_names[0]
        dev._note_bind_conflict("OutOfCapacity", _pod("x"), node)
        assert entry.blocked[entry.row_of[node]]
        # a foreign bind landing on that node re-encodes it from truth
        foreign = _pod("foreign-0")
        foreign.node_name = node
        dev.clientset.create_pod(foreign)
        dev.run_until_idle()
        for i in range(4):
            dev.clientset.create_pod(_pod(f"after-{i}"))
        dev.run_until_idle()
        if dev._hints.entry is entry:  # survived the replay
            assert not entry.blocked[entry.row_of[node]]


class TestMeshAndLapWalk:
    """ROADMAP 12a/12d: the lap-batched walk (one cumsum serves a lap of
    replicas) and mesh-session hint installs (the HintEntry fetches the
    per-node aggregates/score vector from the SHARDED carry via one
    device→host gather at clean session end)."""

    def test_mesh_session_installs_hint_from_sharded_carry(self):
        from kubernetes_tpu.parallel import make_mesh
        oracle = TPUScheduler(max_batch=64, mesh=None)
        oracle._hints.enabled = False
        dev = TPUScheduler(max_batch=64, mesh=make_mesh(n_cells=1))
        assert dev.mesh is not None and dev._hints.enabled
        for s in (oracle, dev):
            for i in range(24):
                s.clientset.create_node(_node(f"node-{i}"))
        proto = _pod("proto")
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            proto.clone_from_template(f"a-{i}")) for i in range(8)])
        # clean mesh session end → hint installed from the sharded carry
        assert dev._hints.entry is not None, (
            "mesh session did not install a score hint")
        batches0 = dev.device_batches
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            proto.clone_from_template(f"b-{i}")) for i in range(12)])
        _assert_identical(oracle, dev, "(mesh hint binds)")
        assert dev.hint_hits >= 12, dev.hint_hits
        assert dev.device_batches == batches0, (
            "hint-eligible replicas dispatched to the mesh anyway")

    def test_lap_batched_walk_is_bit_identical_and_engaged(self):
        """With adaptive-sampling truncation live (to_find << feasible),
        the walk precomputes a LAP of placements per cumsum — assert it
        demonstrably engages (lap_walks < hits) and stays bit-identical
        to the always-dispatch oracle."""
        oracle = TPUScheduler(max_batch=32, mesh=None)
        oracle._hints.enabled = False
        dev = TPUScheduler(max_batch=32, mesh=None)
        for s in (oracle, dev):
            s.percentage_of_nodes_to_score = 10  # to_find=20 at 200 nodes
            for i in range(200):
                s.clientset.create_node(_node(f"node-{i}"))
        proto = _pod("proto", cpu="100m")
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            proto.clone_from_template(f"a-{i}")) for i in range(8)])
        entry = dev._hints.entry
        assert entry is not None
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            proto.clone_from_template(f"b-{i}")) for i in range(60)])
        _assert_identical(oracle, dev, "(lap walk)")
        assert dev.hint_hits >= 60
        e = dev._hints.entry
        assert e is not None and e.lap_walks >= 1
        # batching engaged: far fewer full walks than pods served
        assert e.lap_walks * 2 <= dev.hint_hits, (
            e.lap_walks, dev.hint_hits)

    def test_without_truncation_the_walk_is_per_pod(self):
        """The default percentage_of_nodes_to_score finds at least 100
        feasible nodes: of 150 a lap would hold one window (L < 2), so each
        pod walks alone — no lap, still bit-identical."""
        oracle = TPUScheduler(max_batch=32, mesh=None)
        oracle._hints.enabled = False
        dev = TPUScheduler(max_batch=32, mesh=None)
        for s in (oracle, dev):
            for i in range(150):
                s.clientset.create_node(_node(f"node-{i}"))
        proto = _pod("proto", cpu="100m")
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            proto.clone_from_template(f"a-{i}")) for i in range(8)])
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            proto.clone_from_template(f"b-{i}")) for i in range(20)])
        _assert_identical(oracle, dev, "(per-pod walk)")
        e = dev._hints.entry
        assert e is not None and e.lap_walks == 0
        assert dev.hint_hits >= 20


class TestRequeueConflictEnqueuedAt:
    def test_async_conflict_requeue_preserves_enqueued_at(self):
        """Regression (ISSUE 12 satellite): the async bind-conflict path
        rebuilds a QueuedPodInfo from the bare Pod — it must carry the
        ORIGINAL queue-admission instant so the e2e histogram covers the
        whole conflict retry, not just the post-conflict leg."""
        s = Scheduler()
        s.clientset.create_node(_node("n-0"))
        p = _pod("victim")
        s.queue.add(p)
        qpi = s.queue.pop()
        orig = qpi.enqueued_at
        assert orig is not None
        s.queue.done(p.uid)
        # simulate the winning scheduler's raced bind: 409 on our async bind
        p.node_name = "n-0"
        s.cache.assume_pod(p, qpi.pod_info)

        class _E(Exception):
            code = 409

            def read(self):
                return b'{"error": "AlreadyBound"}'

        s.handle.on_async_bind_error(p, _E())
        requeued = (s.queue.backoff_q.get(p.uid)
                    or s.queue.active_q.get(p.uid))
        assert requeued is not None
        assert requeued.enqueued_at == orig, (
            "conflict requeue restarted the e2e clock")

    def test_sync_conflict_requeue_preserves_enqueued_at(self):
        """The sync path passes the original qpi through requeue_conflict —
        pin that it keeps enqueued_at while resetting the backoff stamp."""
        s = Scheduler()
        p = _pod("victim")
        s.queue.add(p)
        qpi = s.queue.pop()
        orig = qpi.enqueued_at
        s.queue.done(p.uid)
        s.queue.requeue_conflict(qpi)
        got = s.queue.backoff_q.get(p.uid) or s.queue.active_q.get(p.uid)
        assert got is qpi and got.enqueued_at == orig


class TestShardAdoptionMidStream:
    def test_adoption_admits_pods_into_live_hint_run(self):
        """Shard adoption mid-stream: pods initially outside this
        scheduler's admission predicate join the queue later (the
        sweep_pending shape). They must ride the live hint and land
        exactly where the oracle puts them."""
        oracle, dev = _pair()
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"seed-{i}")) for i in range(6)])
        assert dev._hints.entry is not None
        # attach an admission predicate rejecting the adopted range
        rejected = set()
        def admit(pod):
            return pod.name not in rejected
        for s in (oracle, dev):
            s.pod_admission = admit
        rejected.update(f"adopt-{i}" for i in range(10))
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"adopt-{i}")) for i in range(10)])
        assert not any(p.node_name for p in dev.clientset.pods.values()
                       if p.name.startswith("adopt-"))
        # ownership grows: admit and sweep (queue-only — the hint survives)
        rejected.clear()
        def sweep(s):
            for p in s.clientset.pods.values():
                if (p.name.startswith("adopt-") and not p.node_name
                        and not s.queue.has_entity(p.uid)):
                    s.queue.add(p)
        _both(oracle, dev, sweep)
        _assert_identical(oracle, dev)
        assert all(p.node_name for p in dev.clientset.pods.values()
                   if p.name.startswith("adopt-"))
        assert dev.hint_hits > 0


@pytest.mark.parametrize("seed", [3, 11, 42])
def test_churn_equivalence_fuzz(seed):
    """Randomized journal event streams interleaved with hint-path binds:
    placements bit-identical to the always-dispatch oracle, hint path
    demonstrably engaged (hit counter > 0)."""
    rng = random.Random(seed)
    oracle, dev = _pair()
    _both(oracle, dev, lambda s: [s.clientset.create_pod(
        _pod(f"seed-{i}")) for i in range(8)])
    tainted = {}
    wave = 0
    for rnd in range(12):
        action = rng.choice(
            ["replicas", "replicas", "replicas", "taint", "lift",
             "drift", "delete_bound", "namespace", "flood", "ns_sweep"])
        if action == "replicas":
            n = rng.randrange(1, 12)
            wave += 1
            _both(oracle, dev, lambda s, n=n, w=wave: [
                s.clientset.create_pod(_pod(f"w{w}-{i}"))
                for i in range(n)])
        elif action == "taint":
            i = rng.randrange(24)
            tainted[i] = ("maint", "", "NoSchedule")
            _both(oracle, dev, lambda s, i=i: s.clientset.update_node(
                _node(f"node-{i}", taint=tainted[i])))
        elif action == "lift":
            if tainted:
                i = rng.choice(list(tainted))
                del tainted[i]
                _both(oracle, dev, lambda s, i=i: s.clientset.update_node(
                    _node(f"node-{i}")))
        elif action == "drift":
            i = rng.randrange(24)
            cpu = rng.choice([6, 8, 10])
            _both(oracle, dev, lambda s, i=i, cpu=cpu:
                  s.clientset.update_node(
                      _node(f"node-{i}", cpu=cpu,
                            taint=tainted.get(i))))
        elif action == "delete_bound":
            def step(s):
                vs = sorted((p for p in s.clientset.pods.values()
                             if p.node_name), key=lambda p: p.name)
                if vs:
                    s.clientset.delete_pod(vs[0])
            _both(oracle, dev, step)
        elif action == "namespace":
            from kubernetes_tpu.api.types import Namespace
            _both(oracle, dev, lambda s, r=rnd: s.clientset.create_namespace(
                Namespace(name=f"fuzz-ns-{r}", labels={"round": str(r)})))
        elif action == "flood":
            wave += 1
            _both(oracle, dev, lambda s, w=wave: [
                s.clientset.create_pod(_pod(f"big{w}-{i}", cpu="32000m"))
                for i in range(2)])
        elif action == "ns_sweep":
            n = rng.randrange(2, 8)
            wave += 1
            _both(oracle, dev, lambda s, n=n, w=wave: [
                s.clientset.create_pod(
                    _pod(f"ns{w}-{i}", ns=f"ns-{i % 3}"))
                for i in range(n)])
    _assert_identical(oracle, dev, ctx=f"(seed {seed})")
    assert dev.hint_hits > 0, "fuzz never engaged the hint path"


class TestHintLru:
    """The 2-way signature-keyed LRU (ISSUE 19 satellite): alternating
    deployment shapes keep BOTH on the host path. Exactness is
    non-negotiable — every scenario holds the always-dispatch oracle
    equivalence."""

    def test_two_shapes_alternate_without_thrash(self):
        """Two replica shapes interleaving through one queue bind with
        ZERO device dispatches after seeding — the single-slot cache would
        thrash (each shape evicting the other every pod). Cross-entry
        coherence rides along: both entries place onto the SAME nodes, so
        any stale sibling row would diverge from the oracle here."""
        oracle, dev = _pair()
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"seed-a-{i}", cpu="200m")) for i in range(6)])
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"seed-b-{i}", cpu="400m")) for i in range(6)])
        assert len(dev._hints.entries) == 2
        b0, h0 = dev.device_batches, dev.hint_hits
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"alt-{i}", cpu=("200m" if i % 2 == 0 else "400m")))
            for i in range(40)])
        _assert_identical(oracle, dev)
        assert dev.device_batches == b0, "alternating shapes thrashed"
        assert dev.hint_hits - h0 >= 40

    def test_third_shape_evicts_coldest(self):
        """At capacity 2 a third shape pushes out the least-recently-used
        entry; the two survivors keep serving dispatch-free."""
        oracle, dev = _pair()
        for shape, cpu in (("a", "200m"), ("b", "400m"), ("c", "600m")):
            _both(oracle, dev, lambda s, shape=shape, cpu=cpu: [
                s.clientset.create_pod(_pod(f"seed-{shape}-{i}", cpu=cpu))
                for i in range(6)])
        assert len(dev._hints.entries) == 2
        assert dev.metrics.hint_cache_invalidations.value("lru_evict") >= 1
        b0 = dev.device_batches
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"rep-c-{i}", cpu="600m")) for i in range(10)])
        _assert_identical(oracle, dev)
        assert dev.device_batches == b0

    def test_conflict_blocks_row_on_every_entry(self):
        """Bind-409 semantics under the LRU: the conflicted NODE is blocked
        on every live entry (each one's view understates the winner's
        usage), and every entry survives with just that row fenced."""
        oracle, dev = _pair()
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"seed-a-{i}", cpu="200m")) for i in range(6)])
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"seed-b-{i}", cpu="400m")) for i in range(6)])
        es = list(dev._hints.entries)
        assert len(es) == 2
        dev._hints.note_conflict("node-3")
        assert len(dev._hints.entries) == 2
        for e in es:
            row = e.row_of["node-3"]
            assert e.blocked[row] and not e.ok[row]


_DYNAMIC_LANES = ("req_r", "nonzero", "pod_count")
_VERDICT_LANES = ("fit_ok", "fit_sc", "ba", "total", "ok")


def _old_scalar_pass(entry, cache):
    """What `HintEntry.resync_rows` did until PR 41, restated: the journal's
    pod re-encode from cache truth (`NodeInfo.requested`, the pods it
    holds) on every row by name, a scalar `_reval_row` each, blocked rows
    kept blocked. On a copy, so the entry under test stays as it is."""
    want = copy.copy(entry)
    for lane in _DYNAMIC_LANES + _VERDICT_LANES + ("blocked",):
        setattr(want, lane, getattr(entry, lane).copy())
    for name in want.node_names:
        assert want._reencode_pod_row(cache, name, unblock=False) is None
    return want


def _check_siblings_at_install(dev):
    """Wrap the hint install: after each, every entry behind the fresh one
    (a sibling that absorbed the session) is held, lane by lane, to the old
    scalar pass over cache truth. Returns the list of rows checked."""
    checked = []
    install = dev._hints.install

    def checking(*args, **kwargs):
        install(*args, **kwargs)
        for x in dev._hints.entries[1:]:
            want = _old_scalar_pass(x, dev.cache)
            for lane in _DYNAMIC_LANES + _VERDICT_LANES + ("blocked",):
                got = getattr(x, lane)
                assert got.dtype == getattr(want, lane).dtype, lane
                assert (got == getattr(want, lane)).all(), (
                    f"sibling lane {lane} is not cache truth after install "
                    f"{len(checked)}")
            assert x.attempts == dev.attempts and x._pending == []
            checked.append(len(x.node_names))

    dev._hints.install = checking
    return checked


class TestSiblingAbsorbsSession:
    """A clean device session of one template ends while the hint of ANOTHER
    is live (two Deployments through one queue): the sibling takes every
    row's pod state from the session's carry and re-evaluates all rows in
    one array pass (ISSUE 41). It must then serve exactly as one re-encoded
    row by row from cache truth would."""

    A, B = "200m", "400m"

    def _seed_two(self, n_nodes=32, journal_cap=None):
        oracle, dev = _pair(n_nodes=n_nodes, journal_cap=journal_cap)
        checked = _check_siblings_at_install(dev)
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"seed-a-{i}", cpu=self.A)) for i in range(12)])
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"seed-b-{i}", cpu=self.B)) for i in range(12)])
        assert len(dev._hints.entries) == 2 and checked == [n_nodes]
        return oracle, dev, checked

    def test_sessions_take_turns_and_the_sibling_serves(self):
        """Deletes that outrun the journal void the hint that is served
        (and only that one: `serve` validates the matching entry), so each
        template's device sessions take turns with the other's hint live."""
        oracle, dev, checked = self._seed_two(journal_cap=16)
        hits = {"a": 0, "b": 0}
        for rnd in range(3):
            def delete_oldest(s):
                bound = sorted((p for p in s.clientset.pods.values()
                                if p.node_name), key=lambda p: p.name)
                for p in bound[:20]:
                    s.clientset.delete_pod(p)
            _both(oracle, dev, delete_oldest)
            for shape, cpu in (("a", self.A), ("b", self.B)):
                # the served entry meets the journal's gap: a device session,
                # and at its end the OTHER template's entry absorbs it
                b0, n0 = dev.device_batches, len(checked)
                _both(oracle, dev, lambda s, shape=shape, cpu=cpu: [
                    s.clientset.create_pod(
                        _pod(f"r{rnd}-{shape}-{i}", cpu=cpu))
                    for i in range(10)])
                assert dev.device_batches == b0 + 1
                assert len(checked) == n0 + 1, "no sibling absorbed"
            # B's session just ended: A is the SIBLING now, and serves
            for shape, cpu in (("a", self.A), ("b", self.B)):
                b0, h0 = dev.device_batches, dev.hint_hits
                _both(oracle, dev, lambda s, shape=shape, cpu=cpu: [
                    s.clientset.create_pod(
                        _pod(f"h{rnd}-{shape}-{i}", cpu=cpu))
                    for i in range(6)])
                assert dev.device_batches == b0, f"{shape} was dispatched"
                hits[shape] += dev.hint_hits - h0
            _assert_identical(oracle, dev, ctx=f"(round {rnd})")
        assert hits == {"a": 18, "b": 18}
        absorbed = dev.metrics.hint_sibling_absorbed
        assert absorbed.value("siblings") == len(checked) == 7
        assert absorbed.value("rows") == sum(checked) == 7 * 32
        assert dev.metrics.hint_cache_invalidations.value(
            "cross_reencode") == 0

    @pytest.mark.parametrize("event", ["node_added", "node_removed"])
    def test_a_sibling_of_another_row_set_is_dropped_at_install(self, event):
        """A node comes or goes between the sibling's capture and the next
        session's end: its rows are not the session's, and it is dropped
        where the scalar pass either kept it for the journal's structural
        record to end at its next serve (an add) or dropped it too (a
        remove). Either way it never serves a row again."""
        oracle, dev, checked = self._seed_two()
        if event == "node_added":
            _both(oracle, dev, lambda s: s.clientset.create_node(
                _node("node-late")))
        else:
            _both(oracle, dev, lambda s: s.clientset.delete_node("node-31"))
        sibling = next(e for e in dev._hints.entries
                       if e.pod.name.startswith("seed-b"))
        b0, inv0 = dev.device_batches, dev.hint_invalidations
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"after-a-{i}", cpu=self.A)) for i in range(8)])
        # A met the structural record at its serve and ran a session; at
        # its end B's rows (the old cluster's) were not the session's
        assert dev.device_batches == b0 + 1
        assert sibling not in dev._hints.entries
        assert len(dev._hints.entries) == 1 and len(checked) == 1
        inv = dev.metrics.hint_cache_invalidations
        assert inv.value("structural") == 1 and inv.value(
            "cross_reencode") == 1
        assert dev.hint_invalidations == inv0 + 2
        # B's next pods are a session's, not the dropped hint's
        h0 = dev.hint_hits
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"after-b-{i}", cpu=self.B)) for i in range(8)])
        assert dev.device_batches == b0 + 2
        assert dev.hint_hits - h0 < 8
        # ... at whose end A, captured on the same rows, absorbs it
        assert len(checked) == 2 and len(dev._hints.entries) == 2
        _both(oracle, dev, lambda s: [s.clientset.create_pod(
            _pod(f"last-{i}", cpu=(self.A if i % 2 else self.B)))
            for i in range(12)])
        assert dev.device_batches == b0 + 2
        _assert_identical(oracle, dev)

    def test_a_blocked_row_stays_blocked_through_an_absorbed_session(self):
        _oracle, dev, checked = self._seed_two(journal_cap=16)
        sibling = next(e for e in dev._hints.entries
                       if e.pod.name.startswith("seed-b"))
        node = "node-3"
        row = sibling.row_of[node]
        dev._note_bind_conflict("OutOfCapacity", _pod("x"), node)
        assert sibling.blocked[row] and not sibling.ok[row]
        # A's hint meets a journal gap, A runs a session, B absorbs it
        for _ in range(dev.journal.cap + 8):
            dev._record_event("queue", "x")
        for i in range(8):
            dev.clientset.create_pod(_pod(f"after-a-{i}", cpu=self.A))
        dev.run_until_idle()
        assert len(checked) == 2 and sibling in dev._hints.entries
        assert sibling.blocked[row] and not sibling.ok[row]
        assert sibling.blocked.sum() == 1
        # and the fresh entry knows of no conflict
        assert not dev._hints.entries[0].blocked.any()
