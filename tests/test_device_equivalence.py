"""Device↔host equivalence: the TPU batch kernel must produce IDENTICAL
pod→node assignments to the host-oracle sequential scheduler on randomized
cluster states (SURVEY.md §4 'device/host equivalence suite'; the
"identical pod→node assignments" requirement in BASELINE.json).

Both paths run with deterministic_ties so reservoir tie-breaking can't
diverge; everything else — adaptive sampling, rotation, integer score math —
must line up exactly.
"""

import random

import pytest

from kubernetes_tpu.core.scheduler import Scheduler
from kubernetes_tpu.models.tpu_scheduler import TPUScheduler
from kubernetes_tpu.testing.wrappers import make_node, make_pod

ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"


def _mk_cluster(sched, n_nodes, seed=0, zones=4, taint_frac=0.0, unsched_frac=0.0):
    rng = random.Random(seed)
    for i in range(n_nodes):
        b = (make_node().name(f"node-{i}")
             .capacity({"cpu": rng.choice([2, 4, 8, 16]),
                        "memory": f"{rng.choice([4, 8, 16, 32])}Gi",
                        "pods": 110})
             .zone(f"zone-{i % zones}")
             .label("disk", rng.choice(["ssd", "hdd"])))
        if taint_frac and rng.random() < taint_frac:
            b = b.taint("dedicated", "infra", "NoSchedule")
        if unsched_frac and rng.random() < unsched_frac:
            b = b.unschedulable()
        sched.clientset.create_node(b.obj())


def _assignments(sched):
    return {p.name: p.node_name for p in sched.clientset.pods.values()}


def _run_pair(n_nodes, pods_fn, seed=0, cluster=_mk_cluster, **cluster_kw):
    host = Scheduler(deterministic_ties=True)
    dev = TPUScheduler()
    cluster(host, n_nodes, seed=seed, **cluster_kw)
    cluster(dev, n_nodes, seed=seed, **cluster_kw)
    for p in pods_fn():
        host.clientset.create_pod(p)
    for p in pods_fn():
        dev.clientset.create_pod(p)
    host.run_until_idle()
    dev.run_until_idle()
    a_host = _assignments(host)
    a_dev = _assignments(dev)
    diffs = {k: (a_host[k], a_dev.get(k)) for k in a_host if a_host[k] != a_dev.get(k)}
    assert not diffs, f"host/device assignment divergence: {diffs}"
    return host, dev


def _basic_pods(n, cpu="500m", mem="256Mi", labels=None, build=None):
    def fn():
        pods = []
        for i in range(n):
            b = make_pod().name(f"pod-{i}").req({"cpu": cpu, "memory": mem})
            if labels:
                b = b.labels(dict(labels))
            if build:
                b = build(b)
            pods.append(b.obj())
        return pods
    return fn


# -- pods whose NodeAffinity PreFilterResult narrows ---------------------------

def _equal_cluster(sched, n_nodes, seed=0, pods=110):
    """Nodes that tie on every score, so the rotating start index decides."""
    for i in range(n_nodes):
        sched.clientset.create_node(
            make_node().name(f"node-{i}")
            .capacity({"cpu": 8, "memory": "16Gi", "pods": pods})
            .label("disk", "ssd" if i % 2 else "hdd").obj())


def _pinned(name, nodes, cpu="100m", expressions=(), more_terms=()):
    from kubernetes_tpu.api.labels import IN, Requirement
    from kubernetes_tpu.api.types import (Affinity, NodeAffinity as NA,
                                          NodeSelector, NodeSelectorTerm)
    p = make_pod().name(name).req({"cpu": cpu}).obj()
    term = NodeSelectorTerm(
        match_expressions=tuple(expressions),
        match_fields=(Requirement("metadata.name", IN, tuple(nodes)),))
    p.affinity = Affinity(node_affinity=NA(
        required=NodeSelector((term,) + tuple(more_terms))))
    return p


def _case_one_node():
    return 12, lambda: [_pinned(f"ds-{i}", ["node-5"]) for i in range(30)], \
        _mk_cluster, 30, 30


def _case_four_nodes():
    names = ["node-9", "node-2", "node-7", "node-4"]
    return 12, lambda: [_pinned(f"ds-{i}", names) for i in range(30)], \
        _equal_cluster, 30, 30


def _case_120_nodes_the_sample_is_cut():
    # 120 named nodes: the sample is 100 of them, so the window moves on
    names = [f"node-{i}" for i in range(5, 125)]
    return 130, lambda: [_pinned(f"ds-{i}", names) for i in range(12)], \
        _equal_cluster, 12, 12


def _case_a_node_that_does_not_exist():
    def pods():
        return ([_pinned(f"lost-{i}", ["node-404"]) for i in range(3)]
                + [make_pod().name(f"plain-{i}").req({"cpu": "100m"}).obj()
                   for i in range(4)])
    return 12, pods, _equal_cluster, 4, 3


def _case_a_full_node():
    def cluster(sched, n_nodes, seed=0):
        _equal_cluster(sched, n_nodes, pods=2)
    return 6, lambda: [_pinned(f"ds-{i}", ["node-3"]) for i in range(4)], \
        cluster, 2, 4


def _case_a_term_without_match_fields_narrows_nothing():
    from kubernetes_tpu.api.labels import IN, Requirement
    from kubernetes_tpu.api.types import NodeSelectorTerm
    other = NodeSelectorTerm(match_expressions=(
        Requirement("disk", IN, ("ssd",)),))
    return 12, lambda: [_pinned(f"p-{i}", ["node-2"], more_terms=(other,))
                        for i in range(20)], _equal_cluster, 20, 0


def _case_match_fields_with_match_expressions():
    from kubernetes_tpu.api.labels import IN, Requirement
    ssd = (Requirement("disk", IN, ("ssd",)),)  # the odd nodes
    names = ["node-1", "node-2", "node-3", "node-6"]
    return 12, lambda: [_pinned(f"p-{i}", names, expressions=ssd)
                        for i in range(16)], _equal_cluster, 16, 16


def _case_pinned_and_plain_pods_take_turns():
    # the plain pods' nodes are the test of the start index a pinned pod
    # leaves behind: every node ties, the first in walk order wins
    def pods():
        out = []
        for i in range(24):
            if i % 3 == 2:
                out.append(_pinned(f"ds-{i}", ["node-3", "node-8", "node-10"]))
            else:
                out.append(make_pod().name(f"plain-{i}")
                           .req({"cpu": "100m"}).obj())
        return out
    return 12, pods, _equal_cluster, 24, 8


def _case_a_nominated_pinned_pod():
    # node-3 is kept full by low-priority pods; the pinned pod of priority
    # 10 evicts its way in, is nominated there, and binds at its retry
    def pods():
        fill = [make_pod().name(f"fill-{i}").req({"cpu": "3500m"})
                .priority(-10).obj() for i in range(2)]
        for p in fill:
            p.node_name = "node-3"
        pre = _pinned("pre", ["node-3"], cpu="6")
        pre.priority = 10
        return fill + [pre]
    # (the retry's answer comes from the nominated node before the
    # narrowing is looked at, on both paths: one narrowed attempt)
    return 6, pods, _equal_cluster, 1, 1


_NARROWED_CASES = {
    f.__name__[len("_case_"):]: f for f in (
        _case_one_node, _case_four_nodes, _case_120_nodes_the_sample_is_cut,
        _case_a_node_that_does_not_exist, _case_a_full_node,
        _case_a_term_without_match_fields_narrows_nothing,
        _case_match_fields_with_match_expressions,
        _case_pinned_and_plain_pods_take_turns,
        _case_a_nominated_pinned_pod)}


class TestFitEquivalence:
    def test_basic_fit_least_allocated(self):
        host, dev = _run_pair(23, _basic_pods(40))
        assert dev.device_scheduled == 40
        assert dev.host_path_pods == 0

    def test_fill_until_infeasible(self):
        # More pods than capacity: both paths must fail the same pods.
        host, dev = _run_pair(5, _basic_pods(30, cpu="2"))
        assert host.scheduled == dev.scheduled
        assert host.failures > 0

    def test_sampling_truncation_rotation(self):
        # >100 nodes triggers numFeasibleNodesToFind truncation + rotation.
        _run_pair(140, _basic_pods(60))

    def test_zero_request_pods(self):
        _run_pair(9, _basic_pods(12, cpu="0", mem="0"))


class TestTaintEquivalence:
    def test_taints_reject(self):
        _run_pair(16, _basic_pods(20), taint_frac=0.5)

    def test_tolerated_taints(self):
        _run_pair(16, _basic_pods(
            20, build=lambda b: b.toleration("dedicated", "infra", "Equal", "NoSchedule")),
            taint_frac=0.5)

    def test_unschedulable_nodes(self):
        _run_pair(16, _basic_pods(20), unsched_frac=0.3)


class TestSelectorEquivalence:
    def test_node_selector(self):
        _run_pair(20, _basic_pods(15, build=lambda b: b.node_selector({"disk": "ssd"})))

    def test_node_name_pin(self):
        def fn():
            return [make_pod().name(f"pin-{i}").req({"cpu": "100m"})
                    .node(f"node-{i % 3}").obj() for i in range(6)]
        _run_pair(8, fn)


class TestSpreadEquivalence:
    def test_do_not_schedule_spread(self):
        _run_pair(12, _basic_pods(
            24, labels={"app": "web"},
            build=lambda b: b.spread_constraint(1, ZONE, "DoNotSchedule", {"app": "web"})))

    def test_schedule_anyway_spread_scoring(self):
        _run_pair(10, _basic_pods(
            20, labels={"app": "api"},
            build=lambda b: b.spread_constraint(1, ZONE, "ScheduleAnyway", {"app": "api"})))

    def test_hostname_spread(self):
        _run_pair(7, _basic_pods(
            14, labels={"app": "db"},
            build=lambda b: b.spread_constraint(2, HOSTNAME, "DoNotSchedule", {"app": "db"})))


class TestAffinityEquivalence:
    def test_required_anti_affinity(self):
        _run_pair(10, _basic_pods(
            8, labels={"app": "solo"},
            build=lambda b: b.pod_affinity(HOSTNAME, {"app": "solo"}, anti=True)))

    def test_required_affinity_bootstrap(self):
        _run_pair(12, _basic_pods(
            9, labels={"app": "pack"},
            build=lambda b: b.pod_affinity(ZONE, {"app": "pack"})))

    def test_preferred_anti_affinity_scoring(self):
        _run_pair(8, _basic_pods(
            16, labels={"app": "spread-me"},
            build=lambda b: b.pod_affinity(ZONE, {"app": "spread-me"}, anti=True, weight=10)))


    def test_preferred_affinity_on_four_node_sizes(self):
        """The benchmark's `prefaffinity-pools-5k` at toy size: one node of
        each pool's allocatable, its pods (100m / 500Mi, one preferred
        hostname term of weight 1 over both namespaces), 29 bound on the
        third node and 30 on the fourth. The fourth fills by pod count (its
        raw score passes 100 and 200 while the third's stands at 58: the
        pairs (58, 100) and (58, 200), where scoring.go's float form reads
        one under an integer floor), then the third by pod count, then the
        second by memory at 58 pods, and the first takes the rest."""
        import dataclasses
        shapes = [("3920m", "13621Mi"), ("7910m", "29022Mi"),
                  ("15890m", "59824Mi"), ("31850m", "121428Mi")]

        def cluster(sched, n_nodes, seed=0):
            for i, (cpu, mem) in enumerate(shapes[:n_nodes]):
                sched.clientset.create_node(
                    make_node().name(f"node-{i}")
                    .capacity({"cpu": cpu, "memory": mem, "pods": 110}).obj())

        def pod(name, namespace, node=None):
            b = (make_pod().name(name).namespace(namespace)
                 .req({"cpu": "100m", "memory": "500Mi"})
                 .labels({"color": "red"})
                 .pod_affinity(HOSTNAME, {"color": "red"}, weight=1))
            if node:
                b = b.node(node)
            p = b.obj()
            aff = p.affinity.pod_affinity
            last = aff.preferred[-1]
            last = dataclasses.replace(last, term=dataclasses.replace(
                last.term, namespaces=("sched-1", "sched-0")))
            p.affinity = dataclasses.replace(
                p.affinity, pod_affinity=dataclasses.replace(
                    aff, preferred=aff.preferred[:-1] + (last,)))
            return p

        def pods():
            return ([pod(f"init-{i}", "sched-0", "node-2") for i in range(29)]
                    + [pod(f"init-{29 + i}", "sched-0", "node-3")
                       for i in range(30)]
                    + [pod(f"pod-{i}", "sched-1") for i in range(240)])

        host, dev = _run_pair(4, pods, cluster=cluster)
        held = {}
        for p in dev.clientset.pods.values():
            held[p.node_name] = held.get(p.node_name, 0) + 1
        assert held == {"node-3": 110, "node-2": 110, "node-1": 58,
                        "node-0": 21}
        assert dev.host_path_pods == 0
        assert dev.metrics.device_batches.value("scan_normalised") >= 1
        assert len(dev.mirror.shapes) == 4


class TestMixedWorkload:
    def test_mixed_signatures(self):
        """Multiple interleaved deployments → multiple batches per run."""
        def fn():
            pods = []
            for i in range(10):
                pods.append(make_pod().name(f"a-{i}").req({"cpu": "250m", "memory": "128Mi"})
                            .labels({"app": "a"})
                            .spread_constraint(1, ZONE, "DoNotSchedule", {"app": "a"}).obj())
            for i in range(10):
                pods.append(make_pod().name(f"b-{i}").req({"cpu": "1", "memory": "1Gi"})
                            .labels({"app": "b"}).obj())
            for i in range(5):
                pods.append(make_pod().name(f"c-{i}").labels({"app": "c"}).obj())
            return pods
        host, dev = _run_pair(15, fn)
        assert dev.device_batches >= 3


class TestFuzzEquivalence:
    @pytest.mark.parametrize("seed", range(6))
    def test_randomized_clusters(self, seed):
        rng = random.Random(1000 + seed)
        n_nodes = rng.randint(4, 60)

        def fn():
            rng2 = random.Random(2000 + seed)
            pods = []
            n_deploys = rng2.randint(1, 4)
            for d in range(n_deploys):
                n = rng2.randint(1, 12)
                cpu = rng2.choice(["100m", "250m", "1", "2"])
                mem = rng2.choice(["64Mi", "512Mi", "2Gi"])
                labels = {"app": f"d{d}"}
                r = rng2.random()
                for i in range(n):
                    b = (make_pod().name(f"d{d}-{i}")
                         .req({"cpu": cpu, "memory": mem}).labels(dict(labels)))
                    if r < 0.3:
                        b = b.spread_constraint(
                            rng2.choice([1, 2]), ZONE,
                            rng2.choice(["DoNotSchedule", "ScheduleAnyway"]), labels)
                    elif r < 0.5:
                        b = b.pod_affinity(HOSTNAME, labels, anti=True)
                    elif r < 0.6:
                        b = b.node_selector({"disk": "ssd"})
                    pods.append(b.obj())
            return pods

        _run_pair(n_nodes, fn, seed=seed, taint_frac=0.2, unsched_frac=0.1)


class TestSessionEquivalence:
    """The chained-carry session + lap-vectorized kernel against the host
    oracle at scales where adaptive sampling makes multi-pod laps (L>1) and
    multiple chained batches."""

    def test_multi_lap_scale(self):
        # 600 nodes → to_find=max(600*45//100,100)=270 → L=2 laps; enough
        # pods for several chained batches at max_batch=64.
        host = Scheduler(deterministic_ties=True)
        dev = TPUScheduler(max_batch=64)
        _mk_cluster(host, 600, seed=7)
        _mk_cluster(dev, 600, seed=7)
        for s in (host, dev):
            for p in _basic_pods(300, cpu="250m", mem="128Mi")():
                s.clientset.create_pod(p)
        host.run_until_idle()
        dev.run_until_idle()
        a_host, a_dev = _assignments(host), _assignments(dev)
        diffs = {k: (a_host[k], a_dev.get(k)) for k in a_host if a_host[k] != a_dev.get(k)}
        assert not diffs, f"divergence ({len(diffs)}): {dict(list(diffs.items())[:5])}"
        assert dev.device_batches >= 4
        assert dev.host_path_pods == 0

    def test_lap_boundary_with_infeasible_rows(self):
        # Tight capacities make nodes fill mid-session: feasibility flips
        # inside laps, exercising window-boundary recomputation.
        host = Scheduler(deterministic_ties=True)
        dev = TPUScheduler(max_batch=32)
        for s in (host, dev):
            for i in range(150):
                s.clientset.create_node(
                    make_node().name(f"node-{i}")
                    .capacity({"cpu": 1, "memory": "1Gi", "pods": 3})
                    .zone(f"zone-{i % 3}").obj())
            for p in _basic_pods(260, cpu="300m", mem="300Mi")():
                s.clientset.create_pod(p)
        host.run_until_idle()
        dev.run_until_idle()
        a_host, a_dev = _assignments(host), _assignments(dev)
        assert a_host == a_dev
        assert host.scheduled == dev.scheduled

    def test_churn_between_runs_invalidates_session(self):
        # Node add mid-workload: the session must abandon the device carry
        # (cluster_event_seq) and still match a host run seeing the same
        # sequence.
        host = Scheduler(deterministic_ties=True)
        dev = TPUScheduler(max_batch=16)
        for s in (host, dev):
            for i in range(120):
                s.clientset.create_node(
                    make_node().name(f"node-{i}").capacity({"cpu": 8, "pods": 20})
                    .zone(f"zone-{i % 4}").obj())
            for p in _basic_pods(48)():
                s.clientset.create_pod(p)
            s.run_until_idle()
            # churn: new node + another wave
            s.clientset.create_node(
                make_node().name("late-node").capacity({"cpu": 8, "pods": 20})
                .zone("zone-0").obj())
            for i in range(48):
                s.clientset.create_pod(
                    make_pod().name(f"wave2-{i}").req({"cpu": "500m", "memory": "256Mi"}).obj())
            s.run_until_idle()
        assert _assignments(host) == _assignments(dev)


class TestWidenedCoverageEquivalence:
    """Round-3 kernel coverage: node-affinity expressions, preferred node
    affinity, host ports, image locality, NodeDeclaredFeatures — previously
    host-path fallbacks, now device-evaluated via host-built static vectors
    (ops/features.py sel_match / na_raw / extra_ok / il_score). Reference:
    nodeaffinity/node_affinity.go, nodeports/, imagelocality/."""

    def test_node_affinity_expressions(self):
        host, dev = _run_pair(24, _basic_pods(
            18, build=lambda b: b.node_affinity_in("disk", ["ssd"])))
        assert dev.host_path_pods == 0

    def test_node_affinity_hostname_label(self):
        # Required affinity over the hostname LABEL (matchExpressions):
        # static per batch, rides the device via sel_match.
        def fn():
            pods = []
            for i in range(8):
                b = make_pod().name(f"ds-{i}").req({"cpu": "100m"})
                b = b.node_affinity_in("kubernetes.io/hostname", [f"node-{i % 4}"])
                pods.append(b.obj())
            return pods
        host, dev = _run_pair(12, fn)
        assert dev.host_path_pods == 0

    def test_node_affinity_match_fields_narrowing(self):
        # Daemonset shape: matchFields metadata.name pin (daemonset-pod.yaml)
        # triggers the NodeAffinity PreFilterResult narrowing, which changes
        # the rotation/sampling universe: the device path plans over the
        # named nodes' rows only, and assignments must match the oracle.
        from kubernetes_tpu.api.labels import IN, Requirement
        from kubernetes_tpu.api.types import Affinity, NodeAffinity as NA, NodeSelector, NodeSelectorTerm

        def fn():
            pods = []
            for i in range(10):
                p = make_pod().name(f"ds-{i}").req({"cpu": "100m"}).obj()
                term = NodeSelectorTerm(match_fields=(
                    Requirement("metadata.name", IN, (f"node-{i % 4}",)),))
                p.affinity = Affinity(node_affinity=NA(required=NodeSelector((term,))))
                pods.append(p)
            return pods
        host, dev = _run_pair(12, fn)
        assert dev.host_path_pods == 0
        assert dev.device_scheduled == 10
        assert dev.metrics.prefilter_narrowed_pods.value("device") == 10
        assert host.metrics.prefilter_narrowed_pods.value("host") == 10

    @pytest.mark.parametrize("case", sorted(_NARROWED_CASES))
    def test_narrowed_pods_are_placed_as_the_host_places_them(self, case):
        """Every shape of a PreFilterResult that narrows, on the device path
        and on the host's: the same node a pod, the same rotating start
        index at the end (a plain pod behind a pinned one samples the same
        window), the same failures with the same words and the same plugins
        to blame, and no pod on the device scheduler's host path."""
        n_nodes, pods_fn, cluster, bound, narrowed = _NARROWED_CASES[case]()
        host, dev = _run_pair(n_nodes, pods_fn, cluster=cluster)
        assert dev.host_path_pods == 0
        assert host.scheduled == dev.scheduled == bound
        assert host.failures == dev.failures
        assert host.next_start_node_index == dev.next_start_node_index
        # every attempt whose PreFilterResult narrowed, on either path
        assert dev.metrics.prefilter_narrowed_pods.value("device") == narrowed
        assert host.metrics.prefilter_narrowed_pods.value("host") == narrowed
        assert dev.metrics.prefilter_narrowed_pods.value("host") == 0
        for sched in (host, dev):
            sched.said = {
                p.name: [e.message for e in sched.recorder.for_object(
                    f"{p.namespace}/{p.name}") if e.reason == "FailedScheduling"]
                for p in sched.clientset.pods.values() if not p.node_name}
            sched.blamed = {
                q.pod.name: sorted(q.unschedulable_plugins)
                for q in sched.queue.unschedulable.values()}
        # (an identical pod that fails behind the first against the same
        # state is parked from the failure memo, whose words are the short
        # form: `_fail_from_memo`)
        assert host.said.keys() == dev.said.keys()
        for name, words in host.said.items():
            assert len(words) == len(dev.said[name])
            assert all(h == d or h.startswith(d + " for pod")
                       for h, d in zip(words, dev.said[name])), name
        assert any(host.said[n] == dev.said[n] for n in host.said) \
            or not host.said
        assert host.blamed == dev.blamed

    def test_preferred_node_affinity_scoring(self):
        host, dev = _run_pair(20, _basic_pods(
            16, build=lambda b: b.preferred_node_affinity(7, "disk", ["hdd"])))
        assert dev.host_path_pods == 0

    def test_host_ports_self_blocking(self):
        # Identical pods with a host port: at most one per node; both paths
        # must fail the overflow pods identically.
        host, dev = _run_pair(6, _basic_pods(
            9, cpu="100m", build=lambda b: b.host_port(8080)))
        # The 6 placements ride the device; the 3 infeasible overflow pods
        # intentionally re-run host-side for the exact FitError diagnosis.
        assert dev.device_scheduled == 6
        assert host.scheduled == dev.scheduled == 6
        assert host.failures > 0

    def test_image_locality_scoring(self):
        def cluster(sched):
            for i in range(15):
                b = (make_node().name(f"node-{i}")
                     .capacity({"cpu": 8, "memory": "32Gi", "pods": 110}))
                if i % 3 == 0:
                    b = b.image("registry/app:v1", 400 * 1024 * 1024)
                sched.clientset.create_node(b.obj())
        host = Scheduler(deterministic_ties=True)
        dev = TPUScheduler()
        cluster(host)
        cluster(dev)
        def pods():
            return [make_pod().name(f"p-{i}").req({"cpu": "100m"})
                    .image("registry/app:v1").obj() for i in range(10)]
        for p in pods():
            host.clientset.create_pod(p)
        for p in pods():
            dev.clientset.create_pod(p)
        host.run_until_idle()
        dev.run_until_idle()
        assert _assignments(host) == _assignments(dev)
        assert dev.host_path_pods == 0

    def test_node_declared_features(self):
        # NDF is feature-gated off by default (reference kube_features.go):
        # build a profile that enables the plugin on both paths.
        from kubernetes_tpu.core.registry import DEFAULT_PLUGINS, build_framework
        plugins = DEFAULT_PLUGINS + (("NodeDeclaredFeatures", 0),)
        factory = lambda h: {"default-scheduler": build_framework(h, plugins=plugins)}  # noqa: E731

        def cluster(sched):
            for i in range(12):
                b = (make_node().name(f"node-{i}")
                     .capacity({"cpu": 8, "memory": "32Gi", "pods": 110}))
                n = b.obj()
                if i % 2 == 0:
                    n.declared_features = {"feat.a": True, "feat.b": True}
                sched.clientset.create_node(n)
        host = Scheduler(deterministic_ties=True, profile_factory=factory)
        dev = TPUScheduler(profile_factory=factory)
        cluster(host)
        cluster(dev)
        def pods():
            out = []
            for i in range(8):
                p = make_pod().name(f"p-{i}").req({"cpu": "100m"}).obj()
                p.annotations["features.k8s.io/required"] = "feat.a,feat.b"
                out.append(p)
            return out
        for p in pods():
            host.clientset.create_pod(p)
        for p in pods():
            dev.clientset.create_pod(p)
        host.run_until_idle()
        dev.run_until_idle()
        assert _assignments(host) == _assignments(dev)
        assert dev.host_path_pods == 0
        bound = {n for n in _assignments(dev).values() if n}
        assert all(int(n.split("-")[1]) % 2 == 0 for n in bound)


class TestInfeasibleDiagnosisEquivalence:
    """Device-infeasible pods produce the same outcome (failure accounting,
    unschedulable plugin attribution for queueing hints, preemption
    PostFilter behavior) whether diagnosed by the vectorized mirror path or
    the host rerun — and identical floods don't tear down the session."""

    def test_flood_outcomes_match_host(self):
        def pods():
            out = []
            for i in range(25):
                out.append(make_pod().name(f"flood-{i}").req({"cpu": "900"}).obj())
            for i in range(30):
                out.append(make_pod().name(f"ok-{i}").req({"cpu": "100m"}).obj())
            return out
        host, dev = _run_pair(30, pods)
        assert host.scheduled == dev.scheduled == 30
        assert host.failures == dev.failures == 25
        h_plugins = {q.uid: tuple(sorted(q.unschedulable_plugins))
                     for q in host.queue.unschedulable.values()}
        d_plugins = {q.uid: tuple(sorted(q.unschedulable_plugins))
                     for q in dev.queue.unschedulable.values()}
        assert set(h_plugins.values()) == set(d_plugins.values())

    def test_preemptable_infeasible_still_preempts(self):
        # Infeasible only because nodes are FULL (not over-capacity): the
        # diagnosis must leave preemption viable and the high-priority pod
        # must evict a victim on both paths.
        def build(cls):
            from kubernetes_tpu.core import FakeClientset
            cs = FakeClientset()
            s = cls(clientset=cs) if cls is TPUScheduler else cls(
                clientset=cs, deterministic_ties=True)
            for i in range(3):
                cs.create_node(make_node().name(f"n{i}").capacity(
                    {"cpu": 4, "memory": "16Gi", "pods": 110}).obj())
            for i in range(3):
                p = make_pod().name(f"low-{i}").req({"cpu": "4"}).priority(1).obj()
                p.node_name = f"n{i}"
                cs.create_pod(p)
            hi = make_pod().name("hi").req({"cpu": "4"}).priority(50).obj()
            cs.create_pod(hi)
            s.run_until_idle()
            return cs, s, hi
        cs_h, s_h, hi_h = build(Scheduler)
        cs_d, s_d, hi_d = build(TPUScheduler)
        assert hi_h.node_name and hi_d.node_name
        assert hi_h.node_name == hi_d.node_name

    def test_fail_memo_does_not_park_higher_priority_pod(self):
        """A memoized terminal failure must not serve a later pod whose
        priority differs: PostFilter preemption eligibility depends on
        priority (victims in [memo_prio, new_prio) become evictable), so the
        higher-priority pod must run its own attempt — and preempt."""
        from kubernetes_tpu.core import FakeClientset
        cs = FakeClientset()
        s = TPUScheduler(clientset=cs)
        for i in range(2):
            cs.create_node(make_node().name(f"n{i}").capacity(
                {"cpu": 4, "memory": "16Gi", "pods": 110}).obj())
        for i in range(2):
            p = make_pod().name(f"mid-{i}").req({"cpu": "4"}).priority(10).obj()
            p.node_name = f"n{i}"
            cs.create_pod(p)
        # Flood of same-priority hopeless pods primes the memo...
        for i in range(5):
            cs.create_pod(make_pod().name(f"same-{i}").req({"cpu": "4"})
                          .priority(10).obj())
        s.run_until_idle()
        assert s.scheduled == 0
        # ...then an identically-signed HIGHER-priority pod must not be
        # parked from the memo: preemption can make room for it.
        hi = make_pod().name("hi").req({"cpu": "4"}).priority(50).obj()
        cs.create_pod(hi)
        s.run_until_idle()
        assert hi.nominated_node_name or hi.node_name, (
            "higher-priority pod was parked by a stale fail memo")


class TestNominatedLane:
    """Nominated pods ride the kernel as a fit-filter lane
    (runtime/framework.go:1275 two-pass, pass 1 resources) instead of
    disabling the device path wholesale (round-4 VERDICT item 3)."""

    def _pair(self, n_nodes=8, seed=0):
        host = Scheduler(deterministic_ties=True)
        dev = TPUScheduler()
        _mk_cluster(host, n_nodes, seed=seed)
        _mk_cluster(dev, n_nodes, seed=seed)
        return host, dev

    def test_manual_nominations_match_host(self):
        from kubernetes_tpu.core.node_info import PodInfo
        host, dev = self._pair()
        for sched in (host, dev):
            g1 = make_pod().name("ghost1").req({"cpu": "1500m"}).priority(50).obj()
            g2 = make_pod().name("ghost2").req({"cpu": "1"}).priority(50).obj()
            sched.queue.nominator.add_nominated_pod(PodInfo.of(g1), "node-0")
            sched.queue.nominator.add_nominated_pod(PodInfo.of(g2), "node-3")
        proto = make_pod().name("proto").req({"cpu": "500m"}).labels({"a": "b"}).obj()
        for sched in (host, dev):
            for i in range(24):
                sched.clientset.create_pod(proto.clone_from_template(f"p{i}"))
            sched.run_until_idle()
        a_h, a_d = _assignments(host), _assignments(dev)
        assert a_h == a_d
        assert dev.device_scheduled >= 20, (
            f"device path should stay on with nominations "
            f"(device={dev.device_scheduled}, host={dev.host_path_pods})")

    def test_lower_priority_nomination_ignored(self):
        """Only >=-priority nominations count in pass 1
        (framework.go:1280-1284): a LOWER-priority nomination must not
        shrink the fit room for the batch."""
        from kubernetes_tpu.core.node_info import PodInfo
        host, dev = self._pair()
        for sched in (host, dev):
            g = make_pod().name("ghost").req({"cpu": "100"}).priority(-5).obj()
            sched.queue.nominator.add_nominated_pod(PodInfo.of(g), "node-1")
        proto = make_pod().name("proto").req({"cpu": "500m"}).obj()
        for sched in (host, dev):
            for i in range(16):
                sched.clientset.create_pod(proto.clone_from_template(f"p{i}"))
            sched.run_until_idle()
        assert _assignments(host) == _assignments(dev)
        assert dev.device_scheduled >= 14

    def test_preemption_nominations_interleaved(self):
        """The VERDICT done-criterion: real PostFilter preemptions create
        nominations mid-workload; plain pods keep riding the device with
        identical assignments and >=90% device-scheduled."""
        host = Scheduler(deterministic_ties=True)
        dev = TPUScheduler()
        for sched in (host, dev):
            for i in range(10):
                sched.clientset.create_node(
                    make_node().name(f"node-{i}")
                    .capacity({"cpu": 4, "memory": "8Gi", "pods": 20}).obj())
        # fill the cluster with evictable low-priority pods
        low = make_pod().name("low").req({"cpu": "3"}).priority(0).obj()
        for sched in (host, dev):
            for i in range(10):
                sched.clientset.create_pod(low.clone_from_template(f"low-{i}"))
            sched.run_until_idle()
        # preemptors (high priority, need 3 cpu -> must evict) interleaved
        # with plain small pods that fit in the remaining 1-cpu slivers
        hi = make_pod().name("hi").req({"cpu": "3"}).priority(100).obj()
        small = make_pod().name("small").req({"cpu": "200m"}).priority(10).obj()
        for sched in (host, dev):
            for i in range(3):
                sched.clientset.create_pod(hi.clone_from_template(f"hi-{i}"))
                for j in range(8):
                    sched.clientset.create_pod(
                        small.clone_from_template(f"small-{i}-{j}"))
                sched.run_until_idle()
            # let evictions finish and preemptors land
            for _ in range(40):
                sched.process_async_api_errors()
                sched.run_until_idle()
        a_h, a_d = _assignments(host), _assignments(dev)
        small_h = {k: v for k, v in a_h.items() if k.startswith("small")}
        small_d = {k: v for k, v in a_d.items() if k.startswith("small")}
        assert small_h == small_d
        total_small = 24
        assert sum(1 for v in small_d.values() if v) == total_small
        assert dev.device_scheduled >= 0.9 * total_small, (
            f"{dev.device_scheduled} device vs {dev.host_path_pods} host")
