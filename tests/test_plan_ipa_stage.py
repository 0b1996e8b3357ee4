"""The stage `plan.ipa` of the loop's ledger (core/spans.py) and the two
counters PR 27 put on a plan build: the build of the required inter-pod term
tables as a stage of its own inside `plan.build`, with what it cost
(`term.matches` evaluations, existing term-carrying pods), and the plans
built with an anti lane split by `BatchPlan.anti_rowlocal`. A cluster
without any term never opens the stage."""

import pytest

from kubernetes_tpu.core import spans
from kubernetes_tpu.models import TPUScheduler
from kubernetes_tpu.testing import make_node, make_pod

HOSTNAME = "kubernetes.io/hostname"
ZONE = "topology.kubernetes.io/zone"


def _cluster(nodes=24):
    sched = TPUScheduler()
    cs = sched.clientset
    for i in range(nodes):
        cs.create_node(make_node().name(f"n{i}").zone(f"z{i % 3}").capacity(
            {"cpu": "8", "memory": "16Gi", "pods": 110}).obj())
    return sched, cs


def _green(name, key=None):
    b = make_pod().name(name).req({"cpu": "100m"}).label("color", "green")
    if key:
        b = b.pod_affinity(key, {"color": "green"}, anti=True)
    return b.obj()


def _series(sched, name):
    return [line for line in sched.metrics.expose().splitlines()
            if line.startswith(name)]


def _terms(sched):
    """(`term.matches` evaluations, existing term-carrying pods walked)."""
    c = sched.metrics.plan_ipa_terms
    return int(c.value("matches")), int(c.value("term_pods"))


def _lanes(sched):
    """Plans built with an anti lane: (on the lap path, off it)."""
    c = sched.metrics.plan_anti_lane
    return int(c.value("true")), int(c.value("false"))


def test_plan_ipa_is_a_loop_stage():
    assert "plan.ipa" in spans.STAGES and "plan.ipa" in spans.LOOP_STAGES


def test_a_cluster_without_terms_never_opens_the_stage():
    sched, cs = _cluster()
    for i in range(6):
        cs.create_pod(_green(f"p{i}"))
    sched.run_until_idle()
    assert sched.device_batches >= 1 and sched.host_path_pods == 0
    assert sched.stages.counts["plan.ipa"] == 0
    assert sched.stages.seconds["plan.ipa"] == 0.0
    assert _terms(sched) == (0, 0)
    assert _lanes(sched) == (0, 0)
    assert _series(sched, "scheduler_plan_ipa_terms_total") == []
    assert _series(sched, "scheduler_plan_anti_lane_total") == []
    # the whole build is still what plan_build_s means
    assert sched.plan_build_s == sched.stages.seconds["plan.build"] > 0


def test_hostname_terms_open_the_stage_and_count_what_it_cost():
    sched, cs = _cluster()
    for i in range(5):
        cs.create_pod(_green(f"a{i}", HOSTNAME))
    sched.run_until_idle()
    # first plan: an empty cluster, the pod's own term against itself
    assert sched.stages.counts["plan.ipa"] == 1
    assert _terms(sched) == (1, 0)
    assert _lanes(sched) == (1, 0)
    # a delete of a term-carrying pod voids the plan; the next one is built
    # over the four that are left: each is walked once as a carrier of a term
    # (4 matches against the incoming pod) and once as a pod on a node
    # (4 matches of the incoming pod's term), plus the pod's own
    cs.delete_pod(next(p for p in cs.pods.values() if p.name == "a0"))
    for i in range(5, 8):
        cs.create_pod(_green(f"a{i}", HOSTNAME))
    sched.run_until_idle()
    assert sched.stages.counts["plan.ipa"] == 2
    assert _terms(sched) == (1 + 9, 4)
    assert _lanes(sched) == (2, 0)
    assert sched.host_path_pods == 0
    nodes = [p.node_name for p in cs.pods.values()]
    assert len(set(nodes)) == len(nodes) == 7
    seconds = sched.stages.seconds
    assert seconds["plan.ipa"] > 0
    assert sched.plan_build_s == pytest.approx(
        seconds["plan.build"] + seconds["plan.ipa"])
    assert _series(sched, "scheduler_plan_ipa_terms_total") == [
        'scheduler_plan_ipa_terms_total{what="matches"} 10.0',
        'scheduler_plan_ipa_terms_total{what="term_pods"} 4.0']
    assert _series(sched, "scheduler_plan_anti_lane_total") == [
        'scheduler_plan_anti_lane_total{rowlocal="true"} 2.0']
    sched.stages.publish()
    assert any('stage="plan.ipa"' in line for line in _series(
        sched, "scheduler_loop_stage_seconds_total"))


def test_the_stage_lies_inside_plan_build():
    sched, cs = _cluster()
    cs.create_pod(_green("a0", HOSTNAME))
    sched.run_until_idle()
    roots = [parts for name, _ts, _d, _s, parts in sched.stages.recent
             if parts and "plan.ipa" in parts]
    assert roots and all("plan.build" in parts for parts in roots)


def test_existing_terms_alone_are_an_anti_lane_off_the_lap_path():
    """A plain pod that existing pods' terms refuse: `exist_anti` is the
    lane, the pod has no term of its own, so `anti_rowlocal` is false."""
    sched, cs = _cluster()
    for i in range(4):
        cs.create_pod(_green(f"a{i}", HOSTNAME))
    sched.run_until_idle()
    before = _lanes(sched)
    cs.create_pod(_green("plain"))
    sched.run_until_idle()
    assert _lanes(sched) == (
        before[0], before[1] + 1)
    assert _terms(sched)[1] >= 4
    held = {p.node_name for p in cs.pods.values() if p.name != "plain"}
    plain = next(p for p in cs.pods.values() if p.name == "plain")
    assert plain.node_name and plain.node_name not in held


def test_a_zone_wide_term_counts_as_shared():
    sched, cs = _cluster()
    for i in range(2):
        cs.create_pod(_green(f"z{i}", ZONE))
    sched.run_until_idle()
    assert _lanes(sched)[0] == 0 and _lanes(sched)[1] >= 1
    zones = {cs.nodes[p.node_name].labels[ZONE] for p in cs.pods.values()}
    assert len(zones) == 2
