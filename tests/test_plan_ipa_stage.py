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
from kubernetes_tpu.testing.annotations import StageAnnotations

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


# -- the score-table walk: stage `plan.ipa_score` and the engine counter ----

def _red(name, weight=0, anti=False):
    b = make_pod().name(name).req({"cpu": "100m"}).label("color", "red")
    if weight:
        b = b.pod_affinity(HOSTNAME, {"color": "red"}, anti=anti,
                           weight=weight)
    return b.obj()


def _score_walk(sched):
    """(`term.matches` evaluations of the score walk, pods it visited)."""
    c = sched.metrics.plan_ipa_terms
    return int(c.value("score_matches")), int(c.value("pods_walked"))


def _engines(sched):
    c = sched.metrics.device_batches
    return {e: int(c.value(e))
            for e in ("scan_carried", "scan_normalised", "lap")}


def test_plan_ipa_score_is_a_loop_stage():
    assert "plan.ipa_score" in spans.STAGES
    assert "plan.ipa_score" in spans.LOOP_STAGES


def test_required_terms_alone_never_open_the_score_stage():
    """Required anti-affinity feeds no score: the walk visits the carriers
    and finds no term to match, so the stage stays shut and nothing is
    counted."""
    sched, cs = _cluster()
    for i in range(5):
        cs.create_pod(_green(f"a{i}", HOSTNAME))
    sched.run_until_idle()
    cs.delete_pod(next(p for p in cs.pods.values() if p.name == "a0"))
    cs.create_pod(_green("a5", HOSTNAME))
    cs.create_pod(_green("plain"))
    sched.run_until_idle()
    assert sched.stages.counts["plan.ipa"] >= 2
    assert sched.stages.counts["plan.ipa_score"] == 0
    assert sched.stages.seconds["plan.ipa_score"] == 0.0
    assert _score_walk(sched) == (0, 0)
    assert not any("score_matches" in line or "pods_walked" in line
                   for line in _series(sched, "scheduler_plan_ipa_terms_total"))
    assert _engines(sched)["scan_normalised"] == 0
    assert sum(_engines(sched).values()) == sched.device_batches


def test_a_preferred_term_opens_the_score_stage_and_counts_the_walk():
    sched, cs = _cluster()
    for i in range(4):
        cs.create_pod(_red(f"r{i}", weight=1))
    sched.run_until_idle()
    # the first plan is built over an empty cluster: the incoming pod has a
    # preferred term and the walk meets no pod to match it with, so the
    # stage stays shut
    assert sched.stages.counts["plan.ipa_score"] == 0
    assert _score_walk(sched) == (0, 0)
    # the four pack onto one node: the score decided
    assert len({p.node_name for p in cs.pods.values()}) == 1
    # a delete voids the plan; the next is built over the three that are
    # left: each is matched by the incoming pod's term and matches it with
    # its own (2 a pod)
    cs.delete_pod(next(p for p in cs.pods.values() if p.name == "r0"))
    for i in range(4, 6):
        cs.create_pod(_red(f"r{i}", weight=1))
    sched.run_until_idle()
    assert sched.stages.counts["plan.ipa_score"] == 1
    assert _score_walk(sched) == (6, 3)
    assert len({p.node_name for p in cs.pods.values()}) == 1
    assert sched.host_path_pods == 0
    # every batch rode the scan that normalises (preferred terms couple the
    # whole window), and the engines sum to the batches
    engines = _engines(sched)
    assert engines["scan_normalised"] == sched.device_batches >= 2
    assert engines["scan_carried"] == engines["lap"] == 0
    assert _series(sched, "scheduler_device_batches_total") == [
        f'scheduler_device_batches_total{{engine="scan_normalised"}} '
        f'{float(sched.device_batches)}']
    assert 'scheduler_plan_ipa_terms_total{what="pods_walked"} 3.0' in _series(
        sched, "scheduler_plan_ipa_terms_total")
    # inside the build, and inside what plan_build_s means
    seconds = sched.stages.seconds
    assert seconds["plan.ipa_score"] > 0
    assert sched.plan_build_s == pytest.approx(
        seconds["plan.build"] + seconds["plan.ipa"]
        + seconds["plan.ipa_score"])
    roots = [parts for _n, _ts, _d, _s, parts in sched.stages.recent
             if parts and "plan.ipa_score" in parts]
    assert roots and all("plan.build" in parts for parts in roots)
    sched.stages.publish()
    assert any('stage="plan.ipa_score"' in line for line in _series(
        sched, "scheduler_loop_stage_seconds_total"))


def test_existing_preferred_terms_open_the_stage_for_a_plain_pod():
    """The symmetric half alone: the incoming pod has no term, the pods
    already there pull it, and the stage opens at the first of them."""
    sched, cs = _cluster()
    for i in range(3):
        cs.create_pod(_red(f"r{i}", weight=1))
    sched.run_until_idle()
    before = sched.stages.counts["plan.ipa_score"]
    cs.create_pod(_red("plain"))
    sched.run_until_idle()
    assert sched.stages.counts["plan.ipa_score"] == before + 1
    # three carriers visited, one match each (their own term against the
    # incoming pod; it has none to match them with)
    assert _score_walk(sched) == (3, 3)
    held = {p.node_name for p in cs.pods.values() if p.name != "plain"}
    plain = next(p for p in cs.pods.values() if p.name == "plain")
    assert held == {plain.node_name}


def test_plain_pods_ride_the_carried_scan_or_the_lap():
    sched, cs = _cluster()
    for i in range(6):
        cs.create_pod(_green(f"p{i}"))
    sched.run_until_idle()
    engines = _engines(sched)
    assert engines["scan_normalised"] == 0
    assert sum(engines.values()) == sched.device_batches >= 1


def test_a_dispatch_carries_its_engine_into_the_profiler(monkeypatch):
    """What is known of a stage as it opens rides its annotation, so a
    profiler session holds each `sched.device.dispatch` with the engine
    that placed the batch as a stat of the event."""
    annotations = StageAnnotations()
    opened = annotations.opened
    sched, cs = _cluster()
    monkeypatch.setattr(sched.stages, "_annotation", annotations)
    for i in range(3):
        cs.create_pod(_red(f"r{i}", weight=1))
    sched.run_until_idle()
    dispatches = [stats for name, stats in opened
                  if name == "sched.device.dispatch"]
    assert len(dispatches) == sched.device_batches >= 1
    assert all(d == {"batch": 3, "engine": "scan_normalised",
                     "batch_pad": 1024, "steps": 3, "seq": seq,
                     "inflight": 0}
               for seq, d in enumerate(dispatches, 1))
    # what a stage says while it is open rides the annotation too: a plan's
    # kind, and why a full build is one (PR 40)
    # (and, PR 48, the transfers of its acquisition: the features' one;
    # PR 50, the allocatable shapes among its nodes: here all alike;
    # PR 51, the mirror rows it brought in line: the 24 nodes, all new to
    # it, and once more when the build met the term's hostname axis)
    assert ("sched.plan.build", {"batch": 3, "kind": "full",
                                 "cause": "first", "transfers": 1,
                                 "node_shapes": 1, "rows_encoded": 48,
                                 "rows_by_column": 0}) in opened
