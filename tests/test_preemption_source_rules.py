"""DefaultPreemption in the program, held to the source where PR 43 mended
it: the node a preemptor takes (`Evaluator.select_candidate`, the source's
criteria in the source's order, by the hand cases of the benchmark's
reference), the room held for a nominated pod in the what-if (host dry run
and kernel, row for row), a nominated pod's retry on the device path, and
the `nomination` cause of a full plan build. No timing is asserted."""

import random

import pytest

from kubernetes_tpu.core import Scheduler, spans
from kubernetes_tpu.core.framework import CycleState
from kubernetes_tpu.core.node_info import PodInfo
from kubernetes_tpu.plugins.preemption import Candidate, Evaluator
from kubernetes_tpu.testing.annotations import StageAnnotations
from kubernetes_tpu.testing.wrappers import make_node, make_pod

LOWEST = -(1 << 31)


def _node(name, cpu="4"):
    return make_node().name(name).capacity(
        {"cpu": cpu, "memory": "32Gi", "pods": 110}).zone("zone-0").obj()


_ORDINAL = [0]


def _pod(name, cpu="100m", priority=0, born=None):
    pod = (make_pod().name(name).uid(name)
           .req({"cpu": cpu, "memory": "500Mi"}).priority(priority).obj())
    if born is None:
        born = _ORDINAL[0]
        _ORDINAL[0] += 1
    pod.creation_ts = float(born)
    return pod


def _candidate(node, victims, pdb=0):
    """`victims`: (priority, born) pairs."""
    return Candidate(
        node_name=node, num_pdb_violations=pdb,
        victims=[PodInfo(_pod(f"{node}-{i}", priority=p, born=b))
                 for i, (p, b) in enumerate(victims)])


# pickOneNodeForPreemption by hand, each case decided by one criterion with
# every earlier one tied: (the two nodes' victims as (priority, born), the
# node the source takes, why). The fifth and the third are the reference's
# own hand cases (tests/benchmark/test_benchmark_preemption.py
# `test_then_the_latest_start_of_the_most_important_victims`,
# `test_then_the_sum_of_priorities_each_raised_by_two_to_the_31`).
PICKS = {
    "lowest_highest_victim_priority":
        ([(3, 0), (1, 2), (1, 4), (1, 6)], [(2, 1), (2, 3), (2, 5), (2, 7)],
         "v"),
    # plain sums 2 and 3 would take u; 3 * 2**31 + 2 against 2 * 2**31 + 3
    "sum_of_priorities_each_raised_by_two_to_the_31":
        ([(2, 0), (0, 2), (0, 4)], [(2, 1), (1, 3)], "v"),
    # the cell's own: four victims of a negative priority against three. The
    # plain sum, -40 against -30, would take the four
    "negative_priorities_three_victims_against_four":
        ([(-10, 0), (-10, 1), (-10, 2), (-10, 3)],
         [(-10, 4), (-10, 5), (-10, 6)], "v"),
    # equal sums and unequal counts only where a term is 0
    "fewest_victims":
        ([(5, 0)], [(5, 1), (LOWEST, 2)], "u"),
    # the earliest start among each node's victims of priority 1 is p's (0)
    # on u and r's (1) on v: v, though the latest start of all is q's, on u
    "latest_start_of_the_most_important_victims":
        ([(1, 0), (0, 3)], [(1, 1), (0, 2)], "v"),
    # the cell's rule: three victims of one priority a node, the node whose
    # OLDEST victim is youngest; the youngest victim of all lies on u
    "the_earliest_of_each_node_decides_not_the_latest":
        ([(-10, 0), (-10, 1), (-10, 9)], [(-10, 2), (-10, 3), (-10, 4)],
         "v"),
    "first_found":
        ([(1, 0)], [(1, 0)], "u"),
}


@pytest.mark.parametrize("swapped", (False, True))
@pytest.mark.parametrize("case", sorted(PICKS))
def test_the_node_is_picked_by_the_sources_criteria_in_its_order(
        case, swapped):
    u, v, want = PICKS[case]
    found = [_candidate("u", u), _candidate("v", v)]
    if swapped and case != "first_found":
        found.reverse()         # the order found decides nothing before it
    assert Evaluator.select_candidate(found).node_name == want


def test_fewer_pdb_violations_come_before_everything():
    found = [_candidate("u", [(1, 0)], pdb=1),
             _candidate("v", [(9, 1), (9, 2)])]
    assert Evaluator.select_candidate(found).node_name == "v"
    assert Evaluator.select_candidate([]) is None
    assert Evaluator.select_candidate(found[:1]).node_name == "u"


# -- two preemptors in flight: the room held in the what-if --------------------

def _scheduler(kind, **more):
    if kind == "host":
        return Scheduler(deterministic_ties=True)
    from kubernetes_tpu.models import TPUScheduler
    return TPUScheduler(**more)


def _full_cluster(sched, nodes, low=-10):
    """`nodes` nodes of 4 cpu, each kept full by four pods of 900m at a low
    priority: 400m left, as in `preempt-5k`."""
    cs = sched.clientset
    for i in range(nodes):
        cs.create_node(_node(f"n{i}"))
    for i in range(4 * nodes):
        cs.create_pod(_pod(f"init-{i}", cpu="900m", priority=low))
    sched.run_until_idle()
    assert all(p.node_name for p in cs.pods.values())
    return cs


def _by_node(cs, prefix):
    out = {}
    for p in cs.pods.values():
        if p.name.startswith(prefix) and p.node_name:
            out.setdefault(p.node_name, []).append(p.name)
    return out


@pytest.mark.parametrize("kind", ("host", "device"))
def test_the_second_preemptor_does_not_take_the_room_held_for_the_first(kind):
    """Two nodes, a preemptor of 2 cpu and one of 3 cpu created together.
    The first evicts two pods of one node and is nominated there; the node
    then holds 1,800m. Without the held room the second preemptor's what-if
    finds that node a candidate with ONE victim (3 cpu beside one pod of
    900m), which beats the other node's three, and evicts a pod for room
    that is taken. With it the node is no candidate (4 cpu less the 2 held
    leave 2), the second takes the other node, and five pods are evicted in
    all: two and three. (A second preemptor of the first one's own size
    never shows this: the room its victims left fits it without a victim,
    which is no candidate either way. That is `preempt-5k`'s case, PERF.md
    section 4.)"""
    sched = _scheduler(kind)
    cs = _full_cluster(sched, 2)
    rec = sched.stages._annotation = StageAnnotations()
    cs.create_pod(_pod("hi-0", cpu="2", priority=10))
    cs.create_pod(_pod("hi-1", cpu="3", priority=10))
    sched.run_until_idle()
    highs = _by_node(cs, "hi-")
    assert sorted(highs) == ["n0", "n1"], highs
    left = {node: len(names)
            for node, names in _by_node(cs, "init-").items()}
    first = next(n for n, names in highs.items() if names == ["hi-0"])
    second = next(n for n, names in highs.items() if names == ["hi-1"])
    assert (left[first], left[second]) == (2, 1)
    assert sched.failures == 2
    assert sched.metrics.preemption_victims.sum() == 5
    said = [s for name, s in rec.opened if name == "sched.postfilter.preempt"]
    assert [s["victims"] for s in said] == [2, 3]
    assert all(s["nominated"] == 1 and s["select_ms"] >= 0
               and s["verify_ms"] >= 0 and s["evict_ms"] >= 0 for s in said)
    runs = sched.metrics.preemption_dry_runs
    if kind == "device":
        assert sched.host_path_pods == 0
        assert (runs.value("device"), runs.value("host")) == (2, 0)
        # the second what-if met the first's room: one row of the lane
        assert [s["nom_rows"] for s in said] == [0, 1]
        assert [s["engine"] for s in said] == ["device", "device"]
        assert sched.metrics.nominated_evaluations.value("bound") == 2
        evals = [s for name, s in rec.opened
                 if name == "sched.nominated.eval"]
        assert [s["outcome"] for s in evals] == ["bound", "bound"]
        assert all(s["engine"] == "device" for s in evals)
    else:
        assert (runs.value("device"), runs.value("host")) == (0, 2)


def lane_left_out(monkeypatch):
    """The what-if as it was before PR 43, host and kernel alike: the
    nominated lane left out of the dry run (`run_filter_plugins` on the
    host, no lane in the plan the kernel reads). Also loaded by
    tests/benchmark/test_benchmark_preempt5k.py."""
    from kubernetes_tpu.core.framework import Framework
    from kubernetes_tpu.models import TPUScheduler
    whole = Framework.run_filter_plugins_with_nominated_pods
    dry_run = Evaluator.dry_run_on_node

    def without(self, state, pod, node_info):
        monkeypatch.setattr(
            Framework, "run_filter_plugins_with_nominated_pods",
            lambda fw, st, p, ni, nominator=None:
                fw.run_filter_plugins(st, p, ni))
        try:
            return dry_run(self, state, pod, node_info)
        finally:
            monkeypatch.setattr(
                Framework, "run_filter_plugins_with_nominated_pods", whole)
    monkeypatch.setattr(Evaluator, "dry_run_on_node", without)
    inner = TPUScheduler._device_dry_run_preemption
    lane = TPUScheduler._nominated_lane

    def no_lane(self, *args):
        monkeypatch.setattr(TPUScheduler, "_nominated_lane",
                            lambda s, pod: None)
        try:
            return inner(self, *args)
        finally:
            monkeypatch.setattr(TPUScheduler, "_nominated_lane", lane)
    monkeypatch.setattr(TPUScheduler, "_device_dry_run_preemption", no_lane)


@pytest.mark.parametrize("kind", ("host", "device"))
def test_with_the_lane_left_out_the_second_preemptor_takes_that_room(
        kind, monkeypatch):
    """The same two preemptors with the what-if as it was before PR 43
    (`run_filter_plugins`, no nominated lane in the kernel): the second one
    evicts a third pod of the first one's node. What the test above guards
    is therefore not met by chance."""
    sched = _scheduler(kind)
    cs = _full_cluster(sched, 2)
    lane_left_out(monkeypatch)
    cs.create_pod(_pod("hi-0", cpu="2", priority=10))
    cs.create_pod(_pod("hi-1", cpu="3", priority=10))
    first = _until_nominated(sched, cs, "hi-0")
    second = _until_nominated(sched, cs, "hi-1")
    assert second == first
    assert len(_by_node(cs, "init-")[first]) == 1       # a third pod went


def test_the_host_what_if_leaves_out_its_own_and_every_lower_nomination():
    """`dry_run_on_node` on the node a preemptor is nominated to: its own
    nomination does not count against it, one of lower priority does not
    either, one of equal priority does."""
    sched = Scheduler(deterministic_ties=True)
    cs = _full_cluster(sched, 1)
    fw = sched.framework_for_pod(next(iter(cs.pods.values())))
    ev = fw.plugin("DefaultPreemption").evaluator
    sched.cache.update_snapshot(sched.snapshot)
    ni = sched.snapshot.get("n0")
    mine = _pod("mine", cpu="3", priority=10)
    nominator = sched.queue.nominator

    def victims():
        cand = ev.dry_run_on_node(CycleState(), mine, ni)
        return None if cand is None else len(cand.victims)

    assert victims() == 3
    nominator.add_nominated_pod(PodInfo(mine), "n0")
    assert victims() == 3                   # its own
    nominator.add_nominated_pod(
        PodInfo(_pod("lower", cpu="3", priority=5)), "n0")
    assert victims() == 3                   # lower: it would be cleared
    nominator.add_nominated_pod(
        PodInfo(_pod("equal", cpu="500m", priority=10)), "n0")
    assert victims() == 4                   # 500m held: the fourth goes too
    nominator.add_nominated_pod(
        PodInfo(_pod("other", cpu="3", priority=10)), "n0")
    assert victims() is None                # no room however many leave


def _random_cluster(sched, rng, nodes, most):
    """Nodes of 8 cpu with up to `most` pods each of random size and
    priority, every pod with a start time of its own; returns the pods that
    were left pending (none should be)."""
    cs = sched.clientset
    for i in range(nodes):
        cs.create_node(_node(f"n{i}", cpu="8"))
    for i in range(nodes):
        for j in range(rng.randint(1, most)):
            pod = _pod(f"p{i}-{j}", cpu=f"{rng.choice((100, 300, 700))}m",
                       priority=rng.choice((-10, -5, 0, 3)))
            pod.node_name = ""
            pod.node_selector = {"kubernetes.io/hostname": f"n{i}"}
            cs.create_pod(pod)
    sched.run_until_idle()
    return cs


@pytest.mark.parametrize("nominations", (0, 5))
@pytest.mark.parametrize("most", (8, 12))
@pytest.mark.parametrize("seed", (1, 2, 3))
def test_the_kernel_and_the_host_dry_run_agree_row_for_row(
        seed, most, nominations):
    """Seeded random clusters: the device's candidates (`dry_run_preemption`,
    every row at once) against `Evaluator.dry_run_on_node` node by node: the
    same nodes, and on each the same victims in the same order, with and
    without room held for nominated pods (of equal, higher and lower
    priority than the preemptor, its own nomination among them), at two
    victim widths."""
    from kubernetes_tpu.models import TPUScheduler
    rng = random.Random(seed)
    sched = TPUScheduler()
    cs = _random_cluster(sched, rng, 24, most)
    nodes = sorted(cs.nodes)
    pre = _pod("pre", cpu=f"{rng.choice((5, 6, 7))}", priority=4)
    nominator = sched.queue.nominator
    for i in range(nominations):
        other = pre if i == 0 else _pod(
            f"nom-{i}", cpu=f"{rng.choice((1, 2, 4))}",
            priority=rng.choice((2, 4, 9)))
        nominator.add_nominated_pod(PodInfo(other), rng.choice(nodes))
    fw = sched.framework_for_pod(pre)
    n = len(nodes)
    device = sched.device_dry_run_preemption(fw, None, pre, {}, n, 0)
    assert device is not None
    ev = fw.plugin("DefaultPreemption").evaluator
    sched.cache.update_snapshot(sched.snapshot)
    host = []
    for ni in sched.snapshot.node_info_list:
        cand = ev.dry_run_on_node(CycleState(), pre, ni)
        if cand is not None:
            host.append(cand)
    assert [c.node_name for c in device] == [c.node_name for c in host]
    for d, h in zip(device, host):
        assert ([pi.pod.name for pi in d.victims]
                == [pi.pod.name for pi in h.victims]), d.node_name
    assert host, "a cluster on which nobody is a candidate pins nothing"
    k = max(len([p for p in ni.pods if p.pod.priority < pre.priority])
            for ni in sched.snapshot.node_info_list)
    assert (k > 8) == (most > 8)       # both widths of the victim tensor


def test_an_empty_and_a_filled_lane_are_one_compiled_program():
    from kubernetes_tpu.models import TPUScheduler
    from kubernetes_tpu.ops.kernel import dry_run_preemption
    sched = TPUScheduler()
    cs = _full_cluster(sched, 4)
    pre = _pod("pre", cpu="3", priority=10)
    fw = sched.framework_for_pod(pre)
    assert len(sched.device_dry_run_preemption(fw, None, pre, {}, 4, 0)) == 4
    size = dry_run_preemption._cache_size()
    sched.queue.nominator.add_nominated_pod(
        PodInfo(_pod("held", cpu="3", priority=10)), "n2")
    found = sched.device_dry_run_preemption(fw, None, pre, {}, 4, 0)
    assert [c.node_name for c in found] == ["n0", "n1", "n3"]
    assert dry_run_preemption._cache_size() == size
    sched.warm_for_preemption(pre)              # evicts nobody, counts nothing
    assert sched.preemption_device_evals == 2
    assert len(cs.pods) == 16


# -- a nominated pod's retry on the device path --------------------------------

def _until_nominated(sched, cs, name):
    for _ in range(50):
        sched.schedule_one()
        if cs.pods[name].nominated_node_name:
            return cs.pods[name].nominated_node_name
    raise AssertionError(f"{name} was never nominated")


def _placements(cs):
    return {p.name: p.node_name for p in cs.pods.values()}


def _room_gone(kind):
    """A preemptor is nominated; before its retry a pod of HIGHER priority,
    against which no room is held, takes the room its victims left."""
    sched = _scheduler(kind)
    cs = _full_cluster(sched, 3)
    cs.create_pod(_pod("hi", cpu="3", priority=10))
    first = _until_nominated(sched, cs, "hi")
    cs.create_pod(_pod("higher", cpu="3", priority=20))
    sched.run_until_idle()
    return sched, cs, first


def test_a_retry_whose_room_is_gone_falls_through_to_the_ordinary_cycle():
    host, host_cs, _ = _room_gone("host")
    sched, cs, first = _room_gone("device")
    assert cs.pods["higher"].node_name == first
    assert cs.pods["hi"].node_name not in ("", first)
    assert _placements(cs) == _placements(host_cs)
    assert sched.host_path_pods == 0
    evals = sched.metrics.nominated_evaluations
    assert (evals.value("fell_through"), evals.value("bound")) == (1, 1)
    assert sched.failures == host.failures == 2
    assert sched.next_start_node_index == host.next_start_node_index


def test_a_nominated_retry_leaves_the_start_index_where_it_was():
    """130 nodes: the adaptive sample stops at 100 feasible nodes, so an
    ordinary placement advances the start index. The plain pods placed
    before leave it off zero; the failed attempt walks every node and the
    nominated retry evaluates one, and neither moves it."""
    from kubernetes_tpu.models import TPUScheduler
    ends = {}
    for kind in ("host", "device"):
        sched = _scheduler(kind)
        cs = _full_cluster(sched, 130)
        for i in range(7):
            cs.create_pod(_pod(f"plain-{i}", cpu="100m"))
        sched.run_until_idle()
        start = sched.next_start_node_index
        assert start != 0
        cs.create_pod(_pod("hi", cpu="3", priority=10))
        sched.run_until_idle()
        assert cs.pods["hi"].node_name
        assert sched.next_start_node_index == start
        if isinstance(sched, TPUScheduler):
            assert sched.host_path_pods == 0
            assert sched.metrics.nominated_evaluations.value("bound") == 1
        ends[kind] = (_placements(cs), sched.next_start_node_index)
    assert ends["host"] == ends["device"]


def test_several_new_preemptors_in_one_batch_stay_off_the_host_path():
    """Five preemptors in the queue at once are one device batch; the first
    one's nomination ends the session, and the four behind it are diagnosed
    anew from the mirror with the nominations as they stand, not sent down
    the host path."""
    host = _scheduler("host")
    sched = _scheduler("device")
    for s in (host, sched):
        cs = _full_cluster(s, 8)
        for i in range(5):
            cs.create_pod(_pod(f"hi-{i}", cpu="3", priority=10))
        s.run_until_idle()
    assert _placements(sched.clientset) == _placements(host.clientset)
    assert sched.host_path_pods == 0 and sched.failures == 5
    assert len(_by_node(sched.clientset, "hi-")) == 5
    runs = sched.metrics.preemption_dry_runs
    assert (runs.value("device"), runs.value("host")) == (5, 0)


def test_the_stage_of_a_nominated_evaluation_is_pinned():
    assert "nominated.eval" in spans.STAGES
    assert "nominated.eval" in spans.LOOP_STAGES


# -- the cause of a full build -------------------------------------------------

def test_a_kept_plan_that_only_the_nominator_voids_says_nomination():
    from kubernetes_tpu.models import TPUScheduler
    sched = TPUScheduler()
    sched._hints.enabled = False    # every pod a device session
    rec = sched.stages._annotation = StageAnnotations()
    cs = sched.clientset
    for i in range(8):
        cs.create_node(_node(f"n{i}"))

    def session(prefix, cpu="100m", n=3):
        for i in range(n):
            cs.create_pod(_pod(f"{prefix}{i}", cpu=cpu))
        sched.run_until_idle()
        return [(s.get("kind"), s.get("cause")) for name, s in rec.opened
                if name == "sched.plan.build"][-1]

    assert session("a") == ("full", "first")
    assert session("b")[0] in ("resume", "delta")
    held = PodInfo(_pod("held", cpu="1", priority=10))
    sched.queue.nominator.add_nominated_pod(held, "n3")
    assert session("c") == ("full", "nomination")
    assert sched.plan_build_cause == "nomination"
    assert sched.metrics.plan_rebuild_cause.value("nomination") == 1
    adopted = [s for name, s in rec.opened if name == "sched.plan.adopt"][-1]
    assert adopted["cause"] == "nomination"
    # the lane is in the plan now: nothing moved, nothing is rebuilt
    assert session("d")[0] in ("resume", "delta")
    sched.queue.nominator.delete_nominated_pod(held.pod)
    assert session("e") == ("full", "nomination")
    # another template's plan is still another pod's
    assert session("big", cpu="1", n=1) == ("full", "other_pod")
    assert sched.metrics.plan_rebuild_cause.value("nomination") == 2


def test_the_vector_diagnosis_counts_the_nominated_room():
    """A pod that fits one node only, on which a nominated pod of higher
    priority holds the room: the device finds no node, and the diagnosis
    made from the mirror says so for every node without the host rerun."""
    from kubernetes_tpu.models import TPUScheduler
    sched = TPUScheduler()
    cs = sched.clientset
    for i in range(3):
        cs.create_node(_node(f"n{i}"))
    for i in range(2):
        cs.create_pod(_pod(f"full-{i}", cpu="3500m"))
    sched.run_until_idle()
    free = next(n for n in ("n0", "n1", "n2")
                if n not in {p.node_name for p in cs.pods.values()})
    sched.queue.nominator.add_nominated_pod(
        PodInfo(_pod("held", cpu="3", priority=10)), free)
    cs.create_pod(_pod("late", cpu="2"))
    sched.run_until_idle()
    assert not cs.pods["late"].node_name
    assert sched.failures == 1 and sched.host_path_pods == 0
    # against a higher priority the room is not held
    cs.create_pod(_pod("urgent", cpu="2", priority=20))
    sched.run_until_idle()
    assert cs.pods["urgent"].node_name == free
    assert sched.host_path_pods == 0
