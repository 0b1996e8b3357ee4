"""The backlog a pop meets (PR 33): every `queue.pop` stage of the device path
opens with the active queue's depth as the pop begins, on the span and as a
stat of the profiler event. One stage a batch: the hint walk's per-pod pops
open no span and say nothing. What the pop accepted (PR 37): it closes with
`pods`, the pods it took into a device batch, and `run`, those of them taken
on the session template's verdict; the same two numbers move
`scheduler_queue_popped_pods_total{how}`."""

import pytest

from kubernetes_tpu.api.types import PodGroup
from kubernetes_tpu.core import FakeClientset
from kubernetes_tpu.models import TPUScheduler
from kubernetes_tpu.testing import make_node, make_pod
from kubernetes_tpu.testing.annotations import StageAnnotations


def _scheduler(monkeypatch, nodes=40, **kw):
    annotations = StageAnnotations()
    opened = annotations.opened
    cs = FakeClientset()
    sched = TPUScheduler(clientset=cs, **kw)
    monkeypatch.setattr(sched.stages, "_annotation", annotations)
    for i in range(nodes):
        cs.create_node(make_node().name(f"n{i}").capacity(
            {"cpu": 8, "memory": "16Gi", "pods": 110}).zone(f"z{i % 4}").obj())
    return sched, cs, opened


def _pops(opened):
    return [stats for name, stats in opened if name == "sched.queue.pop"]


@pytest.mark.parametrize("clones", [False, True],
                         ids=["built_one_by_one", "clones_of_a_template"])
def test_a_session_of_several_batches_opens_each_pop_with_the_depth_it_met(
        monkeypatch, clones):
    """100 plain pods on a scheduler that places 16 a batch: the cycle's pop
    meets all 100, each refill of the session 16 fewer, the pop that ends the
    session and the one that finds the loop idle meet none. Every pop says
    how many pods it took and how many of them as a run: all but the
    session's head where the pods are clones of one template, none where
    each was built alone."""
    sched, cs, opened = _scheduler(monkeypatch, max_batch=16)
    proto = make_pod().name("proto").req({"cpu": "100m"}).obj()
    for i in range(100):
        cs.create_pod(proto.clone_from_template(f"p{i}") if clones else
                      make_pod().name(f"p{i}").req({"cpu": "100m"}).obj())
    sched.run_until_idle()
    assert sched.scheduled == 100 and sched.host_path_pods == 0
    assert sched.device_batches == 7
    pops = _pops(opened)
    assert all(set(p) == {"backlog", "pods", "run", "narrowed"} for p in pops)
    assert not any(p["narrowed"] for p in pops)  # nobody is pinned here
    met = [p["backlog"] for p in pops]
    assert met[:7] == [100, 84, 68, 52, 36, 20, 4]
    assert set(met[7:]) == {0} and len(met) >= 8
    assert sched.stages.counts["queue.pop"] == len(met)
    assert [p["pods"] for p in pops[:7]] == [16] * 6 + [4]
    assert [p["run"] for p in pops[:7]] == (
        [15] + [16] * 5 + [4] if clones else [0] * 7)
    assert all(p["pods"] == p["run"] == 0 for p in pops[7:])
    assert sched.popped_pods == (
        {"run": 99, "single": 1} if clones else {"run": 0, "single": 100})
    exposed = sched.expose_metrics()
    for how, n in sched.popped_pods.items():
        line = f'scheduler_queue_popped_pods_total{{how="{how}"}} {float(n)}'
        assert (line in exposed) == bool(n)


def test_pods_decoded_from_the_wire_are_popped_one_by_one(monkeypatch):
    """A pod that came over HTTP is decoded alone and shares no signature
    holder with any other: every one takes the full check (`single`), as in
    the served cells."""
    from kubernetes_tpu.core.apiserver import pod_from_wire, pod_to_wire
    sched, cs, opened = _scheduler(monkeypatch, max_batch=16)
    proto = make_pod().name("proto").req({"cpu": "100m"}).obj()
    for i in range(40):
        cs.create_pod(pod_from_wire(pod_to_wire(
            proto.clone_from_template(f"p{i}"))))
    sched.run_until_idle()
    assert sched.scheduled == 40 and sched.host_path_pods == 0
    assert sched.popped_pods == {"run": 0, "single": 40}
    pops = _pops(opened)
    assert sum(p["pods"] for p in pops) == 40
    assert not any(p["run"] for p in pops)


def test_the_gang_session_says_it_too(monkeypatch):
    sched, cs, opened = _scheduler(monkeypatch)
    cs.create_pod_group(PodGroup(name="g", min_count=5))
    for i in range(5):
        pod = make_pod().name(f"g{i}").req({"cpu": "100m"}).obj()
        pod.pod_group = "g"
        cs.create_pod(pod)
    sched.run_until_idle()
    assert sched.scheduled == 5 and sched.host_path_pods == 0
    pops = _pops(opened)
    met = [p["backlog"] for p in pops]
    # the gang is one entity of the active queue
    assert met[0] == 1 and set(met[1:]) <= {0}
    # its five members went to a device pack, none on a template's verdict
    assert [(p["pods"], p["run"]) for p in pops][0] == (5, 0)
    assert sched.popped_pods == {"run": 0, "single": 5}


def test_the_hint_walks_pops_say_nothing(monkeypatch):
    """A second run of identical pods binds from the score hint, pod by pod:
    its pops are leaves of the stage table, with no span and no backlog."""
    sched, cs, opened = _scheduler(monkeypatch)
    for i in range(8):
        cs.create_pod(make_pod().name(f"a{i}").req({"cpu": "100m"}).obj())
    sched.run_until_idle()
    spans_before = len(_pops(opened))
    stages_before, hits = sched.stages.counts["queue.pop"], sched.hint_hits
    for i in range(8):
        cs.create_pod(make_pod().name(f"b{i}").req({"cpu": "100m"}).obj())
    sched.run_until_idle()
    assert sched.hint_hits - hits == 8 and sched.device_batches == 1
    # the walk's eight per-pod pops moved the table and opened no span; the
    # pops after it found an empty queue
    later = _pops(opened)[spans_before:]
    assert all(p == {"backlog": 0, "pods": 0, "run": 0, "narrowed": 0}
               for p in later)
    assert sched.stages.counts["queue.pop"] - stages_before >= 8 + len(later)
