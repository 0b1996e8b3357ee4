"""The mirror's census of allocatable shapes (PR 50): `NodeStateMirror.shapes`
maps (milli cpu, memory, pod count) to the rows that hold a node of that
shape, moved where `_encode_row` finds a row's allocatable other than it was
and where a row leaves, with no pass over the rows; `sched.plan.build` says
its size as `node_shapes` and the gauge `scheduler_plan_node_shapes` holds the
same. Held here to a count from the snapshot itself after each kind of
change."""

import collections

import pytest

from kubernetes_tpu.core import FakeClientset
from kubernetes_tpu.models import TPUScheduler
from kubernetes_tpu.ops.device_state import NodeStateMirror
from kubernetes_tpu.testing import make_node, make_pod
from kubernetes_tpu.testing.annotations import StageAnnotations

SMALL = {"cpu": "3920m", "memory": "13621Mi", "pods": 110}
LARGE = {"cpu": "15890m", "memory": "59824Mi", "pods": 110}
HUGE = {"cpu": "31850m", "memory": "121428Mi", "pods": 110}


def _node(name, capacity):
    return make_node().name(name).capacity(capacity).obj()


def _scheduler(*groups):
    """A scheduler over `groups` of (count, capacity), its stages heard."""
    cs = FakeClientset()
    sched = TPUScheduler(clientset=cs)
    rec = StageAnnotations()
    sched.stages._annotation = rec
    i = 0
    for count, capacity in groups:
        for _ in range(count):
            cs.create_node(_node(f"n{i}", capacity))
            i += 1
    return sched, cs, rec


def _from_the_snapshot(sched):
    """The census counted the slow way: one pass over the snapshot."""
    return dict(collections.Counter(
        (ni.allocatable.milli_cpu, ni.allocatable.memory,
         ni.allocatable.allowed_pod_number)
        for ni in sched.snapshot.node_info_list if ni.node is not None))


def _place(sched, cs, n, tag):
    """`n` pods with a preferred inter-pod term: no score hint serves them,
    so every call is a device session with a `plan.build` of its own."""
    for i in range(n):
        cs.create_pod(make_pod().name(f"{tag}-{i}").req(
            {"cpu": "100m", "memory": "500Mi"}).labels({"color": "red"})
            .pod_affinity("kubernetes.io/hostname", {"color": "red"},
                          weight=1).obj())
    sched.run_until_idle()
    assert sched.host_path_pods == 0


def _said(rec):
    return [stats["node_shapes"] for name, stats in rec.opened
            if name == "sched.plan.build"]


def _holds(sched, rec, want):
    assert sched.mirror.shapes == _from_the_snapshot(sched)
    assert len(sched.mirror.shapes) == want
    assert _said(rec)[-1] == want
    assert sched.metrics.plan_node_shapes.value() == want
    # one shape a valid row, none for the padding
    rows = sched.mirror._row_shape
    assert sum(s is not None for s in rows) == sum(sched.mirror.shapes.values())


def test_a_node_of_a_new_shape_joins_and_the_last_of_a_shape_leaves():
    sched, cs, rec = _scheduler((5, SMALL), (3, LARGE))
    _place(sched, cs, 4, "a")
    _holds(sched, rec, 2)
    assert sorted(sched.mirror.shapes.values()) == [3, 5]
    # a third pool of one node
    cs.create_node(_node("huge", HUGE))
    _place(sched, cs, 3, "b")
    _holds(sched, rec, 3)
    assert sched.mirror.shapes[31850, 121428 * 2**20, 110] == 1
    # one of three LARGE nodes leaves: the shape stays
    cs.delete_node("n6")
    _place(sched, cs, 3, "c")
    _holds(sched, rec, 3)
    assert sched.mirror.shapes[15890, 59824 * 2**20, 110] == 2
    # the last node of a shape leaves: the shape goes
    cs.delete_node("huge")
    _place(sched, cs, 3, "d")
    _holds(sched, rec, 2)
    assert (31850, 121428 * 2**20, 110) not in sched.mirror.shapes


def test_a_nodes_allocatable_is_updated():
    sched, cs, rec = _scheduler((4, SMALL))
    _place(sched, cs, 2, "a")
    _holds(sched, rec, 1)
    # one node grows: a second shape of one row
    cs.update_node(_node("n1", LARGE))
    _place(sched, cs, 2, "b")
    _holds(sched, rec, 2)
    assert sorted(sched.mirror.shapes.values()) == [1, 3]
    # its pod count alone is a shape too
    cs.update_node(_node("n2", dict(SMALL, pods=64)))
    _place(sched, cs, 2, "c")
    _holds(sched, rec, 3)
    # and back: the census forgets a shape nobody has
    cs.update_node(_node("n1", SMALL))
    cs.update_node(_node("n2", SMALL))
    _place(sched, cs, 2, "d")
    _holds(sched, rec, 1)
    assert sched.mirror.shapes == {(3920, 13621 * 2**20, 110): 4}


def test_a_restore_and_a_change_of_tier_leave_the_census_true():
    """Pods deleted and created again (a wave's restore) re-encode rows
    whose allocatable did not move: nothing counts twice. A mirror told to
    encode everything again (`invalidate`, after a device failure) and one
    whose capacity tier changed (every row encoded anew into fresh staging)
    read the same census."""
    sched, cs, rec = _scheduler((6, SMALL), (2, HUGE))
    _place(sched, cs, 12, "a")
    _holds(sched, rec, 2)
    before = dict(sched.mirror.shapes)
    for pod in [p for p in cs.pods.values() if p.name.startswith("a-")]:
        cs.delete_pod(pod)
    _place(sched, cs, 12, "again")
    _holds(sched, rec, 2)
    assert sched.mirror.shapes == before
    sched.mirror.invalidate()
    _place(sched, cs, 2, "b")
    _holds(sched, rec, 2)
    assert sched.mirror.shapes == before
    # past the 64-row tier: staging is allocated anew and every row encoded
    was = sched.mirror.np_cap
    for i in range(70):
        cs.create_node(_node(f"more-{i}", LARGE))
    _place(sched, cs, 2, "c")
    assert sched.mirror.np_cap > was
    _holds(sched, rec, 3)
    assert sched.mirror.shapes[15890, 59824 * 2**20, 110] == 70


def test_the_bare_mirror_counts_rows_that_leave_at_a_shrink():
    """`sync` over a shorter list invalidates the tail rows: their shapes
    leave the census with them."""
    sched, cs, _ = _scheduler((3, SMALL), (2, LARGE))
    sched._sync_mirror()
    infos = list(sched.snapshot.node_info_list)
    mirror = NodeStateMirror()
    mirror.sync(infos)
    assert sorted(mirror.shapes.values()) == [2, 3]
    mirror.sync(infos[:3])
    assert mirror.shapes == {(3920, 13621 * 2**20, 110): 3}
    mirror.sync(infos)
    assert sorted(mirror.shapes.values()) == [2, 3]


@pytest.mark.parametrize("groups, want", [
    (((8, SMALL),), 1),
    (((4, SMALL), (4, LARGE)), 2),
    (((2, SMALL), (2, LARGE), (2, HUGE), (2, dict(HUGE, pods=90000))), 4),
])
def test_every_plan_build_says_node_shapes_and_the_gauge_agrees(groups, want):
    sched, cs, rec = _scheduler(*groups)
    _place(sched, cs, 5, "a")
    _place(sched, cs, 5, "b")          # a second session: resumed or built
    said = _said(rec)
    assert len(said) >= 2 and set(said) == {want}
    builds = [stats for name, stats in rec.opened
              if name == "sched.plan.build"]
    for stats in builds:               # beside what the stage said before
        assert {"kind", "cause", "transfers", "node_shapes"} <= set(stats)
    assert sched.metrics.plan_node_shapes.value() == want
    sched.expose_metrics()
    assert f"scheduler_plan_node_shapes {want}" in sched.metrics.expose()
