"""A dirty row crosses a session boundary once (PR 51): a clean session's end
adopts the rows it landed on from the live cache and refreshes no snapshot
(`TPUScheduler._close_session`); the cache keeps its dirty rows for the next
reader, and every reader of the snapshot makes its own refresh. Held here, at
toy size: a wave and its restore cost one refresh with rows to clone, not
two, and whoever comes after a clean end (a resumed session, a delta session,
a failed attempt's diagnosis, a preemptor's what-if, a pod on the host path)
sees the binds of the session before it, its placements equal to
`core.Scheduler(deterministic_ties=True)`."""

import pytest

from kubernetes_tpu.api.storage import (WAIT_FOR_FIRST_CONSUMER,
                                        PersistentVolumeClaim, StorageClass)
from kubernetes_tpu.api.types import Volume
from kubernetes_tpu.core.scheduler import Scheduler
from kubernetes_tpu.models.tpu_scheduler import TPUScheduler
from kubernetes_tpu.testing.annotations import StageAnnotations
from kubernetes_tpu.testing.wrappers import make_node, make_pod


def _node(name, cpu="1"):
    return make_node().name(name).capacity(
        {"cpu": cpu, "memory": "8Gi", "pods": 110}).zone(
            f"zone-{len(name) % 3}").obj()


def _pod(name, cpu="600m", priority=0):
    return make_pod().name(name).uid(name).req(
        {"cpu": cpu, "memory": "64Mi"}).priority(priority).obj()


def _pair(nodes=6, cpu="1", hints=False, max_batch=16):
    """(host oracle, device scheduler) over equal clusters; without the
    score hint every pod is placed by a device session."""
    host = Scheduler(deterministic_ties=True)
    dev = TPUScheduler(max_batch=max_batch)
    if not hints:
        dev._hints.enabled = False
    for s in (host, dev):
        for i in range(nodes):
            s.clientset.create_node(_node(f"node-{i}", cpu))
    dev.stages._annotation = StageAnnotations()
    return host, dev


def _both(host, dev, fn):
    for s in (host, dev):
        fn(s)
        s.run_until_idle()


def _state(s):
    """What a run left behind: every pod's node and every nomination."""
    return {p.name: (p.node_name, p.nominated_node_name)
            for p in s.clientset.pods.values()}


def _same(host, dev):
    assert _state(dev) == _state(host)
    assert (dev.scheduled, dev.failures) == (host.scheduled, host.failures)
    assert dev.host_path_pods == 0


def _said(dev, span, stat):
    return [stats[stat] for name, stats in dev.stages._annotation.opened
            if name == span and stat in stats]


def _refreshes(dev):
    """`Cache.update_snapshot` watched: the dirty rows each call found."""
    found = []
    refresh = dev.cache.update_snapshot

    def watched(snapshot):
        found.append(len(dev.cache._dirty))
        return refresh(snapshot)
    dev.cache.update_snapshot = watched
    return found


@pytest.mark.parametrize("hints", [False, True],
                         ids=["sessions_only", "a_hint_serves_the_tail"])
def test_a_wave_and_its_restore_clone_their_rows_once(hints):
    host, dev = _pair(nodes=12, cpu="8", hints=hints)
    # a restore outruns the journal, as a wave cell's 2,000 to 10,000
    # deletes do: every wave's first session builds in full
    dev.journal.cap = 8
    found = _refreshes(dev)
    for wave in range(3):
        before = len(found)
        _both(host, dev, lambda s: [s.clientset.create_pod(
            _pod(f"w{wave}-{i}", cpu="500m")) for i in range(30)])
        _same(host, dev)
        with_rows = [n for n in found[before:] if n]
        assert len(with_rows) == 1, found[before:]
        # the restore: every measured pod leaves, as the waves driver's does
        _both(host, dev, lambda s: [
            s.clientset.delete_pod(p) for p in list(s.clientset.pods.values())
            if p.name.startswith(f"w{wave}-")])
    assert dev.device_scheduled + dev.hint_hits == 90
    assert dev.plan_rebuilds_full == 3 and dev.plan_rebuilds_delta == 0
    # the first wave's build encoded the cluster; the later ones found the
    # restore's rows, their nodes the ones encoded, and wrote their columns
    encoded = _said(dev, "sched.plan.build", "rows_encoded")
    by_column = _said(dev, "sched.plan.build", "rows_by_column")
    assert encoded[0] == 12 and not any(encoded[1:])
    assert by_column[0] == 0 and sum(by_column) >= 2 * 12
    assert _said(dev, "sched.plan.adopt", "snapshot_refreshed") == [0] * len(
        _said(dev, "sched.plan.adopt", "rows_adopted"))
    assert sum(_said(dev, "sched.plan.adopt", "rows_adopted")) >= 12
    rows = dev.metrics.mirror_rows
    assert rows.value("encoded") == 12
    assert rows.value("by_column") == sum(by_column)
    assert rows.value("adopted") == sum(
        _said(dev, "sched.plan.adopt", "rows_adopted"))


def test_a_resumed_session_sees_the_binds_of_the_session_before():
    host, dev = _pair()
    _both(host, dev, lambda s: [s.clientset.create_pod(_pod(f"a-{i}"))
                                for i in range(4)])
    found = _refreshes(dev)
    _both(host, dev, lambda s: [s.clientset.create_pod(_pod(f"b-{i}"))
                                for i in range(4)])
    _same(host, dev)
    assert dev.plan_rebuilds_resume >= 1 and dev.plan_rebuilds_full == 1
    # two nodes were left: two of the four fit, two were diagnosed, on a
    # snapshot that the diagnosis refreshed for itself
    assert sum(bool(node) for node, _nom in _state(dev).values()) == 6
    assert dev.failures == 2 and any(found)


def test_a_delta_session_sees_the_binds_and_the_delete_between():
    host, dev = _pair()
    _both(host, dev, lambda s: [s.clientset.create_pod(_pod(f"a-{i}"))
                                for i in range(5)])
    _both(host, dev, lambda s: s.clientset.delete_pod(
        s.clientset.pods["a-2"]))
    _both(host, dev, lambda s: [s.clientset.create_pod(_pod(f"b-{i}"))
                                for i in range(3)])
    _same(host, dev)
    assert dev.plan_rebuilds_delta >= 1 and dev.plan_rebuilds_full == 1
    assert dev.failures == 1  # six nodes, seven pods of more than half one


def test_a_failed_attempts_diagnosis_straight_after_a_clean_end():
    host, dev = _pair(nodes=4)
    _both(host, dev, lambda s: [s.clientset.create_pod(_pod(f"a-{i}"))
                                for i in range(4)])
    assert dev.failures == 0
    _both(host, dev, lambda s: s.clientset.create_pod(_pod("late")))
    _same(host, dev)
    assert dev.failures == 1

    def why(s):
        return [e.message for e in s.recorder.events
                if e.reason == "FailedScheduling"]
    assert why(dev) == why(host)
    assert "0/4 nodes are available" in why(dev)[0]
    # and the queue parked it for the same plugin: every node was full
    parked = [{q.pod.name: sorted(q.unschedulable_plugins)
               for q in s.queue.unschedulable.values()} for s in (dev, host)]
    assert parked[0] == parked[1] == {"late": ["NodeResourcesFit"]}


def test_a_preemptors_plan_sees_the_victims_a_session_bound():
    host, dev = _pair(nodes=4)
    _both(host, dev, lambda s: [
        s.clientset.create_pod(_pod(f"low-{i}", cpu="400m", priority=-10))
        for i in range(8)])  # two a node, bound by a device session
    assert dev.device_scheduled == 8
    _both(host, dev, lambda s: [
        s.clientset.create_pod(_pod(f"high-{i}", cpu="700m", priority=10))
        for i in range(2)])
    _same(host, dev)
    assert all(node for name, (node, _nom) in _state(dev).items()
               if name.startswith("high-"))
    assert sorted(_state(dev)) == sorted(_state(host))  # the same victims
    assert len([n for n in _state(dev) if n.startswith("low-")]) < 8
    assert dev.metrics.preemption_dry_runs.value("device") >= 1


def test_a_pod_on_the_host_path_sees_the_binds_of_the_session_before():
    host, dev = _pair(nodes=3)
    _both(host, dev, lambda s: [s.clientset.create_pod(_pod(f"a-{i}"))
                                for i in range(2)])

    def claimed(s):
        cs = s.clientset
        cs.create_storage_class(StorageClass(
            name="wffc", provisioner="csi.example.com",
            volume_binding_mode=WAIT_FOR_FIRST_CONSUMER))
        cs.create_pvc(PersistentVolumeClaim.of("c", "1Gi",
                                               storage_class="wffc"))
        pod = _pod("claims")  # only the node no session landed on has room
        pod.volumes.append(Volume(name="data", pvc_name="c"))
        cs.create_pod(pod)
    _both(host, dev, claimed)
    assert _state(dev) == _state(host)
    assert dev.host_path_pods == 1 and dev.failures == 0
    taken = {node for node, _nom in _state(dev).values()}
    assert len(taken) == 3
