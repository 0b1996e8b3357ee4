"""A run of identical pods is popped as a run (PR 37).

`PriorityQueue.pop_run(limit, accept)` hands out what `limit` calls of
`pop()` would, in that order and with the same stamps, on one reading of the
clock; `TPUScheduler._refill`, the one refill of both batch collectors, takes
a pod on its template's verdict where nothing per pod could change the
answer, and `_session_compatible` for every other entity. Placements are
the per-pod loop's, pod for pod."""

import pytest

from kubernetes_tpu.api.dra import (Device, DeviceRequest, ResourceClaim,
                                    ResourceSlice)
from kubernetes_tpu.api.types import PodGroup
from kubernetes_tpu.core import FakeClientset, Scheduler
from kubernetes_tpu.core.config import SchedulerConfiguration
from kubernetes_tpu.core.queue import QueuedPodGroupInfo, QueuedPodInfo
from kubernetes_tpu.core.registry import DEFAULT_PLUGINS, build_framework
from kubernetes_tpu.models import TPUScheduler
from kubernetes_tpu.testing import make_node, make_pod


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def _queue(fair, pop_from_backoff):
    """A host scheduler's queue (PrioritySort keys, gangs on) on a clock that
    only the test moves, filled with what a run has to get through: two
    templates of two priorities in two namespaces, interleaved as they were
    created, a pod group that enters as one entity, and four pods waiting out
    a backoff, two of them past it."""
    clock = _Clock()
    cs = FakeClientset()
    sched = Scheduler(
        clientset=cs, now=clock, config=SchedulerConfiguration(
            fair_tenant_dequeue=fair, tenant_weights={"a": 2.0, "b": 1.0}))
    q = sched.queue
    q.pop_from_backoff_q = pop_from_backoff
    low = make_pod().name("low").namespace("a").req({"cpu": "100m"}).obj()
    high = (make_pod().name("high").namespace("b").priority(10)
            .req({"cpu": "100m"}).obj())
    for i in range(4):      # the backoffQ: popped once, then a bind conflict
        cs.create_pod(low.clone_from_template(f"back{i}"))
    back = [q.pop() for _ in range(4)]
    for i, qpi in enumerate(back):
        clock.t += 0.25
        q.requeue_conflict(qpi)
        q.done(qpi.uid)
    cs.create_pod_group(PodGroup(name="g", namespace="a", min_count=3))
    for i in range(24):
        clock.t += 0.001
        cs.create_pod(low.clone_from_template(f"low{i}"))
        clock.t += 0.001
        cs.create_pod(high.clone_from_template(f"high{i}"))
        if 10 <= i < 13:
            member = low.clone_from_template(f"g{i}")
            member.pod_group = "g"
            cs.create_pod(member)
    # the first two backed-off pods are past their second; the others not
    clock.t = back[1].timestamp + q.backoff_duration(back[1]) + 0.01
    assert len(q.backoff_q) == 4 and len(q.active_q) == 49
    return q, clock


def _label(e):
    return e.uid if isinstance(e, QueuedPodGroupInfo) else e.pod.name


def _state(q, popped):
    """What the twins are compared by (uids come from a process-wide
    counter, so entities go by their names)."""
    label = {e.uid: _label(e) for e in popped}
    return {
        "order": [_label(e) for e in popped],
        "stamps": [(e.attempts, e.initial_attempt_timestamp,
                    None if isinstance(e, QueuedPodGroupInfo)
                    else e.pod.__dict__.get("_enqueued_at"))
                   for e in popped],
        "in_flight": {label[uid]: at for uid, at in q._in_flight.items()},
        "event_log": len(q._event_log),
        "left": (len(q.active_q), len(q.backoff_q)),
    }


def _single(q, n):
    out = []
    for _ in range(n):
        e = q.pop()
        if e is None:
            break
        out.append(e)
    return out


@pytest.mark.parametrize("pop_from_backoff", [True, False],
                         ids=["backoff_pops", "backoff_waits"])
@pytest.mark.parametrize("fair", [False, True], ids=["heap", "fair_tenants"])
def test_a_run_is_that_many_single_pops(fair, pop_from_backoff):
    """Runs of 7, 16 and the rest against as many `pop()` calls on a twin
    queue, with events logged and entities done in between: the same order
    (priority first, the tenants' weighted round robin under fairness, the
    group entity in its place, the backoffQ by its rule), the same stamps,
    the same places in the event log, and `done()` trims the log alike."""
    one, clock_one = _queue(fair, pop_from_backoff)
    run, clock_run = _queue(fair, pop_from_backoff)
    popped_one, popped_run = [], []
    for step, n in enumerate((7, 16, 100)):
        clock_one.t += 0.1
        clock_run.t += 0.1
        popped_one += _single(one, n)
        got, refused, now = run.pop_run(n, lambda e: True)
        assert refused is None and now == clock_run.t
        popped_run += got
        assert _state(run, popped_run) == _state(one, popped_one)
        # cluster events while those are in flight: they land in the shared
        # log once, and every later pop records the position it met
        for q in (one, run):
            for _ in range(3 if step else 4200):
                q.move_all_to_active_or_backoff("NodeAdd")
        assert _state(run, popped_run) == _state(one, popped_one)
        # entities finish: the first of the first run, then the rest of it,
        # and past 4,096 events the log is trimmed to what the entities
        # still in flight can reference, and their positions rebased
        for q, popped in ((one, popped_one), (run, popped_run)):
            for e in popped[:1] if not step else popped[1:7]:
                q.done(e.uid)
        assert _state(run, popped_run) == _state(one, popped_one)
        assert len(run._event_log) == (4200, 3, 6)[step]
    kinds = {type(e) for e in popped_run}
    assert kinds == {QueuedPodInfo, QueuedPodGroupInfo}
    # a third backoff ran out before the last run; the fourth is handed out
    # only where the queue pops from the backoffQ once the activeQ is empty
    assert len(popped_run) == 24 + 24 + 1 + (4 if pop_from_backoff else 3)
    assert len(run.backoff_q) == (0 if pop_from_backoff else 1)
    assert set(run._in_flight.values()) == {0, 3}
    if not fair:
        # queue-sort order: every priority-10 pod before any other
        names = [e.pod.name for e in popped_run]
        assert all(n.startswith("high") for n in names[:24])


@pytest.mark.parametrize("refuse_at", [0, 5, 23])
def test_the_refused_entity_is_popped_stamped_and_returned_apart(refuse_at):
    one, _ = _queue(False, True)
    run, _ = _queue(False, True)
    singles = _single(one, refuse_at + 1)
    seen = []

    def accept(e):
        seen.append(e)
        return len(seen) <= refuse_at

    got, refused, _now = run.pop_run(40, accept)
    assert len(got) == refuse_at and refused is seen[-1]
    assert _state(run, got + [refused]) == _state(one, singles)
    assert refused.uid in run._in_flight and refused.attempts == 1


def test_a_dropped_entity_does_not_count_against_the_limit():
    """`accept` answers None for an entity it has settled itself (a pod
    deleted while it was queued): it is neither in the run nor the end of
    it, and the run still grows to its limit."""
    run, _ = _queue(False, True)
    dropped = []

    def accept(e):
        if e.pod.name in ("high3", "high4"):
            run.done(e.uid)
            dropped.append(e.pod.name)
            return None
        return True

    got, refused, _now = run.pop_run(10, accept)
    assert refused is None and dropped == ["high3", "high4"]
    assert [e.pod.name for e in got] == [
        f"high{i}" for i in (0, 1, 2, 5, 6, 7, 8, 9, 10, 11)]
    assert set(run._in_flight) == {e.uid for e in got}


def test_an_empty_queue_gives_an_empty_run():
    q = Scheduler(clientset=FakeClientset()).queue
    got, refused, now = q.pop_run(8, lambda e: True)
    assert got == [] and refused is None and now > 0.0
    assert q.pop_run(0, lambda e: True)[:2] == ([], None)


@pytest.mark.parametrize("keyed", [True, False], ids=["sort_key", "less"])
def test_the_heap_orders_by_key_then_arrival(keyed):
    """The heap the run pop draws from, unchanged by it: entries compare by
    the sort key (or the comparison shim), then by arrival: under a sort key
    equal keys leave in arrival order, the entity itself is never compared,
    and a deleted or re-pushed entity is skipped where it lies."""
    from types import SimpleNamespace

    from kubernetes_tpu.core.queue import _Heap

    def entity(name, priority, ts):
        return SimpleNamespace(uid=name, priority=priority, timestamp=ts)

    def less(a, b):
        return (-a.priority, a.timestamp) < (-b.priority, b.timestamp)

    heap = _Heap(less, sort_key=(
        lambda e: (-e.priority, e.timestamp)) if keyed else None)
    for i in range(30):
        heap.push(entity(f"e{i}", i % 3, 5.0 if i < 20 else float(i)))
    assert len(heap) == 30 and "e7" in heap and heap.peek().uid == "e2"
    assert heap.delete("e5").uid == "e5" and heap.delete("e5") is None
    heap.push(entity("e2", 0, 1.0))     # an update: the old entry is void
    assert heap.get("e2").priority == 0 and len(heap) == 29
    order = []
    while True:
        e = heap.pop()
        if e is None:
            break
        order.append(e.uid)
    want = sorted((f"e{i}" for i in range(30) if i not in (2, 5)),
                  key=lambda n: (-(int(n[1:]) % 3),
                                 5.0 if int(n[1:]) < 20 else float(n[1:]),
                                 int(n[1:])))
    want.insert(want.index("e0"), "e2")  # priority 0 now, the oldest stamp
    assert len(heap) == 0 and heap.peek() is None
    if keyed:
        assert order == want
    else:
        # the shim has no equality: equal keys leave in no promised order
        def key(n):
            i = int(n[1:])
            return (0 if n == "e2" else -(i % 3),
                    1.0 if n == "e2" else 5.0 if i < 20 else float(i))
        assert sorted(order) == sorted(want)
        assert [key(n) for n in order] == [key(n) for n in want]


# -- the device path's refill ------------------------------------------------


def _cluster(cls, nodes=12, gpus=False, **kw):
    cs = FakeClientset()
    if cls is Scheduler:
        kw.pop("max_batch", None)
        kw.setdefault("deterministic_ties", True)
    if gpus:
        plugins = DEFAULT_PLUGINS + (("DynamicResources", 0),)
        kw["profile_factory"] = lambda h: {
            "default-scheduler": build_framework(h, plugins=plugins)}
    sched = cls(clientset=cs, **kw)
    for i in range(nodes):
        cs.create_node(make_node().name(f"n{i}").capacity(
            {"cpu": 8, "memory": "16Gi", "pods": 110}).zone(f"z{i % 4}").obj())
        if gpus:
            cs.create_resource_slice(ResourceSlice(
                node_name=f"n{i}", driver="gpu.example.com",
                devices=[Device(name=f"n{i}-gpu{j}") for j in range(2)]))
    return sched, cs


def _plain(cs, sched):
    proto = make_pod().name("proto").req({"cpu": "100m"}).obj()
    for i in range(300):
        cs.create_pod(proto.clone_from_template(f"p{i}"))
    return {"run": 299, "single": 1}


def _two_templates_by_priority(cs, sched):
    """Two templates whose pods are created in turn; the queue hands out the
    higher priority first, so each template is a session of its own: the
    head of each through the full check, the others on its verdict."""
    low = make_pod().name("low").req({"cpu": "100m"}).obj()
    high = make_pod().name("high").priority(5).req({"cpu": "200m"}).obj()
    for i in range(40):
        cs.create_pod(low.clone_from_template(f"low{i}"))
        cs.create_pod(high.clone_from_template(f"high{i}"))
    return {"run": 78, "single": 2}


def _two_templates_one_signature(cs, sched):
    """Two templates equal in everything the signature covers: one session
    takes both, the second template's pods through `_session_compatible`
    (another holder than the head's), one by one."""
    a = make_pod().name("a").req({"cpu": "100m"}).obj()
    b = make_pod().name("b").req({"cpu": "100m"}).obj()
    for i in range(20):
        cs.create_pod(a.clone_from_template(f"a{i}"))
        cs.create_pod(b.clone_from_template(f"b{i}"))
    return {"run": 19, "single": 21}


def _group_mid_run(cs, sched):
    proto = make_pod().name("proto").req({"cpu": "100m"}).obj()
    cs.create_pod_group(PodGroup(name="g", min_count=3))
    for i in range(30):
        cs.create_pod(proto.clone_from_template(f"p{i}"))
        if 10 <= i < 13:
            member = proto.clone_from_template(f"g{i}")
            member.pod_group = "g"
            cs.create_pod(member)
    # two plain sessions around the gang's own: two heads, three members
    return {"run": 28, "single": 5}


def _no_holder(cs, sched):
    """Pods built one by one, as every pod decoded from the wire is: no
    shared holder, so every one takes the full check."""
    for i in range(40):
        cs.create_pod(make_pod().name(f"p{i}").req({"cpu": "100m"}).obj())
    return {"run": 0, "single": 40}


def _claim_clone(cs, sched):
    """Clones of one template, three of which carry a resource claim of
    their own: the memo does not cover claims, so those three never join on
    the template's verdict (each ends the plain session; the claim pods make
    sessions of their own shape)."""
    proto = make_pod().name("proto").req({"cpu": "100m"}).obj()
    for i in range(30):
        pod = proto.clone_from_template(f"p{i}")
        if i in (7, 8, 20):
            cs.create_resource_claim(ResourceClaim(
                name=f"c{i}", requests=[DeviceRequest(count=1)]))
            pod.resource_claims = [f"c{i}"]
        cs.create_pod(pod)
    return None


SCENARIOS = {
    "plain_300": (_plain, {}),
    "two_templates_by_priority": (_two_templates_by_priority, {}),
    "two_templates_one_signature": (_two_templates_one_signature, {}),
    "group_mid_run": (_group_mid_run, {}),
    "no_holder": (_no_holder, {}),
    "claim_clone": (_claim_clone, {"gpus": True}),
}


@pytest.mark.parametrize("fair", [False, True], ids=["heap", "fair_tenants"])
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_the_run_places_every_pod_where_the_per_pod_loop_does(scenario, fair):
    """At `max_batch=16` (19 refills for 300 pods) against the sequential
    host scheduler with deterministic ties, as the device tests compare."""
    fill, cluster = SCENARIOS[scenario]
    config = dict(config=SchedulerConfiguration(fair_tenant_dequeue=True)) \
        if fair else {}
    host, cs_h = _cluster(Scheduler, **cluster, **config)
    fill(cs_h, host)
    host.run_until_idle()
    dev, cs_d = _cluster(TPUScheduler, max_batch=16, **cluster, **config)
    want = fill(cs_d, dev)
    dev.run_until_idle()
    placed_h = {p.name: p.node_name for p in cs_h.pods.values()}
    placed_d = {p.name: p.node_name for p in cs_d.pods.values()}
    assert placed_d == placed_h and all(placed_d.values())
    assert dev._holdover is None and not dev.queue._in_flight
    popped = dev.popped_pods
    if want is not None:
        assert popped == want
        assert dev.host_path_pods == 0
    else:
        assert popped["run"] == 27 - 3 and popped["single"] >= 3 + 3
    assert sum(popped.values()) + dev.host_path_pods == len(placed_d)
    # every pod's wait was observed once, whichever way it was accepted (a
    # gang's members wait and record as one entity, not here)
    assert dev.metrics.pod_stage_duration.count("queue.wait") == sum(
        not p.pod_group for p in cs_d.pods.values())


def test_the_refused_entity_lands_in_the_holdover_and_heads_the_next_session():
    """Twenty clones, a pod of another shape, twenty more: the refill that
    meets the odd pod keeps it, popped and in flight, and the next cycle's
    batch starts with it without another pop."""
    dev, cs = _cluster(TPUScheduler, max_batch=64)
    proto = make_pod().name("proto").req({"cpu": "100m"}).obj()
    for i in range(20):
        cs.create_pod(proto.clone_from_template(f"a{i}"))
    cs.create_pod(make_pod().name("odd").req({"cpu": "300m"})
                  .node_selector({"topology.kubernetes.io/zone": "z1"}).obj())
    for i in range(20):
        cs.create_pod(proto.clone_from_template(f"b{i}"))
    with dev._pop_stage() as took:
        fw, batch, reason = dev._collect_batch(took)
    assert reason is None and [q.pod.name for q in batch] == [
        f"a{i}" for i in range(20)]
    held = dev._holdover
    assert held.pod.name == "odd" and held.attempts == 1
    assert held.uid in dev.queue._in_flight
    assert held.pod.__dict__["_enqueued_at"] == held.enqueued_at
    assert len(dev.queue.active_q) == 20
    assert dev.popped_pods == {"run": 19, "single": 1}
    # a refill of the same session meets the holdover again and takes nothing
    with dev._pop_stage() as took:
        assert dev._collect_session_batch(
            fw, fw.sign_pod(batch[0].pod), took) == []
    assert dev._holdover is held and len(dev.queue.active_q) == 20
    dev.run_device_session(fw, batch)
    with dev._pop_stage() as took:
        fw2, batch2, reason2 = dev._collect_batch(took)
    assert [q.pod.name for q in batch2] == ["odd"] and batch2[0] is held
    assert dev._holdover.pod.name == "b0" and held.attempts == 1
    dev.run_device_session(fw2, batch2)
    dev.run_until_idle()
    assert all(p.node_name for p in cs.pods.values())
    (odd,) = [p for p in cs.pods.values() if p.name == "odd"]
    assert odd.node_name in ("n1", "n5", "n9")      # its zone's nodes
    # each pod's queue wait observed once, the holdover's at its pop
    assert dev.metrics.pod_stage_duration.count("queue.wait") == 41


@pytest.mark.parametrize("how", ["deleting", "already_placed"])
def test_a_pod_that_must_not_be_scheduled_is_settled_and_dropped(how):
    """A pod deleted (finalizers pending) or placed by the cache while it
    waited is skipped with `queue.done`, inside a run as `_pop` skips it
    alone: never in a batch, never dispatched, and the run goes on."""
    dev, cs = _cluster(TPUScheduler, max_batch=16)
    proto = make_pod().name("proto").req({"cpu": "100m"}).obj()
    pods = [proto.clone_from_template(f"p{i}") for i in range(40)]
    for p in pods:
        cs.create_pod(p)
    victims = [pods[5], pods[21]]
    for p in victims:
        if how == "deleting":
            p.deletion_ts = 1.0
        else:
            dev.cache.pod_states[p.uid] = object()
    with dev._pop_stage() as took:
        fw, batch, _reason = dev._collect_batch(took)
    # sixteen pods and the dropped one: the drop does not shorten the batch
    assert [q.pod.name for q in batch] == [
        f"p{i}" for i in range(17) if i != 5]
    assert victims[0].uid not in dev.queue._in_flight
    dev.run_device_session(fw, batch)
    dev.run_until_idle()
    if how == "already_placed":
        for p in victims:
            del dev.cache.pod_states[p.uid]
    unbound = [p.name for p in cs.pods.values() if not p.node_name]
    assert unbound == ["p5", "p21"] and not dev.queue._in_flight
    assert dev.popped_pods == {"run": 37, "single": 1}


def test_a_priority_change_between_two_runs_takes_the_full_check():
    """The template's verdict reads `priority` per pod: a clone whose
    priority was set after stamping is no member on that verdict."""
    dev, cs = _cluster(TPUScheduler, max_batch=16)
    proto = make_pod().name("proto").req({"cpu": "100m"}).obj()
    for i in range(20):
        pod = proto.clone_from_template(f"p{i}")
        if i == 12:
            pod.priority = 0        # equal: the verdict holds
        if i == 13:
            pod.nominated_node_name = "n3"
        cs.create_pod(pod)
    dev.run_until_idle()
    assert all(p.node_name for p in cs.pods.values())
    # the nominated pod ends the run and heads a batch of its own: its node
    # first and alone, on the device path since PR 43 (it took the host path
    # until then)
    assert dev.host_path_pods == 0
    assert dev.metrics.nominated_evaluations.value("bound") == 1
    assert {p.name: p.node_name for p in cs.pods.values()}["p13"] == "n3"
    assert dev.popped_pods == {"run": 17, "single": 3}


def test_sampled_pods_get_the_same_rows_after_the_run():
    """With every pod sampled: one queue.admission and one queue.wait row a
    pod, `attempts` 1, each batch's contexts in the batch's order."""
    from kubernetes_tpu.core.spans import SpanRecorder, set_default_tracer
    tracer = SpanRecorder(sample_n=1, proc="t", enabled=True)
    set_default_tracer(tracer)
    try:
        dev, cs = _cluster(TPUScheduler, max_batch=16)
        assert dev.tracer is tracer
        proto = make_pod().name("proto").req({"cpu": "100m"}).obj()
        for i in range(40):
            cs.create_pod(proto.clone_from_template(f"p{i}"))
        with dev._pop_stage() as took:
            fw, batch, _reason = dev._collect_batch(took)
        assert len(batch.sampled) == 16
        assert batch.sampled_at == list(range(16))
        dev.run_device_session(fw, batch)
        dev.run_until_idle()
    finally:
        set_default_tracer(None)
    rows = list(tracer.ring)
    for name in ("queue.admission", "queue.wait"):
        assert sum(r["name"] == name for r in rows) == 40
    assert {r["attrs"]["attempts"] for r in rows
            if r["name"] == "queue.wait"} == {1}
    assert sum(r["name"] == "pod.e2e" for r in rows) == 40
