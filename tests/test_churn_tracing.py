"""The tracing a churn wave needs (PR 40): the loop stage
`postfilter.preempt` around a failed attempt's PostFilter with the dry run's
engine and parts said on it, the `cause` of a full plan build on `plan.build`
and on its session's `plan.adopt`, the wait of a node event that another
thread parked, and (PR 41) the sibling score hints that took a session in at
its adoption. No timing is asserted."""

import threading

import pytest

from kubernetes_tpu.core import FakeClientset, Scheduler, spans
from kubernetes_tpu.testing.annotations import StageAnnotations
from kubernetes_tpu.testing.wrappers import make_node, make_pod


def _node(name, cpu="4"):
    return make_node().name(name).capacity(
        {"cpu": cpu, "memory": "32Gi", "pods": 110}).zone("zone-0").obj()


def _pod(name, cpu="100m", priority=0):
    return (make_pod().name(name).uid(name)
            .req({"cpu": cpu, "memory": "500Mi"}).priority(priority).obj())


def _cluster(sched, nodes=8, pods=12):
    cs = sched.clientset
    for i in range(nodes):
        cs.create_node(_node(f"n{i}"))
    for i in range(pods):
        cs.create_pod(_pod(f"p{i}"))
    sched.run_until_idle()
    return cs


def test_the_stage_is_pinned_and_only_a_stage_that_is_open_and_heard_is_told():
    assert "postfilter.preempt" in spans.STAGES
    assert "postfilter.preempt" in spans.LOOP_STAGES
    ledger = spans.StageLedger(spans.SpanRecorder(sample_n=1))
    ledger._annotation = StageAnnotations()
    assert ledger.heard("postfilter.preempt") is None     # nothing is open
    with ledger.stage("host.commit") as outer:
        # another caller of the dry run: the stats are not the commit's
        assert ledger.heard("postfilter.preempt") is None
        with ledger.stage("postfilter.preempt") as inner:
            ledger.heard("postfilter.preempt").say(engine="device", rows=8)
        assert ledger.heard("host.commit") is outer
    assert inner.attrs == {"engine": "device", "rows": 8}
    assert outer.attrs == {}
    assert ledger.counts["postfilter.preempt"] == 1
    # a child: its seconds leave the commit's self time, the sum stays
    assert ledger.seconds["host.commit"] >= 0
    # nobody listens (no profiler, no recorder): no stats, so no clocks
    quiet = spans.StageLedger(spans.SpanRecorder(enabled=False))
    with quiet.stage("postfilter.preempt"):
        assert quiet.heard("postfilter.preempt") is None


def test_the_host_schedulers_failed_attempt_opens_the_stage_and_says_host():
    sched = Scheduler(deterministic_ties=True)
    rec = sched.stages._annotation = StageAnnotations()
    cs = _cluster(sched)
    before = sched.stages.counts["postfilter.preempt"]
    cs.create_pod(_pod("large", cpu="9", priority=10))
    sched.run_until_idle()
    assert sched.failures == 1 and not cs.pods["large"].node_name
    assert sched.stages.counts["postfilter.preempt"] == before + 1
    said = [stats for name, stats in rec.opened
            if name == "sched.postfilter.preempt"]
    assert len(said) == 1
    assert said[0]["engine"] == "host" and said[0]["candidates"] == 0
    assert said[0]["host_ms"] >= 0
    # a pod that may preempt nobody says so and runs no dry run
    never = _pod("never", cpu="9", priority=10)
    never.preemption_policy = "Never"
    cs.create_pod(never)
    sched.run_until_idle()
    said = [stats for name, stats in rec.opened
            if name == "sched.postfilter.preempt"]
    assert said[-1] == {"engine": "none"}


def test_the_device_schedulers_dry_run_says_its_parts_and_its_shapes():
    from kubernetes_tpu.models import TPUScheduler
    sched = TPUScheduler()
    rec = sched.stages._annotation = StageAnnotations()
    cs = _cluster(sched)
    evals = sched.preemption_device_evals
    cs.create_pod(_pod("large", cpu="9", priority=10))
    sched.run_until_idle()
    assert sched.failures == 1 and not cs.pods["large"].node_name
    assert sched.preemption_device_evals == evals + 1
    said = [stats for name, stats in rec.opened
            if name == "sched.postfilter.preempt"][-1]
    assert said["engine"] == "device" and said["candidates"] == 0
    for part in ("victims_ms", "plan_ms", "dispatch_ms", "fetch_ms"):
        assert said[part] >= 0, part
    assert said["rows"] >= 8 and said["k"] == 8 and said["r"] >= 2
    # the stage lies under the commit of the batch that failed, and the
    # table's rows still sum: nothing is counted twice
    names = [name for name, _ in rec.opened]
    at = names.index("sched.postfilter.preempt")
    assert "sched.host.commit" in names[:at]


def test_a_full_build_says_why_it_is_full_and_its_adoption_says_it_too():
    from kubernetes_tpu.models import TPUScheduler
    sched = TPUScheduler()
    rec = sched.stages._annotation = StageAnnotations()
    cs = sched.clientset
    for i in range(8):
        cs.create_node(_node(f"n{i}"))

    def builds():
        return [(stats.get("kind"), stats.get("cause"))
                for name, stats in rec.opened if name == "sched.plan.build"]

    def session(prefix, cpu="100m", priority=0, n=4):
        for i in range(n):
            cs.create_pod(_pod(f"{prefix}{i}", cpu=cpu, priority=priority))
        sched.run_until_idle()

    session("a")
    assert builds()[-1] == ("full", "first")
    cs.create_node(_node("late"))
    session("b")
    assert builds()[-1] == ("full", "structural")
    cs.delete_node("late")
    session("c")
    assert builds()[-1] == ("full", "structural")
    # another template's session takes the one kept plan
    session("big", cpu="1", priority=5, n=1)
    assert builds()[-1] == ("full", "other_pod")
    assert sched.plan_build_cause == "other_pod"
    adopts = [stats for name, stats in rec.opened
              if name == "sched.plan.adopt"]
    assert [(a["kind"], a["cause"]) for a in adopts] == builds()
    text = sched.expose_metrics()
    for cause, builds_of in (("first", 1), ("structural", 2),
                             ("other_pod", 1)):
        assert (f'scheduler_plan_rebuild_cause_total{{cause="{cause}"}} '
                f'{float(builds_of)}') in text, cause
    assert sched.plan_rebuilds_full == 4


def test_an_adoption_says_the_sibling_hints_that_absorbed_its_session():
    """A churn wave's turns at toy size (PR 41): plain pods, then a node and
    a pod no node can hold, plain pods, both deleted, plain pods. Two pod
    templates take turns, so at most session ends the other template's
    score hint is live: `sched.plan.adopt` says how many such siblings took
    the session in (`siblings`) and over how many rows (`sibling_rows`),
    and `scheduler_hint_sibling_absorbed_total` counts the same. A sibling
    captured on another row set (the node came or went in between) is
    dropped instead, reason `cross_reencode`; there is no scalar pass left
    to fall back to, so nothing counts one."""
    from kubernetes_tpu.models import TPUScheduler
    sched = TPUScheduler()
    rec = sched.stages._annotation = StageAnnotations()
    cs = sched.clientset
    for i in range(8):
        cs.create_node(_node(f"n{i}"))

    def plain(prefix):
        for i in range(4):
            cs.create_pod(_pod(f"{prefix}{i}"))
        sched.run_until_idle()

    def said():
        return [(a.get("siblings"), a.get("sibling_rows"))
                for name, a in rec.opened if name == "sched.plan.adopt"]

    plain("a")                                  # the only hint: no sibling
    for step in (1, 3):
        cs.create_node(_node(f"churn-node-{step}"))
        cs.create_pod(_pod(f"churn-pod-{step}", cpu="9", priority=10))
        sched.run_until_idle()                  # its session places nothing
        assert not cs.pods[f"churn-pod-{step}"].node_name
        plain(f"b{step}")                       # 9 rows, as the churn pod's
        cs.delete_node(f"churn-node-{step}")
        cs.delete_pod(cs.pods[f"churn-pod-{step}"])
        plain(f"c{step}")                       # 8 rows again
    assert sched.failures == 2 and sched.host_path_pods == 0
    assert said() == [
        (0, 0),             # a
        (0, 0), (1, 9),     # the churn pod drops a's hint (8 rows); b1 is taken
        (0, 0),             # c1 drops the churn pod's (9 rows)
        (0, 0), (1, 9),     # the same, second step
        (0, 0)]
    absorbed = sched.metrics.hint_sibling_absorbed
    assert absorbed.value("siblings") == 2 and absorbed.value("rows") == 18
    inv = sched.metrics.hint_cache_invalidations
    assert inv.value("cross_reencode") == 4
    text = sched.expose_metrics()
    assert 'scheduler_hint_sibling_absorbed_total{what="rows"} 18.0' in text
    assert 'scheduler_hint_sibling_absorbed_total{what="siblings"} 2.0' in text
    assert sorted(absorbed._values) == [("rows",), ("siblings",)]
    # nobody listens: the series still counts, the stage is told nothing
    quiet = TPUScheduler()
    for i in range(8):
        quiet.clientset.create_node(_node(f"n{i}"))
    for prefix, cpu in (("a", "100m"), ("b", "200m")):
        for i in range(4):
            quiet.clientset.create_pod(_pod(f"{prefix}{i}", cpu=cpu))
        quiet.run_until_idle()
    assert quiet.metrics.hint_sibling_absorbed.value("rows") == 8


def test_a_parked_node_event_observes_its_own_wait_as_it_is_replayed():
    sched = Scheduler(deterministic_ties=True)
    cs = sched.clientset
    hist = sched.metrics.cluster_event_wait
    cs.create_node(_node("inline"))                # the loop's own thread
    assert hist.count("node") == 0
    worker = threading.Thread(target=lambda: (
        cs.create_node(_node("parked-0")), cs.create_pod(_pod("p0")),
        cs.create_node(_node("parked-1"))))
    worker.start()
    worker.join()
    assert set(sched.cache.nodes) >= {"inline"} and len(sched._event_inbox) == 3
    assert "parked-0" not in sched.cache.nodes
    assert sched.drain_event_inbox() == 3
    assert {"parked-0", "parked-1"} <= set(sched.cache.nodes)
    # one observation a node event, none for the pod between them
    assert hist.count("node") == 2 and hist.sum("node") > 0
    assert sched.metrics.inbox_oldest_wait.count() == 1
    text = sched.expose_metrics()
    assert 'scheduler_cluster_event_wait_seconds_count{kind="node"} 2' in text


def _backlog(sched, n):
    cs = sched.clientset
    for i in range(30):
        cs.create_node(make_node().name(f"n{i}").capacity(
            {"cpu": "64", "memory": "256Gi", "pods": 110}).zone("zone-0").obj())
    proto = make_pod().name("proto").req({"cpu": "10m", "memory": "16Mi"}).obj()
    for i in range(n):
        pod = proto.clone_from_template(f"p{i}")
        pod.uid = pod.name
        cs.create_pod(pod)
    return cs


def test_a_node_parked_under_a_backlog_is_taken_between_batches():
    """As counts of work: a session that finds a cluster event parked stops
    refilling, retires what is in flight and ends as a session that ran dry;
    the next turn replays the event with an empty pipeline and builds the
    plan anew (`structural`). Before PR 40 the event waited for the whole
    backlog and the batch in flight when it was seen took the host path (52
    pods here)."""
    from kubernetes_tpu.core.cache import EV_STRUCTURAL
    from kubernetes_tpu.models import TPUScheduler
    sched = TPUScheduler()
    rec = sched.stages._annotation = StageAnnotations()
    n = 2 * sched.max_batch + 52
    cs = _backlog(sched, n)
    seen = {"dispatches": 0, "bound_when_parked": None}

    def at(point):
        if point != "dispatch":
            return
        seen["dispatches"] += 1
        if seen["dispatches"] == 2:         # two batches out, one more to pop
            seen["bound_when_parked"] = sched.scheduled
            worker = threading.Thread(
                target=lambda: cs.create_node(_node("late")))
            worker.start()
            worker.join()

    sched._fault_hook = at
    assert sched.cluster_events_parked == 0
    assert sched.schedule_one()             # the turn ends with the pipeline
    assert seen["dispatches"] == 2 and seen["bound_when_parked"] == 0
    assert sched.scheduled == 2 * sched.max_batch and len(sched.queue) == 52
    assert sched.cluster_events_parked == 1 and "late" not in sched.cache.nodes
    assert sched.schedule_one()             # the event first, then the rest
    assert sched.cluster_events_parked == 0 and "late" in sched.cache.nodes
    assert sched.scheduled == n and len(sched.queue) == 0
    assert seen["dispatches"] == 3
    assert sched.metrics.cluster_event_wait.count("node") == 1
    assert any(e.kind == EV_STRUCTURAL and e.key == "late"
               for e in sched.journal.since(0) or ())
    assert sched.host_path_pods == 0
    builds = [(st.get("kind"), st.get("cause"))
              for name, st in rec.opened if name == "sched.plan.build"]
    assert builds == [("full", "first"), ("full", "structural")]


def test_a_sessions_own_drain_stops_in_front_of_a_parked_node_event():
    """The queue runs dry with a batch in flight and the inbox holds pods, a
    node, more pods: the session's drain replays the pods in front of the
    node and leaves the node and what is behind it to the turn's drain."""
    sched = Scheduler(deterministic_ties=True)
    cs = sched.clientset
    worker = threading.Thread(target=lambda: (
        cs.create_pod(_pod("a")), cs.create_node(_node("held")),
        cs.create_pod(_pod("b"))))
    worker.start()
    worker.join()
    assert sched.drain_event_inbox(hold_cluster_events=True) == 1
    assert len(sched._event_inbox) == 2 and sched.cluster_events_parked == 1
    assert sched.drain_event_inbox(hold_cluster_events=True) == 0
    assert "held" not in sched.cache.nodes
    assert sched.drain_event_inbox() == 2
    assert "held" in sched.cache.nodes and sched.cluster_events_parked == 0
    # the held event's wait runs from its own park, not from the first drain
    assert sched.metrics.cluster_event_wait.count("node") == 1


def test_parks_from_many_threads_and_replays_by_the_loop_lose_no_count():
    """`cluster_events_parked` is parks less replays: the parkers count under
    their lock, the loop counts alone. More threads than cores park nodes
    while this thread drains, on a switch interval short enough to split a
    read-modify-write: a lost update would leave the count off zero for
    good, and every later session would stop at its first batch."""
    import sys
    sched = Scheduler(deterministic_ties=True)
    cs = sched.clientset
    threads, each = 16, 40

    def park(t):
        for i in range(each):
            cs.create_node(_node(f"t{t}-n{i}"))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=park, args=(t,))
                   for t in range(threads)]
        for w in workers:
            w.start()
        while any(w.is_alive() for w in workers):
            sched.drain_event_inbox()
            assert sched.cluster_events_parked >= 0
        for w in workers:
            w.join(timeout=30)
            assert not w.is_alive()
    finally:
        sys.setswitchinterval(interval)
    sched.drain_event_inbox()
    assert sched._cluster_parks == sched._cluster_replays == threads * each
    assert sched.cluster_events_parked == 0
    assert len(sched.cache.nodes) == threads * each
    assert sched.metrics.cluster_event_wait.count("node") == threads * each
