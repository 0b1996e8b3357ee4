"""The batch tail (PR 35): a retired device batch that qualifies is committed
in passes over the batch (one assume, one bulk bind against the in-process
store, one settle) instead of one `_commit` call a pod, to the per-pod tail's
outcome. The per-pod tail is forced the thread-safe way: a clientset without
the bulk verb. No timing is asserted."""


import pytest

from kubernetes_tpu.core import FakeClientset
from kubernetes_tpu.core.metrics import Histogram
from kubernetes_tpu.models import TPUScheduler
from kubernetes_tpu.plugins.basic import DefaultBinder
from kubernetes_tpu.testing import make_node, make_pod
from kubernetes_tpu.testing.annotations import StageAnnotations


class _NoBulkVerb(FakeClientset):
    """The in-process store as it was before it had the verb."""
    bind_many = None


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def _plain(name):
    return make_pod().name(name).uid("u-" + name).req(
        {"cpu": "100m", "memory": "128Mi"})


def _anti(name):
    return _plain(name).label("color", "green").pod_affinity(
        "kubernetes.io/hostname", {"color": "green"}, anti=True)


def _preferred(name):
    return _plain(name).label("color", "red").pod_affinity(
        "kubernetes.io/hostname", {"color": "red"}, weight=1)


KINDS = {"plain": _plain, "required_anti_affinity": _anti,
         "preferred_affinity": _preferred}


def _cluster(cs_class, nodes=40, max_batch=16):
    clock = _Clock()
    cs = cs_class()
    sched = TPUScheduler(clientset=cs, max_batch=max_batch, now=clock)
    seen = []
    cs.on_pod_event(lambda kind, old, new: seen.append((kind, new.uid)))
    for i in range(nodes):
        cs.create_node(make_node().name(f"n{i}").capacity(
            {"cpu": 4, "memory": "32Gi", "pods": 110})
            .label("kubernetes.io/hostname", f"n{i}").zone(f"z{i % 4}").obj())
    return sched, cs, clock, seen


def _run_and_read_tails(monkeypatch, cs_class, pods, **cluster):
    """Schedule `pods` and return the `tail` each `sched.host.commit` stage
    opened with (the annotation's stats, as a profiler session gets them)."""
    annotations = StageAnnotations()
    opened = annotations.opened
    sched, cs, _clock, _seen = _cluster(cs_class, **cluster)
    monkeypatch.setattr(sched.stages, "_annotation", annotations)
    for p in pods:
        cs.create_pod(p.obj())
    sched.run_until_idle()
    return sched, cs, [stats["tail"] for name, stats in opened
                       if name == "sched.host.commit"]


def _cache_state(sched):
    cache = sched.cache
    return {
        "pod_states": {uid: (st.pod.name, st.pod.node_name, st.deadline,
                             st.binding_finished)
                       for uid, st in cache.pod_states.items()},
        "assumed": set(cache.assumed_pods),
        "nodes": {name: ([pi.pod.uid for pi in ni.pods],
                         ni.requested.milli_cpu, ni.requested.memory,
                         ni.non_zero_requested.milli_cpu)
                  for name, ni in cache.nodes.items()},
        "dirty": set(cache._dirty),
    }


def _account(sched, cs, seen):
    m = sched.metrics
    sched.expose_metrics()
    return {
        "placements": dict(cs.bindings),
        "cache": _cache_state(sched),
        "store": {uid: p.node_name for uid, p in cs.pods.items()},
        "rv_order": [uid for uid, _p in sorted(
            cs.pods.items(), key=lambda kv: kv[1].resource_version)],
        "events": list(seen),
        "counts": {
            "e2e": m.e2e_scheduling_duration.count(),
            "bind.post": m.pod_stage_duration.count("bind.post"),
            "queue.wait": m.pod_stage_duration.count("queue.wait"),
            "pod events": m.event_handling_duration.count("pod"),
            "request pods": m.bind_request_pods.value(),
        },
        "e2e_sum": m.e2e_scheduling_duration.sum(),
        "scheduled": (sched.scheduled, sched.device_scheduled,
                      sched.attempts, sched.failures, sched.host_path_pods),
        "recorder": sorted((e.object_key, e.reason, e.count, e.message)
                           for e in sched.recorder.events),
        "in_flight": dict(sched.queue._in_flight),
        "event_log": len(sched.queue._event_log),
        "moved": sched.queue.moved_count,
        "next_start": sched.next_start_node_index,
        "seq": sched.cluster_event_seq,
        "batches": sched.device_batches,
    }


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_batch_tail_commits_as_the_per_pod_tail_does(kind):
    """36 pods in batches of 16, 16 and 4 over 40 nodes, admitted 5 s before
    the loop runs: placements, cache, store, the events a second handler
    sees, the three histograms, the counters, the recorder and the queue end
    equal; what differs is which tail was counted and how many requests
    carried the binds."""
    n, sides = 36, {}
    for name, cs_class in (("batch", FakeClientset), ("single", _NoBulkVerb)):
        sched, cs, clock, seen = _cluster(cs_class)
        for i in range(n):
            cs.create_pod(KINDS[kind](f"p{i}").obj())
        clock.t += 5.0
        sched.run_until_idle()
        assert sched.api_dispatcher.mode == "inline"
        sides[name] = (sched, _account(sched, cs, seen))
    (batch, got), (single, want) = sides["batch"], sides["single"]
    assert len(want["placements"]) == n and want["scheduled"][:2] == (n, n)
    assert want["cache"]["assumed"] == set() and want["in_flight"] == {}
    for key in want:
        assert got[key] == want[key], key
    # every pod once in each histogram, its e2e ending as its request did
    assert got["counts"]["e2e"] == got["counts"]["bind.post"] == n
    assert got["e2e_sum"] == pytest.approx(5.0 * n)
    assert [e for e in got["events"] if e[0] == "update"] == [
        ("update", f"u-p{i}") for i in range(n)]
    for sched in (batch, single):
        assert sched.metrics.pod_stage_duration.sum("bind.post") > 0
        assert sched.metrics.event_handling_duration.sum("pod") > 0
    # which tail, and how the binds went out
    assert batch.commit_pods == {"batch": n, "single": 0}
    assert single.commit_pods == {"batch": 0, "single": n}
    requests = [(s.metrics.bind_requests.value("single"),
                 s.metrics.bind_requests.value("bulk"),
                 s.stages.counts["bind.post"], s.api_dispatcher.executed)
                for s in (batch, single)]
    assert requests == [(0, 3, 3, n), (n, 0, n, n)]


def test_the_commit_stage_opens_with_the_tail_it_takes(monkeypatch):
    """The attr rides the annotation (a stat of the profiler event): `batch`
    where every pod qualifies, `single` for a batch whose first pod the
    device could not place, and over a clientset without the verb."""
    sched, _cs, got = _run_and_read_tails(
        monkeypatch, FakeClientset, [_plain(f"p{i}") for i in range(20)],
        nodes=4)
    assert got == ["batch", "batch"]
    assert sched.commit_pods == {"batch": 20, "single": 0}
    # 4 nodes of 4 cpu hold 16 pods of 1 cpu: the four of the second batch
    # come back unplaced
    sched, _cs, got = _run_and_read_tails(
        monkeypatch, FakeClientset,
        [_plain(f"p{i}").req({"cpu": 1}) for i in range(20)], nodes=4)
    assert got == ["batch", "single"] and sched.scheduled == 16
    sched, _cs, got = _run_and_read_tails(
        monkeypatch, _NoBulkVerb, [_plain(f"p{i}") for i in range(20)],
        nodes=4)
    assert got == ["single", "single"]
    assert sched.commit_pods == {"batch": 0, "single": 20}


def test_a_run_that_ends_early_is_mixed_and_places_as_the_per_pod_tail(
        monkeypatch):
    """18 pods of 1 cpu in one batch of 32 over 4 nodes of 4 cpu: the device
    places 16, the 17th ends the run."""
    pods = [_plain(f"p{i}").req({"cpu": 1}) for i in range(18)]
    batch, bcs, btails = _run_and_read_tails(
        monkeypatch, FakeClientset, pods, nodes=4, max_batch=32)
    pods = [_plain(f"p{i}").req({"cpu": 1}) for i in range(18)]
    single, scs, stails = _run_and_read_tails(
        monkeypatch, _NoBulkVerb, pods, nodes=4, max_batch=32)
    assert btails[0] == "mixed" and stails[0] == "single"
    assert batch.commit_pods["batch"] == 16
    assert bcs.bindings == scs.bindings and len(bcs.bindings) == 16
    assert (batch.scheduled, batch.failures, batch.host_path_pods) == (
        single.scheduled, single.failures, single.host_path_pods)
    assert _cache_state(batch) == _cache_state(single)


# -- a bind refused inside the run ---------------------------------------------


def _doomed_run(cs_class, victim="p21"):
    """40 plain pods in batches of 16; `victim` (sixth of the second batch)
    leaves the store as its batch retires, and the event of that reaches
    the handlers once the batch is committed."""
    sched, cs, clock, seen = _cluster(cs_class)
    retired, requests = [], []
    commit = sched._commit_batch

    def doomed(b, res, fw, node_names, ok_rows, dirty_rows, *run):
        gone = (cs.pods.pop("u-" + victim)
                if victim in [q.pod.name for q in b] else None)
        before = (len(ok_rows), len(dirty_rows), sched.host_path_pods)
        invalidated = commit(b, res, fw, node_names, ok_rows, dirty_rows,
                             *run)
        retired.append(([q.pod.name for q in b], ok_rows[before[0]:],
                        dirty_rows[before[1]:], invalidated,
                        sched.host_path_pods - before[2]))
        if gone is not None:
            for h in cs._pod_handlers:
                h("delete", gone, gone)
        return invalidated
    sched._commit_batch = doomed
    if cs.bind_many is not None:
        bind_many = cs.bind_many

        def spy(pairs):
            out = bind_many(pairs)
            requests.append(([p.name for p, _n in pairs], list(out),
                             set(cs.bindings)))
            return out
        cs.bind_many = spy
    for i in range(40):
        cs.create_pod(_plain(f"p{i}").obj())
    clock.t += 5.0
    sched.run_until_idle()
    return sched, cs, seen, retired, requests


def test_a_pod_deleted_in_flight_ends_the_run_as_it_ends_the_per_pod_tail():
    batch, bcs, bseen, bretired, requests = _doomed_run(FakeClientset)
    single, scs, sseen, sretired, _none = _doomed_run(_NoBulkVerb)
    # per retired batch: ok_rows, dirty_rows, invalidated, pods host-pathed
    assert bretired == sretired
    names, ok, dirty, invalidated, host_pathed = bretired[1]
    assert names == [f"p{i}" for i in range(16, 32)]
    assert (len(ok), len(dirty), invalidated, host_pathed) == (5, 11, True, 10)
    assert batch.host_path_pods == single.host_path_pods
    got, want = _account(batch, bcs, bseen), _account(single, scs, sseen)
    # the stopped request carried the ten pods it never reached, and they
    # went out again one by one
    assert got["counts"].pop("request pods") == 40 + 10
    assert want["counts"].pop("request pods") == 40
    for key in want:
        assert got[key] == want[key], key
    assert len(got["placements"]) == 39 and "u-p21" not in got["placements"]
    # none left assumed without a bind, the refused one unwound
    assert got["cache"]["assumed"] == set()
    assert "u-p21" not in got["cache"]["pod_states"]
    assert batch.state_unwinds == single.state_unwinds == 1
    # the request that carried the victim stopped at it: five verdicts of
    # None and its KeyError, nothing after it bound by that request
    (pairs, verdicts, bound_then), = [r for r in requests if "p21" in r[0]]
    assert pairs == [f"p{i}" for i in range(16, 32)]
    assert verdicts[:5] == [None] * 5 and isinstance(verdicts[5], KeyError)
    assert len(verdicts) == 6
    assert not {f"u-p{i}" for i in range(22, 32)} & bound_then
    assert batch.commit_pods == {"batch": 16 + 6, "single": 0}


class _Conflict(Exception):
    code = 409

    def read(self):
        return b'{"error": "AlreadyBound"}'


class _AnswersEveryItem(FakeClientset):
    """A store whose bulk verb goes on after a refusal, as the apiserver's
    does: one verdict an item, the items after a 409 bound all the same."""

    def __init__(self, refuse):
        super().__init__()
        self.refuse = {refuse}

    def bind_many(self, pairs):
        out = []
        for pod, node in pairs:
            if pod.name in self.refuse:
                self.refuse.discard(pod.name)
                out.append(_Conflict())
                continue
            self.bind(pod, node)
            out.append(None)
        return out


def test_a_store_that_answers_every_item_has_every_verdict_honoured():
    """The ninth of 16 pods is refused with a 409 and the seven after it are
    bound by the same request: those seven are settled where they landed,
    the refused one goes to the backoff queue as a conflict and binds on
    its next try, the session invalidates, nothing stays assumed."""
    clock = _Clock()
    cs = _AnswersEveryItem("p8")
    sched = TPUScheduler(clientset=cs, max_batch=16, now=clock)
    for i in range(8):
        cs.create_node(make_node().name(f"n{i}").capacity(
            {"cpu": 4, "memory": "32Gi", "pods": 110}).obj())
    for i in range(16):
        cs.create_pod(_plain(f"p{i}").obj())
    assert sched.schedule_one()
    assert sched.scheduled == 15 and sched.bind_conflicts == 1
    assert sched.conflict_requeues == 1 and sched.state_unwinds == 1
    assert sched.cache.assumed_pods == set()
    assert "u-p8" not in cs.bindings and len(cs.bindings) == 15
    assert sched.commit_pods == {"batch": 16, "single": 0}
    assert sched.metrics.batch_cache_flushed.value("session_invalidated") == 1
    clock.t += 60.0
    sched.run_until_idle()
    assert sched.scheduled == 16 and len(cs.bindings) == 16
    assert sched.failures == 0 and sched.queue._in_flight == {}
    on = {}
    for uid, node in cs.bindings.items():
        on.setdefault(node, []).append(uid)
    assert {n: sorted(pi.pod.uid for pi in ni.pods)
            for n, ni in sched.cache.nodes.items() if ni.pods} == {
        n: sorted(uids) for n, uids in on.items()}
    assert sched.metrics.e2e_scheduling_duration.count() == 16


def test_the_stores_bulk_verb_alone_and_under_the_dispatchers_bulk_path():
    cs = FakeClientset()
    seen = []
    cs.on_pod_event(lambda kind, old, new: seen.append((kind, new.name)))
    pods = [cs.create_pod(_plain(f"p{i}").obj()) for i in range(5)]
    del seen[:]
    cs.pods.pop(pods[2].uid)
    pairs = [(p, f"n{i}") for i, p in enumerate(pods)]
    out = cs.bind_many(pairs)
    # one verdict a pair it reached, stopping at the first refusal
    assert out[:2] == [None, None] and isinstance(out[2], KeyError)
    assert len(out) == 3
    assert cs.bindings == {pods[0].uid: "n0", pods[1].uid: "n1"}
    assert seen == [("update", "p0"), ("update", "p1")]
    versions = [cs.pods[p.uid].resource_version for p in pods[:2]]
    assert versions == sorted(versions) and versions[0] > pods[4].resource_version
    assert cs.bind_many([]) == []

    # the thread mode's worker takes the verb, and sends on what a stopped
    # request never reached: queued binds are independent
    class _Handle:
        clientset = cs

    class _Call:
        def __init__(self, pair):
            self.bind_args = pair
    out = DefaultBinder(_Handle())._bulk_bind([_Call(p) for p in pairs])
    assert [type(r) for r in out] == [
        type(None), type(None), KeyError, type(None), type(None)]
    assert set(cs.bindings) == {p.uid for i, p in enumerate(pods) if i != 2}


# -- Histogram.observe_many ----------------------------------------------------


@pytest.mark.parametrize("labels", [(), ("bind.post",)])
def test_observe_many_is_that_many_observes(labels):
    names = ("stage",) if labels else ()
    one = Histogram("h_seconds", "help", names)
    many = Histogram("h_seconds", "help", names)
    first, last = one.buckets[0], one.buckets[-1]
    # below the first bucket, on bounds, between them, and in +Inf
    values = [0.0, first / 3, first, one.buckets[3], 0.0051, 0.7, last,
              last * 2, 1e9] * 7
    for v in values:
        one.observe(v, *labels)
    many.observe_many(values[:20], *labels)
    many.observe_many(values[20:], *labels)
    many.observe_many([], *labels)
    assert many._counts == one._counts
    assert many._totals == one._totals == {labels: len(values)}
    assert many._sums[labels] == pytest.approx(one._sums[labels], rel=1e-12)
    for q in (0.0, 0.5, 0.9, 0.99, 1.0):
        assert many.percentile(q, *labels) == one.percentile(q, *labels)
    strip = lambda lines: [l for l in lines if "_sum" not in l]  # noqa: E731
    assert strip(many.expose()) == strip(one.expose())
    # nothing observed: no series appears
    empty = Histogram("h_seconds", "help", names)
    empty.observe_many([], *labels)
    assert empty._totals == {} and empty.count(*labels) == 0
