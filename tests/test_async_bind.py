"""Queued binds (core/api_dispatcher.py thread mode + plugins/basic.py
DefaultBinder): the dispatcher's mode follows the clientset, a queued bind is
settled at the apiserver's acknowledgement and on the loop's thread, every
latency series is fed for every pod in both modes, the request counters add up,
and a shutdown settles what was acknowledged.

Every test carries a time limit of its own (`_drive`, `_LIMIT_S`): a loop
that does not come to rest fails that test and leaves the suite its time."""

import io
import json
import threading
import time
from urllib import request as urlrequest
from urllib.error import HTTPError

import pytest

from kubernetes_tpu.core import FakeClientset, Scheduler
from kubernetes_tpu.core.apiserver import APIServer, HTTPClientset
from kubernetes_tpu.core.clientset import RetryingClientset
from kubernetes_tpu.core.config import SchedulerConfiguration
from kubernetes_tpu.models import TPUScheduler
from kubernetes_tpu.testing.wrappers import make_node, make_pod

_LIMIT_S = 60.0
MODES = ("inline", "thread")


def _config(mode: str, **kw) -> SchedulerConfiguration:
    return SchedulerConfiguration(async_dispatch_threads=(mode == "thread"),
                                  **kw)


def _nodes(n: int, cpu: str = "8"):
    return [make_node().name(f"n{i}")
            .capacity({"cpu": cpu, "memory": "64Gi", "pods": 110})
            .zone(f"z{i % 3}").obj() for i in range(n)]


def _pods(n: int, prefix: str = "p"):
    proto = (make_pod().name("proto").req({"cpu": "100m", "memory": "64Mi"})
             .labels({"app": "w"}).obj())
    return [proto.clone_from_template(f"{prefix}{i}") for i in range(n)]


def _drive(sched, until, limit_s: float = _LIMIT_S) -> None:
    """Run the loop until `until()` holds; fail the test at the limit."""
    end = time.monotonic() + limit_s
    while True:
        sched.run_until_idle()
        if until():
            return
        if time.monotonic() > end:
            pytest.fail("not reached within %.0fs:\n%s" % (
                limit_s, sched.stages.report()))
        time.sleep(0.002)


def _stage_count(sched, stage: str) -> int:
    return sched.metrics.pod_stage_duration.count(stage)


def _bind_requests(sched):
    sched.expose_metrics()   # the two counters are published at scrape time
    m = sched.metrics
    return (m.bind_requests.value("single"), m.bind_requests.value("bulk"),
            m.bind_request_pods.value())


# -- (a) the mode follows the clientset ------------------------------------


def _api():
    api = APIServer()
    return api, f"http://127.0.0.1:{api.serve(0)}"


def _post(url: str, path: str, wires: list) -> None:
    req = urlrequest.Request(
        url + path, data=json.dumps(wires).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    urlrequest.urlopen(req, timeout=60).read()


@pytest.mark.parametrize("clientset,gate,want", [
    ("store", True, "inline"),
    ("http", True, "thread"),
    ("retrying-http", True, "thread"),
    ("retrying-store", True, "inline"),
    ("http", False, "inline"),
    ("retrying-http", False, "inline"),
])
def test_mode_follows_the_clientset(clientset, gate, want):
    api = http = None
    try:
        if clientset.endswith("http"):
            api, url = _api()
            cs = http = HTTPClientset(url)
        else:
            cs = FakeClientset()
        if clientset.startswith("retrying"):
            cs = RetryingClientset(cs)
        cfg = SchedulerConfiguration(
            feature_gates={"SchedulerAsyncAPICalls": gate})
        sched = Scheduler(clientset=cs, config=cfg)
        assert sched.api_dispatcher.mode == want
        sched.shutdown()
    finally:
        if http is not None:
            http.close()
        if api is not None:
            api.shutdown()


def test_configuration_still_forces_the_worker_and_the_gate_wins():
    on = Scheduler(clientset=FakeClientset(), config=_config("thread"))
    assert on.api_dispatcher.mode == "thread"
    on.shutdown()
    off = Scheduler(clientset=FakeClientset(), config=_config(
        "thread", feature_gates={"SchedulerAsyncAPICalls": False}))
    assert off.api_dispatcher.mode == "inline"


# -- (b) a bind is settled at its acknowledgement ---------------------------


class _HeldBulk(FakeClientset):
    """In-process store whose bulk bind waits for `release`: what an
    apiserver that is slow to answer looks like to the worker."""

    def __init__(self):
        super().__init__()
        self.release = threading.Event()
        self.entered = threading.Event()
        self.batches = []

    def bind_many(self, pairs):
        self.entered.set()
        assert self.release.wait(_LIMIT_S), "the test never released the bind"
        self.batches.append(len(pairs))
        out = []
        for pod, node in pairs:
            try:
                self.bind(pod, node)
                out.append(None)
            except KeyError as e:     # the item's own verdict: NotFound
                out.append(e)
        return out

    def bind(self, pod, node_name):
        if not self.release.is_set():
            self.entered.set()
            assert self.release.wait(_LIMIT_S)
        super().bind(pod, node_name)


@pytest.mark.parametrize("scheduler", [Scheduler, TPUScheduler])
def test_loop_goes_on_while_binds_are_out_and_settles_at_the_ack(scheduler):
    n, hold = 40, 0.25
    cs = _HeldBulk()
    sched = scheduler(clientset=cs, config=_config("thread"))
    for node in _nodes(6):
        cs.create_node(node)
    for p in _pods(n):
        cs.create_pod(p)
    t0 = time.monotonic()
    end = t0 + _LIMIT_S
    # the loop keeps popping and assuming while every bind is held
    while len(sched.cache.assumed_pods) < n:
        sched.schedule_one()
        assert time.monotonic() < end, sched.stages.report()
    assert cs.entered.wait(_LIMIT_S)
    assert sched.queue.pending_counts()[0] == 0
    assert len(sched._unsettled) == n
    assert sched.scheduled == 0
    assert sched.metrics.e2e_scheduling_duration.count() == 0
    assert _stage_count(sched, "bind.post") == 0
    assert not cs.bindings
    assert not any(st.binding_finished
                   for st in sched.cache.pod_states.values())
    time.sleep(max(0.0, hold - (time.monotonic() - t0)))
    cs.release.set()
    _drive(sched, lambda: sched.scheduled == n)
    assert len(cs.bindings) == n and not sched._unsettled
    e2e = sched.metrics.e2e_scheduling_duration
    assert e2e.count() == n
    assert _stage_count(sched, "bind.post") == n
    assert _stage_count(sched, "bind.queue") == n
    assert _stage_count(sched, "queue.wait") == n
    # every sample ends at the acknowledgement, which came after the hold
    below = sum(c for edge, c in zip(e2e.buckets, e2e._counts[()])
                if edge < hold)
    assert below == 0, (e2e._counts, hold)
    assert e2e.sum() >= n * hold
    # the loop was never blocked in a bind: its own table has none
    assert sched.stages.counts["bind.post"] == 0
    if scheduler is TPUScheduler:
        assert sched.device_scheduled + sched.host_path_pods >= n
    sched.shutdown()


# -- (c) per-item verdicts of a bulk reply -----------------------------------


def _http_error(code: int, reason: str) -> HTTPError:
    body = ('{"code": %d, "error": "%s"}' % (code, reason)).encode()
    return HTTPError("http://test/api/v1/bindings", code, reason, None,
                     io.BytesIO(body))


class _VerdictBulk(FakeClientset):
    """Bulk bind that answers the named pods once with the given verdicts
    (as HTTPClientset.bind_many maps a per-item code) and binds the rest."""

    def __init__(self, verdicts):
        super().__init__()
        self.verdicts = dict(verdicts)
        self.gate = threading.Event()

    def bind_many(self, pairs):
        assert self.gate.wait(_LIMIT_S)
        out = []
        for pod, node in pairs:
            verdict = self.verdicts.pop(pod.name, None)
            if verdict is not None:
                out.append(_http_error(*verdict))
                continue
            super().bind(pod, node)
            out.append(None)
        return out

    def bind(self, pod, node_name):
        assert self.gate.wait(_LIMIT_S)
        super().bind(pod, node_name)


def test_bulk_reply_with_a_409_and_a_500_requeues_those_two_only():
    """Eight identical replicas ride the score hint and go out in one bulk
    request; the apiserver refuses one with a 409 and one with a 500. Those
    two were never counted bound, their optimistic hint hits are taken back,
    they are requeued and bind on the second try; the six batch-mates
    settle from the same reply."""
    cs = _VerdictBulk({"rep-2": (409, "AlreadyBound"),
                       "rep-5": (500, "boom")})
    cs.gate.set()
    cfg = _config("thread", pod_initial_backoff_seconds=0.05,
                  pod_max_backoff_seconds=0.1)
    sched = TPUScheduler(clientset=cs, config=cfg, mesh=None)
    for node in _nodes(8):
        cs.create_node(node)
    for p in _pods(6, "seed-"):
        cs.create_pod(p)
    _drive(sched, lambda: sched.scheduled == 6)
    assert sched._hints.entry is not None, "no score hint to ride"
    hits0, e2e0 = sched.hint_hits, sched.metrics.e2e_scheduling_duration.count()
    cs.gate.clear()              # hold the worker: the 8 go out together
    reps = _pods(8, "rep-")
    for p in reps:
        cs.create_pod(p)
    end = time.monotonic() + _LIMIT_S
    while len(sched._unsettled) < 8:
        sched.schedule_one()
        assert time.monotonic() < end, sched.stages.report()
    assert sched.hint_hits - hits0 == 8      # optimistic, all eight
    assert sched.scheduled == 6
    cs.gate.set()
    end = time.monotonic() + _LIMIT_S
    while sched.state_unwinds < 2:           # the reply's two refusals
        sched.process_async_api_errors()
        assert time.monotonic() < end, sched.stages.report()
        time.sleep(0.001)
    # the refused two: never counted, hint hits taken back, not in the cache
    assert sched.scheduled == 6 + 6
    assert sched.metrics.e2e_scheduling_duration.count() - e2e0 == 6
    assert sched.hint_hits - hits0 == 6
    assert sched.bind_conflicts == 1 and sched.conflict_requeues == 1
    lost = {p.name: p for p in reps if p.name in ("rep-2", "rep-5")}
    for p in lost.values():
        assert p.uid not in sched.cache.pod_states
        assert p.uid not in cs.bindings and not p.node_name
    assert any("boom" in line for line in sched.error_log)
    # requeued through the paths that exist, and bound on the second try
    _drive(sched, lambda: sched.scheduled == 6 + 8)
    assert all(p.uid in cs.bindings for p in reps)
    assert sched.metrics.e2e_scheduling_duration.count() - e2e0 == 8
    assert not sched._unsettled
    sched.shutdown()


# -- (d) placements over a real apiserver, both modes, against the oracle ----


def _oracle(n_nodes: int, n_pods: int):
    cs = FakeClientset()
    host = Scheduler(clientset=cs, deterministic_ties=True)
    for node in _nodes(n_nodes, cpu="32"):
        cs.create_node(node)
    for p in _pods(n_pods):
        cs.create_pod(p)
    host.run_until_idle()
    assert len(cs.bindings) == n_pods
    return {cs.pods[u].name: node for u, node in cs.bindings.items()}


@pytest.fixture(scope="module")
def oracle_2000():
    return _oracle(40, 2000)


@pytest.mark.parametrize("mode", MODES)
def test_served_wave_places_as_the_oracle_in_both_modes(mode, oracle_2000):
    """2,000 plain pods over an in-process APIServer + HTTPClientset: the
    placements are decided on the assumed cache in the same order whether
    binds block the loop or go out in bulk behind it."""
    from kubernetes_tpu.core.apiserver import node_to_wire, pod_to_wire
    n = 2000
    api, url = _api()
    client = HTTPClientset(url)
    gates = {"SchedulerAsyncAPICalls": mode == "thread"}
    sched = TPUScheduler(clientset=RetryingClientset(client), mesh=None,
                         config=SchedulerConfiguration(feature_gates=gates))
    try:
        assert sched.api_dispatcher.mode == mode
        _post(url, "/api/v1/nodes",
              [node_to_wire(nd) for nd in _nodes(40, cpu="32")])
        _drive(sched, lambda: len(sched.cache.nodes) == 40)
        pods = _pods(n)
        for i in range(0, n, 500):
            _post(url, "/api/v1/pods",
                  [pod_to_wire(p) for p in pods[i:i + 500]])
        _drive(sched, lambda: sched.scheduled == n, limit_s=120.0)
        got = {api.store.pods[u].name: node
               for u, node in api.store.bindings.items()}
        assert got == oracle_2000
        assert not sched.error_log and sched.failures == 0
        assert sched.metrics.e2e_scheduling_duration.count() == n
        assert _stage_count(sched, "bind.post") == n
        single, bulk, carried = _bind_requests(sched)
        assert carried == n
        if mode == "thread":
            assert _stage_count(sched, "bind.queue") == n
            assert bulk >= 1 and carried / (single + bulk) > 1.0
            assert sched.stages.counts["bind.post"] == 0
        else:
            # the loop's own requests: one bulk bind a retired batch
            # (PR 35's batch tail, whose clientset has the bulk verb)
            assert (single, bulk) == (0, sched.device_batches)
            assert sched.stages.counts["bind.post"] == bulk
            assert sched.commit_pods == {"batch": n, "single": 0}
    finally:
        sched.shutdown()
        client.close()
        api.shutdown()


# -- (e) the request counters add up ------------------------------------------


@pytest.mark.parametrize("mode", MODES)
def test_bind_request_counters_add_up_to_the_binds(mode):
    cs = _HeldBulk()
    cs.release.set()
    sched = Scheduler(clientset=cs, config=_config(mode))
    for node in _nodes(4):
        cs.create_node(node)
    # one pod at a time: each bind goes out alone
    for p in _pods(3, "one-"):
        cs.create_pod(p)
        _drive(sched, lambda: not sched._unsettled
               and sched.scheduled == len(cs.bindings) == len(cs.pods))
    assert _bind_requests(sched) == (3, 0, 3)
    # a held burst: in thread mode the run of queued binds is one request
    cs.release.clear()
    cs.entered.clear()
    for p in _pods(10, "burst-"):
        cs.create_pod(p)
    if mode == "thread":
        end = time.monotonic() + _LIMIT_S
        while len(sched._unsettled) < 10:
            sched.schedule_one()
            assert time.monotonic() < end
    cs.release.set()
    _drive(sched, lambda: sched.scheduled == 13)
    single, bulk, carried = _bind_requests(sched)
    assert carried == 13 == len(cs.bindings)
    if mode == "thread":
        # whatever the worker found queued when it woke went out first
        # (alone, if that was one bind), the rest behind the held worker
        # as one bulk request
        assert bulk == len(cs.batches) >= 1 and single in (3, 4)
        assert single + sum(cs.batches) == 13
    else:
        assert (single, bulk) == (13, 0) and cs.batches == []
    assert _stage_count(sched, "bind.post") == 13
    assert sched.metrics.e2e_scheduling_duration.count() == 13
    sched.shutdown()


# -- (f) shutdown with binds in flight -----------------------------------------


def test_shutdown_settles_the_acknowledged_binds():
    """The worker is inside a bulk request when the process is told to stop:
    the request is answered during the shutdown's bounded wait, and its pods
    are settled and counted before the scheduler's last word."""
    cs = _HeldBulk()
    sched = Scheduler(clientset=cs, config=_config("thread"))
    for node in _nodes(4):
        cs.create_node(node)
    for p in _pods(12):
        cs.create_pod(p)
    end = time.monotonic() + _LIMIT_S
    while len(sched._unsettled) < 12:
        sched.schedule_one()
        assert time.monotonic() < end
    assert cs.entered.wait(_LIMIT_S) and sched.scheduled == 0
    threading.Timer(0.2, cs.release.set).start()
    t0 = time.monotonic()
    sched.shutdown(timeout=10.0)
    assert time.monotonic() - t0 < 10.0
    assert sched.scheduled == 12 == len(cs.bindings)
    assert sched.metrics.e2e_scheduling_duration.count() == 12
    assert not sched._unsettled and sched.api_dispatcher.idle()


def test_shutdown_is_bounded_and_leaves_unsent_binds_pending():
    """An apiserver that never answers: the shutdown gives up after its
    bound, nothing unacknowledged is counted, and the pods stay assumed
    here and pending there, as after a crash."""
    cs = _HeldBulk()
    sched = Scheduler(clientset=cs, config=_config("thread"))
    for node in _nodes(4):
        cs.create_node(node)
    for p in _pods(5):
        cs.create_pod(p)
    end = time.monotonic() + _LIMIT_S
    while len(sched._unsettled) < 5:
        sched.schedule_one()
        assert time.monotonic() < end
    t0 = time.monotonic()
    sched.shutdown(timeout=0.3)
    assert time.monotonic() - t0 < 5.0
    assert sched.scheduled == 0 and not cs.bindings
    assert sched.metrics.e2e_scheduling_duration.count() == 0
    assert len(sched._unsettled) == 5
    cs.release.set()   # let the worker thread end


# -- the hand-off itself ---------------------------------------------------


def test_every_outcome_is_handed_to_the_loop_exactly_once():
    """The worker and the loop share the two inboxes: under a shortened
    switch interval, with producers beside the loop, every call comes back
    once, as done or as failed, and none on the worker's thread."""
    import sys
    from kubernetes_tpu.core.api_dispatcher import (APICall, APIDispatcher,
                                                    CALL_BINDING)
    from kubernetes_tpu.core.backoff import RetryConfig
    n_threads, per_thread = 8, 250
    loop_thread = threading.get_ident()
    seen = {"done": [], "failed": [], "elsewhere": 0}

    def bulk(calls):
        return [ValueError("odd") if int(c.object_uid) % 7 == 0 else None
                for c in calls]

    def note(kind, uid):
        if threading.get_ident() != loop_thread:
            seen["elsewhere"] += 1
        seen[kind].append(uid)

    d = APIDispatcher(mode="thread", retry=RetryConfig(max_attempts=1))
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def produce(k):
            for i in range(per_thread):
                uid = str(k * per_thread + i)
                d.add(APICall(
                    CALL_BINDING, uid, execute=lambda: None,
                    bulk_execute=bulk,
                    on_done=lambda c: note("done", c.object_uid),
                    on_error=lambda e, _u=uid: note("failed", _u)))
        threads = [threading.Thread(target=produce, args=(k,), daemon=True)
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        end = time.monotonic() + _LIMIT_S
        total = n_threads * per_thread
        while len(seen["done"]) + len(seen["failed"]) < total:
            for call in d.drain_done():
                call.on_done(call)
            for call, exc in d.drain_errors():
                call.on_error(exc)
            assert time.monotonic() < end, (len(seen["done"]),
                                            len(seen["failed"]))
        for t in threads:
            t.join(timeout=10)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
        d.close()
    assert d.idle() and seen["elsewhere"] == 0
    got = sorted(seen["done"] + seen["failed"], key=int)
    assert got == [str(i) for i in range(total)]
    # a call that went out alone ran `execute`, which never fails here
    assert set(seen["failed"]) <= {str(i) for i in range(0, total, 7)}
    assert d.bind_request_pods == total
    assert all(c > 0 for c in (d.executed, len(seen["done"])))


def test_a_pod_deleted_while_its_bind_is_out_is_dropped_not_requeued():
    """The apiserver answers NotFound for a pod that was deleted while its
    bind was queued: the placement is unwound and the pod is gone, not
    back in the queue to be scheduled and refused for ever."""
    cs = _HeldBulk()
    sched = Scheduler(clientset=cs, config=_config("thread"))
    for node in _nodes(2):
        cs.create_node(node)
    pods = _pods(3)
    for p in pods:
        cs.create_pod(p)
    end = time.monotonic() + _LIMIT_S
    while len(sched._unsettled) < 3:
        sched.schedule_one()
        assert time.monotonic() < end
    assert cs.entered.wait(_LIMIT_S)
    cs.delete_pod(cs.pods[pods[1].uid])
    cs.release.set()
    _drive(sched, lambda: sched.scheduled == 2)
    assert sched.queue.pending_counts() == (0, 0, 0)
    assert pods[1].uid not in sched.cache.pod_states
    assert not sched._unsettled and not sched.error_log
    assert sched.state_unwinds == 1
    assert sched.metrics.e2e_scheduling_duration.count() == 2
    sched.shutdown()
