"""Sustained concurrency stress — the -race-CI analogue (hack/make-rules/
test.sh:87 runs the reference's tests under the race detector; this drives
every concurrent seam of THIS design at once and asserts the invariants the
race detector would protect):

- a creator thread writing pods through the watch-seam transport
  (core/remote.py apiserver thread → cross-thread reflector inbox),
- a churn thread creating/deleting nodes and deleting scheduled pods,
- the thread-mode async API dispatcher executing binds off the loop,
- the device scheduler running sessions with invalidation mid-flight.

Invariants at the end: no scheduler errors, cache ≡ API (CacheDebugger
comparer), every surviving pod bound exactly once to a live-or-deleted node,
in-flight accounting empty, and the run survived without deadlock.
"""

import threading
import time

import pytest

from kubernetes_tpu.core.config import SchedulerConfiguration
from kubernetes_tpu.core.debugger import CacheDebugger
from kubernetes_tpu.core.remote import RemoteClientset
from kubernetes_tpu.models import TPUScheduler
from kubernetes_tpu.testing.wrappers import make_node, make_pod


def test_sustained_concurrent_churn_and_scheduling():
    cs = RemoteClientset(rtt=0.0002)
    cfg = SchedulerConfiguration(async_dispatch_threads=True)
    sched = TPUScheduler(clientset=cs, config=cfg)
    for i in range(60):
        cs.create_node(make_node().name(f"n{i}")
                       .capacity({"cpu": "16", "memory": "64Gi", "pods": 110})
                       .zone(f"z{i % 4}").obj())

    N_PODS = 400
    stop = threading.Event()
    errors = []

    def creator():
        try:
            proto = make_pod().name("proto").req(
                {"cpu": "100m", "memory": "64Mi"}).labels({"app": "s"}).obj()
            for i in range(N_PODS):
                if stop.is_set():
                    return
                cs.create_pod(proto.clone_from_template(f"s-{i}"))
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    def churner():
        try:
            seq = 0
            while not stop.is_set():
                seq += 1
                cs.create_node(make_node().name(f"churn-{seq}")
                               .capacity({"cpu": "8", "pods": 50}).obj())
                if seq > 3:
                    cs.delete_node(f"churn-{seq - 3}")
                # delete an already-scheduled pod now and then
                for p in list(cs.pods.values())[:1]:
                    if p.node_name:
                        cs.delete_pod(p)
                        break
                time.sleep(0.003)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=creator, daemon=True),
               threading.Thread(target=churner, daemon=True)]
    for t in threads:
        t.start()

    deadline = time.monotonic() + 60

    def limit():
        # run_until_idle has been seen to spin without end here (about 1 run
        # in 20): then fail this one test, with the loop's own account of
        # where it is, and leave the suite its time.
        if time.monotonic() > deadline + 30:
            stop.set()
            pytest.fail("the scheduling loop did not come to rest:\n"
                        + sched.stages.report(last=16)
                        + f"\nqueue {sched.queue.pending_counts()} "
                        f"dispatcher idle {sched.api_dispatcher.idle()} "
                        f"inbox {len(sched._event_inbox)}")

    sched.loop_hook = limit
    while time.monotonic() < deadline:
        sched.run_until_idle()
        sched.api_dispatcher.flush()
        sched.process_async_api_errors()
        if (not threads[0].is_alive()
                and sched.scheduled >= N_PODS - 40  # churn deletes some
                and not sched.queue.active_q.items()):
            break
        time.sleep(0.002)
    stop.set()
    for t_ in threads:
        t_.join(timeout=5)
    assert not any(t_.is_alive() for t_ in threads), "writer thread hung"
    sched.api_dispatcher.flush()
    sched.run_until_idle()

    assert not errors, errors
    assert not sched.error_log, sched.error_log[:5]
    # every pending pod processed; in-flight accounting empty
    assert not sched.queue._in_flight
    # cache ≡ API store (the race detector's cache-coherence claim)
    dbg = CacheDebugger(sched)
    diffs = dbg.compare()
    assert not diffs, diffs[:5]
    # each surviving bound pod is on exactly one node, and bindings agree
    for p in cs.pods.values():
        if p.node_name:
            assert cs.bindings.get(p.uid) == p.node_name
    cs.close()
