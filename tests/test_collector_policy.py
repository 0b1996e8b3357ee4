"""The scheduler's collector policy (core/collector.py): thresholds sized to a
batch while a scheduler exists, the heap frozen at the loop's first idle after
work, everything given back when the last scheduler closes.

The policy is process-wide, and a test worker's process holds schedulers of
other tests, so what is said of the process (thresholds found and restored,
the frozen set, the last holder) is observed in an interpreter of its own:
`_PROBE` runs once and every case below reads one of its observations.
"""

import gc
import json
import os
import subprocess
import sys

import pytest

from kubernetes_tpu.core import collector

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = r"""
import gc, json, sys
from kubernetes_tpu.core import FakeClientset, Scheduler, collector
from kubernetes_tpu.testing import make_node, make_pod

obs = {}
def see(name):
    obs[name] = {"threshold": list(gc.get_threshold()),
                 "frozen": gc.get_freeze_count(),
                 "freezes": collector.POLICY.freezes}

gc.unfreeze()                 # whatever the interpreter froze at start-up
see("before")
obs["callbacks"] = {"before": len(gc.callbacks)}
cs = FakeClientset()
a = Scheduler(clientset=cs)
see("constructed")
for i in range(40):
    cs.create_node(make_node().name(f"n{i}").capacity(
        {"cpu": "64", "memory": "128Gi", "pods": 110}).obj())
for _ in range(3):
    a.schedule_one()          # idle polls of a loop that has done no work
see("idle_before_work")

import weakref
refs = []
def wave(sched, cs, tag, n):
    pods = [cs.create_pod(make_pod().name(f"{tag}-{i}").req(
        {"cpu": "100m"}).obj()) for i in range(n)]
    refs.extend(weakref.ref(p) for p in pods)
    sched.run_until_idle()
    bound = sum(1 for p in pods if cs.pods[p.uid].node_name)
    for p in pods:
        cs.delete_pod(cs.pods[p.uid])
    sched.run_until_idle()
    return bound

obs["bound"] = wave(a, cs, "init", 300)
see("first_idle_after_work")
obs["attrs"] = [a.gc_freezes, a.gc_frozen_objects]
obs["metrics"] = [l for l in a.expose_metrics().splitlines()
                  if l.startswith("scheduler_gc_")]
obs["settle_count"] = a.stages.counts["gc.settle"]
# the settle's own collection ran on the loop's thread inside that stage
obs["pause_in_settle"] = [a.stages.counts["gc.pause"],
                          a.stages.seconds["gc.pause"],
                          collector.POLICY.clock.seconds[2]]
obs["settle_in_report"] = "gc.settle" in a.stages.report()
obs["settle_published"] = [
    l for l in a.expose_metrics().splitlines()
    if l.startswith('scheduler_loop_stages_total{stage="gc.settle"}')]

# twenty create-bind-delete waves: nothing leaks through the frozen set
tracked = []
for w in range(20):
    obs["bound"] += wave(a, cs, f"w{w}", 300)
    tracked.append(len(gc.get_objects()) + gc.get_freeze_count())
obs["tracked"] = tracked
# the last wave's pods wait in the snapshot for the next cycle's refresh
obs["pods_alive"] = sum(1 for r in refs[:-300] if r() is not None)
see("after_waves")

# a second scheduler shares the policy and its freeze
cs2 = FakeClientset()
b = Scheduler(clientset=cs2)
for i in range(4):
    cs2.create_node(make_node().name(f"m{i}").capacity(
        {"cpu": "64", "memory": "128Gi", "pods": 110}).obj())
obs["shared"] = b.collector is a.collector is collector.POLICY
wave(b, cs2, "b", 20)
see("second_scheduler_idle")
obs["callbacks"]["two_alive"] = len(gc.callbacks)
obs["ledgers_two_alive"] = sorted(
    l is a.stages or l is b.stages for l in collector.POLICY._ledgers)
a.close()
a.close()                     # twice is once
see("first_closed")
del b, cs2                    # dropped unclosed: given back when collected
gc.collect()
see("last_gone")
obs["callbacks"]["both_gone"] = len(gc.callbacks)
obs["clock_listening"] = collector.POLICY.clock._callback in gc.callbacks

# engaged again by a later scheduler: it freezes at its own first idle
cs3 = FakeClientset()
c = Scheduler(clientset=cs3)
cs3.create_node(make_node().name("k0").capacity(
    {"cpu": "64", "memory": "128Gi", "pods": 110}).obj())
wave(c, cs3, "c", 10)
see("engaged_again")
c.shutdown()
see("shut_down")
print(json.dumps(obs))
"""


@pytest.fixture(scope="module")
def probe():
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "-c", _PROBE], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.splitlines()[-1])


DEFAULT = [700, 10, 10]
ENGAGED = list(collector.THRESHOLDS)


@pytest.mark.parametrize("moment, threshold, frozen, freezes", [
    ("before", DEFAULT, False, 0),
    ("constructed", ENGAGED, False, 0),
    ("idle_before_work", ENGAGED, False, 0),
    ("first_idle_after_work", ENGAGED, True, 1),
    ("after_waves", ENGAGED, True, 1),
    ("second_scheduler_idle", ENGAGED, True, 1),
    ("first_closed", ENGAGED, True, 1),
    ("last_gone", DEFAULT, False, 1),
    ("engaged_again", ENGAGED, True, 2),
    ("shut_down", DEFAULT, False, 2),
])
def test_the_policy_holds_from_the_first_scheduler_to_the_last(
        probe, moment, threshold, frozen, freezes):
    """Thresholds from construction, the freeze at the first idle after work
    and not before, both given back by the last holder only (closed, shut
    down or collected), and a later scheduler starts over."""
    seen = probe[moment]
    assert seen["threshold"] == threshold
    assert (seen["frozen"] > 10_000) if frozen else (seen["frozen"] == 0)
    assert seen["freezes"] == freezes


def test_every_pod_of_the_probe_was_bound(probe):
    assert probe["bound"] == 300 * 21


def test_the_freeze_is_counted_on_metrics_and_on_the_scheduler(probe):
    freezes, frozen = probe["attrs"]
    assert freezes == 1 and frozen > 10_000
    lines = probe["metrics"]
    assert "scheduler_gc_freezes_total 1.0" in lines
    # the gauge is read at scrape time: the snapshot has dropped the init
    # pods since the attribute was read
    gauge = [l for l in lines if l.startswith("scheduler_gc_frozen_objects ")]
    assert len(gauge) == 1 and 10_000 < float(gauge[0].split()[1]) <= frozen
    # the clock runs for a library-driven scheduler: the settle's own full
    # collection is on it
    full = [l for l in lines if l.startswith(
        'scheduler_gc_collections_total{generation="2"}')]
    assert float(full[0].split()[1]) >= 1.0
    assert any(l.startswith('scheduler_gc_pause_seconds_total{generation="2"}')
               for l in lines)


def test_the_settle_is_a_stage_of_the_loop(probe):
    assert probe["settle_count"] == 1
    assert probe["settle_in_report"]
    assert probe["settle_published"] == [
        'scheduler_loop_stages_total{stage="gc.settle"} 1.0']


def test_the_settles_collection_is_the_loops_pause(probe):
    """`gc.collect()` inside `gc.settle` runs on the loop's thread with that
    stage open: the table books it under `gc.pause`, for at least the seconds
    the policy's clock counted of it (the stage encloses the clock)."""
    count, seconds, clock_full_s = probe["pause_in_settle"]
    assert count >= 1 and seconds > 0
    assert seconds >= clock_full_s * 0.5


def test_one_callback_for_all_schedulers_and_none_after_the_last(probe):
    """The pauses reach every scheduler's table through the ONE
    `gc.callbacks` entry the policy's clock holds, however many schedulers
    are alive; the last holder takes it away."""
    seen = probe["callbacks"]
    assert seen["two_alive"] == seen["before"] + 1
    assert seen["both_gone"] == seen["before"]
    assert probe["ledgers_two_alive"] == [True, True]


def test_twenty_waves_leak_nothing_through_the_frozen_set(probe):
    """The first wave's pods are bound, and so frozen with the heap, when the
    loop first goes idle; they and every later wave's are reclaimed by their
    reference counts once deleted. Tracked plus frozen objects after each
    create-bind-delete wave of 300 pods stay within 20,000 of the first
    wave's: they grow by 11,000-13,000 with or without the policy (the span
    ring and the event logs filling to their caps), where
    one leaked wave a time would be 300 pods x 35 objects x 20 (the span
    recorder keeps no memo of contexts any more: a wave cannot overflow it)."""
    assert probe["pods_alive"] == 0
    tracked = probe["tracked"]
    assert len(tracked) == 20
    assert max(abs(t - tracked[0]) for t in tracked) < 20_000, tracked


def test_a_second_scheduler_shares_the_policy_and_its_clock(probe):
    assert probe["shared"]
    assert not probe["clock_listening"]  # closed with the last holder


def test_placements_with_the_policy_engaged_equal_the_oracles():
    """An existing equivalence case (test_device_equivalence's basic fit),
    with the heap frozen under the device scheduler before its pods come."""
    from tests.test_device_equivalence import (_assignments, _basic_pods,
                                               _mk_cluster)
    from kubernetes_tpu.core.scheduler import Scheduler
    from kubernetes_tpu.models.tpu_scheduler import TPUScheduler

    host = Scheduler(deterministic_ties=True)
    dev = TPUScheduler()
    for sched in (host, dev):
        _mk_cluster(sched, 23)
        for p in _basic_pods(8, cpu="100m")():
            p.name = "first-" + p.name
            sched.clientset.create_pod(p)
        sched.run_until_idle()
    assert dev.collector is collector.POLICY
    assert dev.gc_freezes >= 1 and gc.get_freeze_count() > 10_000
    assert gc.get_threshold() == collector.THRESHOLDS
    for sched in (host, dev):
        for p in _basic_pods(40)():
            sched.clientset.create_pod(p)
        sched.run_until_idle()
    assert dev.device_scheduled == 48 and dev.host_path_pods == 0
    assert _assignments(dev) == _assignments(host)
    host.close()
    dev.close()


def test_a_refreeze_follows_a_heap_that_outgrew_the_frozen_one():
    """After the first freeze an idle is O(1) until a full collection has
    run; then the unfrozen heap is counted, and frozen again only if it has
    grown past REFREEZE_SHARE of the frozen one."""
    from kubernetes_tpu.core import FakeClientset, Scheduler
    from kubernetes_tpu.testing import make_node, make_pod

    cs = FakeClientset()
    sched = Scheduler(clientset=cs)
    try:
        cs.create_node(make_node().name("n0").capacity(
            {"cpu": "64", "memory": "128Gi", "pods": 110}).obj())

        def work(name):
            cs.create_pod(make_pod().name(name).req({"cpu": "100m"}).obj())
            sched.run_until_idle()

        work("p0")
        policy = sched.collector
        gc.collect()
        work("p0b")                      # this worker's own growth: caught up
        settled = policy.freezes
        assert settled >= 1
        work("p1")                       # no full collection since: O(1)
        assert policy.freezes == settled
        gc.collect()                     # the interpreter's sign
        work("p2")                       # counted, and small: stays as it is
        assert policy.freezes == settled
        ballast = [[i] for i in range(
            int(collector.REFREEZE_SHARE * gc.get_freeze_count()) + 1000)]
        gc.collect()
        work("p3")                       # grown past the share: frozen again
        assert policy.freezes == settled + 1
        assert len(gc.get_objects()) < len(ballast)
        assert sched.stages.counts["gc.settle"] >= 1
    finally:
        sched.close()
