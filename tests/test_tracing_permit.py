"""Timer-driven Permit WAIT expiry (runtime/framework.go:2097), the slow-stage
log (schedule_one.go:574), and the event recorder (schedule_one.go:1138)."""

import logging

from kubernetes_tpu.core import FakeClientset, Scheduler, spans
from kubernetes_tpu.core.framework import OK, Status, WAIT
from kubernetes_tpu.core.registry import DEFAULT_PLUGINS, build_framework
from kubernetes_tpu.testing.wrappers import make_node, make_pod


class ParkOnce:
    """Permit plugin: WAIT the first pod forever (nobody allows it)."""

    name = "ParkOnce"

    def __init__(self):
        self.parked = []

    def permit(self, state, pod, node_name):
        if not self.parked:
            self.parked.append(pod.uid)
            return Status(WAIT, ("parked",), self.name)
        return OK


def test_permit_timeout_fires_under_continuous_load():
    """A parked pod must time out WHILE the scheduler stays busy — no idle
    moment ever happens (round-2 verdict: expiry was idle-poll-driven)."""
    clock = [0.0]
    parker = ParkOnce()

    def factory(h):
        fw = build_framework(h)
        fw.permit_plugins.append(parker)
        return {"default-scheduler": fw}

    cs = FakeClientset()
    s = Scheduler(clientset=cs, profile_factory=factory,
                  deterministic_ties=True, now=lambda: clock[0])
    s.permit_wait_timeout = 30.0
    for i in range(4):
        cs.create_node(make_node().name(f"n{i}")
                       .capacity({"cpu": 64, "memory": "256Gi", "pods": 500}).obj())
    cs.create_pod(make_pod().name("parked").req({"cpu": "100m"}).obj())
    assert s.schedule_one()
    assert len(s.waiting_pods) == 1

    # Continuous load: one new pod per tick, clock advancing past the
    # deadline — the queue NEVER goes empty between cycles.
    for i in range(40):
        clock[0] += 1.0
        cs.create_pod(make_pod().name(f"busy-{i}").req({"cpu": "100m"}).obj())
        s.schedule_one()
    assert not s.waiting_pods, "parked pod never timed out under load"
    parked = cs.pods[parker.parked[0]]
    assert not parked.node_name  # rejected, not bound
    evs = s.recorder.for_object(f"{parked.namespace}/{parked.name}")
    assert any(e.reason == "FailedScheduling" for e in evs)


def test_scheduled_events_recorded():
    cs = FakeClientset()
    s = Scheduler(clientset=cs, deterministic_ties=True)
    cs.create_node(make_node().name("n0").capacity(
        {"cpu": 8, "memory": "32Gi", "pods": 110}).obj())
    cs.create_pod(make_pod().name("p0").req({"cpu": "1"}).obj())
    s.run_until_idle()
    evs = s.recorder.for_object("default/p0")
    assert any(e.reason == "Scheduled" and "n0" in e.message for e in evs)


def test_slow_stage_logs(caplog, monkeypatch):
    """The slow-stage rule on the host path: a cycle over its threshold is
    logged with the pod it was scheduling (utiltrace's slow-step log)."""
    monkeypatch.setattr(spans, "SLOW_STAGE_S", -1.0)  # every stage is slow
    cs = FakeClientset()
    s = Scheduler(clientset=cs, deterministic_ties=True)
    cs.create_node(make_node().name("n0").capacity(
        {"cpu": 8, "memory": "32Gi", "pods": 110}).obj())
    cs.create_pod(make_pod().name("slow").req({"cpu": "1"}).obj())
    with caplog.at_level(logging.WARNING, logger="kubernetes_tpu"):
        s.run_until_idle()
    slow = [r.getMessage() for r in caplog.records
            if "slow scheduling stage: host.commit" in r.getMessage()]
    assert slow and "pod=default/slow" in slow[0] and "total=" in slow[0]
    assert s.stages.seconds["host.commit"] > 0
