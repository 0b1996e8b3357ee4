"""The reader of `bind_batch_mean` (benchmark/layer_metrics/bind_batch_mean.py)
on canned `/metrics` deltas: the mean over single and bulk requests, a window
of single requests reading 1, and a program without the two counters (the
parent) or a window without a binding request reading nothing. And on the
program itself: what a scheduler's `/metrics` page says after a run in each
dispatcher mode reads as that run's mean batch."""

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import prom  # noqa: E402


def _reader():
    path = os.path.join(BENCH, "layer_metrics", "bind_batch_mean.py")
    spec = importlib.util.spec_from_file_location("reader_bind_batch_mean", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _obs(before: str, after: str) -> dict:
    return {"prom": {"scheduler": prom.delta(prom.parse(after),
                                             prom.parse(before))}}


BEFORE = ('scheduler_bind_requests_total{kind="single"} 2000.0\n'
          'scheduler_bind_requests_total{kind="bulk"} 16.0\n'
          'scheduler_bind_request_pods_total 4000.0\n'
          'scheduler_e2e_scheduling_duration_seconds_count 4000\n')


@pytest.mark.parametrize("after,want", [
    # 30,000 pods in 236 bulk requests and 4 that went out alone
    ('scheduler_bind_requests_total{kind="single"} 2004.0\n'
     'scheduler_bind_requests_total{kind="bulk"} 252.0\n'
     'scheduler_bind_request_pods_total 34000.0\n', 125.0),
    # every bind a request of its own (inline, or an open loop's arrivals)
    ('scheduler_bind_requests_total{kind="single"} 19600.0\n'
     'scheduler_bind_requests_total{kind="bulk"} 16.0\n'
     'scheduler_bind_request_pods_total 21600.0\n', 1.0),
    # no binding request in the window: nothing, not a division by zero
    (BEFORE, None),
    # the parent has no such counters: nothing
    ('scheduler_e2e_scheduling_duration_seconds_count 34000\n', None),
])
def test_mean_batch_from_a_window_delta(after, want):
    got = _reader()(_obs(BEFORE if "bind_re" in after else "", after))
    assert got == want if want is None else got == pytest.approx(want)


def test_nothing_to_read_without_a_metrics_page():
    read = _reader()
    assert read({}) is None
    assert read({"prom": {}}) is None
    assert read({"prom": {"scheduler": {}}}) is None


@pytest.mark.parametrize("mode", ("inline", "thread"))
def test_the_programs_own_page_reads_as_its_mean_batch(mode):
    """24 pods bound by a scheduler in each dispatcher mode, read back
    through the page it serves: 1 a request inline, and pods over the
    worker's requests (however it found them queued) in thread mode."""
    from kubernetes_tpu.core import FakeClientset, Scheduler
    from kubernetes_tpu.core.config import SchedulerConfiguration
    from kubernetes_tpu.testing.wrappers import make_node, make_pod
    cs = FakeClientset()
    sched = Scheduler(clientset=cs, config=SchedulerConfiguration(
        async_dispatch_threads=(mode == "thread")))
    before = sched.expose_metrics()
    for i in range(4):
        cs.create_node(make_node().name(f"n{i}").capacity(
            {"cpu": "8", "memory": "16Gi", "pods": 110}).obj())
    for i in range(24):
        cs.create_pod(make_pod().name(f"p{i}").req({"cpu": "100m"}).obj())
    sched.run_until_idle()
    assert sched.scheduled == 24
    got = _reader()(_obs(before, sched.expose_metrics()))
    requests = sum(sched.api_dispatcher.bind_requests.values())
    if mode == "inline":
        assert requests == 0 and got == 1.0
    else:
        assert 1 <= requests <= 24 and got == pytest.approx(24 / requests)
    sched.shutdown()
