"""End-to-end scheduling telemetry (core/spans.py; docs/OBSERVABILITY.md):
deterministic head sampling, ring-buffer wraparound, cross-process trace
context propagation over the real apiserver wire (bind POST → WAL → BOUND
event → foreign observer span), the crash-safe flight recorder (SIGUSR2 +
real two-OS-process artifacts), the loop's stage ledger (self times, the
counters as views of it, the slow-stage rule, `sched.*` spans in a profiler
trace), the /debug/events read surface, and the trace analyzer CLI's golden
output on a recorded fixture trace. No timing is asserted."""

import io
import json
import logging
import os
import signal
import subprocess
import sys
import time

import pytest

from kubernetes_tpu.core import FakeClientset, Scheduler, spans
from kubernetes_tpu.core.spans import (FlightRecorder, SpanRecorder,
                                       format_ctx, parse_ctx, sampled_uid,
                                       trace_id_for, write_jsonl)
from kubernetes_tpu.testing.wrappers import make_node, make_pod
from kubernetes_tpu.testing.annotations import StageAnnotations

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def tracer():
    """Fresh sample-everything tracer installed as the process default;
    restored afterward so other tests keep the head-sampled default."""
    prev = spans.default_tracer()
    t = SpanRecorder(sample_n=1, proc="test")
    spans.set_default_tracer(t)
    yield t
    spans.set_default_tracer(prev)


def _node(name, cpu="8", pods=110):
    return (make_node().name(name)
            .capacity({"cpu": cpu, "memory": "32Gi", "pods": pods}).obj())


def _pod(name, cpu="200m"):
    return make_pod().name(name).req({"cpu": cpu, "memory": "128Mi"}).obj()


# ---------------------------------------------------------------------------
# sampling + ring mechanics
# ---------------------------------------------------------------------------


class TestSampling:
    def test_sampling_is_deterministic_across_processes(self):
        """Two independent tracers (≈ two processes) must agree on every
        pod's sampling verdict AND, for the pods that record, on the trace
        id, with no coordination — the property the whole cross-process
        merge stands on."""
        a = SpanRecorder(sample_n=16, proc="a")
        b = SpanRecorder(sample_n=16, proc="b")
        sampled = 0
        for i in range(500):
            uid = f"uid-{i}"
            ca, cb = a.context_for(uid), b.context_for(uid)
            assert (ca is None) == (cb is None) == (not sampled_uid(uid, 16))
            if ca is not None:
                assert ca.sampled and cb.sampled
                assert ca.trace_id == cb.trace_id == trace_id_for(uid)
                sampled += 1
        # 1-in-16 head sampling: statistically ~31 of 500
        assert 5 <= sampled <= 100

    def test_force_overrides_head_sampling(self):
        t = SpanRecorder(sample_n=1 << 30)  # nothing head-samples
        uid = "conflict-pod"
        assert t.context_for(uid) is None and not t.wants(t.context_for(uid))
        forced = t.context_for(uid, force=True)
        assert forced.sampled and forced.trace_id == trace_id_for(uid)
        # the verdict is NOT poisoned by the forced context
        assert t.context_for(uid) is None

    def test_an_unsampled_pod_pays_no_digest_and_every_process_agrees(
            self, monkeypatch):
        """20,000 distinct uids outside the sample (a wave twice the size of
        the memo that PR 36 removed): no `blake2b`, no `SpanContext`; two
        recorders give every uid the same verdict, and the wire form of a
        context carries it through `format_ctx`/`parse_ctx`."""
        import hashlib
        a = SpanRecorder(sample_n=16, proc="a")
        b = SpanRecorder(sample_n=16, proc="b")
        uids = [f"w7-{i}" for i in range(22_000)]
        outside = [u for u in uids if not sampled_uid(u, 16)][:20_000]
        assert len(outside) == 20_000
        digests, made = [], []
        real = hashlib.blake2b
        monkeypatch.setattr(hashlib, "blake2b", lambda *a_, **kw: (
            digests.append(1), real(*a_, **kw))[1])
        init = spans.SpanContext.__init__
        monkeypatch.setattr(spans.SpanContext, "__init__", lambda self, *a_: (
            made.append(1), init(self, *a_))[1])
        for u in outside:
            assert a.context_for(u) is None and b.context_for(u) is None
        assert digests == [] and made == []
        # the sampled sixteenth pays the digest, once a context, and the
        # verdict survives the wire
        inside = [u for u in uids if sampled_uid(u, 16)]
        assert 0.04 < len(inside) / len(uids) < 0.09
        for u in inside[:200]:
            ca, cb = a.context_for(u), b.context_for(u)
            assert ca.trace_id == cb.trace_id and ca.sampled and cb.sampled
            back = parse_ctx(format_ctx(ca))
            assert back.trace_id == ca.trace_id and back.sampled
        assert len(digests) == 400 and len(made) == 400 + 200  # + parse_ctx
        # an unsampled verdict on the wire (a forced context's opposite)
        off = parse_ctx(f"{trace_id_for(outside[0])}-00")
        assert not off.sampled and not a.wants(off)

    def test_wire_context_roundtrip(self):
        ctx = SpanRecorder(sample_n=1).context_for("u1")
        wire = format_ctx(ctx)
        back = parse_ctx(wire)
        assert back.trace_id == ctx.trace_id and back.sampled
        assert parse_ctx("garbage") is None
        off = parse_ctx(f"{ctx.trace_id}-00")
        assert off is not None and not off.sampled

    def test_ring_buffer_wraparound(self):
        t = SpanRecorder(capacity=8, sample_n=1)
        for i in range(20):
            t.record(f"s{i}", t.context_for(f"u{i}"))
        rows = t.snapshot()
        assert len(rows) == 8
        assert [r["name"] for r in rows] == [f"s{i}" for i in range(12, 20)]
        assert t.recorded == 20  # accepted count survives eviction

    def test_disabled_tracer_records_nothing(self):
        t = SpanRecorder(sample_n=1, enabled=False)
        t.record("x", t.context_for("u"))
        with t.span("y", t.context_for("u")):
            pass
        assert t.snapshot() == []

    def test_scoped_span_records_error_attr(self):
        t = SpanRecorder(sample_n=1)
        with pytest.raises(ValueError):
            with t.span("stage", t.context_for("u")):
                raise ValueError("boom")
        (row,) = t.snapshot()
        assert row["attrs"]["error"] == "ValueError"


# ---------------------------------------------------------------------------
# in-process pipeline chain + e2e histogram
# ---------------------------------------------------------------------------


class TestSchedulerSpans:
    def test_host_path_chain_and_e2e_histogram(self, tracer):
        cs = FakeClientset()
        s = Scheduler(clientset=cs, deterministic_ties=True)
        for i in range(4):
            cs.create_node(_node(f"n{i}"))
        for i in range(6):
            cs.create_pod(_pod(f"p{i}"))
        s.run_until_idle()
        assert s.scheduled == 6
        names = {r["name"] for r in s.tracer.snapshot()}
        assert {"queue.admission", "queue.wait",
                "host.commit", "pod.e2e"} <= names
        # e2e histogram fed for EVERY bound pod (latency truth, unsampled
        # pods included) and exposed on /metrics
        assert s.metrics.e2e_scheduling_duration.count() == 6
        assert ("scheduler_e2e_scheduling_duration_seconds"
                in s.expose_metrics())

    def test_unsampled_pods_feed_histogram_but_not_ring(self):
        prev = spans.default_tracer()
        spans.set_default_tracer(SpanRecorder(sample_n=1 << 30, proc="off"))
        try:
            cs = FakeClientset()
            s = Scheduler(clientset=cs, deterministic_ties=True)
            cs.create_node(_node("n0"))
            cs.create_pod(_pod("p0"))
            s.run_until_idle()
            assert s.scheduled == 1
            assert s.metrics.e2e_scheduling_duration.count() == 1
            assert s.tracer.snapshot() == []
        finally:
            spans.set_default_tracer(prev)

    def test_bind_conflict_records_forced_span(self, tracer):
        from tests.test_shard_plane import _Conflict409, _ConflictOnce

        cs = FakeClientset()
        sched = Scheduler(clientset=_ConflictOnce(cs),
                          deterministic_ties=True)
        for i in range(4):
            cs.create_node(_node(f"n{i}"))
        cs.create_pod(_pod("racer"))
        sched.run_until_idle()
        rows = [r for r in sched.tracer.snapshot()
                if r["name"] == "bind.conflict"]
        assert len(rows) == 1
        assert rows[0]["attrs"]["reason"] == "already_bound"
        assert rows[0]["attrs"]["node"]
        assert rows[0]["trace"] == trace_id_for(
            next(iter(cs.pods.values())).uid)

    def test_device_path_records_stage_spans(self, tracer):
        from kubernetes_tpu.models import TPUScheduler

        cs = FakeClientset()
        s = TPUScheduler(clientset=cs)
        for i in range(8):
            cs.create_node(_node(f"n{i}", cpu="32"))
        proto = _pod("proto", cpu="100m")
        for i in range(32):
            cs.create_pod(proto.clone_from_template(f"p{i}"))
        s.run_until_idle()
        assert s.device_scheduled > 0
        names = {r["name"] for r in s.tracer.snapshot()}
        assert {"queue.wait", "plan.build", "device.dispatch",
                "device.wait", "host.commit", "pod.e2e"} <= names
        kinds = {r["attrs"].get("kind") for r in s.tracer.snapshot()
                 if r["name"] == "plan.build"}
        assert kinds & {"full", "delta", "resume"}
        # span ends also feed the extension-point histogram (p50/p99 truth)
        h = s.metrics.framework_extension_point_duration
        for point in ("DevicePlan", "DeviceWait", "HostCommit"):
            assert h.count(point, "Success", "") >= 1, point


# ---------------------------------------------------------------------------
# the loop's stage ledger
# ---------------------------------------------------------------------------


class TestStageLedger:
    def test_self_times_of_a_nest_sum_to_the_roots_duration(self):
        """Self time = duration minus what the children cover, so the self
        times of a whole nest add up to the root's duration exactly —
        whatever the clock did (no timing is asserted)."""
        ledger = spans.StageLedger(SpanRecorder(sample_n=1, proc="t"))
        t0 = time.perf_counter()
        with ledger.stage("cycle"):
            with ledger.stage("queue.pop"):
                sum(range(2000))
            with ledger.stage("plan.build", kind="full"):
                with ledger.stage("plan.patch"):
                    sum(range(2000))
            with ledger.stage("host.commit"):
                ledger.leaf("bind.post", 1e-4)  # a child timed by its caller
                with ledger.stage("host.commit", annotate=False):
                    pass                        # a stage nested in itself
        wall = time.perf_counter() - t0
        name, _ts, duration, self_s, parts = ledger.recent[-1]
        assert name == "cycle" and 0 < duration <= wall
        assert sum(ledger.seconds.values()) == pytest.approx(duration,
                                                             abs=1e-9)
        assert self_s + sum(parts.values()) == pytest.approx(duration,
                                                             abs=1e-9)
        assert set(parts) == {"queue.pop", "plan.build", "plan.patch",
                              "host.commit", "bind.post"}
        assert ledger.counts["host.commit"] == 2
        assert ledger.counts["cycle"] == ledger.counts["bind.post"] == 1
        assert ledger.seconds["bind.post"] == 1e-4
        assert not ledger._stack
        report = ledger.report()
        assert "plan.build" in report and "open now: -" in report

    def test_an_exception_closes_the_stage_it_left(self):
        ledger = spans.StageLedger(SpanRecorder(sample_n=1, proc="t"))
        with pytest.raises(KeyError):
            with ledger.stage("cycle"):
                with ledger.stage("plan.build"):
                    raise KeyError("boom")
        assert not ledger._stack
        assert ledger.counts["cycle"] == ledger.counts["plan.build"] == 1

    def test_counters_are_views_of_the_table(self, tracer):
        """plan_build_s / device_wait_s / host_commit_s are computed from
        the table, /metrics publishes the same table, and every pod — not
        only the sampled — feeds the per-pod stage histogram."""
        from kubernetes_tpu.models import TPUScheduler

        cs = FakeClientset()
        s = TPUScheduler(clientset=cs)
        for i in range(8):
            cs.create_node(_node(f"n{i}", cpu="32"))
        proto = _pod("proto", cpu="100m")
        for i in range(32):
            cs.create_pod(proto.clone_from_template(f"p{i}"))
        s.run_until_idle()
        assert s.device_scheduled == 32
        sec, cnt = s.stages.seconds, s.stages.counts
        assert s.plan_build_s == sec["plan.build"] > 0
        assert s.device_wait_s == sec["device.wait"] > 0
        assert s.host_commit_s == sec["host.commit"] + sec["bind.post"] > 0
        # the table counts requests: the batch tail's one bulk bind (PR 35)
        assert cnt["bind.post"] == s.device_batches == 1
        assert cnt["plan.build"] == 1
        assert cnt["device.dispatch"] == cnt["device.wait"] == s.device_batches
        assert cnt["cycle"] >= 1 and cnt["queue.pop"] >= 1
        h = s.metrics.pod_stage_duration
        assert h.count("queue.wait") == h.count("bind.post") == 32
        text = s.expose_metrics()
        assert (f'scheduler_loop_stages_total{{stage="device.wait"}} '
                f'{float(s.device_batches)}') in text
        assert (f'scheduler_loop_stage_seconds_total{{stage="plan.build"}} '
                f'{s.plan_build_s}') in text
        # the collector policy's clock: library-driven schedulers too
        assert 'scheduler_gc_pause_seconds_total{generation="2"}' in text

    def test_unsampled_batch_copies_no_span_and_looks_nothing_up(self):
        """A batch's sampled members are found once, at collection: with no
        sampled member the batch stages leave nothing in the ring."""
        from kubernetes_tpu.models import TPUScheduler

        prev = spans.default_tracer()
        t = SpanRecorder(sample_n=1 << 60, proc="test")  # nobody sampled
        spans.set_default_tracer(t)
        try:
            cs = FakeClientset()
            s = TPUScheduler(clientset=cs)
            for i in range(4):
                cs.create_node(_node(f"n{i}", cpu="32"))
            proto = _pod("proto", cpu="100m")
            for i in range(16):
                cs.create_pod(proto.clone_from_template(f"p{i}"))
            s.run_until_idle()
            assert s.device_scheduled == 16
            assert not [r for r in t.snapshot()
                        if r["name"] != "trace.slow_stage"]
            assert s.metrics.pod_stage_duration.count("queue.wait") == 16
        finally:
            spans.set_default_tracer(prev)

    def test_a_collection_on_the_loops_thread_is_the_stage_gc_pause(self):
        """A collection that the loop's own thread runs inside an open stage
        leaves that stage's self time and lands in `gc.pause` (a child of
        whatever is open, `generation` its stat); the nest still sums to the
        root's duration."""
        import gc
        opened = []

        class Annotation:
            def __init__(self, name, **stats):
                opened.append((name, stats))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        ledger = spans.StageLedger(SpanRecorder(sample_n=1, proc="t"))
        ledger._annotation = Annotation
        clock = spans.GcClock([ledger]).install()
        gc.disable()            # only the collections this test asks for
        try:
            gc.collect()        # no stage open: nobody's pause
            assert ledger.counts["gc.pause"] == 0
            with ledger.stage("cycle"):
                with ledger.stage("plan.build") as build:
                    t0 = time.perf_counter()
                    gc.collect()
                    inside = time.perf_counter() - t0
                with ledger.stage("host.commit"):
                    pass
        finally:
            gc.enable()
            clock.close()
        assert ledger.counts["gc.pause"] == 1
        pause = ledger.seconds["gc.pause"]
        assert 0 < pause <= inside
        # the clock counted two collections, the table the one it was in
        assert clock.collections[2] == 2
        assert ("sched.gc.pause", {"generation": 2}) in opened
        # plan.build's self time is what its duration leaves beside the pause
        assert build._child_s == pytest.approx(pause, abs=1e-9)
        name, _ts, duration, self_s, parts = ledger.recent[-1]
        assert name == "cycle" and parts["gc.pause"] == pause
        assert sum(ledger.seconds.values()) == pytest.approx(duration,
                                                             abs=1e-9)
        assert self_s + sum(parts.values()) == pytest.approx(duration,
                                                             abs=1e-9)
        assert not ledger._stack

    def test_a_collection_on_another_thread_changes_no_row(self):
        """The client thread of a wave or the reflector collects while the
        loop has a stage open: the policy's clock counts the pause, the
        table is not charged (a loop parked outside the interpreter was not
        delayed by it)."""
        import gc
        import threading
        ledger = spans.StageLedger(SpanRecorder(sample_n=1, proc="t"))
        clock = spans.GcClock([ledger]).install()
        gc.disable()
        try:
            with ledger.stage("cycle"):
                with ledger.stage("device.wait"):
                    other = threading.Thread(target=gc.collect)
                    other.start()
                    other.join()
        finally:
            gc.enable()
            clock.close()
        assert clock.collections[2] == 1 and clock.seconds[2] > 0
        assert ledger.counts["gc.pause"] == 0
        assert ledger.seconds["gc.pause"] == 0.0
        assert ledger.counts["device.wait"] == ledger.counts["cycle"] == 1
        assert set(ledger.recent[-1][4]) == {"device.wait"}

    def test_a_batch_keeps_its_seq_from_dispatch_to_wait(self, monkeypatch):
        """Three batches of one session, the second refused a bind (the
        session invalidates, the third retires to the host path): every
        `device.dispatch` opens with the scheduler's next `seq` and the
        depth of the pipeline it found, and the `device.wait` that retires
        a batch opens with that batch's `seq`."""
        from kubernetes_tpu.models import TPUScheduler

        class Refusing(FakeClientset):
            def bind(self, pod, node_name):
                if pod.name == "p20" and not getattr(self, "refused", False):
                    self.refused = True
                    raise KeyError("refused once")
                return super().bind(pod, node_name)

        annotations = StageAnnotations()
        opened = annotations.opened
        cs = Refusing()
        s = TPUScheduler(clientset=cs, max_batch=16)
        monkeypatch.setattr(s.stages, "_annotation", annotations)
        for i in range(8):
            cs.create_node(_node(f"n{i}", cpu="32"))
        for i in range(48):
            cs.create_pod(_pod(f"p{i}", cpu="100m"))
        assert s.schedule_one()      # one cycle, one session
        assert s.device_batches == s.dispatch_seq == 3
        assert s.metrics.batch_cache_flushed.value("session_invalidated") == 1
        dispatches = [st for name, st in opened
                      if name == "sched.device.dispatch"]
        waits = [st for name, st in opened if name == "sched.device.wait"]
        assert [d["seq"] for d in dispatches] == [1, 2, 3]
        assert [d["inflight"] for d in dispatches] == [0, 1, 1]
        assert [(w["seq"], w["batch"]) for w in waits] == [
            (d["seq"], d["batch"]) for d in dispatches]
        # in order on the loop: a wait follows its own dispatch, and the
        # pipeline put dispatch 2 ahead of wait 1
        order = [(name.rsplit(".", 1)[1], st["seq"]) for name, st in opened
                 if name in ("sched.device.dispatch", "sched.device.wait")]
        assert order == [("dispatch", 1), ("dispatch", 2), ("wait", 1),
                         ("dispatch", 3), ("wait", 2), ("wait", 3)]
        # the loop's turn says how many pauses the table holds
        cycles = [st for name, st in opened if name == "sched.cycle"]
        assert cycles and all(set(c) == {"pauses"} for c in cycles)
        s.run_until_idle()
        assert s.scheduled == 48
        # the next session goes on counting
        for i in range(4):
            cs.create_pod(_pod(f"q{i}", cpu="300m"))
        s.run_until_idle()
        later = [st["seq"] for name, st in opened
                 if name == "sched.device.dispatch"][3:]
        assert later == list(range(4, 4 + len(later)))

    def test_a_parked_events_wait_is_observed_once_a_drain(self):
        """Served: a pod POSTed to the apiserver reaches the scheduler on the
        reflector's thread while the loop sleeps; the drain that replays it
        observes how long the oldest parked event waited, once."""
        from kubernetes_tpu.core.apiserver import (APIServer, HTTPClientset,
                                                   pod_to_wire)
        import json as _json
        from urllib import request as urlrequest

        api = APIServer()
        url = f"http://127.0.0.1:{api.serve(0)}"
        cs = HTTPClientset(url)
        s = Scheduler(clientset=cs)
        hist = s.metrics.inbox_oldest_wait
        try:
            assert s.drain_event_inbox() == 0 and hist.count() == 0
            for name in ("late-0", "late-1", "late-2"):
                req = urlrequest.Request(
                    url + "/api/v1/pods", method="POST",
                    data=_json.dumps(pod_to_wire(_pod(name))).encode(),
                    headers={"Content-Type": "application/json"})
                urlrequest.urlopen(req, timeout=30).read()
            end = time.monotonic() + 30
            while len(s._event_inbox) < 3:      # the loop "sleeps"
                assert time.monotonic() < end, "the watch never delivered"
                time.sleep(0.002)
            seen = time.perf_counter()
            time.sleep(0.05)
            remainder = time.perf_counter() - seen
            assert s.drain_event_inbox() == 3
            # three events parked, one observation: the oldest one's wait
            assert hist.count() == 1
            assert remainder <= hist.sum() < 30
            assert s.drain_event_inbox() == 0 and hist.count() == 1
            # on the loop's own thread nothing is parked, nothing observed
            text = s.expose_metrics()
            assert "scheduler_inbox_oldest_wait_seconds_count 1" in text
        finally:
            s.shutdown()
            cs.close()
            api.shutdown()

    def test_gc_clock_counts_collections_by_generation(self):
        import gc
        clock = spans.GcClock().install()
        try:
            gc.collect()
        finally:
            clock.close()
        assert clock.collections[2] >= 1 and clock.seconds[2] >= 0.0
        lines = clock.expose("scheduler")
        assert 'scheduler_gc_collections_total{generation="2"} ' \
            f'{float(clock.collections[2])}' in lines
        assert any(line.startswith(
            'scheduler_gc_pause_seconds_total{generation="0"}')
            for line in lines)
        before = list(clock.collections)
        gc.collect()
        assert clock.collections == before  # closed: no longer listening

    def test_rehearsal_trace_holds_the_programs_spans_inside_the_wave(self):
        """A profiler trace of a cell shows the program's own `sched.*`
        spans on the timeline of the device operations, nested inside the
        benchmark's `bench.wave` (CPU rehearsal of a traced run)."""
        bench = os.path.join(REPO, "benchmark")
        env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
        proc = subprocess.run(
            [sys.executable, os.path.join(bench, "run.py"), "--workload",
             "spread-5k.waves", "--seed", "2147483777", "--seconds", "0.5",
             "--trace", "1", "--rehearse", "--keep-out"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
        import re
        import shutil
        kept = re.findall(r"kept (\S+)", proc.stdout)
        assert len(kept) == 1
        try:
            if bench not in sys.path:
                sys.path.insert(0, bench)
            import progspans
            import tracereduce
            events = progspans.host_events(
                tracereduce.newest_xplane(os.path.join(kept[0], "trace")))
        finally:
            shutil.rmtree(kept[0])
        waves = [(s0, s0 + d) for n, s0, d in events if n == "bench.wave"]
        assert waves

        def inside(name, outer):
            return [(s0, s0 + d) for n, s0, d in events if n == name
                    and any(a <= s0 and s0 + d <= b for a, b in outer)]

        cycles = inside("sched.cycle", waves)
        assert cycles
        for stage in ("sched.plan.build", "sched.device.dispatch",
                      "sched.device.wait", "sched.host.commit",
                      "sched.queue.pop", "sched.plan.adopt"):
            assert inside(stage, cycles), stage
        line = json.loads(proc.stdout.strip().splitlines()[-1])
        assert 0 <= line["metrics"]["loop_unnamed_share"]["value"] <= 100


# ---------------------------------------------------------------------------
# cross-process propagation over the real wire
# ---------------------------------------------------------------------------


class TestWirePropagation:
    def test_trace_id_survives_bind_wal_bound_observer(self, tracer, tmp_path):
        """bind POST → apiserver commit → WAL append → slim BOUND event →
        a SECOND watch client's bound.observe span, all under the pod's
        deterministic trace id; the WAL record preserves the context."""
        from kubernetes_tpu.core.apiserver import APIServer, HTTPClientset

        api = APIServer(data_dir=str(tmp_path / "state"))
        api.tracer = tracer
        port = api.serve(0)
        binder = observer = None
        try:
            binder = HTTPClientset(f"http://127.0.0.1:{port}")
            observer = HTTPClientset(f"http://127.0.0.1:{port}")
            binder.create_node(_node("n0"))
            p = _pod("traced")
            binder.create_pod(p)
            binder.bind(p, "n0")
            # Wait for the BOUND event on BOTH watch streams: each records
            # its bound.observe before updating its bindings cache.
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if all(c.bindings.get(p.uid) == "n0"
                       for c in (binder, observer)):
                    break
                time.sleep(0.02)
            assert observer.bindings.get(p.uid) == "n0"
            assert binder.bindings.get(p.uid) == "n0"
            tid = trace_id_for(p.uid)
            names = sorted(r["name"] for r in tracer.snapshot()
                           if r["trace"] == tid)
            # binder + observer both decode the BOUND event → 2 observes
            assert names == ["api.bind", "bind.post", "bound.fanout",
                             "bound.observe", "bound.observe", "wal.append"]
            # WAL records are binary wire frames now (core/wire.py):
            # interning splits a string's bytes across define/ref sites,
            # so decode the records instead of grepping raw text.
            from kubernetes_tpu.core import wire as _wire
            buf = (tmp_path / "state" / "wal.log").read_bytes()
            tctxs, pos = [], 0
            while True:
                got = _wire.scan(buf, pos)
                if got is None:
                    break
                rec, pos = got
                tctx = (rec.get("object") or {}).get("tctx")
                if tctx:
                    tctxs.append(tctx)
            assert format_ctx(tracer.context_for(p.uid)) in tctxs
        finally:
            for c in (binder, observer):
                if c is not None:
                    c.close()
            api.shutdown()

    def test_bulk_bind_items_carry_context(self, tracer):
        from kubernetes_tpu.core.apiserver import APIServer, HTTPClientset

        api = APIServer()
        api.tracer = tracer
        port = api.serve(0)
        cs = None
        try:
            cs = HTTPClientset(f"http://127.0.0.1:{port}")
            cs.create_node(_node("n0", cpu="32"))
            pods = [_pod(f"b{i}", cpu="100m") for i in range(4)]
            for p in pods:
                cs.create_pod(p)
            assert cs.bind_many([(p, "n0") for p in pods]) == [None] * 4
            rows = tracer.snapshot()
            posts = [r for r in rows if r["name"] == "bind.post"]
            assert len(posts) == 4
            assert all(r["attrs"]["bulk"] == 4 for r in posts)
            binds = {r["trace"] for r in rows if r["name"] == "api.bind"}
            assert binds == {trace_id_for(p.uid) for p in pods}
        finally:
            if cs is not None:
                cs.close()
            api.shutdown()

    @pytest.mark.chaos
    def test_real_two_process_roundtrip_artifact(self, tracer, tmp_path):
        """REAL two-OS-process round trip: the apiserver runs as its own
        process (flight recorder installed into its data dir), the client
        binds over the socket, and the server's flight-recorder artifact
        holds the server-side half of the SAME trace id."""
        from kubernetes_tpu.core.apiserver import HTTPClientset
        from kubernetes_tpu.testing.faults import ApiServerProcess

        api = ApiServerProcess(str(tmp_path / "state"))
        cs = None
        try:
            cs = HTTPClientset(api.url)
            cs.create_node(_node("n0"))
            p = _pod("crosswire")
            cs.create_pod(p)
            cs.bind(p, "n0")
            # The BOUND event arrives asynchronously on the watch stream;
            # _dispatch records bound.observe BEFORE updating the bindings
            # cache on the same thread, so the cache is the ready signal.
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if cs.bindings.get(p.uid) == "n0":
                    break
                time.sleep(0.02)
            tid = trace_id_for(p.uid)
            local = {r["name"] for r in tracer.snapshot()
                     if r["trace"] == tid}
            assert {"bind.post", "bound.observe"} <= local
        finally:
            if cs is not None:
                cs.close()
            api.stop()  # SIGTERM → graceful shutdown dump
        arts = [f for f in os.listdir(tmp_path / "state")
                if f.startswith("flightrec-") and f.endswith(".jsonl")]
        assert arts, "apiserver process left no flight-recorder artifact"
        rows = []
        for a in arts:
            with open(tmp_path / "state" / a) as f:
                rows.extend(json.loads(line) for line in f if line.strip())
        server_side = {r["name"] for r in rows
                       if r.get("kind") == "span" and r.get("trace") == tid}
        assert {"api.bind", "wal.append", "bound.fanout"} <= server_side
        assert any(r.get("kind") == "meta" and r.get("proc") == "apiserver"
                   for r in rows)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_dump_on_sigusr2_and_parses(self, tracer, tmp_path):
        cs = FakeClientset()
        s = Scheduler(clientset=cs, deterministic_ties=True)
        cs.create_node(_node("n0"))
        cs.create_pod(_pod("p0"))
        s.run_until_idle()
        fr = FlightRecorder(str(tmp_path), tracer=tracer,
                            recorder=s.recorder, scheduler=s).install(
            on_crash=False)
        try:
            signal.raise_signal(signal.SIGUSR2)
            path = tmp_path / f"flightrec-{os.getpid()}.jsonl"
            assert path.exists()
            rows = [json.loads(line) for line in path.read_text().splitlines()]
            kinds = {r["kind"] for r in rows}
            assert {"meta", "span", "event", "counters"} <= kinds
            meta = rows[0]
            assert meta["kind"] == "meta" and meta["reason"] == "sigusr2"
            counters = next(r for r in rows if r["kind"] == "counters")
            assert counters["scheduled"] == 1
            assert any(r["kind"] == "event" and r["reason"] == "Scheduled"
                       for r in rows)
        finally:
            fr.close()

    def test_rate_limited_request_dump_and_slow_stage_trigger(
            self, tracer, tmp_path, caplog, monkeypatch):
        """The one slow-stage rule on the host path: a stage over its
        threshold is logged by name, leaves a forced span on the pod's
        trace and dumps the flight recorder (which then rate-limits)."""
        monkeypatch.setattr(spans, "SLOW_STAGE_S", -1.0)  # all stages slow
        fr = FlightRecorder(str(tmp_path), tracer=tracer).install(
            sigusr2=False, on_crash=False)
        try:
            cs = FakeClientset()
            s = Scheduler(clientset=cs, deterministic_ties=True)
            fr.scheduler = s
            cs.create_node(_node("n0"))
            pod = _pod("slowpod")
            cs.create_pod(pod)
            with caplog.at_level(logging.WARNING, logger="kubernetes_tpu"):
                s.run_until_idle()
            # the offending stage named explicitly, with the pod
            assert any("slow scheduling stage: host.commit" in r.getMessage()
                       and "pod=default/slowpod" in r.getMessage()
                       for r in caplog.records)
            # a forced span for it, on the pod's trace
            slow = [r for r in tracer.snapshot()
                    if r["name"] == "trace.slow_stage"
                    and r["attrs"]["stage"] == "host.commit"]
            assert slow and slow[0]["trace"] == trace_id_for(pod.uid)
            assert slow[0]["attrs"]["pod"] == "default/slowpod"
            assert {"self_ms", "inflight", "compiles"} <= set(slow[0]["attrs"])
            # the breach dumped the flight recorder (then rate-limits), and
            # the dump carries the stage table
            assert fr.dumps == 1
            assert fr.dump("again", rate_limited=True) is None
            rows = [json.loads(line) for line in
                    open(fr.path).read().splitlines()]
            table = next(r for r in rows if r["kind"] == "stages")
            assert set(table["seconds"]) == set(spans.LOOP_STAGES)
        finally:
            fr.close()

    def test_slow_stage_without_pod_ctx_uses_proc_ctx(
            self, tracer, monkeypatch):
        """A slow stage that belongs to no sampled pod (a loop turn, a
        batch with no sampled member) reports on the process's trace."""
        monkeypatch.setattr(spans, "SLOW_STAGE_S", -1.0)
        monkeypatch.setattr(spans, "SLOW_BATCH_STAGE_S", -1.0)
        ledger = spans.StageLedger(tracer)
        with ledger.stage("cycle"):
            ledger.leaf("queue.pop", 0.0)
        slow = {r["attrs"]["stage"]: r for r in tracer.snapshot()
                if r["name"] == "trace.slow_stage"}
        assert set(slow) == {"cycle", "queue.pop"}
        assert all(r["trace"] == tracer.proc_ctx().trace_id
                   for r in slow.values())

    def test_slow_stage_in_a_device_session(self, tracer, tmp_path,
                                            monkeypatch):
        """The rule covers device sessions (where the host path's old
        StepTrace never looked): a slow batch stage leaves a forced span
        with the batch size, the plan kind, the pipeline depth and whether
        a compile ran inside it — and a dump."""
        from kubernetes_tpu.models import TPUScheduler

        monkeypatch.setattr(spans, "SLOW_BATCH_STAGE_S", -1.0)
        fr = FlightRecorder(str(tmp_path), tracer=tracer).install(
            sigusr2=False, on_crash=False)
        try:
            cs = FakeClientset()
            s = TPUScheduler(clientset=cs)
            for i in range(8):
                cs.create_node(_node(f"n{i}", cpu="32"))
            proto = _pod("proto", cpu="100m")
            for i in range(32):
                cs.create_pod(proto.clone_from_template(f"p{i}"))
            s.run_until_idle()
            assert s.device_batches > 0
            slow = {}
            for r in tracer.snapshot():
                if r["name"] == "trace.slow_stage":
                    slow.setdefault(r["attrs"]["stage"], r)
            assert {"plan.build", "device.dispatch", "device.wait",
                    "host.commit", "plan.adopt"} <= set(slow)
            assert slow["plan.build"]["attrs"]["kind"] == "full"
            assert slow["device.wait"]["attrs"]["batch"] == "32"
            assert slow["device.wait"]["attrs"]["inflight"] == 0
            assert slow["device.dispatch"]["attrs"]["compiles"] >= 0
            # every pod is sampled here: the batch stages report on the
            # first member's trace, not on the process's
            assert slow["device.wait"]["trace"] != tracer.proc_ctx().trace_id
            assert fr.dumps >= 1
        finally:
            fr.close()

    def test_autodump_timer_leaves_periodic_artifacts(self, tracer, tmp_path):
        fr = FlightRecorder(str(tmp_path), tracer=tracer).install(
            sigusr2=False, on_crash=False, autodump_interval=0.05)
        try:
            deadline = time.monotonic() + 5
            path = tmp_path / f"flightrec-{os.getpid()}.jsonl"
            while time.monotonic() < deadline and not path.exists():
                time.sleep(0.02)
            assert path.exists()
            rows = [json.loads(line) for line in path.read_text().splitlines()]
            assert rows[0]["reason"] == "periodic"
        finally:
            fr.close()


# ---------------------------------------------------------------------------
# /debug/events (EventRecorder read-side staleness fix)
# ---------------------------------------------------------------------------


class TestDebugEvents:
    def test_recent_resorts_aggregated_events_newest_first(self):
        from kubernetes_tpu.core.tracing import EventRecorder

        rec = EventRecorder()
        rec.eventf("default/a", "Warning", "FailedScheduling", "no fit")
        rec.eventf("default/b", "Normal", "Scheduled", "assigned b")
        # aggregate re-fires for a: its timestamp moves PAST b's, but the
        # deque insertion order still has a first — the staleness bug
        rec.eventf("default/a", "Warning", "FailedScheduling", "still no fit")
        recent = rec.recent()
        assert [e.object_key for e in recent] == ["default/a", "default/b"]
        assert recent[0].count == 2
        only_b = rec.recent("default/b")
        assert len(only_b) == 1 and only_b[0].reason == "Scheduled"

    def test_debug_events_endpoint_serves_recorder(self):
        from urllib.request import urlopen

        from kubernetes_tpu.core.server import SchedulerServer

        cs = FakeClientset()
        s = Scheduler(clientset=cs, deterministic_ties=True)
        cs.create_node(_node("n0"))
        cs.create_pod(_pod("p0"))
        cs.create_pod(_pod("huge", cpu="64"))
        s.run_until_idle()
        srv = SchedulerServer(s)
        port = srv.serve(0)
        try:
            body = json.loads(urlopen(
                f"http://127.0.0.1:{port}/debug/events", timeout=5).read())
            assert {e["reason"] for e in body} >= {"Scheduled",
                                                   "FailedScheduling"}
            # newest-first: the repeatedly re-aggregated FailedScheduling
            # (huge requeues) must sort to the top despite older insertion
            assert body[0]["timestamp"] >= body[-1]["timestamp"]
            one = json.loads(urlopen(
                f"http://127.0.0.1:{port}/debug/events?object=default/p0",
                timeout=5).read())
            assert one and all(e["object"] == "default/p0" for e in one)
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------------
# trace analyzer CLI (golden output on a recorded fixture trace)
# ---------------------------------------------------------------------------


def _fixture_spans(tmp_path):
    """A hand-recorded 2-process fixture: one complete bound-pod trace with
    a cross-shard conflict, one incomplete trace."""
    t0 = 1000.0
    tid = trace_id_for("fixture-pod")
    shard = [
        {"trace": tid, "span": "1.1", "parent": "", "name": "queue.admission",
         "proc": "shard-0", "pid": 1, "ts": t0, "dur": 0.0, "attrs": {}},
        {"trace": tid, "span": "1.2", "parent": "", "name": "queue.wait",
         "proc": "shard-0", "pid": 1, "ts": t0, "dur": 0.010, "attrs": {}},
        {"trace": tid, "span": "1.3", "parent": "", "name": "bind.conflict",
         "proc": "shard-0", "pid": 1, "ts": t0 + 0.012, "dur": 0.0,
         "attrs": {"node": "n3", "reason": "already_bound"}},
        {"trace": tid, "span": "1.4", "parent": "", "name": "host.commit",
         "proc": "shard-0", "pid": 1, "ts": t0 + 0.050, "dur": 0.002,
         "attrs": {}},
        {"trace": tid, "span": "1.5", "parent": "", "name": "bind.post",
         "proc": "shard-0", "pid": 1, "ts": t0 + 0.052, "dur": 0.003,
         "attrs": {"bulk": 2}},
        {"trace": tid, "span": "1.6", "parent": "", "name": "pod.e2e",
         "proc": "shard-0", "pid": 1, "ts": t0, "dur": 0.056, "attrs": {}},
        {"trace": trace_id_for("incomplete"), "span": "1.7", "parent": "",
         "name": "queue.wait", "proc": "shard-0", "pid": 1, "ts": t0,
         "dur": 0.001, "attrs": {}},
    ]
    api = [
        {"trace": tid, "span": "2.1", "parent": "", "name": "api.bind",
         "proc": "apiserver", "pid": 2, "ts": t0 + 0.053, "dur": 0.001,
         "attrs": {"node": "n5", "code": 200}},
        {"trace": tid, "span": "2.2", "parent": "", "name": "wal.append",
         "proc": "apiserver", "pid": 2, "ts": t0 + 0.0535, "dur": 0.0005,
         "attrs": {"rv": 7}},
        {"trace": tid, "span": "2.3", "parent": "", "name": "bound.fanout",
         "proc": "apiserver", "pid": 2, "ts": t0 + 0.054, "dur": 0.0002,
         "attrs": {"watchers": 2}},
    ]
    write_jsonl(str(tmp_path / "spans-shard0.jsonl"), shard)
    write_jsonl(str(tmp_path / "spans-api.jsonl"), api)
    return tid


class TestAnalyzerCLI:
    def test_golden_report_on_fixture_trace(self, tmp_path):
        from kubernetes_tpu import trace as trace_mod

        tid = _fixture_spans(tmp_path)
        buf = io.StringIO()
        rc = trace_mod.main([str(tmp_path), "--critical-paths", "1"], out=buf)
        assert rc == 0
        out = buf.getvalue()
        # merged across both processes
        assert "2 process(es): apiserver, shard-0" in out
        # completeness: 1 bound trace, complete core chain
        assert "complete chains: 1/1 bound traces (100.0%)" in out
        # per-stage table with pipeline ordering and p50/p95/p99 columns
        assert "per-stage latency (ms):" in out
        assert out.index("queue.wait") < out.index("bind.post") \
            < out.index("wal.append")
        # conflict timeline: who lost which node, and the wait→retry cost
        assert "shard-0 lost n3 (already_bound)" in out
        assert "rebound after" in out
        # critical path breakdown names the trace and its stages in order
        assert f"trace {tid}" in out
        assert "[apiserver]" in out and "[shard-0]" in out

    def test_json_summary_and_chrome_trace_export(self, tmp_path):
        from kubernetes_tpu import trace as trace_mod

        _fixture_spans(tmp_path)
        out_json = tmp_path / "chrome.json"
        buf = io.StringIO()
        rc = trace_mod.main([str(tmp_path), "--json",
                             "--chrome-trace", str(out_json)], out=buf)
        assert rc == 0
        summary = json.loads(buf.getvalue())
        assert summary["completeness"]["complete_chains"] == 1
        assert summary["stages"]["queue.wait"]["count"] == 2
        assert summary["conflicts"][0]["retry_cost_s"] > 0
        chrome = json.loads(out_json.read_text())
        assert chrome["traceEvents"]
        assert {e["ph"] for e in chrome["traceEvents"]} == {"X", "M"}
        names = {e["args"]["name"] for e in chrome["traceEvents"]
                 if e["ph"] == "M"}
        assert names == {"shard-0", "apiserver"}

    def test_cli_module_entrypoint(self, tmp_path):
        import subprocess

        _fixture_spans(tmp_path)
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "kubernetes_tpu.trace", str(tmp_path)],
            capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
        assert proc.returncode == 0, proc.stderr
        assert "per-stage latency" in proc.stdout
        empty = subprocess.run(
            [sys.executable, "-m", "kubernetes_tpu.trace",
             str(tmp_path / "nothing-here")],
            capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
        assert empty.returncode == 1

    def test_flightrec_artifacts_load_as_spans(self, tmp_path, tracer):
        """load_spans must accept flight-recorder artifacts (kind-tagged
        rows, non-span rows skipped) and torn final lines."""
        from kubernetes_tpu import trace as trace_mod

        tracer.record("queue.wait", tracer.context_for("u1"), 0.001)
        fr = FlightRecorder(str(tmp_path), tracer=tracer)
        fr.dump("test")
        # torn tail: a crash can cut a line mid-write
        with open(fr.path, "a") as f:
            f.write('{"kind": "span", "trace": "tr')
        spans_loaded = trace_mod.load_spans([str(tmp_path)])
        assert len(spans_loaded) == 1
        assert spans_loaded[0]["name"] == "queue.wait"
