"""Auxiliary subsystems: cache debugger, leader election, scheduler server,
extra plugins (SURVEY.md §5, §2.3 tail)."""

import json
import urllib.request

from kubernetes_tpu.core.config import PluginSet, ProfileConfig, SchedulerConfiguration
from kubernetes_tpu.core.debugger import CacheDebugger
from kubernetes_tpu.core.leaderelection import LeaderElector, LeaseStore
from kubernetes_tpu.core.scheduler import Scheduler
from kubernetes_tpu.core.server import SchedulerServer
from kubernetes_tpu.testing.wrappers import make_node, make_pod


def _basic_sched():
    s = Scheduler()
    s.clientset.create_node(
        make_node().name("n0").capacity({"cpu": "4", "pods": 10}).obj())
    s.clientset.create_pod(make_pod().name("p").req({"cpu": "1"}).obj())
    s.run_until_idle()
    return s


class TestCacheDebugger:
    def test_dump_and_compare_clean(self):
        s = _basic_sched()
        d = CacheDebugger(s)
        out = d.dump()
        assert "n0" in out and "Queue:" in out
        assert d.compare() == []

    def test_compare_detects_divergence(self):
        s = _basic_sched()
        # sabotage: drop the node from the cache behind the scheduler's back
        s.cache.remove_node("n0")
        d = CacheDebugger(s)
        problems = d.compare()
        assert any("n0" in p for p in problems)


class TestLeaderElection:
    def test_single_candidate_acquires(self):
        store = LeaseStore()
        t = [0.0]
        e = LeaderElector(store, "a", now=lambda: t[0])
        assert e.tick() and e.is_leader()

    def test_failover_after_expiry(self):
        store = LeaseStore()
        t = [0.0]
        a = LeaderElector(store, "a", now=lambda: t[0])
        b = LeaderElector(store, "b", now=lambda: t[0])
        assert a.tick()
        assert not b.tick()  # a holds the lease
        t[0] = 20.0          # a missed renewals past leaseDuration (15s)
        assert b.tick() and b.is_leader()
        assert not a.tick()  # a observes the takeover and steps down
        assert not a.is_leader()

    def test_voluntary_release(self):
        store = LeaseStore()
        a = LeaderElector(store, "a")
        b = LeaderElector(store, "b")
        a.tick()
        a.release()
        assert b.tick()


class TestSchedulerServer:
    def test_endpoints(self):
        s = _basic_sched()
        srv = SchedulerServer(s)
        port = srv.serve()
        try:
            def get(path):
                with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as r:
                    return r.status, r.read().decode()

            assert get("/healthz")[0] == 200
            assert get("/readyz")[0] == 200
            status, body = get("/metrics")
            assert status == 200 and "scheduler_schedule_attempts_total" in body
            status, body = get("/debug/cache")
            assert status == 200 and "n0" in body
            status, body = get("/debug/comparer")
            assert status == 200 and json.loads(body) == []
        finally:
            srv.shutdown()

    def test_run_cycles_requires_leadership(self):
        store = LeaseStore()
        s1 = Scheduler()
        srv1 = SchedulerServer(s1, identity="a", lease_store=store, leader_elect=True)
        s2 = Scheduler()
        srv2 = SchedulerServer(s2, identity="b", lease_store=store, leader_elect=True)
        for srv in (srv1, srv2):
            srv.scheduler.clientset.create_node(
                make_node().name("n0").capacity({"cpu": "4", "pods": 10}).obj())
            srv.scheduler.clientset.create_pod(
                make_pod().name("p").req({"cpu": "1"}).obj())
        srv1.run_cycles()
        srv2.run_cycles()
        assert s1.scheduled == 1   # leader scheduled
        assert s2.scheduled == 0   # standby did nothing


class TestExtraPlugins:
    def test_node_declared_features(self):
        cfg = SchedulerConfiguration(profiles=[ProfileConfig(
            plugins=PluginSet(enabled=(("NodeDeclaredFeatures", 0),)))])
        s = Scheduler(config=cfg, deterministic_ties=True)
        n_plain = make_node().name("plain").capacity({"cpu": "4", "pods": 10}).obj()
        n_feat = make_node().name("featured").capacity({"cpu": "4", "pods": 10}).obj()
        n_feat.declared_features = {"fast-net": True}
        s.clientset.create_node(n_plain)
        s.clientset.create_node(n_feat)
        p = make_pod().name("p").req({"cpu": "1"}).obj()
        p.annotations["features.k8s.io/required"] = "fast-net"
        s.clientset.create_pod(p)
        s.run_until_idle()
        assert list(s.clientset.bindings.values()) == ["featured"]

    def test_deferred_pod_scheduling(self):
        t = [100.0]
        cfg = SchedulerConfiguration(profiles=[ProfileConfig(
            plugins=PluginSet(enabled=(("DeferredPodScheduling", 0),)),
            plugin_config={"DeferredPodScheduling": {"now": lambda: t[0]}})])
        s = Scheduler(config=cfg)
        s.clientset.create_node(
            make_node().name("n0").capacity({"cpu": "4", "pods": 10}).obj())
        p = make_pod().name("deferred").req({"cpu": "1"}).obj()
        p.annotations["scheduling.k8s.io/defer-until"] = "200.0"
        s.clientset.create_pod(p)
        s.run_until_idle()
        assert s.scheduled == 0  # gated
        t[0] = 250.0
        updated = p  # annotation unchanged; deadline passed
        s.clientset.update_pod(updated)
        s.run_until_idle()
        assert s.scheduled == 1


def test_remote_clientset_equivalence_with_latency():
    """The watch-seam transport (core/remote.py): scheduling against a
    1ms-RTT apiserver thread with the async dispatcher produces the SAME
    assignments as the in-process clientset, with watch events crossing
    threads through the reflector inbox."""
    from kubernetes_tpu.core import FakeClientset, Scheduler
    from kubernetes_tpu.core.config import SchedulerConfiguration
    from kubernetes_tpu.core.remote import RemoteClientset
    from kubernetes_tpu.models import TPUScheduler
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    def load(cs):
        for i in range(20):
            cs.create_node(make_node().name(f"n{i}")
                           .capacity({"cpu": "8", "memory": "16Gi", "pods": 110})
                           .zone(f"z{i % 4}").obj())
        proto = make_pod().name("proto").req({"cpu": "500m"}).obj()
        pods = [proto.clone_from_template(f"p{i}") for i in range(80)]
        for p in pods:
            cs.create_pod(p)
        return pods

    cs_h = FakeClientset()
    host = Scheduler(clientset=cs_h, deterministic_ties=True)
    ph = load(cs_h)
    host.run_until_idle()

    cs_r = RemoteClientset(rtt=0.001)
    cfg = SchedulerConfiguration(async_dispatch_threads=True)
    dev = TPUScheduler(clientset=cs_r, config=cfg)
    pr = load(cs_r)
    import time
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and dev.scheduled < 80:
        dev.run_until_idle()
        time.sleep(0.002)
    dev.api_dispatcher.flush()
    dev.run_until_idle()
    hb = {p.name: cs_h.bindings.get(p.uid) for p in ph}
    rb = {p.name: cs_r.bindings.get(p.uid) for p in pr}
    assert hb == rb
    assert cs_r.calls >= 180  # every write crossed the transport
    cs_r.close()


def test_scheduler_binary_once_mode(tmp_path):
    """The cmd/kube-scheduler analogue (python -m kubernetes_tpu): bootstrap
    a cluster manifest, serve endpoints, drain the queue, exit cleanly. The
    ready line names the backend JAX gave the process, and the spawned
    binary caches its compiles where the environment says (threshold 0: CPU
    compiles sit under JAX's default 1 s)."""
    import os
    import subprocess
    import sys

    from kubernetes_tpu import compile_cache

    manifest = tmp_path / "cluster.yaml"
    manifest.write_text(
        "nodes:\n- {count: 6, cpu: 8, memory: 32Gi, pods: 110, zones: 2}\n"
        "pods:\n- {count: 12, cpu: 250m}\n")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    placed = str(tmp_path / "placed-cache")
    env = dict(os.environ, JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env[compile_cache.ENV_VAR] = placed
    out = subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu", "--cluster", str(manifest),
         "--port", "0", "--once", "--platform", "cpu"],
        capture_output=True, text=True, timeout=180, cwd=repo_root, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "scheduled=12 failures=0" in out.stdout
    assert "backend=cpu device_kind='cpu' devices=8" in out.stdout
    assert compile_cache.entry_count(placed) > 0
