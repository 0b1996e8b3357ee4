"""Chaos suite: fault injection against every resilience boundary
(docs/RESILIENCE.md).

Deterministic-seed tests carry the `chaos` marker and run in tier-1; the
long kill/restart stress is `slow` (excluded by `-m 'not slow'`).
"""

import json
import os
import socket
import threading
import time

import pytest

from kubernetes_tpu.core import FakeClientset, Scheduler
from kubernetes_tpu.core.api_dispatcher import (APICall, APIDispatcher,
                                                CALL_BINDING)
from kubernetes_tpu.core.backoff import (CircuitBreaker, RetryConfig,
                                         TransientAPIError, is_retriable,
                                         retry_call)
from kubernetes_tpu.core.clientset import RetryingClientset
from kubernetes_tpu.testing.faults import (ChaosTCPProxy, DeviceFaults,
                                           FlakyClientset)
from kubernetes_tpu.testing.wrappers import make_node, make_pod

FAST_RETRY = RetryConfig(initial_backoff=0.001, max_backoff=0.01,
                         max_attempts=4, seed=0)


def _nodes(n, cpu=16):
    return [make_node().name(f"n{i}")
            .capacity({"cpu": cpu, "memory": "64Gi", "pods": 110})
            .zone(f"z{i % 4}").obj() for i in range(n)]


def _pods(n, cpu="100m"):
    proto = (make_pod().name("proto").req({"cpu": cpu, "memory": "64Mi"})
             .labels({"app": "chaos"}).obj())
    return [proto.clone_from_template(f"p{i}") for i in range(n)]


# ---------------------------------------------------------------------------
# backoff.py units
# ---------------------------------------------------------------------------


class TestBackoff:
    def test_delays_deterministic_and_bounded(self):
        cfg = RetryConfig(initial_backoff=0.1, max_backoff=0.5,
                          multiplier=2.0, jitter=0.2, max_attempts=6, seed=7)
        a, b = list(cfg.delays()), list(cfg.delays())
        assert a == b  # same seed, same sequence
        assert len(a) == 5
        assert all(d <= 0.5 * 1.2 + 1e-9 for d in a)
        assert a[0] < a[-1]  # grows toward the cap

    def test_is_retriable_taxonomy(self):
        import http.client
        from urllib.error import HTTPError, URLError
        assert is_retriable(TransientAPIError("x"))
        assert is_retriable(ConnectionResetError())
        assert is_retriable(TimeoutError())
        assert is_retriable(socket.timeout())
        assert is_retriable(HTTPError("u", 503, "boom", {}, None))
        assert not is_retriable(HTTPError("u", 404, "nope", {}, None))
        assert is_retriable(URLError(ConnectionResetError()))
        assert is_retriable(http.client.RemoteDisconnected())
        assert not is_retriable(KeyError("pod not found"))
        assert not is_retriable(ValueError("bad spec"))

    def test_retry_call_replays_then_succeeds(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise TransientAPIError("blip")
            return "ok"

        assert retry_call(flaky, FAST_RETRY, sleep=lambda d: None) == "ok"
        assert calls["n"] == 3

    def test_retry_call_nonretriable_raises_immediately(self):
        calls = {"n": 0}

        def broken():
            calls["n"] += 1
            raise KeyError("missing")

        with pytest.raises(KeyError):
            retry_call(broken, FAST_RETRY, sleep=lambda d: None)
        assert calls["n"] == 1

    def test_retry_call_budget_exhausted(self):
        with pytest.raises(TransientAPIError):
            retry_call(lambda: (_ for _ in ()).throw(TransientAPIError("x")),
                       FAST_RETRY, sleep=lambda d: None)

    def test_circuit_breaker_lifecycle(self):
        t = {"now": 0.0}
        br = CircuitBreaker(failure_threshold=3, cooldown=5.0,
                            clock=lambda: t["now"])
        assert br.allows() and br.state == "closed"
        assert not br.record_failure()
        assert not br.record_failure()
        assert br.record_failure()  # third consecutive: opens
        assert br.state == "open" and not br.allows()
        t["now"] = 5.1
        assert br.state == "half-open" and br.allows()  # one probe
        assert br.record_failure()  # failed probe: re-opens
        assert not br.allows()
        t["now"] = 10.3
        assert br.allows()
        br.record_success()  # clean probe: closes
        assert br.state == "closed" and br.open_count == 2
        br.record_failure()
        br.record_success()  # success resets the consecutive count
        br.record_failure()
        br.record_failure()
        assert br.state == "closed"


# ---------------------------------------------------------------------------
# clientset write retries
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestClientsetRetries:
    def test_write_retries_transparent(self):
        inner = FakeClientset()
        flaky = FlakyClientset(inner, fail_first={"create_pod": 2, "bind": 1})
        rcs = RetryingClientset(flaky, retry=FAST_RETRY)
        pod = _pods(1)[0]
        rcs.create_node(_nodes(1)[0])
        rcs.create_pod(pod)  # 2 injected faults, then lands
        assert pod.uid in inner.pods
        rcs.bind(pod, "n0")
        assert inner.bindings[pod.uid] == "n0"
        assert rcs.retries_total == 3
        assert flaky.injected == {"create_pod": 2, "bind": 1}
        assert rcs.give_ups == 0

    def test_semantic_error_not_retried(self):
        inner = FakeClientset()
        rcs = RetryingClientset(FlakyClientset(inner), retry=FAST_RETRY)
        with pytest.raises(KeyError):
            rcs.bind(_pods(1)[0], "n0")  # pod never created: not transient
        assert rcs.retries_total == 0

    def test_budget_exhaustion_propagates(self):
        inner = FakeClientset()
        flaky = FlakyClientset(inner, fail_first={"create_pod": 99})
        rcs = RetryingClientset(flaky, retry=FAST_RETRY)
        with pytest.raises(TransientAPIError):
            rcs.create_pod(_pods(1)[0])
        assert rcs.give_ups == 1
        assert rcs.retries_total == FAST_RETRY.max_attempts - 1

    def test_reads_and_registration_delegate(self):
        inner = FakeClientset()
        rcs = RetryingClientset(FlakyClientset(inner), retry=FAST_RETRY)
        seen = []
        rcs.on_pod_event(lambda kind, old, new: seen.append(kind))
        rcs.create_pod(_pods(1)[0])
        assert seen == ["add"]
        assert rcs.pods is inner.pods


# ---------------------------------------------------------------------------
# async API dispatcher retries
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestDispatcherRetries:
    def _flaky_call(self, fails, log):
        state = {"left": fails}

        def execute():
            if state["left"] > 0:
                state["left"] -= 1
                raise TransientAPIError("write timeout")
            log.append("done")

        return execute

    def test_inline_mode_retries_before_error(self):
        d = APIDispatcher(mode="inline", retry=FAST_RETRY)
        log = []
        d.add(APICall(CALL_BINDING, "u1", self._flaky_call(2, log)))
        assert log == ["done"]
        assert d.retried == 2 and d.executed == 1 and not d.errors

    def test_thread_mode_retries_then_inbox_on_exhaustion(self):
        d = APIDispatcher(mode="thread", retry=FAST_RETRY)
        try:
            log = []
            d.add(APICall(CALL_BINDING, "ok", self._flaky_call(3, log)))
            d.flush()
            assert log == ["done"] and not d.has_errors()
            failed = []
            d.add(APICall(CALL_BINDING, "doomed", self._flaky_call(99, []),
                          on_error=lambda e: failed.append(e)))
            d.flush()
            deadline = time.monotonic() + 5
            while not d.has_errors() and time.monotonic() < deadline:
                time.sleep(0.01)
            drained = d.drain_errors()
            assert len(drained) == 1  # only the budget-exhausted call
            assert isinstance(drained[0][1], TransientAPIError)
        finally:
            d.close()

    def test_semantic_error_skips_retry(self):
        d = APIDispatcher(mode="inline", retry=FAST_RETRY)
        errs = []
        d.add(APICall(CALL_BINDING, "u9",
                      lambda: (_ for _ in ()).throw(KeyError("pod gone")),
                      on_error=lambda e: errs.append(e)))
        assert d.retried == 0 and len(errs) == 1


# ---------------------------------------------------------------------------
# sidecar: disconnects, kill + restart, request replay
# ---------------------------------------------------------------------------


def _start_sidecar(path, max_batch=64):
    from kubernetes_tpu.parallel.sidecar import SidecarServer
    # mesh=None: the single-device kernel path — this environment's XLA
    # miscompiles the SPMD partitioning of the scan (pre-existing; the
    # breaker contains it), and chaos tests need a WORKING device path.
    server = SidecarServer(path, max_batch=max_batch, mesh=None)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            probe = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            probe.connect(path)
            probe.close()
            return server, t
        except OSError:
            time.sleep(0.02)
    raise TimeoutError("sidecar never came up")


@pytest.mark.chaos
def test_sidecar_survives_client_disconnects(tmp_path):
    from kubernetes_tpu.parallel.sidecar import SidecarClient
    path = str(tmp_path / "tpu.sock")
    server, _ = _start_sidecar(path)
    try:
        # A client that sends a truncated frame and vanishes...
        rude = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        rude.connect(path)
        rude.sendall(b"\x00\x00\x00\x10partial")
        rude.close()
        # ...and one that resets mid-exchange...
        rude2 = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        rude2.connect(path)
        rude2.sendall(b"\x00\x00")
        rude2.close()
        # ...must not take the server down.
        client = SidecarClient(path, timeout=10)
        assert client.ping()
        client.close()
        assert server.served_connections >= 3
    finally:
        server.shutdown()


def _oracle_assignments(nodes_fn, pods_fn):
    cs = FakeClientset()
    host = Scheduler(clientset=cs, deterministic_ties=True)
    for n in nodes_fn():
        cs.create_node(n)
    for p in pods_fn():
        cs.create_pod(p)
    host.run_until_idle()
    return {cs.pods[u].name: n for u, n in cs.bindings.items()}


def _run_sidecar_batches(tmp_path, n_nodes, n_pods, batch, kill_at=()):
    """Feed pods through the sidecar in batches, killing + restarting the
    server process-analogue before the batch indices in `kill_at`. Returns
    (assignments, client)."""
    from kubernetes_tpu.parallel.sidecar import SidecarClient
    path = str(tmp_path / "tpu.sock")
    server, _ = _start_sidecar(path)
    client = SidecarClient(
        path, timeout=60,
        retry=RetryConfig(initial_backoff=0.05, max_backoff=0.5,
                          max_attempts=10, seed=3))
    got = {}
    try:
        client.sync_nodes(_nodes(n_nodes))
        pods = _pods(n_pods)
        for bi in range(0, n_pods, batch):
            if bi // batch in kill_at:
                server.kill()  # SIGKILL analogue: no goodbye
                server, _ = _start_sidecar(path)
            chunk = pods[bi:bi + batch]
            assignments = client.schedule(chunk)
            for p, a in zip(chunk, assignments):
                got[p.name] = a
    finally:
        client.shutdown_server()
        client.close()
        server.shutdown()
    return got, client


@pytest.mark.chaos
def test_sidecar_kill_restart_replay(tmp_path):
    """One sidecar kill+restart mid-run (100 nodes / 1000 pods): the client
    reconnects, resyncs nodes + bound load + rotation, replays the lost
    request, and the full assignment map still matches a fault-free
    in-process oracle."""
    got, client = _run_sidecar_batches(
        tmp_path, n_nodes=100, n_pods=1000, batch=100, kill_at={3})
    oracle = _oracle_assignments(lambda: _nodes(100), lambda: _pods(1000))
    assert client.reconnects >= 1
    unassigned = [k for k, v in got.items() if not v]
    assert not unassigned, f"{len(unassigned)} pods unassigned"
    diffs = {k: (oracle.get(k), got[k]) for k in got if got[k] != oracle.get(k)}
    assert not diffs, f"{len(diffs)} divergences, e.g. {list(diffs.items())[:5]}"


@pytest.mark.slow
def test_sidecar_repeated_kill_stress(tmp_path):
    """Long-running kill/restart stress: three kills across a 1000-pod run."""
    got, client = _run_sidecar_batches(
        tmp_path, n_nodes=100, n_pods=1000, batch=50, kill_at={4, 9, 14})
    oracle = _oracle_assignments(lambda: _nodes(100), lambda: _pods(1000))
    assert client.reconnects >= 3
    assert {k: v for k, v in got.items() if v} == oracle


# ---------------------------------------------------------------------------
# device-path circuit breaker + the shape-error regression
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestDeviceBreaker:
    def test_preemption_never_interned_scalar_regression(self):
        """A preemptor carrying a scalar resource the
        mirror never interned grows r_slots inside build_plan AFTER the
        victim tensors were built; the dry run must zero-pad and run, not
        crash the PostFilter cycle with a shape error."""
        from kubernetes_tpu.models import TPUScheduler
        cs = FakeClientset()
        sched = TPUScheduler(clientset=cs, max_batch=16, mesh=None)
        # Four node-level scalar resources fill the mirror's default
        # scalar tier exactly (s_cap=4): the NEXT interned scalar _grow()s.
        for i in range(4):
            cs.create_node(
                make_node().name(f"n{i}")
                .capacity({"cpu": 4, "memory": "8Gi", "pods": 110,
                           "r0.example.com/a": 8, "r1.example.com/b": 8,
                           "r2.example.com/c": 8, "r3.example.com/d": 8})
                .obj())
        for p in _pods(4, cpu="3"):  # victims: one 3-cpu pod per node
            cs.create_pod(p)
        sched.run_until_idle()
        assert len(cs.bindings) == 4
        r_slots_before = sched.mirror.r_slots
        pre = (make_pod().name("preemptor").priority(10)
               .req({"cpu": "2", "memory": "64Mi",
                     "ghost.example.com/widget": 1}).obj())
        fw = sched.framework_for_pod(pre)
        # Pre-fix this raised a shape error out of the kernel call.
        out = sched.device_dry_run_preemption(fw, None, pre, {}, 10, 0)
        assert sched.mirror.r_slots > r_slots_before  # the tier DID grow
        assert out is not None and out == []  # ghost resource: no candidate
        assert sched.preemption_device_evals == 1
        assert sched.device_breaker.state == "closed"
        # The fix handles it exactly — no fallback was needed.
        assert sched.metrics.device_path_fallback.value("RuntimeError") == 0

    def test_preemption_kernel_crash_falls_back_to_host(self):
        """The breaker backstop for the same class of failure: an injected
        kernel fault makes the dry run return None (host Evaluator owns the
        PostFilter), never a crash."""
        from kubernetes_tpu.models import TPUScheduler
        cs = FakeClientset()
        sched = TPUScheduler(clientset=cs, max_batch=16, mesh=None)
        for n in _nodes(4, cpu=4):
            cs.create_node(n)
        for p in _pods(4, cpu="3"):
            cs.create_pod(p)
        sched.run_until_idle()
        faults = DeviceFaults(preempt={1})
        sched._fault_hook = faults
        pre = (make_pod().name("pre").priority(10)
               .req({"cpu": "2", "memory": "64Mi"}).obj())
        fw = sched.framework_for_pod(pre)
        out = sched.device_dry_run_preemption(fw, None, pre, {}, 10, 0)
        assert out is None  # host path owns the dry run
        assert faults.injected["preempt"] == 1
        assert sched.metrics.device_path_fallback.value("RuntimeError") == 1
        assert sched.device_breaker.consecutive_failures == 1
        # Next call (fault cleared) succeeds and closes the count.
        sched._fault_hook = None
        out2 = sched.device_dry_run_preemption(fw, None, pre, {}, 10, 0)
        assert out2 is not None and len(out2) > 0
        assert sched.device_breaker.consecutive_failures == 0

    def test_session_crash_recovers_and_breaker_opens(self):
        """Every dispatch fails → sessions crash → stranded pods rerun on
        the host path, the breaker opens and pins the host path, and after
        the cool-down a clean probe closes it. All pods bind throughout."""
        from kubernetes_tpu.models import TPUScheduler
        cs = FakeClientset()
        sched = TPUScheduler(clientset=cs, max_batch=16, mesh=None)
        t = {"now": 0.0}
        sched.device_breaker = CircuitBreaker(
            failure_threshold=2, cooldown=5.0, clock=lambda: t["now"])
        for n in _nodes(8):
            cs.create_node(n)
        faults = DeviceFaults(dispatch=set(range(1, 100)))
        sched._fault_hook = faults
        for p in _pods(40):
            cs.create_pod(p)
        sched.run_until_idle()
        assert len(cs.bindings) == 40  # zero stranded pods, zero crashes
        assert sched.device_breaker.open_count >= 1
        assert not sched.device_breaker.allows()  # open: host path pinned
        assert sched.metrics.device_breaker_state.value() == 1.0
        fallbacks = sched.metrics.device_path_fallback.value("RuntimeError")
        assert fallbacks >= 2
        calls_while_open = faults.calls["dispatch"]
        for p in _pods(20):
            p.uid += "-b"  # fresh uids for a second wave
            cs.create_pod(p)
        sched.run_until_idle()
        assert len(cs.bindings) == 60
        assert faults.calls["dispatch"] == calls_while_open  # breaker held
        # Cool-down elapses; a clean probe session closes the breaker.
        t["now"] = 6.0
        sched._fault_hook = None
        for p in _pods(20):
            p.uid += "-c"
            cs.create_pod(p)
        sched.run_until_idle()
        assert len(cs.bindings) == 80
        assert sched.device_breaker.state == "closed"
        assert sched.metrics.device_breaker_state.value() == 0.0
        assert sched.device_scheduled > 0  # the device path came back


# ---------------------------------------------------------------------------
# watch re-list / resume over the wire
# ---------------------------------------------------------------------------


def _call_http(base, method, path, body=None):
    import json
    from urllib import request as urlrequest

    def once():
        from urllib.error import HTTPError
        data = json.dumps(body).encode() if body is not None else None
        req = urlrequest.Request(base + path, data=data, method=method,
                                 headers={"Content-Type": "application/json"})
        try:
            with urlrequest.urlopen(req, timeout=30) as resp:
                return json.loads(resp.read())
        except HTTPError as e:
            if e.code == 409:
                # AlreadyExists: an earlier attempt landed but its reply was
                # lost — the write is durable, which is all a retry wants.
                return {"conflict": True}
            raise

    # The test driver is an API client like any other: transient transport
    # failures against the loaded ThreadingHTTPServer (broken pipe under
    # thread churn) retry exactly as production clients do.
    return retry_call(once, RetryConfig(initial_backoff=0.05,
                                        max_backoff=0.5, max_attempts=6,
                                        seed=5))


class _Driver:
    """Run a scheduler loop on a thread, recording any crash."""

    def __init__(self, sched):
        self.sched = sched
        self.errors = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while not self._stop.is_set():
            try:
                if not self.sched.run_until_idle():
                    time.sleep(0.01)
            except Exception as e:  # noqa: BLE001 - the assertion target
                self.errors.append(e)
                return

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=10)


@pytest.mark.chaos
def test_watch_drop_relist_convergence_mid_churn():
    """Kill every scheduler↔apiserver connection mid-MixedChurn: the
    reflector reconnects with its last resourceVersion, replays the missed
    events (RESUME), and assignments still match the in-process oracle."""
    from kubernetes_tpu.core.apiserver import (APIServer, HTTPClientset,
                                               node_to_wire, pod_to_wire)
    api = APIServer()
    port = api.serve(0)
    proxy = ChaosTCPProxy("127.0.0.1", port)
    direct = f"http://127.0.0.1:{port}"
    http_cs = HTTPClientset(proxy.url)
    rcs = RetryingClientset(http_cs, retry=RetryConfig(
        initial_backoff=0.005, max_backoff=0.1, max_attempts=6, seed=11))
    sched = Scheduler(clientset=rcs, deterministic_ties=True)
    driver = _Driver(sched)
    try:
        nodes = _nodes(20)
        for n in nodes:
            _call_http(direct, "POST", "/api/v1/nodes", node_to_wire(n))
        deadline = time.monotonic() + 30
        while len(http_cs.nodes) < 20 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(http_cs.nodes) == 20
        pods = _pods(300)
        for i, p in enumerate(pods):
            _call_http(direct, "POST", "/api/v1/pods", pod_to_wire(p))
            if i % 15 == 5:
                # churn irrelevant to scheduling outcomes (labels no plugin
                # reads) — pure watch traffic for the re-list to replay
                n = nodes[i % len(nodes)]
                w = node_to_wire(n)
                w["labels"]["churn"] = str(i)
                _call_http(direct, "PUT", f"/api/v1/nodes/{n.name}", w)
            if i == 150:
                proxy.drop_connections()  # watch streams die mid-churn
                for j in range(8):  # events the dead streams will miss
                    n = nodes[j]
                    w = node_to_wire(n)
                    w["labels"]["churn"] = f"offline-{j}"
                    _call_http(direct, "PUT", f"/api/v1/nodes/{n.name}", w)
        deadline = time.monotonic() + 120
        while len(api.store.bindings) < 300 and time.monotonic() < deadline:
            time.sleep(0.1)
        assert not driver.errors, f"scheduler crashed: {driver.errors!r}"
        bound = {api.store.pods[u].name: nn
                 for u, nn in api.store.bindings.items()}
        assert len(bound) == 300, f"only {len(bound)}/300 bound"
        oracle = _oracle_assignments(lambda: _nodes(20), lambda: _pods(300))
        diffs = {k: (oracle[k], bound.get(k)) for k in oracle
                 if oracle[k] != bound.get(k)}
        assert not diffs, f"{len(diffs)} divergences: {list(diffs.items())[:5]}"
        assert http_cs.resumes["pods"] + http_cs.resumes["nodes"] >= 1, \
            "reconnect never took the resourceVersion resume path"
    finally:
        driver.stop()
        http_cs.close()
        proxy.close()
        api.shutdown()


@pytest.mark.chaos
def test_chaos_end_to_end_100n_1000p():
    """The acceptance run: 100 nodes / 1000 pods over a real socket with
    (a) transient apiserver write failures, (b) a dropped watch stream
    mid-churn, and (c) injected device-path faults that trip and then
    clear the circuit breaker — assignments identical to a fault-free
    in-process oracle, zero scheduler crashes, breaker fired + recovered."""
    from kubernetes_tpu.core.apiserver import (APIServer, HTTPClientset,
                                               node_to_wire, pod_to_wire)
    from kubernetes_tpu.models import TPUScheduler
    api = APIServer()
    port = api.serve(0)
    proxy = ChaosTCPProxy("127.0.0.1", port)
    direct = f"http://127.0.0.1:{port}"
    http_cs = HTTPClientset(proxy.url)
    flaky = FlakyClientset(http_cs, seed=42, failure_rate=0.03)
    rcs = RetryingClientset(flaky, retry=RetryConfig(
        initial_backoff=0.005, max_backoff=0.05, max_attempts=5, seed=1))
    sched = TPUScheduler(clientset=rcs, max_batch=64, mesh=None)
    sched.device_breaker = CircuitBreaker(failure_threshold=3, cooldown=1.0)
    faults = DeviceFaults(dispatch={3, 4, 5})  # three consecutive crashes
    sched._fault_hook = faults
    driver = _Driver(sched)
    try:
        nodes = _nodes(100)
        for n in nodes:
            _call_http(direct, "POST", "/api/v1/nodes", node_to_wire(n))
        deadline = time.monotonic() + 60
        while len(http_cs.nodes) < 100 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(http_cs.nodes) == 100
        pods = _pods(1000)
        for i, p in enumerate(pods):
            _call_http(direct, "POST", "/api/v1/pods", pod_to_wire(p))
            if i % 25 == 10:  # outcome-irrelevant label churn
                n = nodes[i % len(nodes)]
                w = node_to_wire(n)
                w["labels"]["churn"] = str(i)
                _call_http(direct, "PUT", f"/api/v1/nodes/{n.name}", w)
            if i == 400:
                proxy.drop_connections()  # one dropped watch stream
                for j in range(10):
                    n = nodes[j]
                    w = node_to_wire(n)
                    w["labels"]["churn"] = f"offline-{j}"
                    _call_http(direct, "PUT", f"/api/v1/nodes/{n.name}", w)
        deadline = time.monotonic() + 300
        while len(api.store.bindings) < 1000 and time.monotonic() < deadline:
            time.sleep(0.1)
        # zero scheduler crashes
        assert not driver.errors, f"scheduler crashed: {driver.errors!r}"
        bound = {api.store.pods[u].name: nn
                 for u, nn in api.store.bindings.items()}
        assert len(bound) == 1000, f"only {len(bound)}/1000 bound"
        # assignments identical to the fault-free oracle
        oracle = _oracle_assignments(lambda: _nodes(100), lambda: _pods(1000))
        diffs = {k: (oracle[k], bound.get(k)) for k in oracle
                 if oracle[k] != bound.get(k)}
        assert not diffs, f"{len(diffs)} divergences: {list(diffs.items())[:5]}"
        # the write faults really fired and were retried away
        assert sum(flaky.injected.values()) > 0
        assert rcs.retries_total > 0 and rcs.give_ups == 0
        # the watch drop really resumed
        assert http_cs.resumes["pods"] + http_cs.resumes["nodes"] >= 1
        # the breaker fired and recovered
        assert faults.injected["dispatch"] == 3
        assert sched.metrics.device_path_fallback.value("RuntimeError") >= 3
        assert sched.device_breaker.open_count >= 1
        assert sched.device_breaker.allows()  # recovered (closed/half-open)
        assert sched.device_batches >= 1  # the device path did real work
    finally:
        driver.stop()
        http_cs.close()
        proxy.close()
        api.shutdown()


# ---------------------------------------------------------------------------
# apiserver kill -9 + WAL restart (PR-2 durability acceptance)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
@pytest.mark.parametrize("wire_plane", ["binary", "json"])
def test_apiserver_kill9_restart_mixed_churn(tmp_path, monkeypatch,
                                             wire_plane):
    """The durability acceptance run: ``kill -9`` the apiserver OS process
    mid-MixedChurn, restart it in place from WAL+snapshot (same port, same
    data dir) — the reflector resumes on the PERSISTED epoch (RESUME, never
    a Replace re-list), zero bindings lost, zero duplicated, and terminal
    assignments identical to a no-fault in-process oracle."""
    from kubernetes_tpu.core.apiserver import (HTTPClientset, node_to_wire,
                                               pod_to_wire)
    from kubernetes_tpu.testing.faults import ApiServerProcess

    # Both wire planes (core/wire.py): binary is the negotiated default;
    # the json run pins the whole plane (WAL records, watch streams,
    # bodies) to the compat codec — the exactly-once/RESUME contract is
    # codec-independent. Subprocesses inherit the env.
    monkeypatch.setenv("TPU_SCHED_WIRE", wire_plane)
    N_PODS = 240
    # snapshot_every > total writes: this run recovers through pure WAL
    # replay, which keeps the recovered backlog covering the reflector's rv
    # deterministically (compaction+snapshot recovery is pinned by
    # tests/test_durability.py; a compaction racing the kill could
    # legitimately 410 the resume and flake the no-Replace assertion).
    api = ApiServerProcess(str(tmp_path / "apiserver-state"),
                           snapshot_every=100_000)
    http_cs = None
    driver = None
    try:
        http_cs = HTTPClientset(api.url)
        rcs = RetryingClientset(http_cs, retry=RetryConfig(
            initial_backoff=0.05, max_backoff=0.5, max_attempts=40, seed=13))
        sched = Scheduler(clientset=rcs, deterministic_ties=True)
        driver = _Driver(sched)
        nodes = _nodes(20)
        for n in nodes:
            _call_http(api.url, "POST", "/api/v1/nodes", node_to_wire(n))
        deadline = time.monotonic() + 30
        while len(http_cs.nodes) < 20 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(http_cs.nodes) == 20
        relists_before = dict(http_cs.relists)
        pods = _pods(N_PODS)
        for i, p in enumerate(pods):
            _call_http(api.url, "POST", "/api/v1/pods", pod_to_wire(p))
            if i % 15 == 5:
                # outcome-irrelevant node churn: pure watch traffic the
                # recovered backlog must replay across the restart
                n = nodes[i % len(nodes)]
                w = node_to_wire(n)
                w["labels"]["churn"] = str(i)
                _call_http(api.url, "PUT", f"/api/v1/nodes/{n.name}", w)
            if i == N_PODS // 2:
                api.kill9()    # SIGKILL mid-flight: in-flight binds die raw
                api.restart()  # recover WAL on the same port
        deadline = time.monotonic() + 120
        got = []
        while time.monotonic() < deadline:
            got = _call_http(api.url, "GET", "/api/v1/pods")
            if sum(1 for p in got if p["nodeName"]) >= N_PODS:
                break
            time.sleep(0.1)
        assert not driver.errors, f"scheduler crashed: {driver.errors!r}"
        bound = {p["name"]: p["nodeName"] for p in got if p["nodeName"]}
        # zero lost bindings (pre-crash binds recovered from the WAL,
        # in-flight ones replayed by the retry layer)...
        assert len(bound) == N_PODS, f"only {len(bound)}/{N_PODS} bound"
        # ...and zero duplicates: one store object per pod, one binding
        # each (a conflicting rebind 409s server-side and would have
        # surfaced in driver.errors).
        names = [p["name"] for p in got]
        assert len(names) == len(set(names)) == N_PODS
        oracle = _oracle_assignments(lambda: _nodes(20),
                                     lambda: _pods(N_PODS))
        diffs = {k: (oracle[k], bound.get(k)) for k in oracle
                 if oracle[k] != bound.get(k)}
        assert not diffs, f"{len(diffs)} divergences: {list(diffs.items())[:5]}"
        # the kill really happened, and the reflector rode the persisted
        # epoch straight through: RESUME on reconnect, never a Replace
        assert api.kills == 1 and api.restarts == 1
        assert http_cs.resumes["pods"] + http_cs.resumes["nodes"] >= 1
        assert dict(http_cs.relists) == relists_before
        # Flight recorder (core/spans.py): the chaos kill leaves forensic
        # artifacts in the data dir instead of nothing — the SIGKILLed
        # process's periodic dumps and/or the restarted process's dumps
        # (its graceful stop below guarantees a shutdown dump). Every
        # artifact parses line-by-line and leads with a meta row.
        api.stop()  # graceful: SIGTERM → shutdown dump (idempotent w/ finally)
        art_dir = str(tmp_path / "apiserver-state")
        arts = [f for f in os.listdir(art_dir)
                if f.startswith("flightrec-") and f.endswith(".jsonl")]
        assert arts, "apiserver chaos run left no flight-recorder artifact"
        for name in arts:
            with open(os.path.join(art_dir, name)) as f:
                rows = [json.loads(line) for line in f if line.strip()]
            assert rows and rows[0]["kind"] == "meta"
            assert rows[0]["proc"] == "apiserver"
    finally:
        if driver is not None:
            driver.stop()
        if http_cs is not None:
            http_cs.close()
        api.stop()


# ---------------------------------------------------------------------------
# shard-kill failover (PR-5 shard plane acceptance; docs/SHARDING.md)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
@pytest.mark.parametrize("wire_plane", [
    "binary", pytest.param("json", marks=pytest.mark.slow)])
def test_shard_kill_adoption_mixed_churn(tmp_path, monkeypatch, wire_plane):
    """SIGKILL one of 3 shard scheduler PROCESSES mid-MixedChurn: its lease
    ages past expiry unrenewed, the ring successor adopts the dead range
    (sweeping the informer backlog the dead shard never drained), and the
    run still binds every pod exactly once — zero lost, zero duplicated.
    Failover needs no handoff protocol: adoption is recomputed from the
    server-evaluated lease table, and any transient overlap resolves
    through the binding subresource's 409s."""
    from kubernetes_tpu.shard.harness import _call, run_sharded_cluster

    # Wire-plane parameterization: binary (the negotiated default) in
    # tier-1, the json compat plane in the slow tier — adoption and
    # exactly-once must hold identically on both.
    monkeypatch.setenv("TPU_SCHED_WIRE", wire_plane)
    LEASE = 2.0
    state = {"killed_at": 0.0, "nodes": None, "churn": 0}

    def cb(bound, cluster):
        if state["nodes"] is None:
            state["nodes"] = _call(cluster.base, "GET", "/api/v1/nodes")
        if not cluster.killed:
            # Kill at the FIRST progress poll: bulk binding commits drain a
            # 240-pod backlog within ~2 polls, so any bound-count trigger
            # fires after the victim already finished its range and the
            # failover would have nothing to adopt. At poll one the pods
            # are created but shard 1's range is still (mostly) pending —
            # the range MUST drain through lease expiry + adoption.
            cluster.kill(1)  # SIGKILL: no goodbye, lease left to expire
            state["killed_at"] = time.monotonic()
        # outcome-irrelevant label churn on every poll: live watch traffic
        # the survivors keep classifying while the failover runs
        state["churn"] += 1
        w = dict(state["nodes"][state["churn"] % len(state["nodes"])])
        w["labels"] = dict(w.get("labels") or {}, churn=str(state["churn"]))
        _call(cluster.base, "PUT", f"/api/v1/nodes/{w['name']}", w)

    flightrec_dir = str(tmp_path / "flightrec")
    out = run_sharded_cluster(
        3, 40, 240, lease_duration=LEASE, warm_pods=24,
        progress_cb=cb, timeout=420.0, flightrec_dir=flightrec_dir)
    assert out["killed_shards"] == [1]
    # zero lost bindings: the dead shard's range drained through adoption
    assert out["all_bound"], f"lost bindings: {out}"
    # zero duplicates: one store object per pod, one node each
    assert out["distinct_bound_pods"] == 240 + 24
    # the failover demonstrably ran: a survivor adopted ≥1 expired range
    # and the two survivors ended up owning all 3 slots between them
    survivors = out["shard_metrics"]
    assert sum(m.get("scheduler_shard_adoptions_total", 0)
               for m in survivors) >= 1, survivors
    assert sum(m.get("scheduler_shard_owned_shards", 0)
               for m in survivors) >= 3, survivors
    assert state["killed_at"] > 0  # the kill actually fired mid-run
    # Flight recorder (core/spans.py): the chaos kill leaves forensic
    # artifacts — the SIGKILLed member's periodic dumps survive on disk,
    # the survivors dump at shutdown, and the ADOPTER's artifact carries
    # the 100%-sampled shard.adopt span marking the failover instant.
    arts = [f for f in os.listdir(flightrec_dir)
            if f.startswith("flightrec-") and f.endswith(".jsonl")]
    assert len(arts) >= 3, f"expected artifacts from ≥3 processes: {arts}"
    adopt_spans = []
    for name in arts:
        with open(os.path.join(flightrec_dir, name)) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        assert rows and rows[0]["kind"] == "meta"
        adopt_spans += [r for r in rows
                        if r.get("kind") == "span"
                        and r.get("name") == "shard.adopt"]
    assert adopt_spans, "no shard.adopt span in any flight-recorder artifact"
    assert adopt_spans[0]["attrs"]["shards"]


# ---------------------------------------------------------------------------
# replicated control plane: leader/follower kill -9
# (kubernetes_tpu/replication/; docs/RESILIENCE.md § replication)
# ---------------------------------------------------------------------------


def _wait_true(cond, timeout=30.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


def _flight_spans(flight_dir, name):
    spans = []
    for fname in os.listdir(flight_dir):
        if not (fname.startswith("flightrec-") and fname.endswith(".jsonl")):
            continue
        with open(os.path.join(flight_dir, fname)) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        assert rows and rows[0]["kind"] == "meta"
        spans += [r for r in rows
                  if r.get("kind") == "span" and r.get("name") == name]
    return spans


@pytest.mark.chaos
@pytest.mark.parametrize("wire_plane", [
    "binary", pytest.param("json", marks=pytest.mark.slow)])
def test_leader_kill9_promotion_mixed_churn(tmp_path, monkeypatch,
                                            wire_plane):
    """The replication acceptance run: ``kill -9`` the LEADER apiserver
    mid-MixedChurn with TWO shard schedulers reading from two followers.
    The lowest-ranked live follower promotes within the lease TTL (fenced
    by the epoch bump), the shards' follower-served watch streams never
    re-list (no 410), every pod binds exactly once, and the terminal
    assignments match the oracle — pods are node-selector-pinned, so the
    expected placement is interleaving-independent and any lost/replayed/
    misrouted bind shows up as a divergence."""
    from kubernetes_tpu.core.apiserver import (HTTPClientset, node_from_wire,
                                               node_to_wire)
    from kubernetes_tpu.shard import ShardMember
    from kubernetes_tpu.testing.faults import ReplicaSet

    # Wire-plane parameterization (core/wire.py): the binary run is the
    # negotiated default (tier-1); the json run rides the slow tier and
    # proves promotion/exactly-once are codec-independent.
    monkeypatch.setenv("TPU_SCHED_WIRE", wire_plane)
    N_PODS, N_NODES, LEASE = 240, 20, 2.0
    flight = str(tmp_path / "flightrec")
    rs = ReplicaSet(str(tmp_path / "replicas"), followers=2,
                    repl_lease=LEASE, flightrec_dir=flight)
    members, drivers, clients = [], [], []
    try:
        for i in range(2):
            base = rs.follower_urls[i]
            fb = [u for u in rs.follower_urls if u != base] + [rs.leader_url]
            http_cs = HTTPClientset(base, fallbacks=fb)
            clients.append(http_cs)
            rcs = RetryingClientset(http_cs, retry=RetryConfig(
                initial_backoff=0.05, max_backoff=0.5, max_attempts=40,
                seed=17 + i))
            sched = Scheduler(clientset=rcs, deterministic_ties=True)
            # Generous shard leases: the failover under test is the CONTROL
            # PLANE's; shard ranges must not flap around it.
            member = ShardMember(sched, i, 2, lease_duration=30.0,
                                 identity=f"chaos-shard-{i}")
            member.start_renewer()
            members.append(member)
            drivers.append(_Driver(sched))
        # The create/churn driver is an API client like any other — and it
        # rides the same NotLeader/re-resolve protocol across the kill.
        wcs = HTTPClientset(rs.follower_urls[0],
                            fallbacks=[rs.follower_urls[1]])
        clients.append(wcs)
        writer = RetryingClientset(wcs, retry=RetryConfig(
            initial_backoff=0.05, max_backoff=0.5, max_attempts=40, seed=99))
        nodes = [make_node().name(f"n{i}")
                 .capacity({"cpu": 16, "memory": "64Gi", "pods": 110})
                 .label("slot", str(i)).obj() for i in range(N_NODES)]
        for n in nodes:
            writer.create_node(n)
        for cs in clients[:2]:
            assert _wait_true(lambda cs=cs: len(cs.nodes) == N_NODES)
        relists0 = [dict(cs.relists) for cs in clients[:2]]
        pods = [make_pod().name(f"p{i}")
                .req({"cpu": "100m", "memory": "64Mi"})
                .node_selector({"slot": str(i % N_NODES)}).obj()
                for i in range(N_PODS)]
        t_promoted = None
        for i, p in enumerate(pods):
            writer.create_pod(p)
            if i % 15 == 5:
                # outcome-irrelevant node churn: live watch traffic the
                # follower streams keep fanning out through the failover
                w = node_to_wire(nodes[i % N_NODES])
                w["labels"] = dict(w["labels"], churn=str(i))
                writer.update_node(node_from_wire(w))
            if i == N_PODS // 2:
                rs.kill9_leader()  # SIGKILL: no flush, no goodbye
                t_kill = time.monotonic()
                new_leader = rs.wait_for_leader(timeout=LEASE * 5)
                t_promoted = time.monotonic() - t_kill
                # The lowest-ranked live follower took over...
                assert new_leader == rs.follower_urls[0], new_leader
                # ...inside the failover budget: one lease TTL of silence
                # to detect, then probe + promote.
                assert t_promoted < LEASE * 2.5, t_promoted
        # drain: every measured pod bound, observed via FOLLOWER reads
        assert _wait_true(
            lambda: _call_http(rs.follower_urls[1], "GET",
                               "/api/v1/pods?summary=true")["bound"]
            >= N_PODS, timeout=120)
        for d in drivers:
            assert not d.errors, f"scheduler crashed: {d.errors!r}"
        got = _call_http(rs.follower_urls[0], "GET", "/api/v1/pods")
        bound = {p["name"]: p["nodeName"] for p in got if p["nodeName"]}
        # zero lost bindings, zero duplicates
        assert len(bound) == N_PODS, f"only {len(bound)}/{N_PODS} bound"
        names = [p["name"] for p in got]
        assert len(names) == len(set(names)) == N_PODS
        # oracle-identical assignments (selector-pinned placement)
        oracle = {f"p{i}": f"n{i % N_NODES}" for i in range(N_PODS)}
        diffs = {k: (oracle[k], bound.get(k)) for k in oracle
                 if oracle[k] != bound.get(k)}
        assert not diffs, f"{len(diffs)} divergences: {list(diffs.items())[:5]}"
        # follower-served reads NEVER re-listed across the failover window
        for cs, before in zip(clients[:2], relists0):
            assert dict(cs.relists) == before
            assert cs.failover_count >= 1
        # the promotion is fenced: the new leader runs epoch 2
        st = rs.status(rs.follower_urls[0])
        assert st["role"] == "leader" and st["replEpoch"] >= 2
        # forensics: the promoted follower's flight-recorder artifact
        # carries the 100%-sampled replication.promote span
        promote_spans = _flight_spans(flight, "replication.promote")
        assert promote_spans, "no replication.promote span in any artifact"
        assert promote_spans[0]["attrs"]["epoch"] >= 2
        assert promote_spans[0]["proc"] == "apiserver-r1"
    finally:
        for m in members:
            m.stop()
        for d in drivers:
            d.stop()
        for cs in clients:
            cs.close()
        rs.stop()


@pytest.mark.chaos
def test_follower_kill9_read_plane_failover(tmp_path):
    """``kill -9`` a FOLLOWER mid-MixedChurn: the scheduler reading from it
    rotates its reflector to a sibling replica and RESUMEs from the shared
    rv/epoch space (no re-list, stall bounded by a few connect backoffs),
    the run binds every pod exactly once, and assignments still match the
    no-fault in-process oracle."""
    from kubernetes_tpu.core.apiserver import (HTTPClientset, node_to_wire,
                                               pod_to_wire)
    from kubernetes_tpu.testing.faults import ReplicaSet

    N_PODS, N_NODES = 160, 20
    flight = str(tmp_path / "flightrec")
    rs = ReplicaSet(str(tmp_path / "replicas"), followers=2,
                    repl_lease=2.0, flightrec_dir=flight)
    http_cs = None
    driver = None
    try:
        http_cs = HTTPClientset(
            rs.follower_urls[0],
            fallbacks=[rs.follower_urls[1], rs.leader_url])
        rcs = RetryingClientset(http_cs, retry=RetryConfig(
            initial_backoff=0.05, max_backoff=0.5, max_attempts=40, seed=23))
        sched = Scheduler(clientset=rcs, deterministic_ties=True)
        driver = _Driver(sched)
        nodes = _nodes(N_NODES)
        for n in nodes:
            _call_http(rs.leader_url, "POST", "/api/v1/nodes",
                       node_to_wire(n))
        assert _wait_true(lambda: len(http_cs.nodes) == N_NODES)
        relists0 = dict(http_cs.relists)
        pods = _pods(N_PODS)
        t_kill = None
        for i, p in enumerate(pods):
            _call_http(rs.leader_url, "POST", "/api/v1/pods", pod_to_wire(p))
            if i % 15 == 5:
                n = nodes[i % N_NODES]
                w = node_to_wire(n)
                w["labels"]["churn"] = str(i)
                _call_http(rs.leader_url, "PUT", f"/api/v1/nodes/{n.name}", w)
            if i == N_PODS // 2:
                rs.kill9_follower(0)  # the scheduler's read replica dies
                t_kill = time.monotonic()
        assert _wait_true(
            lambda: _call_http(rs.leader_url, "GET",
                               "/api/v1/pods?summary=true")["bound"]
            >= N_PODS, timeout=120)
        assert not driver.errors, f"scheduler crashed: {driver.errors!r}"
        assert t_kill is not None
        got = _call_http(rs.leader_url, "GET", "/api/v1/pods")
        bound = {p["name"]: p["nodeName"] for p in got if p["nodeName"]}
        assert len(bound) == N_PODS, f"only {len(bound)}/{N_PODS} bound"
        names = [p["name"] for p in got]
        assert len(names) == len(set(names)) == N_PODS
        oracle = _oracle_assignments(lambda: _nodes(N_NODES),
                                     lambda: _pods(N_PODS))
        diffs = {k: (oracle[k], bound.get(k)) for k in oracle
                 if oracle[k] != bound.get(k)}
        assert not diffs, f"{len(diffs)} divergences: {list(diffs.items())[:5]}"
        # the read plane failed over by ROTATION + RESUME, never a re-list
        assert http_cs.read_rotations >= 1
        assert dict(http_cs.relists) == relists0
        assert (http_cs.resumes["pods"] + http_cs.resumes["nodes"]) >= 1
        # forensics: graceful stop (SIGTERM -> shutdown dump; idempotent
        # with the finally) guarantees survivor artifacts, and a run that
        # outlives the periodic timer leaves the SIGKILLed follower's too
        rs.stop()
        arts = [f for f in os.listdir(flight)
                if f.startswith("flightrec-") and f.endswith(".jsonl")]
        assert arts, "follower chaos run left no flight-recorder artifact"
    finally:
        if driver is not None:
            driver.stop()
        if http_cs is not None:
            http_cs.close()
        rs.stop()


# ---------------------------------------------------------------------------
# overload plane: flood shedding, preemption storms, failover under flood
# (core/flowcontrol.py; docs/RESILIENCE.md § overload & fairness)
# ---------------------------------------------------------------------------


def _p99_of_window(hist, before_counts):
    """p99 over the observations a histogram gained SINCE `before_counts`
    (a snapshot of its unlabeled per-bucket counts): bucket-diff fed back
    through the same interpolation — per-phase latency truth without
    per-pod timestamps."""
    from kubernetes_tpu.core.metrics import Histogram

    after = list(hist._counts.get((), [0] * (len(hist.buckets) + 1)))
    diff = [a - b for a, b in zip(after, before_counts)]
    h = Histogram("window", "", buckets=hist.buckets)
    h._counts[()] = diff
    h._totals[()] = sum(diff)
    return h.percentile(0.99)


def _hist_counts(hist):
    return list(hist._counts.get((), [0] * (len(hist.buckets) + 1)))


def _pick_flood_namespace(avoid_flows, queues, hand_size):
    """A flood namespace whose shuffle-shard hand shares no queue with the
    well-behaved flows' hands — the isolation the test then PROVES held."""
    from kubernetes_tpu.core.flowcontrol import WORKLOAD, shuffle_shard_hand

    taken = set()
    for flow in avoid_flows:
        taken |= set(shuffle_shard_hand(WORKLOAD, flow, queues, hand_size))
    for i in range(256):
        ns = f"flood-{i}"
        if not (set(shuffle_shard_hand(WORKLOAD, ns, queues, hand_size))
                & taken):
            return ns
    raise AssertionError("no isolated flood namespace found")


@pytest.mark.chaos
def test_adversarial_tenant_flood_fairness(tmp_path, monkeypatch):
    """Scenario 1 of the overload pack: one adversarial tenant hammers
    creates while two well-behaved namespaces keep scheduling. The flood
    is SHED at 429 (every shed carrying Retry-After), the well-behaved
    tenants' p99 e2e latency stays within 2x their unloaded baseline,
    every well-behaved pod binds exactly once oracle-identically, and the
    scheduler's fair dequeue keeps serving both tenants.

    The plane is a REAL replicated pair (leader + follower OS processes):
    reply gating holds each write's admission seat across the ship-ack
    round trip, so concurrent requests genuinely contend at the gate.
    (In-process, the whole admit->write->release window runs without a
    blocking point and the GIL serializes handlers straight through it —
    shedding then hinges on preemption luck, not on load.)"""
    import http.client as _hc
    from urllib.parse import urlsplit

    from kubernetes_tpu.core.apiserver import (HTTPClientset, node_to_wire,
                                               pod_to_wire)
    from kubernetes_tpu.core.config import SchedulerConfiguration
    from kubernetes_tpu.core import wire as _wire
    from kubernetes_tpu.shard.harness import scrape_labeled
    from kubernetes_tpu.testing.faults import ReplicaSet

    N_NODES, PER_NS = 12, 24
    # A deliberately tight workload lane (env seam — the spawned
    # apiservers take no constructor args) so the 16-thread flood
    # saturates it: 2 seats, 4 queues of 2, 1-wide hands, 0.25s max_wait.
    # Exempt/system stay stock — nothing can make the exempt lane shed.
    monkeypatch.setenv("TPU_SCHED_APF_WORKLOAD", "2,4,2,1,0.25")
    rs = ReplicaSet(str(tmp_path / "replicas"), followers=1, repl_lease=5.0)
    base = rs.leader_url
    host, _, port = urlsplit(base).netloc.partition(":")
    port = int(port)
    flood_ns = _pick_flood_namespace(["web", "batch"], queues=4, hand_size=1)
    http_cs = HTTPClientset(base)
    rcs = RetryingClientset(http_cs, retry=RetryConfig(
        initial_backoff=0.02, max_backoff=0.5, max_attempts=40, seed=5,
        retry_after_cap=1.0))
    sched = Scheduler(clientset=rcs, deterministic_ties=True,
                      config=SchedulerConfiguration(fair_tenant_dequeue=True))
    driver = _Driver(sched)
    flood_stop = threading.Event()
    flood_stats = []  # per-worker dicts (no racy shared increments)

    def flood_worker(widx):
        # BULK creates, deleted right back (the same create/delete churn
        # hammer the sharded flood uses): each accepted bulk holds its
        # admission seat across store+WAL+fanout AND the replication
        # ship-ack gate, so the other workers' requests pile up behind it
        # and shed — while the delete-back keeps the store and the
        # scheduler's unschedulable pool from accumulating the flood.
        stats = {"shed": 0, "posted": 0, "bad_envelope": 0}
        flood_stats.append(stats)
        conn = _hc.HTTPConnection(host, port, timeout=30)
        seq = 0
        proto = (make_pod().name("proto").namespace(flood_ns)
                 .req({"cpu": "4096", "memory": "1Gi"}).obj())

        def rt(method, path, body=None):
            conn.request(method, path, body=body)
            resp = conn.getresponse()
            resp.read()
            if resp.status == 429:
                stats["shed"] += 1
                if resp.getheader("Retry-After") is None:
                    stats["bad_envelope"] += 1  # broken shed contract
                return None
            return resp.status

        while not flood_stop.is_set():
            seq += 1
            pods = [proto.clone_from_template(f"fl-{widx}-{seq}-{i}")
                    for i in range(24)]
            try:
                if rt("POST", "/api/v1/pods", _wire.jdumps(
                        [pod_to_wire(p) for p in pods]).encode()) is None:
                    flood_stop.wait(0.05)  # shed: even adversaries pause
                    continue
                stats["posted"] += 1
                for p in pods:
                    # best-effort delete-back; a shed delete just retries
                    # next round — the residue stays bounded.
                    for _ in range(3):
                        if rt("DELETE", f"/api/v1/pods/{p.uid}") is not None:
                            break
                        flood_stop.wait(0.02)
            except (OSError, _hc.HTTPException):
                conn.close()
                conn = _hc.HTTPConnection(host, port, timeout=30)
        conn.close()

    try:
        for i in range(N_NODES):
            _call_http(base, "POST", "/api/v1/nodes", node_to_wire(
                make_node().name(f"n{i}")
                .capacity({"cpu": 16, "memory": "64Gi", "pods": 110})
                .label("slot", str(i)).obj()))
        assert _wait_true(lambda: len(http_cs.nodes) == N_NODES)

        def make_tenant_pods(phase):
            out = []
            for ns in ("web", "batch"):
                for i in range(PER_NS):
                    out.append(make_pod().name(f"{ns}-{phase}-{i}")
                               .namespace(ns)
                               .req({"cpu": "100m", "memory": "64Mi"})
                               .node_selector({"slot": str(i % N_NODES)})
                               .obj())
            return out

        def bound_count():
            s = _call_http(base, "GET", "/api/v1/pods?summary=true")
            return s["bound"]

        # Phase A — unloaded baseline.
        e2e = sched.metrics.e2e_scheduling_duration
        snap0 = _hist_counts(e2e)
        for p in make_tenant_pods("a"):
            _call_http(base, "POST", "/api/v1/pods", pod_to_wire(p))
        assert _wait_true(lambda: bound_count() >= 2 * PER_NS, timeout=60)
        p99_base = _p99_of_window(e2e, snap0)

        # Phase B — the same well-behaved load, under a 16-thread flood.
        snap1 = _hist_counts(e2e)
        threads = [threading.Thread(target=flood_worker, args=(w,),
                                    daemon=True) for w in range(16)]
        for t in threads:
            t.start()
        time.sleep(0.5)  # flood saturates its lane first
        for p in make_tenant_pods("b"):
            rcs.create_pod(p)  # Retry-After-honoring writer
        assert _wait_true(lambda: bound_count() >= 4 * PER_NS, timeout=120)
        p99_flood = _p99_of_window(e2e, snap1)
        flood_stop.set()
        for t in threads:
            t.join(timeout=30)

        # The flood really was shed, with the full envelope, every time —
        # and the exempt lane (the replication control traffic that kept
        # the follower in quorum throughout) was never queued or shed.
        shed = sum(s["shed"] for s in flood_stats)
        rejected = scrape_labeled(base, "apiserver_flowcontrol_rejected_total",
                                  "priority_level")
        queued = scrape_labeled(base, "apiserver_flowcontrol_queued_total",
                                "priority_level")
        assert shed > 0, (flood_stats, rejected, queued)
        assert sum(s["bad_envelope"] for s in flood_stats) == 0
        assert rejected.get("workload", 0) >= shed
        assert rejected.get("exempt", 0) == 0
        assert queued.get("exempt", 0) == 0
        # Well-behaved tenants: all bound, exactly once, oracle-identical.
        got = _call_http(base, "GET", "/api/v1/pods")
        tenant = [p for p in got if p["namespace"] in ("web", "batch")]
        assert len(tenant) == 4 * PER_NS
        assert all(p["nodeName"] for p in tenant)
        names = [p["name"] for p in tenant]
        assert len(names) == len(set(names))
        for p in tenant:
            slot = p["name"].rsplit("-", 1)[1]
            assert p["nodeName"] == f"n{int(slot) % N_NODES}", p
        # Bounded degradation: within 2x the unloaded p99 (+1 bucket of
        # slack for the 2-core box's scheduling noise).
        assert p99_flood <= 2.0 * p99_base + 1.0, (p99_base, p99_flood)
        # Fair dequeue engaged and served both well-behaved tenants; the
        # flood pods that landed popped too (into the unschedulable pool —
        # cpu 4096 fits nowhere) instead of monopolizing the queue.
        assert sched.queue.fair_tenant_dequeue
        pops = sched.queue.active_q.pops
        assert pops.get("web", 0) >= PER_NS
        assert pops.get("batch", 0) >= PER_NS
        assert not driver.errors, driver.errors
        # Starvation gauge renders per-namespace (flood pods pending).
        assert "scheduler_queue_starvation_seconds" in sched.metrics.expose()
    finally:
        flood_stop.set()
        driver.stop()
        http_cs.close()
        rs.stop()


class _CountingClientset:
    """Clientset decorator counting delete_pod calls per uid — the
    exactly-once-victim probe for preemption storms."""

    def __init__(self, inner):
        self._inner = inner
        self.deletes = {}

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if name == "delete_pod":
            def counted(pod, _attr=attr):
                self.deletes[pod.uid] = self.deletes.get(pod.uid, 0) + 1
                return _attr(pod)
            return counted
        return attr


def _run_gang_storm():
    """One full gangs-preempting-gangs storm on the in-process plane;
    returns (final placements by name, per-uid delete counts, scheduler)."""
    from kubernetes_tpu.api.types import PodGroup
    from kubernetes_tpu.core.registry import gang_placement_profiles

    cs = _CountingClientset(FakeClientset())
    names = {}  # uid -> name (uids are globally sequenced across runs)
    s = Scheduler(clientset=cs, profile_factory=gang_placement_profiles,
                  deterministic_ties=True)
    for i in range(10):
        cs.create_node(make_node().name(f"n{i}")
                       .capacity({"cpu": 8, "memory": "32Gi", "pods": 110})
                       .zone(f"z{i % 2}").obj())
    # Fill tier: 10 low-priority gangs of 2 — the cluster is exactly full.
    for g in range(10):
        cs.create_pod_group(PodGroup(name=f"fill-{g}", min_count=2))
        for i in range(2):
            p = (make_pod().name(f"fill-{g}-{i}").req({"cpu": "4"})
                 .priority(1).obj())
            p.pod_group = f"fill-{g}"
            names[p.uid] = p.name
            cs.create_pod(p)
    s.run_until_idle()
    assert len(cs.bindings) == 20, "fill tier must saturate the cluster"
    # Storm: 5 high-priority gangs and 5 mid-priority singles arrive
    # together over the full cluster — gangs preempt gangs.
    for g in range(5):
        cs.create_pod_group(PodGroup(name=f"storm-{g}", min_count=2))
        for i in range(2):
            p = (make_pod().name(f"storm-{g}-{i}").req({"cpu": "4"})
                 .priority(100).obj())
            p.pod_group = f"storm-{g}"
            cs.create_pod(p)
    for i in range(5):
        cs.create_pod(make_pod().name(f"mid-{i}").req({"cpu": "4"})
                      .priority(50).obj())
    for _ in range(50):
        s.run_until_idle()
        s.process_async_api_errors()
        storm = [p for p in cs.pods.values()
                 if p.name.startswith(("storm-", "mid-"))]
        if len(storm) == 15 and all(p.node_name for p in storm):
            break
        time.sleep(0.01)
    placements = {p.name: p.node_name for p in cs.pods.values()}
    deletes_by_name = {names.get(uid, uid): c
                       for uid, c in cs.deletes.items()}
    return placements, deletes_by_name, s


@pytest.mark.chaos
def test_preemption_storm_gangs_exactly_once_victims():
    """Scenario 2a: priority tiers over a FULL cluster, gangs preempting
    gangs — every storm pod lands, every victim is deleted EXACTLY once
    (never re-deleted by a second cycle racing the first's async victim
    deletion), no node ends overcommitted, and the whole storm is
    deterministic (two identical runs, identical placements)."""
    placements, deletes, s = _run_gang_storm()
    storm = {n: node for n, node in placements.items()
             if n.startswith(("storm-", "mid-"))}
    assert len(storm) == 15 and all(storm.values()), storm
    # Exactly-once victims: every deleted fill pod deleted once, and gone.
    assert deletes, "the storm preempted nobody"
    assert all(c == 1 for c in deletes.values()), deletes
    fills_left = [n for n in placements if n.startswith("fill-")]
    # Storm demand = 15 pods x 4 cpu over 10x8 cpu: exactly 15 victims.
    assert len(deletes) == 15 and len(fills_left) == 5
    # No node overcommitted: cpu 8 holds at most 2 of these 4-cpu pods.
    per_node = {}
    for name, node in placements.items():
        per_node[node] = per_node.get(node, 0) + 1
    assert all(c <= 2 for c in per_node.values()), per_node
    # Gang atomicity: each storm gang's members are both placed.
    for g in range(5):
        assert placements[f"storm-{g}-0"] and placements[f"storm-{g}-1"]
    # The async victim-deletion path really ran, successfully.
    assert s.metrics.preemption_goroutines_execution_total.value(
        "success") >= 1
    # Determinism (the in-process oracle property): identical rerun,
    # identical terminal placements and victim set.
    placements2, deletes2, _s2 = _run_gang_storm()
    assert placements2 == placements
    assert set(deletes2) == set(deletes)


@pytest.mark.chaos
def test_preemption_storm_sharded_exactly_once_victims():
    """Scenario 2b: the storm's shard half — 2 shard schedulers over a
    REAL apiserver, high-priority pinned preemptors arriving over a full
    cluster. Victims are deleted exactly once (asserted from a watcher's
    DELETED event counts — a double delete would fan out twice), the
    preemptors land oracle-identically on their pinned nodes, and the
    optimistic bind plane stays overcommit-free under shard conflicts."""
    from kubernetes_tpu.core.apiserver import (APIServer, HTTPClientset,
                                               node_to_wire, pod_to_wire)
    from kubernetes_tpu.shard.plane import ShardPlane

    N_NODES = 10
    api = APIServer()
    port = api.serve(0)
    base = f"http://127.0.0.1:{port}"

    def factory(cs):
        return Scheduler(clientset=cs, deterministic_ties=True)

    plane = ShardPlane(base, 2, lease_duration=30.0,
                       scheduler_factory=factory)
    observer = None
    deleted_counts = {}
    try:
        for i in range(N_NODES):
            _call_http(base, "POST", "/api/v1/nodes", node_to_wire(
                make_node().name(f"n{i}")
                .capacity({"cpu": 8, "memory": "32Gi", "pods": 110})
                .label("slot", str(i)).obj()))
        plane.start()
        # Fill tier: 2 low-priority 4-cpu pods per node, pre-pinned so the
        # fill is deterministic and the cluster ends exactly full.
        fill_uids = set()
        for i in range(2 * N_NODES):
            p = (make_pod().name(f"fill-{i}").req({"cpu": "4"})
                 .priority(1).node_selector({"slot": str(i % N_NODES)})
                 .obj())
            fill_uids.add(p.uid)
            _call_http(base, "POST", "/api/v1/pods", pod_to_wire(p))
        assert _wait_true(
            lambda: _call_http(base, "GET",
                               "/api/v1/pods?summary=true")["bound"]
            >= 2 * N_NODES, timeout=90)
        # Observer counts DELETED fanouts per uid: exactly-once probe.
        observer = HTTPClientset(base)

        def on_delete(kind, old, new):
            if kind == "delete":
                deleted_counts[new.uid] = deleted_counts.get(new.uid, 0) + 1
        observer.on_pod_event(on_delete)
        # Storm: one pinned high-priority preemptor per node — each must
        # evict exactly one fill victim from ITS node, under whatever
        # bind conflicts the two shards produce against shared state.
        storm = [make_pod().name(f"hi-{i}").req({"cpu": "4"}).priority(100)
                 .node_selector({"slot": str(i)}).obj()
                 for i in range(N_NODES)]
        for p in storm:
            _call_http(base, "POST", "/api/v1/pods", pod_to_wire(p))
        assert _wait_true(
            lambda: all(api.store.pods[p.uid].node_name for p in storm
                        if p.uid in api.store.pods), timeout=120)
        assert not plane.errors(), plane.errors()
        # Oracle-identical: every preemptor on its pinned node.
        for i, p in enumerate(storm):
            assert api.store.pods[p.uid].node_name == f"n{i}"
        # Exactly-once victims: one victim per node, each DELETED fanout
        # observed exactly once, victims gone from the store.
        time.sleep(1.0)  # let the observer's stream drain
        victims = fill_uids - set(api.store.pods)
        assert len(victims) == N_NODES, len(victims)
        for uid in victims:
            assert deleted_counts.get(uid, 0) == 1, (uid, deleted_counts)
        # No overcommit anywhere (Omega validation held under conflicts).
        for name, u in api._usage.items():
            assert u["cpu"] <= 8000, (name, u)
    finally:
        if observer is not None:
            observer.close()
        plane.close()
        api.shutdown()


@pytest.mark.chaos
def test_leader_kill9_mid_flood_promotes_inside_ttl(tmp_path, monkeypatch):
    """Scenario 3: ``kill -9`` the LEADER while an adversarial flood is
    being shed. The exempt lane (lease CAS, replication control) is never
    queued behind tenant traffic, so promotion still completes within
    2.5x the lease TTL; the well-behaved tenant's pods bind exactly once
    oracle-identically; the flood keeps getting shed on the NEW leader."""
    from kubernetes_tpu.core.apiserver import (HTTPClientset, node_to_wire,
                                               pod_to_wire)
    from kubernetes_tpu.shard import ShardMember
    from kubernetes_tpu.shard.harness import scrape_labeled
    from kubernetes_tpu.testing.faults import ReplicaSet

    # Tight workload lane in every spawned apiserver (env seam) so a
    # 16-thread flood sheds deterministically; exempt has no override.
    monkeypatch.setenv("TPU_SCHED_APF_WORKLOAD", "2,4,2,1,0.25")
    N_PODS, N_NODES, LEASE = 160, 20, 2.0
    flood_ns = _pick_flood_namespace(["default"], queues=4, hand_size=1)
    rs = ReplicaSet(str(tmp_path / "replicas"), followers=2,
                    repl_lease=LEASE)
    members, drivers, clients = [], [], []
    flood_stop = threading.Event()
    flood_stats = []
    try:
        for i in range(2):
            fb = [u for u in rs.follower_urls if u != rs.follower_urls[i]] \
                + [rs.leader_url]
            http_cs = HTTPClientset(rs.follower_urls[i], fallbacks=fb)
            clients.append(http_cs)
            rcs = RetryingClientset(http_cs, retry=RetryConfig(
                initial_backoff=0.05, max_backoff=0.5, max_attempts=60,
                seed=17 + i, retry_after_cap=1.0))
            sched = Scheduler(clientset=rcs, deterministic_ties=True)
            member = ShardMember(sched, i, 2, lease_duration=30.0,
                                 identity=f"flood-shard-{i}")
            member.start_renewer()
            members.append(member)
            drivers.append(_Driver(sched))
        wcs = HTTPClientset(rs.follower_urls[0],
                            fallbacks=[rs.follower_urls[1], rs.leader_url])
        clients.append(wcs)
        writer = RetryingClientset(wcs, retry=RetryConfig(
            initial_backoff=0.05, max_backoff=0.5, max_attempts=60,
            seed=99, retry_after_cap=1.0))
        fcs = HTTPClientset(rs.follower_urls[1],
                            fallbacks=[rs.follower_urls[0], rs.leader_url])
        clients.append(fcs)

        def flood_worker(widx):
            from urllib.error import HTTPError
            stats = {"shed": 0, "posted": 0}
            flood_stats.append(stats)
            proto = (make_pod().name("proto").namespace(flood_ns)
                     .req({"cpu": "4096", "memory": "1Gi"}).obj())
            seq = 0
            while not flood_stop.is_set():
                seq += 1
                w = pod_to_wire(proto.clone_from_template(
                    f"fl-{widx}-{seq}"))
                try:
                    fcs._write_call("POST", "/api/v1/pods", w)
                    stats["posted"] += 1
                except HTTPError as e:
                    if e.code == 429:
                        stats["shed"] += 1
                except Exception:  # noqa: BLE001 - promotion in flight
                    time.sleep(0.05)

        nodes = [make_node().name(f"n{i}")
                 .capacity({"cpu": 16, "memory": "64Gi", "pods": 110})
                 .label("slot", str(i)).obj() for i in range(N_NODES)]
        for n in nodes:
            writer.create_node(n)
        for cs in clients[:2]:
            assert _wait_true(lambda cs=cs: len(cs.nodes) == N_NODES)
        threads = [threading.Thread(target=flood_worker, args=(w,),
                                    daemon=True) for w in range(16)]
        for t in threads:
            t.start()
        pods = [make_pod().name(f"p{i}")
                .req({"cpu": "100m", "memory": "64Mi"})
                .node_selector({"slot": str(i % N_NODES)}).obj()
                for i in range(N_PODS)]
        t_promoted = None
        for i, p in enumerate(pods):
            writer.create_pod(p)
            if i == N_PODS // 2:
                rs.kill9_leader()  # SIGKILL mid-flood
                t_kill = time.monotonic()
                new_leader = rs.wait_for_leader(timeout=LEASE * 5)
                t_promoted = time.monotonic() - t_kill
                assert new_leader == rs.follower_urls[0], new_leader
                # The failover budget holds DESPITE the flood: the exempt
                # lane never queues behind tenant traffic.
                assert t_promoted < LEASE * 2.5, t_promoted
        assert _wait_true(
            lambda: _call_http(rs.follower_urls[1], "GET",
                               "/api/v1/pods?summary=true")["bound"]
            >= N_PODS, timeout=180)
        flood_stop.set()
        for t in threads:
            t.join(timeout=30)
        for d in drivers:
            assert not d.errors, f"scheduler crashed: {d.errors!r}"
        # Exactly-once, oracle-identical well-behaved binds.
        got = _call_http(rs.follower_urls[0], "GET", "/api/v1/pods")
        tenant = [p for p in got if p["namespace"] == "default"]
        bound = {p["name"]: p["nodeName"] for p in tenant if p["nodeName"]}
        assert len(bound) == N_PODS, f"only {len(bound)}/{N_PODS} bound"
        oracle = {f"p{i}": f"n{i % N_NODES}" for i in range(N_PODS)}
        diffs = {k: (oracle[k], bound.get(k)) for k in oracle
                 if oracle[k] != bound.get(k)}
        assert not diffs, f"{len(diffs)} divergences"
        # The flood really was shed — including on the NEW leader — and
        # the exempt lane was never queued or shed anywhere.
        assert sum(s["shed"] for s in flood_stats) > 0, flood_stats
        new_leader_url = rs.follower_urls[0]
        rejected = scrape_labeled(new_leader_url,
                                  "apiserver_flowcontrol_rejected_total",
                                  "priority_level")
        dispatched = scrape_labeled(new_leader_url,
                                    "apiserver_flowcontrol_dispatched_total",
                                    "priority_level")
        queued = scrape_labeled(new_leader_url,
                                "apiserver_flowcontrol_queued_total",
                                "priority_level")
        assert rejected.get("workload", 0) > 0
        assert rejected.get("exempt", 0) == 0
        assert queued.get("exempt", 0) == 0  # never queued, by construction
        assert dispatched.get("exempt", 0) > 0  # lease CAS kept landing
        # Promotion is fenced on the winner's epoch, as ever.
        st = rs.status(new_leader_url)
        assert st["role"] == "leader" and st["replEpoch"] >= 2
    finally:
        flood_stop.set()
        for m in members:
            m.stop()
        for d in drivers:
            d.stop()
        for cs in clients:
            cs.close()
        rs.stop()


# ---------------------------------------------------------------------------
# lock-order watchdog (testing/lockwatch.py; docs/ANALYSIS.md runtime half)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_lockwatch_reports_synthetic_abba_cycle():
    """Two threads taking the same pair of locks in opposite orders is a
    deadlock waiting for the right interleaving. The watch must report the
    cycle — WITH both acquisition sites — even though this run, executed
    serially, never deadlocks."""
    from kubernetes_tpu.testing.lockwatch import LockWatch

    watch = LockWatch()
    a = watch.wrap(threading.Lock(), "A")
    b = watch.wrap(threading.Lock(), "B")

    def ab():
        with a:
            with b:  # A -> B
                pass

    def ba():
        with b:
            with a:  # B -> A: closes the cycle
                pass

    for fn in (ab, ba):  # run serially: the ORDER GRAPH closes, not a deadlock
        t = threading.Thread(target=fn)
        t.start()
        t.join(timeout=5)
    cycles = watch.cycles()
    assert len(cycles) == 1
    cyc = cycles[0]
    assert set(cyc.locks) == {"A", "B"}
    # both witness edges name this file's acquisition sites
    assert len(cyc.sites) == 2
    for _a, _b, held_site, acq_site in cyc.sites:
        assert "test_faults.py" in held_site
        assert "test_faults.py" in acq_site
    with pytest.raises(AssertionError, match="lock-order cycle"):
        watch.assert_no_cycles()


@pytest.mark.chaos
def test_lockwatch_long_hold_and_rlock_reentry():
    """A hold across a blocking call is reported with its acquire site;
    RLock re-entry must NOT count as a second hold (no self-edges)."""
    from kubernetes_tpu.testing.lockwatch import LockWatch

    watch = LockWatch(hold_threshold=0.03)
    slow = watch.wrap(threading.Lock(), "slow")
    with slow:
        time.sleep(0.06)  # a blocking call under the lock
    assert [h.lock for h in watch.long_holds] == ["slow"]
    assert watch.long_holds[0].seconds >= 0.03
    assert "test_faults.py" in watch.long_holds[0].acquire_site

    r = watch.wrap(threading.RLock(), "re")
    with r:
        with r:  # re-entry: not a new hold, no "re"->"re" edge
            pass
    assert not watch.cycles()
    assert ("re", "re") not in watch.edges


@pytest.mark.chaos
def test_apiserver_chaos_run_under_lockwatch_is_cycle_free():
    """Instrument the REAL apiserver's write/broadcast locks and drive the
    full verb surface (creates, binds incl. a 409 conflict, status patch,
    lease CAS, watch attach) — the recorded acquisition-order graph must
    show the expected write-lock→broadcast-lock nesting and no cycles."""
    from kubernetes_tpu.core.apiserver import APIServer, HTTPClientset
    from kubernetes_tpu.testing.lockwatch import LockWatch

    watch = LockWatch(hold_threshold=5.0)  # cycles only; holds not at issue
    api = APIServer()
    watch.instrument(api, "_lock", "_write_lock", prefix="apiserver")
    port = api.serve(0)
    client = None
    try:
        client = HTTPClientset(f"http://127.0.0.1:{port}")
        for n in _nodes(4, cpu=2):
            client.create_node(n)
        pods = _pods(8)
        for p in pods:
            client.create_pod(p)
        client.bind(pods[0], "n0")
        client.bind(pods[1], "n1")
        from urllib.error import HTTPError
        with pytest.raises(HTTPError):  # AlreadyBound 409: conflict branch
            client.bind(pods[0], "n3")
        client.patch_pod_status(pods[2], nominated_node_name="n2")
        assert client.upsert_lease("shard-0", "holder-a", 1.0) is not None
        assert client.upsert_lease("shard-0", "holder-b", 1.0) is None
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and len(client.pods) < 8:
            time.sleep(0.05)
    finally:
        if client is not None:
            client.close()
        api.shutdown()
    assert watch.acquisitions > 10
    # the designed nesting was actually observed...
    assert ("apiserver._write_lock", "apiserver._lock") in watch.edges
    # ...and only that order, ever: no cycle anywhere in the graph
    watch.assert_no_cycles()


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------


def test_collective_report_nested_replica_groups():
    """Non-greedy regex regression: only the FIRST of nested replica groups
    used to be classified — a later host-spanning group was misreported as
    ICI."""
    from kubernetes_tpu.parallel.mesh import collective_report
    hlo = ("%ar = f32[8]{0} all-reduce(%x), replica_groups={{0,1},{3,4}}, "
           "to_apply=%add\n"
           "%ag = f32[8]{0} all-gather(%y), replica_groups={0,1,2,3}, "
           "dimensions={0}\n")
    rep = collective_report(hlo, n_hosts=2, per_host=4)
    # {0,1} is host-local but {3,4} spans hosts 0 and 1 → DCN.
    assert rep["dcn"].get("all-reduce", 0) == 1
    # flat {0,1,2,3} stays within host 0 → ICI.
    assert rep["ici"].get("all-gather", 0) == 1


def test_resource_metrics_pending_pod_empty_node_label():
    """`/metrics/resources` renders pending pods with node="" (reference
    convention), never the literal string "None"."""
    from kubernetes_tpu.core.server import SchedulerServer
    cs = FakeClientset()
    sched = Scheduler(clientset=cs, deterministic_ties=True)
    pod = _pods(1)[0]
    pod.node_name = None  # the shape that used to render node="None"
    cs.create_pod(pod)
    server = SchedulerServer(sched)
    out = server.expose_resource_metrics()
    assert 'node=""' in out
    assert 'node="None"' not in out
    assert 'phase="Pending"' in out
