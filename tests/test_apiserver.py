"""REST + watch apiserver (core/apiserver.py): the scheduler runs against a
REAL process boundary — JSON on the wire, a reflector thread feeding the
informer cache — and produces the SAME assignments as the in-process run
(client-go reflector.go:470 / shared_informer.go:841 seam; apiserver REST
surface reduced to the scheduler's verbs)."""

import time

from kubernetes_tpu.core import FakeClientset, Scheduler
from kubernetes_tpu.core.apiserver import APIServer, HTTPClientset
from kubernetes_tpu.models import TPUScheduler
from kubernetes_tpu.testing.wrappers import make_node, make_pod


def _nodes():
    out = []
    for i in range(12):
        b = (make_node().name(f"n{i}")
             .capacity({"cpu": "8", "memory": "16Gi", "pods": 110})
             .zone(f"z{i % 3}"))
        if i % 5 == 0:
            b = b.taint("dedicated", "infra", "NoSchedule")
        out.append(b.obj())
    return out


def _pods(n):
    proto = (make_pod().name("proto").req({"cpu": "500m", "memory": "256Mi"})
             .labels({"app": "wire"}).obj())
    return [proto.clone_from_template(f"p{i}") for i in range(n)]


def test_scheduler_over_the_wire_matches_in_process():
    # in-process oracle
    cs_h = FakeClientset()
    host = Scheduler(clientset=cs_h, deterministic_ties=True)
    for node in _nodes():
        cs_h.create_node(node)
    ph = _pods(40)
    for p in ph:
        cs_h.create_pod(p)
    host.run_until_idle()

    # over the wire: apiserver process boundary + reflector-fed scheduler
    api = APIServer()
    port = api.serve(0)
    client = HTTPClientset(f"http://127.0.0.1:{port}")
    sched = TPUScheduler(clientset=client)
    for node in _nodes():
        client.create_node(node)
    pw = _pods(40)
    for p in pw:
        client.create_pod(p)

    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and sched.scheduled < 40:
        sched.run_until_idle()
        time.sleep(0.005)

    # bindings land in the SERVER's store via the binding subresource
    hb = sorted(cs_h.bindings.values())
    wb = sorted(api.store.bindings.values())
    assert sched.scheduled == 40
    assert wb == hb
    # per-pod equality by name (uids differ across the two runs)
    h_by_name = {cs_h.pods[u].name: n for u, n in cs_h.bindings.items()}
    w_by_name = {api.store.pods[u].name: n for u, n in api.store.bindings.items()}
    assert h_by_name == w_by_name
    client.close()
    api.shutdown()


def test_watch_stream_delivers_deletes():
    api = APIServer()
    port = api.serve(0)
    client = HTTPClientset(f"http://127.0.0.1:{port}")
    sched = TPUScheduler(clientset=client)
    client.create_node(make_node().name("n0")
                       .capacity({"cpu": "4", "pods": 10}).obj())
    p = make_pod().name("doomed").req({"cpu": "1"}).obj()
    client.create_pod(p)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and sched.scheduled < 1:
        sched.run_until_idle()
        time.sleep(0.005)
    assert sched.scheduled == 1
    bound = api.store.pods[list(api.store.bindings)[0]]
    client.delete_pod(bound)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and bound.uid in client.pods:
        sched.run_until_idle()
        time.sleep(0.005)
    assert bound.uid not in client.pods  # reflector saw the DELETED event
    sched.run_until_idle()
    assert sched.cache.nodes["n0"].pods == [] or all(
        pi.pod.uid != bound.uid for pi in sched.cache.nodes["n0"].pods)
    client.close()
    api.shutdown()


def test_wire_codec_preserves_scheduling_spec():
    """Round-trip of affinity / spread / gates / host ports / claims — the
    codec must not silently drop scheduling-relevant spec (a gated pod must
    stay gated over the wire, host ports must conflict, anti-affinity must
    spread)."""
    api = APIServer()
    port = api.serve(0)
    client = HTTPClientset(f"http://127.0.0.1:{port}")
    sched = TPUScheduler(clientset=client)
    for i in range(4):
        client.create_node(make_node().name(f"n{i}")
                           .capacity({"cpu": "8", "pods": 20})
                           .zone(f"z{i % 2}").obj())

    gated = (make_pod().name("gated").req({"cpu": "1"})
             .scheduling_gate("wait-for-it").obj())
    client.create_pod(gated)
    anti = []
    for i in range(3):
        p = (make_pod().name(f"anti-{i}").labels({"app": "a"})
             .pod_affinity("kubernetes.io/hostname", {"app": "a"}, anti=True)
             .req({"cpu": "500m"}).obj())
        client.create_pod(p)
        anti.append(p)
    ports = []
    for i in range(2):
        p = make_pod().name(f"hp-{i}").req({"cpu": "100m"}).host_port(8080).obj()
        client.create_pod(p)
        ports.append(p)

    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and sched.scheduled < 5:
        sched.run_until_idle()
        time.sleep(0.005)

    by_name = {api.store.pods[u].name: n
               for u, n in api.store.bindings.items()}
    assert "gated" not in by_name                       # gate survived the wire
    anti_nodes = [by_name[f"anti-{i}"] for i in range(3)]
    assert len(set(anti_nodes)) == 3                    # anti-affinity spread
    hp_nodes = [by_name[f"hp-{i}"] for i in range(2)]
    assert len(set(hp_nodes)) == 2                      # host-port conflict
    client.close()
    api.shutdown()


def test_reflector_relists_after_server_restart():
    """client-go reflector semantics (reflector.go:470): when the watch
    stream dies, the client re-connects and re-lists; objects that vanished
    during the outage are dispatched DELETED at the SYNC barrier, new
    objects ADDED — the informer cache converges on the restarted server's
    truth instead of freezing forever (round-4 advisor finding)."""
    api = APIServer()
    port = api.serve(0)
    api.store.create_node(make_node().name("n0")
                          .capacity({"cpu": "4", "pods": 10}).obj())
    ghost = make_pod().name("ghost").req({"cpu": "1"}).obj()
    api.store.create_pod(ghost)
    client = HTTPClientset(f"http://127.0.0.1:{port}")
    assert ghost.uid in client.pods and "n0" in client.nodes

    # Server restarts: the ghost pod is gone, a new node exists.
    api.shutdown()
    api2 = APIServer()
    api2.store.create_node(make_node().name("n0")
                           .capacity({"cpu": "4", "pods": 10}).obj())
    api2.store.create_node(make_node().name("n1")
                           .capacity({"cpu": "4", "pods": 10}).obj())
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            api2.serve(port)
            break
        except OSError:
            time.sleep(0.1)  # TIME_WAIT on the old socket

    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and (
            ghost.uid in client.pods or "n1" not in client.nodes):
        time.sleep(0.02)
    assert ghost.uid not in client.pods   # Replace barrier delivered delete
    assert "n1" in client.nodes           # re-list delivered the new node
    assert "n0" in client.nodes
    client.close()
    api2.shutdown()


def test_dead_initial_connection_raises():
    """A clientset whose FIRST connection fails must raise, not return a
    silently empty informer cache (round-4 advisor finding)."""
    import pytest
    with pytest.raises((ConnectionError, TimeoutError)):
        HTTPClientset("http://127.0.0.1:1", sync_timeout=5.0)


def _json_call(base, method, path, body=None):
    import json as _json
    from urllib import request as _rq
    data = _json.dumps(body).encode() if body is not None else None
    req = _rq.Request(base + path, data=data, method=method,
                      headers={"Content-Type": "application/json"})
    with _rq.urlopen(req, timeout=30) as resp:
        raw = resp.read()
    return _json.loads(raw) if raw else None


def test_pod_groups_over_the_wire_gate_gangs_and_replay():
    """Gang state over the real HTTP LIST/watch (PR-16 satellite): a
    PodGroup created through one clientset gates the gang on a scheduler
    reading through ANOTHER clientset — the all-or-nothing cycle holds
    across the process boundary, a late subscriber gets the group from
    LIST replay, and the arrival of the final member (over the wire)
    releases the whole gang."""
    from kubernetes_tpu.api.types import PodGroup

    api = APIServer()
    port = api.serve(0)
    base = f"http://127.0.0.1:{port}"
    writer = HTTPClientset(base)
    reader = HTTPClientset(base)
    sched = Scheduler(clientset=reader, deterministic_ties=True)
    try:
        for i in range(3):
            writer.create_node(make_node().name(f"n{i}")
                               .capacity({"cpu": 4, "memory": "8Gi",
                                          "pods": 10}).obj())
        writer.create_pod_group(PodGroup(name="gang", min_count=3))
        pods = []
        for i in range(2):
            p = make_pod().name(f"gang-{i}").req({"cpu": "1"}).obj()
            p.pod_group = "gang"
            pods.append(p)
            writer.create_pod(p)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and (
                len(reader.pods) < 2 or len(reader.nodes) < 3
                or "default/gang" not in reader.pod_groups):
            time.sleep(0.02)
        # the group crossed the wire: the reading scheduler must hold the
        # gang (2 of 3 members present -> nothing schedules)
        assert reader.pod_groups["default/gang"].min_count == 3
        sched.run_until_idle()
        assert not api.store.bindings
        # a LATE subscriber sees the group via LIST replay, no watch race
        late = HTTPClientset(base)
        try:
            assert "default/gang" in late.pod_groups
            assert late.pod_groups["default/gang"].min_count == 3
        finally:
            late.close()
        # the final member arrives over the wire: whole gang releases
        p3 = make_pod().name("gang-2").req({"cpu": "1"}).obj()
        p3.pod_group = "gang"
        writer.create_pod(p3)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and len(api.store.bindings) < 3:
            sched.run_until_idle()
            time.sleep(0.02)
        assert len(api.store.bindings) == 3
        assert set(api.store.bindings) == {p.uid for p in pods} | {p3.uid}
    finally:
        writer.close()
        reader.close()
        api.shutdown()


def test_flow_admin_endpoint_reweights_live():
    """/flow (PR-16 satellite): GET exposes per-level weights + admission
    counters; POST re-weights one level's flows live (applied under the
    flow controller's own lock). Unknown level -> 404; the exempt lane and
    non-positive weights -> 400."""
    api = APIServer()
    port = api.serve(0)
    base = f"http://127.0.0.1:{port}"
    try:
        got = _json_call(base, "GET", "/flow")
        assert "workload" in got["weights"] and "workload" in got["levels"]
        # live re-weight: starve down a flood tenant mid-storm
        got = _json_call(base, "POST", "/flow",
                         {"level": "workload",
                          "weights": {"tenant-flood": 0.25,
                                      "tenant-gold": 4.0}})
        assert got["weights"]["tenant-flood"] == 0.25
        again = _json_call(base, "GET", "/flow")
        assert again["weights"]["workload"]["tenant-flood"] == 0.25
        assert again["weights"]["workload"]["tenant-gold"] == 4.0
        # the write plane still admits (the re-weight never touched the
        # write lock, but prove the server is alive and serving writes)
        cs = HTTPClientset(base)
        try:
            cs.create_node(make_node().name("n0")
                           .capacity({"cpu": 4, "pods": 10}).obj())
            assert "n0" in api.store.nodes
        finally:
            cs.close()
        import pytest
        from urllib.error import HTTPError
        with pytest.raises(HTTPError) as e:
            _json_call(base, "POST", "/flow",
                       {"level": "nope", "weights": {"t": 1.0}})
        assert e.value.code == 404
        with pytest.raises(HTTPError) as e:
            _json_call(base, "POST", "/flow",
                       {"level": "exempt", "weights": {"t": 1.0}})
        assert e.value.code == 400
        with pytest.raises(HTTPError) as e:
            _json_call(base, "POST", "/flow",
                       {"level": "workload", "weights": {"t": 0.0}})
        assert e.value.code == 400
    finally:
        api.shutdown()


def test_a_burst_reaches_a_late_reader_whole_in_order_and_in_few_writes():
    """A watch stream writes everything queued in one send (one chunk an
    event as ever): a burst that queues up behind one slow send goes out
    whole and in order, in far fewer socket writes than events. (On the
    chip machine a stream that sent one small segment an event was seen
    crawling at some ten events a second behind a burst.)"""
    import http.client as hc
    import json

    api = APIServer()
    port = api.serve(0)
    n = 600
    conn = hc.HTTPConnection("127.0.0.1", port, timeout=30)
    writes = []
    try:
        conn.request("GET", "/api/v1/pods?watch=true",
                     headers={"Accept": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        deadline = time.monotonic() + 30
        while json.loads(resp.readline()).get("type") != "SYNC":
            assert time.monotonic() < deadline
        # count the stream thread's writes from here on
        import socketserver
        real = socketserver._SocketWriter.write

        def counting(self, b):
            writes.append(len(b))
            if len(writes) == 1:
                time.sleep(0.5)      # the burst queues up behind this send
            return real(self, b)
        socketserver._SocketWriter.write = counting
        try:
            for p in _pods(n):
                api.store.create_pod(p)
            names = []
            while len(names) < n:
                assert time.monotonic() < deadline
                d = json.loads(resp.readline())
                if d.get("type") == "ADDED":
                    names.append(d["object"]["name"])
        finally:
            socketserver._SocketWriter.write = real
        assert names == [f"p{i}" for i in range(n)]
        assert len(writes) < n / 20, len(writes)
    finally:
        conn.close()
        api.shutdown()
