"""TPU sidecar: the device scheduling backend behind a Unix-domain-socket
RPC boundary.

The reference's natural out-of-process integration shape is the HTTP
scheduler extender (pkg/scheduler/extender.go:44, verbs filter/prioritize/
bind/preempt :46-49); SURVEY §2.4 rows 9-10 call for the TPU build's
equivalent: a colocated sidecar process that OWNS the accelerator and is fed
cluster state + pod batches over gRPC/UDS, so the control-plane scheduler
process never links JAX/XLA. This module is the working UDS prototype of
that contract (docs/SIDECAR.md is the contract document):

- framing: 4-byte big-endian length prefix + JSON body, both directions;
- objects ride the SAME wire codec as the REST apiserver
  (core/apiserver.py pod_to_wire/node_to_wire — one serialization story
  for both process boundaries);
- verbs (mirroring the extender verb set, batched):
    {"verb": "sync",     "nodes": [...]}                  → {"ok": true}
    {"verb": "schedule", "pods": [...]}                   → {"assignments":
        [nodeName | null, ...], "deviceScheduled": n}
    {"verb": "ping"}                                      → {"ok": true}
    {"verb": "shutdown"}                                  → {"ok": true}
  errors: {"error": "..."} with the connection kept open.

The sidecar applies `sync` node diffs to its owned cluster mirror and runs
`schedule` batches through the full TPUScheduler device path; the caller
binds the returned assignments itself (the bind cycle — like the
reference's bind verb — stays host-side unless delegated).
"""

from __future__ import annotations

import json
import os
import socket
import struct
import threading
import time
from typing import List, Optional

_LEN = struct.Struct(">I")


def _send(sock: socket.socket, obj: dict) -> None:
    data = json.dumps(obj).encode()
    sock.sendall(_LEN.pack(len(data)) + data)


def _recv(sock: socket.socket) -> Optional[dict]:
    hdr = b""
    while len(hdr) < _LEN.size:
        chunk = sock.recv(_LEN.size - len(hdr))
        if not chunk:
            return None
        hdr += chunk
    (n,) = _LEN.unpack(hdr)
    body = b""
    while len(body) < n:
        chunk = sock.recv(min(1 << 20, n - len(body)))
        if not chunk:
            return None
        body += chunk
    return json.loads(body)


class SidecarServer:
    """Owns a TPUScheduler; serves the UDS contract. One request at a time
    per connection; multiple sequential connections supported (the host
    scheduler reconnects after a sidecar restart, like any RPC client)."""

    def __init__(self, socket_path: str, max_batch: Optional[int] = None,
                 mesh="auto"):
        self.socket_path = socket_path
        from ..core import FakeClientset
        from ..models import TPUScheduler
        self._cs = FakeClientset()
        self._sched = TPUScheduler(clientset=self._cs, max_batch=max_batch,
                                   mesh=mesh)
        self._listener: Optional[socket.socket] = None
        self._stop = threading.Event()
        self._conns: set = set()  # live client connections (kill())
        self.served_connections = 0  # accepted connections (tests)

    # -- verbs -------------------------------------------------------------

    def _sync(self, req: dict) -> dict:
        """Full node-set replacement (the prototype's re-list; a production
        sidecar would take generation-keyed diffs exactly like the mirror's
        dirty rows). An optional "pods" list carries BOUND pods: after a
        sidecar restart the fresh mirror has no memory of earlier
        placements, so the client's reconnect resync replays them as load
        (the reconstructible-from-host-snapshot contract, docs/SIDECAR.md
        + docs/RESILIENCE.md)."""
        from ..core.apiserver import node_from_wire, pod_from_wire
        wanted = {}
        for w in req.get("nodes", ()):
            node = node_from_wire(w)
            wanted[node.name] = node
        for name in list(self._cs.nodes):
            if name not in wanted:
                self._cs.delete_node(name)
        for name, node in wanted.items():
            if name in self._cs.nodes:
                self._cs.update_node(node)
            else:
                self._cs.create_node(node)
        for w in req.get("pods", ()):
            if w.get("uid") in self._cs.pods:
                continue  # live server, replayed sync: already tracked
            pod = pod_from_wire(w)
            if pod.node_name:  # bound pods only: they are node LOAD
                self._cs.create_pod(pod)
                self._cs.bindings[pod.uid] = pod.node_name
        if "nextStartNodeIndex" in req and not self._cs.bindings:
            # Round-robin rotation point: part of the reconstructible
            # scheduling state — without it a restarted sidecar restarts
            # its rotation at 0 and diverges from a fault-free run. Applied
            # only while this instance has scheduled NOTHING: on a live
            # server a reconnect resync carries the client's STALE value
            # (from the last reply it actually read), and rolling a live
            # rotation back would diverge exactly the way starting at 0
            # would. A live server's own counter is always the truth.
            self._sched.next_start_node_index = int(req["nextStartNodeIndex"])
        return {"ok": True}

    def _schedule(self, req: dict) -> dict:
        from ..core.apiserver import pod_from_wire
        pods = [pod_from_wire(w) for w in req.get("pods", ())]
        for p in pods:
            # Replay-idempotent (a reconnect replays the request whose reply
            # was lost): a pod this mirror already bound keeps its binding
            # instead of being re-created as pending and double-counted.
            if p.uid not in self._cs.bindings:
                self._cs.create_pod(p)
        self._sched.run_until_idle()
        assignments: List[Optional[str]] = []
        for p in pods:
            assignments.append(self._cs.bindings.get(p.uid) or None)
            # The caller owns the cluster truth; the sidecar's copy of the
            # pod served its purpose once scheduled (bound pods stay in the
            # mirror as load; unschedulable ones leave so the next batch
            # doesn't re-attempt them).
            if p.uid not in self._cs.bindings:
                self._cs.delete_pod(p)
        return {"assignments": assignments,
                "deviceScheduled": self._sched.device_scheduled,
                "nextStartNodeIndex": self._sched.next_start_node_index}

    # -- serving -----------------------------------------------------------

    def serve_forever(self) -> None:
        if os.path.exists(self.socket_path):
            os.unlink(self.socket_path)
        self._listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._listener.bind(self.socket_path)
        self._listener.listen(4)
        print(f"kubernetes-tpu-sidecar: serving on {self.socket_path}",
              flush=True)
        while not self._stop.is_set():
            try:
                conn, _ = self._listener.accept()
            except OSError:
                break
            self._conns.add(conn)
            self.served_connections += 1
            try:
                with conn:
                    self._serve_connection(conn)
            except OSError:
                # Client died mid-exchange (reset, broken pipe): this
                # connection is gone; the server survives and accepts the
                # client's reconnect — a sidecar must never crash because
                # its caller did.
                pass
            finally:
                self._conns.discard(conn)
        self._listener.close()

    def _serve_connection(self, conn: socket.socket) -> None:
        while not self._stop.is_set():
            req = _recv(conn)
            if req is None:
                break
            try:
                verb = req.get("verb")
                if verb == "ping":
                    _send(conn, {"ok": True})
                elif verb == "sync":
                    _send(conn, self._sync(req))
                elif verb == "schedule":
                    _send(conn, self._schedule(req))
                elif verb == "shutdown":
                    _send(conn, {"ok": True})
                    self._stop.set()
                else:
                    _send(conn, {"error": f"unknown verb {verb!r}"})
            except OSError:
                raise  # transport dead: drop the connection, not the server
            except Exception as e:  # noqa: BLE001 - wire error reply
                _send(conn, {"error": repr(e)})

    def shutdown(self) -> None:
        self._stop.set()
        if self._listener is not None:
            try:
                self._listener.close()
            except OSError:
                pass

    def kill(self) -> None:
        """Abrupt death (chaos: SIGKILL analogue): tear down the listener
        AND every live connection mid-exchange, no goodbye. Clients see a
        reset; a replacement server may then bind the same socket path."""
        self._stop.set()
        for s in list(self._conns) + ([self._listener] if self._listener else []):
            try:
                s.close()
            except OSError:
                pass


class SidecarClient:
    """The host scheduler's side of the contract.

    Crash-proof: a dead connection (sidecar killed/restarted, reset
    mid-reply) reconnects with backoff and REPLAYS the failed request. The
    sidecar's mirror is reconstructible-from-host-snapshot (docs/SIDECAR.md
    state ownership), so the client re-sends its last `sync` payload on
    every reconnect before the replay — a freshly restarted sidecar sees
    the node set first, exactly like the first connection did. A `schedule`
    whose reply was lost replays whole; the batch re-schedules against the
    re-synced mirror (level-triggered, like a re-attempted in-process
    cycle)."""

    def __init__(self, socket_path: str, timeout: float = 60.0, retry=None):
        from ..core.backoff import RetryConfig
        self._path = socket_path
        self._timeout = timeout
        self._retry_cfg = retry or RetryConfig(
            initial_backoff=0.05, max_backoff=2.0, max_attempts=8)
        self._last_sync: Optional[dict] = None
        # Every placement this client has bound since its last sync, by uid
        # (pod wire + nodeName): the reconnect resync replays these so a
        # RESTARTED sidecar rebuilds its load picture, not just its nodes.
        self._bound_pods: dict = {}
        self._next_start: Optional[int] = None  # rotation point (resync)
        self.reconnects = 0
        self._sock = self._connect()

    def _connect(self) -> socket.socket:
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        sock.settimeout(self._timeout)
        sock.connect(self._path)
        return sock

    def _roundtrip(self, sock: socket.socket, req: dict) -> dict:
        _send(sock, req)
        resp = _recv(sock)
        if resp is None:
            raise ConnectionError("sidecar closed the connection")
        return resp

    def _call(self, req: dict) -> dict:
        try:
            resp = self._roundtrip(self._sock, req)
        except (ConnectionError, OSError):
            resp = self._reconnect_and_replay(req)
        if "error" in resp:
            raise RuntimeError(f"sidecar: {resp['error']}")
        return resp

    def _reconnect_and_replay(self, req: dict) -> dict:
        try:
            self._sock.close()
        except OSError:
            pass
        last_exc: Optional[BaseException] = None
        if req.get("verb") == "sync":
            # The dying request IS a sync: enrich the replay itself with the
            # bound-pod load + rotation point, so a server restarted
            # mid-sync still rebuilds the full mirror state (a bare node
            # list would leave it loadless at rotation 0).
            req = dict(req)
            if self._bound_pods:
                req.setdefault("pods", list(self._bound_pods.values()))
            if self._next_start is not None:
                req.setdefault("nextStartNodeIndex", self._next_start)
        for delay in self._retry_cfg.delays():
            time.sleep(delay)
            try:
                sock = self._connect()
                # Re-establish the mirror before replaying (idempotent if
                # the server never died; required if it restarted empty):
                # the node set from the last sync plus every placement this
                # client has bound since.
                if self._last_sync is not None and req.get("verb") != "sync":
                    resync = dict(self._last_sync)
                    if self._bound_pods:
                        resync["pods"] = list(self._bound_pods.values())
                    if self._next_start is not None:
                        resync["nextStartNodeIndex"] = self._next_start
                    self._roundtrip(sock, resync)
                resp = self._roundtrip(sock, req)
            except (ConnectionError, OSError) as e:
                last_exc = e
                continue
            self._sock = sock
            self.reconnects += 1
            return resp
        raise ConnectionError(
            f"sidecar unreachable at {self._path} after "
            f"{self._retry_cfg.max_attempts - 1} reconnect attempts"
        ) from last_exc

    def ping(self) -> bool:
        return bool(self._call({"verb": "ping"}).get("ok"))

    def sync_nodes(self, nodes) -> None:
        from ..core.apiserver import node_to_wire
        req = {"verb": "sync", "nodes": [node_to_wire(n) for n in nodes]}
        self._last_sync = req
        # _bound_pods is NOT cleared: a later restart-resync must replay
        # every placement this client ever bound, not just the ones since
        # the last node sync (the server keeps them; a fresh server needs
        # them all).
        self._call(req)

    def schedule(self, pods) -> List[Optional[str]]:
        from ..core.apiserver import pod_to_wire
        wires = [pod_to_wire(p) for p in pods]
        resp = self._call({"verb": "schedule", "pods": wires})
        assignments = resp["assignments"]
        for w, node in zip(wires, assignments):
            if node:
                bound = dict(w)
                bound["nodeName"] = node
                self._bound_pods[w["uid"]] = bound
        if resp.get("nextStartNodeIndex") is not None:
            self._next_start = int(resp["nextStartNodeIndex"])
        return assignments

    def shutdown_server(self) -> None:
        # Graceful-stop best effort: no reconnect dance for a server we are
        # telling to exit.
        try:
            self._roundtrip(self._sock, {"verb": "shutdown"})
        except (ConnectionError, OSError):
            pass

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass


def main(argv=None) -> int:
    """`python -m kubernetes_tpu.parallel.sidecar --socket /tmp/tpu.sock
    [--platform cpu|tpu]` — the sidecar as its own OS process. It is a
    JAX process of its own: beside a scheduler that holds the chip it runs
    on the CPU (or on a chip of its own)."""
    import argparse
    import sys

    ap = argparse.ArgumentParser(prog="kubernetes-tpu-sidecar")
    ap.add_argument("--socket", required=True)
    ap.add_argument("--platform", default="auto",
                    choices=("auto", "cpu", "tpu"))
    args = ap.parse_args(argv)
    from ..perf.device import pin_platform
    refused = pin_platform(args.platform)
    if refused:
        print(refused, file=sys.stderr)
        return 3
    SidecarServer(args.socket).serve_forever()
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
