"""Fault injection for the control-plane and device boundaries.

The chaos layer the resilience subsystem (docs/RESILIENCE.md) is tested
against. Three seams, matching the process boundaries the production
deployment has:

- ``FlakyClientset`` — wraps any clientset and makes WRITE verbs raise
  retriable :class:`~..core.backoff.TransientAPIError` (5xx/timeout
  analogue) on a deterministic seeded schedule. Reads and informer
  registration pass through untouched. Pair with ``RetryingClientset``
  (core/clientset.py) to prove write-path retries.

- ``ChaosTCPProxy`` — a byte-pump TCP proxy in front of the REST+watch
  apiserver (core/apiserver.py). ``drop_connections()`` resets every live
  connection mid-stream (the dropped-watch / connection-reset fault);
  ``delay`` slows responses. The reflector's resourceVersion re-list runs
  against exactly this.

- ``DeviceFaults`` — installed as ``TPUScheduler._fault_hook``; raises a
  configured exception on the Nth device kernel boundary crossing
  (``dispatch`` / ``preempt``), driving the device→host fallback and the
  circuit breaker.

- ``ApiServerProcess`` — a real-OS-process apiserver under chaos control:
  spawn with a durable data dir (WAL+snapshot, core/wal.py), ``kill9()``
  (SIGKILL — no goodbye, no flush), ``restart()`` in place on the SAME
  port + data dir. The crash-restart fault the durability layer and the
  scheduler's post-restart reconciliation are tested against.

Sidecar process kill rides ``SidecarServer.kill()`` (parallel/sidecar.py):
an abrupt listener+connection teardown, distinct from graceful shutdown.

Everything is deterministically seeded: a chaos test that fails replays
byte-for-byte from its seed.
"""

from __future__ import annotations

import os
import random
import re
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
from typing import Dict, Iterable, Optional

from ..core.backoff import TransientAPIError

# Clientset write verbs the chaos layer may afflict (the API-mutation
# surface the scheduler exercises).
WRITE_VERBS = (
    "create_pod", "update_pod", "delete_pod", "bind", "bind_many",
    "patch_pod_status",
    "create_node", "update_node", "delete_node", "evict_pod",
)


class FlakyClientset:
    """Deterministic write-fault decorator over any clientset.

    ``fail_first`` maps verb -> how many leading calls of that verb raise;
    ``failure_rate`` additionally fails each write call with the given
    seeded probability. Injected failures raise BEFORE the inner verb runs
    (the write never lands — a replay is required, like a request that
    died on the wire). ``injected`` counts faults by verb for assertions.
    """

    def __init__(self, inner, seed: int = 0, failure_rate: float = 0.0,
                 fail_first: Optional[Dict[str, int]] = None,
                 exc_factory=TransientAPIError):
        self._inner = inner
        self._rng = random.Random(seed)
        self._rate = failure_rate
        self._fail_first = dict(fail_first or {})
        self._exc_factory = exc_factory
        self.injected: Dict[str, int] = {}

    def _maybe_fail(self, verb: str) -> None:
        remaining = self._fail_first.get(verb, 0)
        if remaining > 0:
            self._fail_first[verb] = remaining - 1
        elif not (self._rate and self._rng.random() < self._rate):
            return
        self.injected[verb] = self.injected.get(verb, 0) + 1
        raise self._exc_factory(f"injected fault on {verb}")

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if name in WRITE_VERBS:
            def flaky(*args, _attr=attr, _verb=name, **kwargs):
                self._maybe_fail(_verb)
                return _attr(*args, **kwargs)
            return flaky
        return attr


class ChaosTCPProxy:
    """TCP byte pump with a kill switch, for resetting watch streams and
    in-flight requests between a client and the apiserver."""

    def __init__(self, upstream_host: str, upstream_port: int,
                 delay: float = 0.0):
        self.upstream = (upstream_host, upstream_port)
        self.delay = delay
        self._conns: set = set()
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.drops = 0  # connections reset by drop_connections()
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind(("127.0.0.1", 0))
        self._listener.listen(64)
        self.port = self._listener.getsockname()[1]
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="chaos-proxy-accept", daemon=True)
        self._accept_thread.start()

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                client, _ = self._listener.accept()
            except OSError:
                return
            try:
                server = socket.create_connection(self.upstream, timeout=10)
            except OSError:
                client.close()
                continue
            with self._lock:
                self._conns.add(client)
                self._conns.add(server)
            for src, dst in ((client, server), (server, client)):
                threading.Thread(target=self._pump, args=(src, dst),
                                 daemon=True).start()

    def _pump(self, src: socket.socket, dst: socket.socket) -> None:
        try:
            while True:
                data = src.recv(65536)
                if not data:
                    break
                if self.delay:
                    self._stop.wait(self.delay)
                dst.sendall(data)
        except OSError:
            pass
        finally:
            for s in (src, dst):
                try:
                    s.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                try:
                    s.close()
                except OSError:
                    pass
                with self._lock:
                    self._conns.discard(s)

    def drop_connections(self) -> int:
        """Reset every live proxied connection NOW (watch streams included).
        New connections keep working — this is a network blip, not an
        outage. Returns how many sockets were torn down."""
        with self._lock:
            victims = list(self._conns)
            self._conns.clear()
        for s in victims:
            try:
                # linger(on, 0): close sends RST, not FIN — a real reset.
                s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                             struct.pack("ii", 1, 0))
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass
        self.drops += len(victims)
        return len(victims)

    def close(self) -> None:
        self._stop.set()
        try:
            self._listener.close()
        except OSError:
            pass
        self.drop_connections()


def drain_pipe(proc, keep: int = 200) -> "deque":
    """Start a daemon thread that keeps reading a spawned child's stdout
    AFTER the ready line, retaining the last `keep` lines for diagnostics.

    Without this, a child that logs under load (slow-step warnings, a
    device-fallback traceback) eventually fills the 64KB pipe buffer and
    BLOCKS on the write — mid-scheduling-cycle — which reads as a
    mysterious 2x throughput collapse, not a log problem (PR 8 incident:
    one fallback's host-path slow-step flood stalled a whole shard).
    Returns the deque of retained lines."""
    from collections import deque

    tail: "deque" = deque(maxlen=keep)

    def pump():
        try:
            for line in proc.stdout:
                tail.append(line)
        except (ValueError, OSError):
            pass  # pipe closed at process teardown

    threading.Thread(target=pump, name="pipe-drain", daemon=True).start()
    return tail


def spawn_ready(cmd, pattern, cwd=None, env=None, timeout=120.0):
    """Spawn a subprocess and block until a stdout line matches `pattern`
    (stderr is folded into stdout). select-before-readline: a
    silent-but-alive child trips the deadline instead of hanging the
    harness; a dead child raises immediately. Returns (proc, match).

    NOTE for callers printing a ready line: it must be the FIRST line the
    child emits — readline buffers everything already in the pipe, so a
    line printed BEFORE the ready line that arrives in the same chunk
    would leave select() waiting on a drained fd."""
    import select
    from collections import deque

    proc = subprocess.Popen(cmd, cwd=cwd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    deadline = time.monotonic() + timeout
    # Keep the tail of everything read pre-ready: a child that dies before
    # its ready line usually printed WHY (a traceback) — surfacing it here
    # turns "exited rc=1" into an actionable failure.
    tail: "deque" = deque(maxlen=40)
    while time.monotonic() < deadline:
        ready, _, _ = select.select([proc.stdout], [], [],
                                    max(0.0, deadline - time.monotonic()))
        if not ready:
            break
        line = proc.stdout.readline()
        if line:
            tail.append(line)
        if not line and proc.poll() is not None:
            raise RuntimeError(
                f"{cmd[:3]} exited rc={proc.returncode}; "
                f"output tail:\n{''.join(tail)}")
        m = re.search(pattern, line)
        if m:
            return proc, m
    proc.kill()
    raise TimeoutError(
        f"{cmd[:3]} never printed {pattern!r}; "
        f"output tail:\n{''.join(tail)}")


class ApiServerProcess:
    """Standalone apiserver (`python -m kubernetes_tpu.core.apiserver`) as a
    killable OS process: the control-plane analogue of SidecarServer.kill().

    ``kill9()`` delivers SIGKILL mid-flight; ``restart()`` relaunches on the
    SAME port with the SAME ``--data-dir`` so the new process recovers from
    WAL+snapshot and watch clients reconnect to an identical address — the
    crash-restart fault the durable store is specified against."""

    _READY = re.compile(r"serving on 127\.0\.0\.1:(\d+)")

    def __init__(self, data_dir: str, port: int = 0, fsync: bool = False,
                 snapshot_every: int = 2048, startup_timeout: float = 60.0,
                 extra_args=(), extra_env=None):
        self.data_dir = data_dir
        self.port = port
        self.fsync = fsync
        self.snapshot_every = snapshot_every
        self.startup_timeout = startup_timeout
        # Extension seams for composed harnesses (ReplicaSet): replication
        # flags + per-process env (flight-recorder dir) without a second
        # copy of the spawn/env/teardown mechanics.
        self.extra_args = list(extra_args)
        self.extra_env = dict(extra_env or {})
        self.kills = 0
        self.restarts = 0
        self.proc: Optional[subprocess.Popen] = None
        self._spawn()

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def _spawn(self) -> None:
        repo_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env = dict(os.environ)
        # The apiserver never runs a kernel; should anything in it import
        # JAX, it stays off a chip some other process owns.
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = repo_root
        env.update(self.extra_env)
        cmd = [sys.executable, "-m", "kubernetes_tpu.core.apiserver",
               "--port", str(self.port), "--data-dir", self.data_dir,
               "--snapshot-every", str(self.snapshot_every)]
        if self.fsync:
            cmd.append("--fsync")
        cmd += self.extra_args
        self.proc, m = spawn_ready(cmd, self._READY, cwd=repo_root, env=env,
                                   timeout=self.startup_timeout)
        # Pin the OS-assigned port: restarts re-bind the same one.
        self.port = int(m.group(1))
        # Drained stdout (see drain_pipe): an unread pipe would block the
        # server once it logs more than the 64KB buffer.
        self.log_tail = drain_pipe(self.proc)

    def kill9(self) -> None:
        """SIGKILL — the process dies mid-write, no flush, no shutdown."""
        self.proc.send_signal(signal.SIGKILL)
        self.proc.wait(timeout=30)
        self.kills += 1

    def restart(self) -> None:
        """Relaunch in place (same port, same data dir); blocks until the
        recovered server is serving."""
        assert self.proc.poll() is not None, "kill9()/stop() first"
        self._spawn()
        self.restarts += 1

    def stop(self) -> None:
        if self.proc is None or self.proc.poll() is not None:
            return
        self.proc.terminate()
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            self.proc.kill()


class ReplicaSet:
    """A replicated control plane under chaos control: one leader + N
    follower apiservers (kubernetes_tpu/replication/), each a killable OS
    process (composed :class:`ApiServerProcess` handles) with its own data
    dir. ``kill9_leader()`` is the headline fault — the lowest-ranked live
    follower must promote within the replication lease TTL;
    ``kill9_follower(rank)`` exercises the read plane's client-side
    failover (HTTPClientset fallbacks)."""

    def __init__(self, data_root: str, followers: int = 1,
                 repl_lease: float = 2.0, snapshot_every: int = 100_000,
                 startup_timeout: float = 120.0, flightrec_dir: str = ""):
        self.data_root = data_root
        self.repl_lease = repl_lease
        self.snapshot_every = snapshot_every
        self.startup_timeout = startup_timeout
        self.flightrec_dir = flightrec_dir
        if flightrec_dir:
            os.makedirs(flightrec_dir, exist_ok=True)
        self.kills: Dict[str, int] = {}
        # replicas[0] is the seed leader; replicas[k] is follower rank k.
        self.replicas: list = [self._spawn_replica(
            os.path.join(data_root, "leader"))]
        for rank in range(1, followers + 1):
            self.replicas.append(self._spawn_replica(
                os.path.join(data_root, f"follower-{rank}"),
                replicate_from=self.leader_url, rank=rank))
        # Inject the full rank -> URL topology into every replica (ports
        # are ephemeral, so peers are only known post-spawn). Elections
        # probe this map.
        self.peers = {rank: r.url for rank, r in enumerate(self.replicas)}
        body = {"peers": {str(k): v for k, v in self.peers.items()}}
        for r in self.replicas:
            self._post_json(r.url, "/replication/peers", body)

    @property
    def leader_url(self) -> str:
        return self.replicas[0].url

    @property
    def follower_urls(self) -> list:
        return [r.url for r in self.replicas[1:]]

    def _post_json(self, base: str, path: str, body: dict) -> None:
        # shard/harness._call: the shared pooled keep-alive JSON helper
        # (function-local import — harness itself imports from this
        # module, so a top-level import would cycle).
        from ..shard.harness import _call
        _call(base, "POST", path, body)

    def _spawn_replica(self, data_dir: str, replicate_from: str = "",
                       rank: int = 0) -> ApiServerProcess:
        extra = ["--repl-lease-duration", str(self.repl_lease)]
        if replicate_from:
            extra += ["--replicate-from", replicate_from,
                      "--replica-rank", str(rank)]
        extra_env = ({"TPU_SCHED_FLIGHTREC_DIR": self.flightrec_dir}
                     if self.flightrec_dir else {})
        return ApiServerProcess(
            data_dir, snapshot_every=self.snapshot_every,
            startup_timeout=self.startup_timeout,
            extra_args=extra, extra_env=extra_env)

    def kill9_leader(self) -> None:
        """SIGKILL the leader mid-flight: no flush, no goodbye — the
        promotion path's acceptance fault."""
        self.replicas[0].kill9()
        self.kills["leader"] = self.kills.get("leader", 0) + 1

    def kill9_follower(self, index: int = 0) -> None:
        """SIGKILL follower `index` (rank index+1): its local shards must
        rotate reads to a sibling replica."""
        self.replicas[index + 1].kill9()
        self.kills[f"follower-{index + 1}"] = \
            self.kills.get(f"follower-{index + 1}", 0) + 1

    def status(self, base: str) -> Optional[dict]:
        from ..shard.harness import _call
        try:
            return _call(base, "GET", "/replication/status", timeout=5)
        except Exception:  # noqa: BLE001 - replica down
            return None

    def wait_for_leader(self, timeout: float = 30.0) -> Optional[str]:
        """Block until some live replica reports role=leader; returns its
        base URL (None on timeout)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for r in self.replicas:
                st = self.status(r.url)
                if st is not None and st.get("role") == "leader":
                    return r.url
            time.sleep(0.1)
        return None

    def stop(self) -> None:
        for r in self.replicas:
            r.stop()


class DeviceFaults:
    """Fault hook for TPUScheduler's device kernel boundaries.

    Install as ``scheduler._fault_hook``. Raises ``exc_factory()`` when the
    running count of crossings for a site ('dispatch' | 'preempt') lands in
    that site's configured set. Counts are 1-based and per-site, so a plan
    like ``DeviceFaults(dispatch={3}, preempt={1})`` is fully
    deterministic regardless of interleaving."""

    def __init__(self, dispatch: Iterable[int] = (),
                 preempt: Iterable[int] = (),
                 exc_factory=lambda: RuntimeError("injected device fault")):
        self._plan = {"dispatch": set(dispatch), "preempt": set(preempt)}
        self._exc_factory = exc_factory
        self.calls: Dict[str, int] = {"dispatch": 0, "preempt": 0}
        self.injected: Dict[str, int] = {"dispatch": 0, "preempt": 0}

    def __call__(self, site: str) -> None:
        self.calls[site] = self.calls.get(site, 0) + 1
        if self.calls[site] in self._plan.get(site, ()):
            self.injected[site] = self.injected.get(site, 0) + 1
            raise self._exc_factory()


def scrape_metric(base_url: str, name: str, timeout: float = 5.0) -> float:
    """Fetch `base_url`/metrics and return the value of the un-labelled
    series `name`. Chaos tests poll counters across a kill9 with this;
    raises AssertionError if the series is not exposed (a typo'd series
    name must fail loudly, not read as 0.0)."""
    from urllib import request as _rq

    with _rq.urlopen(base_url + "/metrics", timeout=timeout) as resp:
        text = resp.read().decode()
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    raise AssertionError(f"series {name} not exposed by {base_url}")


def wait_metric(base_url: str, name: str, pred, timeout: float = 60.0,
                poll: float = 0.1) -> float:
    """Poll `scrape_metric` until `pred(value)` holds; returns the value
    that satisfied it. Scrape errors (the target may be mid-kill9) are
    swallowed and retried until the deadline."""
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            last = scrape_metric(base_url, name, timeout=poll * 50)
        except Exception:  # noqa: BLE001 - target racing a death
            last = None
        if last is not None and pred(last):
            return last
        time.sleep(poll)
    raise AssertionError(
        f"timed out waiting for {name} (last observed: {last})")
