"""The fleet conductor: staged bring-up, supervision, and teardown of a
declarative many-process cluster (FleetSpec).

This is the subsystem the reference composes out of kubemark +
scheduler_perf: one object owns the whole process tree — apiserver
leader, follower replicas, shard schedulers, N hollow kubelet planes
splitting one profile by name-prefix range, controller managers — and
runs it as a unit:

- **staged bring-up with readiness barriers** — leader ready → followers
  tailing (election topology injected) → shards leased (the shard-lease
  table shows every slot owned) → hollow fleet registered (every member
  acknowledged its exact sub-range) → controllers active. Every spawn
  blocks on the child's ready line (testing/faults.spawn_ready) and
  every child's stdout is drained for the fleet's whole life
  (drain_pipe — the PR-8 unread-64KB-pipe stall class);
- **supervision with per-role restart policy** (spec.restart): a crashed
  hollow member respawns with ``--adopt`` and re-registers its exact
  prefix range with zero duplicate nodes; a crashed shard is NOT
  respawned — its lease expires and the ring successor adopts the range
  (a conductor respawn would race that adoption); apiserver replicas
  stay down (losing the leader is a failover, not a supervision event).
  Restarts are counted and ledgered in ``events`` — never silent;
- **periodic sampling** — per-process VmRSS peaks fold into one
  consolidated ``detail()`` line alongside bound-pod throughput samples
  (``note_bound``), stage timings, and the restart ledger;
- **flight-record collection** — SIGUSR2 fans out to every member that
  installs a dump handler before teardown, and ``artifacts()`` lists
  what landed in flightrec_dir.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

from ..shard.harness import _call, _env, _repo_root, rss_mb, scrape_metrics
from .spec import FleetSpec

READY_SERVING = r"serving on 127\.0\.0\.1:(\d+)"
READY_REGISTERED = r"registered (\d+) nodes"
READY_METRICS = r"metrics on (127\.0\.0\.1:\d+)"

# Roles whose processes install a SIGUSR2 flight-dump handler (apiserver
# and scheduler via core/spans.FlightRecorder, the hollow plane via its
# stats-line handler). Signalling a process WITHOUT a handler would kill
# it — the fan-out only targets these.
SIGUSR2_ROLES = ("apiserver", "follower", "shard", "hollow")


class FleetMember:
    """One supervised child process: its spawn recipe (for respawns), its
    live handles, and its supervision ledger."""

    def __init__(self, role: str, index: int, cmd: List[str], env: dict,
                 ready_pattern: str, respawn_extra: Optional[List[str]] = None):
        self.role = role
        self.index = index
        self.name = f"{role}-{index}"
        self.cmd = list(cmd)
        self.env = env
        self.ready_pattern = ready_pattern
        # Extra argv appended on a SUPERVISED respawn only (a hollow
        # member restarts with --adopt: survivors of its range are
        # claimed, not duplicated).
        self.respawn_extra = list(respawn_extra or ())
        self.proc = None
        self.tail = None            # drained stdout deque (drain_pipe)
        self.url = ""               # ready-line URL, when the role has one
        self.registered = 0         # hollow: nodes acknowledged at ready
        self.restarts = 0
        self.rss_peak_mb = 0.0
        self.stopping = False       # conductor-initiated stop in progress

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def summary(self) -> dict:
        return {"name": self.name, "role": self.role, "index": self.index,
                "pid": self.proc.pid if self.proc is not None else 0,
                "alive": self.alive(), "url": self.url,
                "restarts": self.restarts,
                "rss_peak_mb": self.rss_peak_mb}


class FleetConductor:
    def __init__(self, spec: FleetSpec):
        self.spec = spec.validate()
        self.members: List[FleetMember] = []
        self.stages: List[dict] = []       # bring-up timeline
        self.events: List[dict] = []       # supervision ledger
        self.restarts_total = 0
        self.base = ""                     # leader URL
        self.follower_urls: List[str] = []
        self.shard_urls: List[str] = []
        self.controller_urls: List[str] = []
        self._bound_samples: List[tuple] = []
        self._lock = threading.Lock()
        self._stopping = threading.Event()
        self._supervisor: Optional[threading.Thread] = None
        self._tmpdir = ""
        self._started = False
        self._env = _env()
        self._env.update(spec.env)
        if spec.flightrec_dir:
            os.makedirs(spec.flightrec_dir, exist_ok=True)
            self._env["TPU_SCHED_FLIGHTREC_DIR"] = spec.flightrec_dir
        if spec.fair_tenants:
            self._env["TPU_SCHED_FAIR_TENANTS"] = "1"
        if spec.apf_workload:
            self._env["TPU_SCHED_APF_WORKLOAD"] = spec.apf_workload

    # -- the ONE spawn site (supervision-discipline: readiness barrier +
    # -- drained pipe wired in the same slice) ------------------------------

    def _spawn(self, member: FleetMember, extra: Optional[List[str]] = None):
        """Spawn (or respawn) a member: block on its ready line, then wire
        the stdout drain for the member's whole life. Every child the
        conductor ever starts goes through here — the readiness barrier
        and the pipe drain are structurally inseparable from the spawn."""
        from ..testing.faults import drain_pipe, spawn_ready

        proc, m = spawn_ready(member.cmd + list(extra or ()),
                              member.ready_pattern, cwd=_repo_root(),
                              env=member.env,
                              timeout=self.spec.startup_timeout_s)
        member.proc = proc
        member.tail = drain_pipe(proc)
        if member.ready_pattern == READY_SERVING:
            member.url = f"http://127.0.0.1:{m.group(1)}"
        elif member.ready_pattern == READY_METRICS:
            member.url = f"http://{m.group(1)}"
        elif member.ready_pattern == READY_REGISTERED:
            member.registered = int(m.group(1))
        return member

    def _stage(self, name: str, t0: float, members: int) -> None:
        self.stages.append({"stage": name,
                            "elapsed_s": round(time.monotonic() - t0, 2),
                            "members": members})

    # -- staged bring-up ----------------------------------------------------

    def start(self) -> "FleetConductor":
        if self._started:
            return self
        self._started = True
        self._tmpdir = tempfile.mkdtemp(prefix="fleet-")
        try:
            self._start_leader()
            self._start_followers()
            self._start_shards()
            self._start_hollow()
            self._start_controllers()
        except BaseException:
            self._stopping.set()
            self._teardown_procs()
            raise
        self._supervisor = threading.Thread(
            target=self._supervise_loop, name="fleet-supervisor", daemon=True)
        self._supervisor.start()
        return self

    def _start_leader(self) -> None:
        t0 = time.monotonic()
        spec = self.spec
        cmd = [sys.executable, "-m", "kubernetes_tpu.core.apiserver",
               "--port", "0"]
        if spec.data_dir:
            cmd += ["--data-dir", spec.data_dir]
        if spec.replicas:
            cmd += ["--repl-lease-duration", str(spec.repl_lease_s)]
        leader = FleetMember("apiserver", 0, cmd, self._env, READY_SERVING)
        self.members.append(self._spawn(leader))
        self.base = leader.url
        self._stage("leader", t0, 1)

    def _start_followers(self) -> None:
        spec = self.spec
        if not spec.replicas:
            return
        t0 = time.monotonic()
        for rank in range(1, spec.replicas + 1):
            cmd = [sys.executable, "-m", "kubernetes_tpu.core.apiserver",
                   "--port", "0", "--replicate-from", self.base,
                   "--replica-rank", str(rank),
                   "--repl-lease-duration", str(spec.repl_lease_s)]
            if spec.data_dir:
                cmd += ["--data-dir", f"{spec.data_dir}-follower-{rank}"]
            f = FleetMember("follower", rank - 1, cmd, self._env,
                            READY_SERVING)
            self.members.append(self._spawn(f))
            self.follower_urls.append(f.url)
        # Ephemeral ports: inject the full election topology post-spawn —
        # only now are the followers "tailing" rather than merely serving.
        peers = {"0": self.base}
        peers.update({str(r + 1): u
                      for r, u in enumerate(self.follower_urls)})
        for url in [self.base] + self.follower_urls:
            _call(url, "POST", "/replication/peers", {"peers": peers})
        self._stage("followers", t0, spec.replicas)

    def _shard_env(self) -> dict:
        spec = self.spec
        env = dict(self._env)
        env.update(spec.shard_env)
        if spec.mesh_devices > 1:
            # Applied where it must land for a CHILD process: XLA_FLAGS
            # before backend init gives every
            # shard a virtual device mesh, so TPUScheduler(mesh="auto")
            # builds it and row-local plans dispatch mesh-SPMD.
            flags = env.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                env["XLA_FLAGS"] = (
                    flags + " --xla_force_host_platform_device_count="
                    + str(spec.mesh_devices)).strip()
        return env

    def _start_shards(self) -> None:
        t0 = time.monotonic()
        spec = self.spec
        env = self._shard_env()

        def build(i: int) -> FleetMember:
            # Shard-per-core placement (n>1 only): without pinning each
            # shard's XLA pool spans every core and the plane ping-pongs
            # instead of overlapping.
            pin: List[str] = []
            if spec.shards > 1 and spec.pin_shards and shutil.which("taskset"):
                pin = ["taskset", "-c",
                       str(i % max(1, os.cpu_count() or 1))]
            api_url = self.base
            extra: List[str] = []
            if self.follower_urls:
                api_url = self.follower_urls[i % len(self.follower_urls)]
                others = [u for u in self.follower_urls if u != api_url] \
                    + [self.base]
                extra = ["--api-fallbacks", ",".join(others)]
            # N shard processes cannot share one chip (a chip belongs to
            # one process), so the shard plane schedules on the CPU —
            # by flag here and by JAX_PLATFORMS in shard/harness.py _env.
            cmd = pin + [sys.executable, "-m", "kubernetes_tpu",
                         "--api-url", api_url, "--platform", "cpu",
                         "--port", "0",
                         "--shard-index", str(i),
                         "--shard-count", str(spec.shards),
                         "--shard-lease-duration", str(spec.shard_lease_s)] \
                + extra
            return FleetMember("shard", i, cmd, env, READY_SERVING)

        shards = [build(i) for i in range(spec.shards)]
        # Parallel spawn: each shard pays the JAX import.
        with ThreadPoolExecutor(max_workers=spec.shards) as ex:
            list(ex.map(self._spawn, shards))
        self.members.extend(shards)
        self.shard_urls = [s.url for s in shards]
        self._wait_shards_leased()
        self._stage("shards", t0, spec.shards)

    def _wait_shards_leased(self) -> None:
        """Barrier: every shard-lease slot is owned. A shard that is
        'serving' but not yet leased would leave its range unscheduled
        until the first lease sweep — the stage gate makes bring-up mean
        bring-up."""
        spec = self.spec
        deadline = time.monotonic() + spec.startup_timeout_s
        while time.monotonic() < deadline:
            owned = 0.0
            for url in self.shard_urls:
                try:
                    owned += scrape_metrics(url).get(
                        "scheduler_shard_owned_shards", 0.0)
                except Exception:  # noqa: BLE001 - metrics not up yet
                    continue
            if owned >= spec.shards:
                return
            if self._stopping.wait(0.2):
                return
        raise TimeoutError(
            f"shards-leased barrier: {owned}/{spec.shards} slots owned "
            f"after {spec.startup_timeout_s}s")

    def _start_hollow(self) -> None:
        spec = self.spec
        if spec.hollow is None:
            return
        t0 = time.monotonic()
        from ..hollow import HollowProfile
        profile = HollowProfile.from_dict(spec.hollow)
        subs = profile.split(spec.hollow_procs)
        hollow_members: List[FleetMember] = []
        for i, sub in enumerate(subs):
            path = os.path.join(self._tmpdir, f"hollow-{i}.json")
            with open(path, "w") as fh:
                json.dump(sub.to_dict(), fh)
            cmd = [sys.executable, "-m", "kubernetes_tpu.hollow",
                   "--api-url", self.base, "--profile", path]
            hollow_members.append(FleetMember(
                "hollow", i, cmd, self._env, READY_REGISTERED,
                respawn_extra=["--adopt"]))
        # Parallel registration: each member bulk-creates its own
        # disjoint range, so the chunked POSTs interleave cleanly.
        with ThreadPoolExecutor(max_workers=len(hollow_members)) as ex:
            list(ex.map(self._spawn, hollow_members))
        self.members.extend(hollow_members)
        got = sum(m.registered for m in hollow_members)
        if got < profile.count:
            raise RuntimeError(
                f"hollow-registered barrier: {got}/{profile.count} nodes "
                f"acknowledged across {len(hollow_members)} members")
        self._stage("hollow", t0, len(hollow_members))

    def _start_controllers(self) -> None:
        spec = self.spec
        if (spec.node_lifecycle is None and spec.workload is None
                and spec.deschedule is None):
            return
        t0 = time.monotonic()
        n = 0
        if spec.node_lifecycle is not None:
            nl = spec.node_lifecycle
            cmd = [sys.executable, "-m", "kubernetes_tpu.controllers",
                   "--api-url", self.base,
                   "--grace", str(nl.get("grace", 4.0)),
                   "--noexec-after", str(nl.get("noexec_after", 2.0)),
                   "--tick", str(nl.get("tick", 0.5)),
                   "--primary-qps", str(nl.get("primary_qps", 2.0)),
                   "--secondary-qps", str(nl.get("secondary_qps", 0.1)),
                   "--unhealthy-threshold",
                   str(nl.get("unhealthy_threshold", 0.55))]
            for url in self.follower_urls:
                cmd += ["--fallback", url]
            m = FleetMember("controller", 0, cmd, self._env, READY_METRICS)
            self.members.append(self._spawn(m))
            self.controller_urls.append(m.url)
            n += 1
        if spec.workload is not None:
            wl = spec.workload
            for i in range(int(wl.get("managers", 2))):
                cmd = [sys.executable, "-m", "kubernetes_tpu.controllers",
                       "--mode", "workload", "--api-url", self.base,
                       "--identity", f"wm-{i}",
                       "--lease-ttl", str(wl.get("lease_ttl", 2.0)),
                       "--tick", str(wl.get("tick", 0.25))]
                for url in self.follower_urls:
                    cmd += ["--fallback", url]
                auto = wl.get("autoscale")
                if auto is not None:
                    cmd += ["--autoscale",
                            "--min-nodes", str(auto.get("min", 0)),
                            "--max-nodes", str(auto.get("max", 100)),
                            "--scale-wave", str(auto.get("wave", 2)),
                            "--pending-age",
                            str(auto.get("pending_age", 2.0)),
                            "--scale-cooldown",
                            str(auto.get("cooldown", 5.0))]
                trace = wl.get("trace")
                if trace is not None:
                    cmd += ["--trace-deployments",
                            str(trace.get("deployments", 0)),
                            "--trace-gangs", str(trace.get("gangs", 0)),
                            "--trace-rate", str(trace.get("rate", 2.0)),
                            "--trace-lifetime",
                            str(trace.get("lifetime", 0.0)),
                            "--trace-seed", str(trace.get("seed", 0))]
                m = FleetMember("workload", i, cmd, self._env, READY_METRICS)
                self.members.append(self._spawn(m))
                n += 1
        if spec.deschedule is not None:
            ds = spec.deschedule
            for i in range(int(ds.get("managers", 2))):
                cmd = [sys.executable, "-m", "kubernetes_tpu.controllers",
                       "--mode", "deschedule", "--api-url", self.base,
                       "--identity", f"dm-{i}",
                       "--lease-ttl", str(ds.get("lease_ttl", 2.0)),
                       "--tick", str(ds.get("tick", 0.25)),
                       "--hysteresis", str(ds.get("hysteresis", 5)),
                       "--margin", str(ds.get("margin", 0.10)),
                       "--max-moves", str(ds.get("max_moves", 64)),
                       "--primary-qps", str(ds.get("primary_qps", 20.0)),
                       "--secondary-qps",
                       str(ds.get("secondary_qps", 0.1))]
                if ds.get("device"):
                    cmd += ["--deschedule-device"]
                for url in self.follower_urls:
                    cmd += ["--fallback", url]
                m = FleetMember("deschedule", i, cmd, self._env,
                                READY_METRICS)
                self.members.append(self._spawn(m))
                n += 1
        self._stage("controllers", t0, n)

    # -- supervision --------------------------------------------------------

    def _supervise_loop(self) -> None:
        interval = self.spec.supervise_interval_s
        while not self._stopping.wait(interval):
            self.sample()
            for member in list(self.members):
                if member.stopping or member.proc is None \
                        or member.proc.poll() is None:
                    continue
                self._handle_exit(member)

    def _handle_exit(self, member: FleetMember) -> None:
        policy = self.spec.restart.get(member.role, "never")
        event = {"t": round(time.monotonic(), 2), "member": member.name,
                 "role": member.role, "exit": member.proc.returncode,
                 "policy": policy}
        if policy == "restart":
            if member.restarts >= self.spec.max_restarts:
                event["action"] = "gave-up"
            else:
                try:
                    # Respawn through the one barrier+drain spawn site;
                    # respawn_extra rides along (--adopt: a hollow member
                    # re-claims the survivors of its exact prefix range).
                    self._spawn(member, extra=member.respawn_extra)
                    member.restarts += 1
                    event["action"] = "restarted"
                    event["restarts"] = member.restarts
                    with self._lock:
                        self.restarts_total += 1
                except Exception as exc:  # noqa: BLE001 - ledger, not crash
                    event["action"] = "restart-failed"
                    event["error"] = str(exc)[:200]
        elif policy == "adopt":
            # The peer protocol absorbs the loss (a shard's lease expires
            # and the ring successor adopts its range). Respawning here
            # would RACE that adoption — record, don't act.
            event["action"] = "left-to-adoption"
            member.stopping = True      # don't re-ledger every tick
        else:
            event["action"] = "down"
            member.stopping = True
        with self._lock:
            self.events.append(event)

    def sample(self) -> None:
        """Fold current per-process VmRSS into each member's peak."""
        for member in self.members:
            if member.alive():
                member.rss_peak_mb = max(member.rss_peak_mb,
                                         rss_mb(member.proc.pid))

    def note_bound(self, bound: int) -> None:
        """Throughput sample from the driving harness's progress poll."""
        with self._lock:
            self._bound_samples.append((time.monotonic(), bound))

    # -- consolidated detail ------------------------------------------------

    def members_of(self, role: str) -> List[FleetMember]:
        return [m for m in self.members if m.role == role]

    def rss_peaks(self) -> Dict[str, object]:
        """Per-role peak-RSS map, shaped for the existing detail-line
        consumers (scalar leader, lists for the scaled-out roles)."""
        self.sample()
        hollows = self.members_of("hollow")
        ctrls = (self.members_of("controller") + self.members_of("workload")
                 + self.members_of("deschedule"))
        leader = self.members_of("apiserver")
        out: Dict[str, object] = {
            "apiserver": leader[0].rss_peak_mb if leader else 0.0,
            "shards": [m.rss_peak_mb for m in self.members_of("shard")],
            "followers": [m.rss_peak_mb for m in self.members_of("follower")],
        }
        if hollows:
            out["hollow"] = max(m.rss_peak_mb for m in hollows)
            out["hollow_members"] = [m.rss_peak_mb for m in hollows]
        if ctrls:
            out["controllers"] = [m.rss_peak_mb for m in ctrls]
        return out

    def detail(self) -> dict:
        """The one consolidated fleet line: stage timeline, per-member
        supervision state, per-role RSS peaks, restart ledger, and the
        bound-pod throughput window."""
        with self._lock:
            samples = list(self._bound_samples)
            events = list(self.events)
        rate = None
        if len(samples) >= 2:
            (t0, b0), (t1, b1) = samples[0], samples[-1]
            rate = {"bound": b1,
                    "window_s": round(t1 - t0, 2),
                    "pods_per_sec": round((b1 - b0) / (t1 - t0), 1)
                    if t1 > t0 else 0.0}
        return {
            "name": self.spec.name,
            "stages": list(self.stages),
            "members": [m.summary() for m in self.members],
            "rss_mb": self.rss_peaks(),
            "restarts": self.restarts_total,
            "events": events,
            "throughput": rate,
            "flightrec_artifacts": len(self.artifacts()),
        }

    # -- flight-record fan-out + teardown -----------------------------------

    def signal_flightrec(self) -> int:
        """SIGUSR2 fan-out: every live member with a dump handler writes
        its flight record / stats line NOW. Returns members signalled."""
        n = 0
        for member in self.members:
            if member.role in SIGUSR2_ROLES and member.alive():
                try:
                    member.proc.send_signal(signal.SIGUSR2)
                    n += 1
                except OSError:
                    continue
        return n

    def artifacts(self) -> List[str]:
        d = self.spec.flightrec_dir
        if not d or not os.path.isdir(d):
            return []
        return sorted(f for f in os.listdir(d)
                      if f.startswith("flightrec-") and f.endswith(".jsonl"))

    def _final_stats(self, member: FleetMember, marker: str):
        """Scan a stopped member's drained tail (newest first) for its
        final one-line JSON stats object."""
        time.sleep(0.1)  # let the drain thread swallow the stats line
        for line in reversed(list(member.tail or ())):
            if marker in line:
                try:
                    return json.loads(line)[marker]
                except (ValueError, KeyError):
                    return None
        return None

    def stop_member(self, member: FleetMember, kill: bool = False) -> None:
        member.stopping = True
        if member.proc is None or member.proc.poll() is not None:
            return
        if kill:
            member.proc.kill()
        else:
            member.proc.terminate()
        try:
            member.proc.wait(timeout=15)
        except Exception:  # noqa: BLE001
            member.proc.kill()

    def stop_hollow(self) -> Optional[dict]:
        """SIGTERM every hollow member and merge their final stats lines
        (counters summed; per-member breakdown under "members")."""
        hollows = self.members_of("hollow")
        if not hollows:
            return None
        for m in hollows:
            self.stop_member(m)
        per = [self._final_stats(m, "hollow_stats") for m in hollows]
        merged: dict = {}
        for stats in per:
            for k, v in (stats or {}).items():
                if k != "offset" and isinstance(v, (int, float)):
                    merged[k] = merged.get(k, 0) + v
        if len(per) > 1:
            merged["members"] = per
        return merged or None

    def stop_workload(self) -> Optional[list]:
        """SIGTERM the workload managers; per-process final stats."""
        managers = self.members_of("workload")
        if not managers:
            return None
        out = []
        for m in managers:
            self.stop_member(m)
            out.append(self._final_stats(m, "controller_stats"))
        return out

    def stop_deschedulers(self) -> Optional[list]:
        """SIGTERM the descheduler managers; per-process final stats."""
        managers = self.members_of("deschedule")
        if not managers:
            return None
        out = []
        for m in managers:
            self.stop_member(m)
            out.append(self._final_stats(m, "controller_stats"))
        return out

    def _teardown_procs(self) -> None:
        """Reverse-stage teardown: controllers → hollow → shards →
        followers → leader."""
        order = ("deschedule", "workload", "controller", "hollow", "shard",
                 "follower", "apiserver")
        for role in order:
            for m in self.members_of(role):
                self.stop_member(m)

    def stop(self) -> None:
        self._stopping.set()
        if self._supervisor is not None:
            self._supervisor.join(timeout=10)
            self._supervisor = None
        if self.spec.flightrec_dir:
            # Last flight records before the tree comes down — even a
            # member that never crashed leaves a fresh artifact.
            self.signal_flightrec()
            time.sleep(0.2)
        self._teardown_procs()
        if self._tmpdir:
            shutil.rmtree(self._tmpdir, ignore_errors=True)
            self._tmpdir = ""
