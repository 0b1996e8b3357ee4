"""Multi-process shard-plane harness: one apiserver process + N scheduler
processes (`python -m kubernetes_tpu --shard-index i --shard-count n`),
driven entirely over HTTP. This is the production-shaped scale-out path —
each shard is an OS process with its own GIL, so shard throughput actually
adds up on CPU — used by the perf harness's sharded and hollow rows
(``python -m kubernetes_tpu.perf --labels sharded``) and the shard-kill
chaos test.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional
from urllib import request as urlrequest

_READY = r"serving on 127\.0\.0\.1:(\d+)"


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def _env() -> dict:
    """Environment of every process this harness (and the fleet conductor)
    spawns. The children are pinned to the CPU: a chip belongs to one
    process, N scheduler processes cannot share it, and the parent
    (`python -m kubernetes_tpu.perf` after an in-process row, a test
    session) may already hold it — with JAX_PLATFORMS=cpu a child never
    initializes the TPU runtime, so it neither fails nor hangs on the
    parent's chip. The one scheduler process that owns a chip is started
    by name with `--platform tpu` (chip_smoke.py's server stage)."""
    from ..compile_cache import export
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _repo_root()
    # One persistent compile cache for the whole plane (compile_cache.py):
    # every shard process compiles the same kernel statics — across a
    # plane AND across runs, only the first ever pays the backend compile.
    return export(env)


_KA_CLIENTS: Dict[str, object] = {}


def rss_mb(pid=None) -> float:
    """VmRSS of `pid` (default: this process) in MiB, straight from
    /proc/<pid>/status — 0.0 when unreadable (process gone, non-Linux).
    The bench/perf poll loops sample this so the bounded-memory claims
    are numbers, not assertions."""
    try:
        with open(f"/proc/{pid or 'self'}/status") as fh:
            for line in fh:
                if line.startswith("VmRSS:"):
                    return round(int(line.split()[1]) / 1024.0, 1)
    except (OSError, ValueError, IndexError):
        pass
    return 0.0


def _call(base: str, method: str, path: str, body=None, timeout: float = 30):
    """Pooled keep-alive call (core/apiserver.py KeepAliveClient): the
    creator threads POST thousands of pods — per-call connection setup
    costs the apiserver a thread spawn per request on top of the TCP
    handshake, CPU the shard schedulers are competing for."""
    from ..core.apiserver import KeepAliveClient

    client = _KA_CLIENTS.get(base)
    if client is None:
        client = _KA_CLIENTS[base] = KeepAliveClient(base)
    return client.call(method, path, body, timeout=timeout)


def scrape_histogram(base: str, name: str,
                     text: Optional[str] = None) -> Optional[dict]:
    """One histogram's merged bucket table across all label
    sets: {"buckets": [(le, cumulative_count)...], "count": n, "sum": s}.
    None when the series is absent. Lets the harness compute cross-shard
    p50/p99 by summing per-shard cumulative buckets (bucket bounds are
    identical — one metrics.py declaration)."""
    if text is None:
        text = _fetch_metrics(base)
    buckets: Dict[float, float] = {}
    count = total = 0.0
    seen = False
    for line in text.splitlines():
        if not line.startswith(name):
            continue
        m = re.match(rf'{name}_bucket{{.*le="([^"]+)".*}} (\S+)', line)
        if m is not None:
            seen = True
            le = float("inf") if m.group(1) == "+Inf" else float(m.group(1))
            buckets[le] = buckets.get(le, 0.0) + float(m.group(2))
            continue
        m = re.match(rf"{name}_count(?:{{[^}}]*}})? (\S+)", line)
        if m is not None:
            count += float(m.group(1))
            continue
        m = re.match(rf"{name}_sum(?:{{[^}}]*}})? (\S+)", line)
        if m is not None:
            total += float(m.group(1))
    if not seen:
        return None
    return {"buckets": sorted(buckets.items()), "count": count, "sum": total}


def merge_histograms(hists: List[Optional[dict]]) -> Optional[dict]:
    merged: Dict[float, float] = {}
    count = total = 0.0
    any_seen = False
    for h in hists:
        if h is None:
            continue
        any_seen = True
        for le, c in h["buckets"]:
            merged[le] = merged.get(le, 0.0) + c
        count += h["count"]
        total += h["sum"]
    if not any_seen:
        return None
    return {"buckets": sorted(merged.items()), "count": count, "sum": total}


def histogram_percentile(hist: dict, q: float) -> float:
    """Bucket-interpolated percentile over a merged cumulative table (the
    same interpolation as core/metrics.py Histogram.percentile)."""
    if not hist or hist["count"] <= 0:
        return 0.0
    target = q * hist["count"]
    prev_le, prev_cum = 0.0, 0.0
    for le, cum in hist["buckets"]:
        if cum >= target:
            if le == float("inf"):
                return prev_le
            span = cum - prev_cum
            frac = (target - prev_cum) / span if span else 1.0
            return prev_le + (le - prev_le) * frac
        prev_le, prev_cum = (0.0 if le == float("inf") else le), cum
    return prev_le


def _fetch_metrics(base: str) -> str:
    """GET /metrics once; every parser below accepts the fetched text so a
    multi-way scrape (totals + histogram + labeled) costs ONE round trip."""
    req = urlrequest.Request(base + "/metrics")
    with urlrequest.urlopen(req, timeout=30) as resp:
        return resp.read().decode()


def scrape_labeled(base: str, name: str, label: str,
                   text: Optional[str] = None) -> Dict[str, float]:
    """One series' per-label-value breakdown, e.g.
    scrape_labeled(url, "scheduler_watch_decoded_events", "form") ->
    {"full": n, "slim": m} (scrape_metrics sums label sets away)."""
    if text is None:
        text = _fetch_metrics(base)
    out: Dict[str, float] = {}
    pat = re.compile(rf'{name}{{.*?{label}="([^"]+)".*?}} (\S+)')
    for line in text.splitlines():
        m = pat.match(line)
        if m is not None:
            try:
                out[m.group(1)] = out.get(m.group(1), 0.0) + float(m.group(2))
            except ValueError:
                continue
    return out


def scrape_metrics(base: str, text: Optional[str] = None) -> Dict[str, float]:
    """{series name: value}, label sets summed per name."""
    if text is None:
        text = _fetch_metrics(base)
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = re.match(r"([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{[^}]*\})? (\S+)", line)
        if m is None:
            continue
        try:
            out[m.group(1)] = out.get(m.group(1), 0.0) + float(m.group(2))
        except ValueError:
            continue
    return out


class ShardedCluster:
    """Handles to a running sharded cluster (context for progress_cb).

    Since the fleet conductor landed (kubernetes_tpu/fleet/), this is a
    compatibility VIEW over a FleetConductor: the conductor owns the
    process tree (staged bring-up, drained pipes, supervision, RSS
    sampling, teardown); this class keeps the attribute surface the
    chaos tests and bench drivers always had."""

    def __init__(self, conductor):
        self.conductor = conductor
        self.killed: List[int] = []

    # -- conductor-derived handles -----------------------------------------

    @property
    def base(self) -> str:
        return self.conductor.base

    @property
    def api_proc(self):
        leaders = self.conductor.members_of("apiserver")
        return leaders[0].proc if leaders else None

    @property
    def shard_procs(self) -> List:
        return [m.proc for m in self.conductor.members_of("shard")]

    @property
    def shard_urls(self) -> List[str]:
        return list(self.conductor.shard_urls)

    @property
    def follower_procs(self) -> List:
        return [m.proc for m in self.conductor.members_of("follower")]

    @property
    def follower_urls(self) -> List[str]:
        return list(self.conductor.follower_urls)

    @property
    def hollow_proc(self):
        hollows = self.conductor.members_of("hollow")
        return hollows[0].proc if hollows else None

    @property
    def log_tails(self) -> List:
        return [m.tail for m in self.conductor.members if m.tail is not None]

    @property
    def rss_peaks(self) -> Dict[str, object]:
        return self.conductor.rss_peaks()

    def sample_rss(self) -> Dict[str, object]:
        """Fold the current per-process VmRSS into the peaks (the
        conductor's supervisor also samples on its own cadence)."""
        return self.conductor.rss_peaks()

    def stop_hollow(self) -> Optional[dict]:
        """SIGTERM the hollow members and merge their final stats lines
        (`{"hollow_stats": ...}`) from the drained tails."""
        return self.conductor.stop_hollow()

    def kill(self, index: int) -> None:
        """SIGKILL one shard scheduler process — no goodbye, no flush.
        The conductor's shard policy is `adopt`: the kill is LEDGERED but
        never respawned — the dead range drains through lease adoption."""
        import signal as _signal
        member = self.conductor.members_of("shard")[index]
        if member.alive():
            member.proc.send_signal(_signal.SIGKILL)
            member.proc.wait(timeout=30)
        self.killed.append(index)

    def alive_shard_urls(self) -> List[str]:
        return [u for i, u in enumerate(self.shard_urls)
                if i not in self.killed]

    def stop(self) -> None:
        self.conductor.stop()


def start_sharded_cluster(n_shards: int, lease_duration: float = 15.0,
                          data_dir: str = "",
                          flightrec_dir: str = "",
                          startup_timeout: float = 180.0,
                          replicas: int = 0,
                          repl_lease: float = 2.0,
                          fair_tenants: bool = False,
                          apf_workload: str = "",
                          spec=None) -> ShardedCluster:
    """Bring up the apiserver + N shard scheduler processes through the
    fleet conductor (kubernetes_tpu/fleet/): staged readiness barriers
    (leader → followers tailing → shards leased), every child's stdout
    drained, per-role supervision. ``flightrec_dir`` installs the flight
    recorder in every process (TPU_SCHED_FLIGHTREC_DIR): periodic + exit
    dumps land there, so even a SIGKILLed member leaves a recent forensic
    artifact.

    ``replicas`` > 0 builds the REPLICATED control plane
    (kubernetes_tpu/replication/): that many follower apiservers tail the
    leader's WAL, and each shard reads (list/watch/RESUME) from follower
    ``i % replicas`` — with the siblings + leader as reflector fallbacks —
    while its writes redirect to the leader. One apiserver process stops
    being both the durability point and the availability ceiling for
    N shards x M watch streams.

    ``spec`` (a fleet.FleetSpec) overrides the argument-built spec
    entirely — the seam `python -m kubernetes_tpu.fleet` drives."""
    from ..fleet import FleetConductor, FleetSpec

    if spec is None:
        spec = FleetSpec(shards=n_shards, shard_lease_s=lease_duration,
                         data_dir=data_dir, flightrec_dir=flightrec_dir,
                         startup_timeout_s=startup_timeout,
                         replicas=replicas, repl_lease_s=repl_lease,
                         fair_tenants=fair_tenants,
                         apf_workload=apf_workload)
    return ShardedCluster(FleetConductor(spec).start())


def start_hollow_plane(base: str, profile, cwd: str, env: dict,
                       timeout: float = 900.0):
    """Spawn the hollow-node plane process (`python -m
    kubernetes_tpu.hollow`) against `base` and block until its fleet is
    registered. Returns (proc, registered_count)."""
    import tempfile

    from ..testing.faults import spawn_ready

    prof_dict = (profile.to_dict() if hasattr(profile, "to_dict")
                 else dict(profile))
    fd, path = tempfile.mkstemp(prefix="hollow-profile-", suffix=".json")
    try:
        with os.fdopen(fd, "w") as fh:
            json.dump(prof_dict, fh)
        cmd = [sys.executable, "-m", "kubernetes_tpu.hollow",
               "--api-url", base, "--profile", path]
        proc, m = spawn_ready(cmd, r"registered (\d+) nodes", cwd=cwd,
                              env=env, timeout=timeout)
    finally:
        # The child reads the profile before printing its ready line —
        # once spawn_ready returns (or fails), the file is garbage.
        try:
            os.unlink(path)
        except OSError:
            pass
    return proc, int(m.group(1))


def start_controller(base: str, cwd: str, env: dict,
                     fallbacks=(), grace: float = 4.0,
                     noexec_after: float = 2.0, tick: float = 0.5,
                     primary_qps: float = 2.0, secondary_qps: float = 0.1,
                     unhealthy_threshold: float = 0.55,
                     timeout: float = 120.0):
    """Spawn the node-lifecycle controller process (`python -m
    kubernetes_tpu.controllers`) against `base` and block until its ready
    line. Returns (proc, metrics_url) — `metrics_url` serves the
    `node_lifecycle_*` series the chaos acceptance scrapes."""
    from ..testing.faults import spawn_ready

    cmd = [sys.executable, "-m", "kubernetes_tpu.controllers",
           "--api-url", base,
           "--grace", str(grace), "--noexec-after", str(noexec_after),
           "--tick", str(tick), "--primary-qps", str(primary_qps),
           "--secondary-qps", str(secondary_qps),
           "--unhealthy-threshold", str(unhealthy_threshold)]
    for url in fallbacks:
        cmd += ["--fallback", url]
    proc, m = spawn_ready(cmd, r"metrics on (127\.0\.0\.1:\d+)", cwd=cwd,
                          env=env, timeout=timeout)
    return proc, f"http://{m.group(1)}"


def start_workload_manager(base: str, cwd: str, env: dict,
                           identity: str = "workload-manager-0",
                           fallbacks=(), lease_ttl: float = 2.0,
                           tick: float = 0.25, autoscale=None, trace=None,
                           timeout: float = 120.0):
    """Spawn one workload controller-manager process (`python -m
    kubernetes_tpu.controllers --mode workload`) against `base` and block
    until its ready line. Spawn TWO with distinct identities for the HA
    pair — they race the shared lease, one ACTIVE, one STANDBY.
    `autoscale` is an optional dict of ClusterAutoscaler bounds
    (min/max/wave/pending_age/cooldown); `trace` an optional dict of
    WorkloadProfile marginals (deployments/gangs/rate/lifetime/seed).
    Returns (proc, metrics_url)."""
    from ..testing.faults import spawn_ready

    cmd = [sys.executable, "-m", "kubernetes_tpu.controllers",
           "--mode", "workload", "--api-url", base,
           "--identity", identity, "--lease-ttl", str(lease_ttl),
           "--tick", str(tick)]
    for url in fallbacks:
        cmd += ["--fallback", url]
    if autoscale is not None:
        cmd += ["--autoscale",
                "--min-nodes", str(autoscale.get("min", 0)),
                "--max-nodes", str(autoscale.get("max", 100)),
                "--scale-wave", str(autoscale.get("wave", 2)),
                "--pending-age", str(autoscale.get("pending_age", 2.0)),
                "--scale-cooldown", str(autoscale.get("cooldown", 5.0))]
    if trace is not None:
        cmd += ["--trace-deployments", str(trace.get("deployments", 0)),
                "--trace-gangs", str(trace.get("gangs", 0)),
                "--trace-rate", str(trace.get("rate", 2.0)),
                "--trace-lifetime", str(trace.get("lifetime", 0.0)),
                "--trace-seed", str(trace.get("seed", 0))]
    proc, m = spawn_ready(cmd, r"metrics on (127\.0\.0\.1:\d+)", cwd=cwd,
                          env=env, timeout=timeout)
    return proc, f"http://{m.group(1)}"


def start_descheduler(base: str, cwd: str, env: dict,
                      identity: str = "descheduler-0",
                      fallbacks=(), lease_ttl: float = 2.0,
                      tick: float = 0.25, hysteresis: int = 5,
                      margin: float = 0.10,
                      max_moves: int = 64, primary_qps: float = 20.0,
                      secondary_qps: float = 0.1, device: bool = False,
                      timeout: float = 120.0):
    """Spawn one descheduler process (`python -m kubernetes_tpu.controllers
    --mode deschedule`) against `base` and block until its ready line.
    Spawn TWO with distinct identities for the HA pair — they race the
    `descheduler` lease, one ACTIVE, one STANDBY; the standby re-derives
    the ACTIVE's `uid@node` intents after a kill9 (docs/DESCHEDULE.md).
    Returns (proc, metrics_url) — `metrics_url` serves the
    `descheduler_*` series."""
    from ..testing.faults import spawn_ready

    cmd = [sys.executable, "-m", "kubernetes_tpu.controllers",
           "--mode", "deschedule", "--api-url", base,
           "--identity", identity, "--lease-ttl", str(lease_ttl),
           "--tick", str(tick), "--hysteresis", str(hysteresis),
           "--margin", str(margin), "--max-moves", str(max_moves),
           "--primary-qps", str(primary_qps),
           "--secondary-qps", str(secondary_qps)]
    if device:
        cmd += ["--deschedule-device"]
    for url in fallbacks:
        cmd += ["--fallback", url]
    proc, m = spawn_ready(cmd, r"metrics on (127\.0\.0\.1:\d+)", cwd=cwd,
                          env=env, timeout=timeout)
    return proc, f"http://{m.group(1)}"


def stop_controller(proc, tail=None):
    """SIGTERM the controller and collect its final stats line
    (`{"controller_stats": ...}`) from a drained tail, if one was kept."""
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except Exception:  # noqa: BLE001
            proc.kill()
    if tail is None:
        return None
    time.sleep(0.1)  # let the drain thread swallow the stats line
    for line in reversed(list(tail)):
        if "controller_stats" in line:
            try:
                return json.loads(line)["controller_stats"]
            except (ValueError, KeyError):
                return None
    return None


def run_sharded_cluster(
    n_shards: int,
    n_nodes: int,
    n_pods: int,
    *,
    lease_duration: float = 15.0,
    warm_pods: int = 256,
    zones: int = 50,
    node_capacity: Optional[dict] = None,
    pod_request: Optional[dict] = None,
    creator_threads: int = 8,
    timeout: float = 900.0,
    progress_cb: Optional[Callable[[int, ShardedCluster], None]] = None,
    flightrec_dir: str = "",
    replicas: int = 0,
    repl_lease: float = 2.0,
    hollow=None,
    hollow_procs: int = 1,
    mesh_devices: int = 0,
    node_lifecycle=None,
    flood=None,
    workload=None,
    deschedule=None,
    settle_s: float = 0.0,
    spec=None,
) -> dict:
    """The sharded SchedulingBasic shape end to end: create `n_nodes`,
    warm the shards with `warm_pods` (XLA compilation + first sessions land
    OUTSIDE the measured window, as every other bench here does), then
    measure wall-clock from the first measured-pod create until every
    measured pod is bound. `progress_cb(bound_count, cluster)` fires on
    every poll — chaos tests churn nodes / SIGKILL shards from it.

    With ``hollow`` set (a kubernetes_tpu/hollow profile dict or
    HollowProfile), the `n_nodes` fleet is IMPERSONATED by a hollow-node
    plane process — registration, heartbeats, capacity drift, and
    cordon/delete/re-register churn all run against the leader for the
    whole measured window — instead of being bulk-created inert.

    With ``flood`` set (``{"threads": T, "namespace": ns, "cpu": req}``),
    an adversarial-tenant flood hammers single-pod creates in its own
    namespace for the whole measured window — flood pods request an
    unsatisfiable CPU so they never consume the measured capacity; the
    result carries ``flood`` stats (posted / shed-at-429 / errors) next
    to the apiserver's flowcontrol counters (docs/RESILIENCE.md
    § overload & fairness), and every shard runs per-tenant fair dequeue.

    With ``workload`` set (``{"managers": 2, "lease_ttl": s, "tick": s,
    "autoscale": {...}, "trace": {...}}``), that many workload
    controller-manager processes run for the whole window as an HA pair
    racing the shared lease — ReplicaSet/Deployment/gang reconcile,
    optional cluster autoscaler and Borg-style trace feed — and the
    result carries each process's final stats (docs/RESILIENCE.md
    § workload controllers).

    With ``deschedule`` set (``{"managers": 2, "lease_ttl": s, "tick": s,
    "hysteresis": n, "margin": f, "max_moves": n}``), that many
    descheduler processes
    run as an HA pair racing their own lease — drift detection, what-if
    scored rebalance moves through the eviction subresource
    (docs/DESCHEDULE.md) — and the result carries each process's final
    stats plus the apiserver's eviction counters (the ``api`` filter
    includes ``eviction`` series). ``settle_s`` holds the cluster up for
    that many extra seconds AFTER the last measured pod binds — the
    rebalance window — still firing ``progress_cb`` on every poll so
    callers can assert invariants (e.g. PDB cleanliness) mid-rebalance.

    Returns the one-line-JSON-able result dict: pods/s, per-shard metric
    scrapes, apiserver conflict counters, peak per-process RSS, and a
    bound-exactly-once check (the store can't hold duplicates, so
    'duplicates' asserts bindings == bound pods)."""
    import threading as _threading
    from urllib.error import HTTPError

    from ..core.apiserver import fetch_paged, node_to_wire, pod_to_wire
    from ..testing.wrappers import make_node, make_pod

    cap = node_capacity or {"cpu": 32, "memory": "256Gi", "pods": 110}
    req = pod_request or {"cpu": "100m", "memory": "128Mi"}
    if spec is None:
        from ..fleet import FleetSpec
        # One declarative spec for the whole process tree — the conductor
        # owns bring-up order, readiness barriers, drained pipes, and
        # per-role supervision (docs/SCALE.md § fleet conductor).
        hollow_dict = None
        if hollow is not None:
            from ..hollow import HollowProfile
            prof = (hollow if isinstance(hollow, HollowProfile)
                    else HollowProfile.from_dict(dict(hollow)))
            prof.count = n_nodes
            if not prof.zones:
                prof.zones = zones
            hollow_dict = prof.to_dict()
        spec = FleetSpec(
            shards=n_shards, shard_lease_s=lease_duration,
            mesh_devices=mesh_devices,
            flightrec_dir=flightrec_dir,
            replicas=replicas, repl_lease_s=repl_lease,
            hollow=hollow_dict, hollow_procs=hollow_procs,
            node_lifecycle=node_lifecycle,
            # HA workload controller-manager pair (or singleton): both
            # race the shared PUT-CAS lease; drained tails keep their
            # SIGTERM stats lines collectable at teardown.
            workload=workload,
            deschedule=deschedule,
            fair_tenants=flood is not None,
            # A tightened workload lane makes shedding demonstrable at
            # test-box scale (stock lanes mostly ADMIT a paced flood — APF
            # bounds concurrency, not rate) while leaving enough seats for
            # the measured tenant's create/bind traffic; override via
            # flood["apf_workload"].
            apf_workload=(flood or {}).get("apf_workload", "4,8,4,2,0.5")
            if flood is not None else "",
            startup_timeout_s=max(timeout, 300.0))
    else:
        hollow = spec.hollow if spec.hollow is not None else hollow
        workload = spec.workload
        deschedule = spec.deschedule
        n_shards = spec.shards
        replicas = spec.replicas
        flightrec_dir = spec.flightrec_dir
        if hollow is not None:
            n_nodes = int(spec.hollow["count"])
    cluster = start_sharded_cluster(n_shards, spec=spec)
    base = cluster.base
    try:

        def post_many(path: str, wires: List[dict], chunk: int = 200) -> None:
            """Bulk creates (JSON-array POST): one HTTP turnaround per
            chunk instead of per object. Chunks stay modest so each bulk
            request's write-lock hold (~0.3ms/object) never stalls the
            bind plane for more than ~60ms. The creator is a client on
            the 429 surface like any other: sheds replay through
            core/backoff.py's Retry-After-honoring retry_call — the
            well-behaved tenant backs off and lands, never errors out."""
            from ..core.backoff import RetryConfig, retry_call

            cfg = RetryConfig(initial_backoff=0.05, max_backoff=1.0,
                              max_attempts=30, seed=11, retry_after_cap=2.0)
            parts = [wires[i:i + chunk] for i in range(0, len(wires), chunk)]
            with ThreadPoolExecutor(max_workers=creator_threads) as ex:
                list(ex.map(
                    lambda c: retry_call(
                        lambda c=c: _call(base, "POST", path, c,
                                          timeout=120), cfg),
                    parts))

        # Hollow fleets were registered during the conductor's bring-up
        # (its hollow stage barrier: every member acknowledged its exact
        # sub-range); inert fleets are bulk-created here.
        if hollow is None:
            nodes = []
            for i in range(n_nodes):
                b = make_node().name(f"node-{i}").capacity(dict(cap))
                if zones:
                    b = b.zone(f"zone-{i % zones}")
                nodes.append(node_to_wire(b.obj()))
            post_many("/api/v1/nodes", nodes)

        proto = make_pod().name("proto").req(dict(req)).labels(
            {"app": "sharded"}).obj()

        def pod_wires(prefix: str, n: int) -> List[dict]:
            return [pod_to_wire(proto.clone_from_template(f"{prefix}-{i}"))
                    for i in range(n)]

        # Follower-served reads (watch-cache read plane): progress polls go
        # to the FOLLOWER replicas when the plane has them — the leader's
        # cycles belong to the write plane. Each replica's watch cache
        # serves the summary under its own lock in the shared rv space;
        # `read_counts` proves where the reads actually landed.
        read_counts = {"leader": 0, "follower": 0}
        poll_bases = cluster.follower_urls or [base]
        poll_state = {"i": 0}

        def poll_summary() -> dict:
            for _ in range(len(poll_bases) + 1):
                url = poll_bases[poll_state["i"] % len(poll_bases)]
                poll_state["i"] += 1
                try:
                    s = _call(url, "GET", "/api/v1/pods?summary=true",
                              timeout=60)
                    read_counts["follower" if url != base else "leader"] += 1
                    return s
                except Exception:  # noqa: BLE001 - replica down: try next
                    continue
            # every follower unreachable: the leader still answers
            s = _call(base, "GET", "/api/v1/pods?summary=true", timeout=60)
            read_counts["leader"] += 1
            return s

        def wait_bound(target: int, deadline: float,
                       cb: Optional[Callable] = None) -> int:
            bound = 0
            while time.monotonic() < deadline:
                # summary=true: the apiserver counts instead of encoding the
                # full pod list — at 10k pods a full-list poll costs the
                # control plane more CPU than the binds themselves, CPU the
                # shard schedulers need on a small box.
                bound = poll_summary()["bound"]
                # Peak-RSS sampling rides the existing poll cadence: the
                # bounded-memory claim of the paged read plane is a
                # sampled number in every detail line. The bound count
                # feeds the conductor's throughput samples too.
                cluster.sample_rss()
                cluster.conductor.note_bound(bound)
                if cb is not None:
                    cb(bound)
                if bound >= target:
                    return bound
                time.sleep(0.5)
            return bound

        t_start = time.monotonic()
        if warm_pods:
            post_many("/api/v1/pods", pod_wires("warm", warm_pods))
            got = wait_bound(warm_pods, t_start + timeout / 2)
            if got < warm_pods:
                raise TimeoutError(
                    f"warm phase stalled: {got}/{warm_pods} bound")

        # Adversarial-tenant flood (overload plane acceptance): T threads
        # hammer single-pod creates in the flood namespace for the whole
        # measured window. Flood pods request an unsatisfiable CPU, so
        # they stress the write plane + scheduler queues without consuming
        # the capacity the measured pods bind into. Each worker keeps its
        # OWN counters (no racy shared increments); stats sum at stop.
        flood_stop = _threading.Event()
        flood_threads: List[_threading.Thread] = []
        flood_counts: List[dict] = []
        if flood is not None:
            flood_ns = flood.get("namespace", "flood-tenant")
            flood_proto = make_pod().name("proto").namespace(flood_ns).req(
                {"cpu": str(flood.get("cpu", 4096)),
                 "memory": "1Gi"}).obj()

            # Pacing: a shed worker backs off briefly (even an adversary
            # pays a network RTT, and an unpaced spin would measure the
            # harness box's CPU, not the plane's shedding). Each accepted
            # pod is deleted right back — the flood is a create/delete
            # churn hammer (TWO admissions per iteration), so it stresses
            # the write plane and the watch fanout at full rate without
            # accumulating an unbounded unschedulable pool in every
            # shard (that accumulation measures the harness box's memory,
            # not the plane's fairness).
            shed_pause = float(flood.get("shed_pause_s", 0.25))
            think = float(flood.get("think_s", 0.05))

            def flood_worker(widx: int) -> None:
                # "shed" counts CREATE 429s only — the FloodSheds floor
                # asserts the create path was shed, not the cleanup. A
                # shed delete-back retries (bounded) so accepted flood
                # pods don't leak into every shard's unschedulable pool
                # for the measured window.
                stats = {"posted": 0, "shed": 0, "errors": 0}
                flood_counts.append(stats)
                seq = 0
                while not flood_stop.is_set():
                    seq += 1
                    pod = flood_proto.clone_from_template(
                        f"flood-{widx}-{seq}")
                    try:
                        _call(base, "POST", "/api/v1/pods",
                              pod_to_wire(pod), timeout=30)
                        stats["posted"] += 1
                    except HTTPError as e:
                        if e.code == 429:
                            stats["shed"] += 1
                            flood_stop.wait(shed_pause)
                        else:
                            stats["errors"] += 1
                        continue
                    except Exception:  # noqa: BLE001 - transport noise
                        stats["errors"] += 1
                        continue
                    for _ in range(4):
                        try:
                            _call(base, "DELETE",
                                  f"/api/v1/pods/{pod.uid}", timeout=30)
                            break
                        except HTTPError as e:
                            if e.code != 429:
                                stats["errors"] += 1
                                break
                            flood_stop.wait(shed_pause)
                        except Exception:  # noqa: BLE001 - transport noise
                            stats["errors"] += 1
                            break
                    flood_stop.wait(think)

            for widx in range(int(flood.get("threads", 48))):
                t = _threading.Thread(target=flood_worker, args=(widx,),
                                      name=f"flood-{widx}", daemon=True)
                t.start()
                flood_threads.append(t)

        t0 = time.perf_counter()
        wires = pod_wires("pod", n_pods)
        t_wires = time.perf_counter()
        post_many("/api/v1/pods", wires)
        t_created = time.perf_counter()
        total = warm_pods + n_pods
        got = wait_bound(
            total, time.monotonic() + timeout,
            cb=(lambda b: progress_cb(b - warm_pods, cluster))
            if progress_cb is not None else None)
        elapsed = time.perf_counter() - t0
        if settle_s > 0:
            # Rebalance window: binds are done; keep the fleet up so the
            # descheduler can repair drift, polling progress_cb so chaos /
            # invariant callbacks (PDB cleanliness, exactly-once ledgers)
            # keep firing through the window.
            settle_deadline = time.monotonic() + settle_s
            while time.monotonic() < settle_deadline:
                if progress_cb is not None:
                    progress_cb(got - warm_pods, cluster)
                time.sleep(0.5)
        flood_result = None
        if flood is not None:
            flood_stop.set()
            for t in flood_threads:
                t.join(timeout=30)
            flood_result = {
                "namespace": flood.get("namespace", "flood-tenant"),
                "threads": len(flood_threads),
                "posted": sum(s["posted"] for s in flood_counts),
                "shed": sum(s["shed"] for s in flood_counts),
                "errors": sum(s["errors"] for s in flood_counts),
            }

        # Exactly-once oracle read, PAGED (`?limit=&continue=`): even the
        # harness's own final sweep never asks for a full-cluster
        # single-response body — apiserver_list_unpaged_total stays 0.
        pods = fetch_paged(base, "pods", limit=2000)
        bound = {p["uid"]: p["nodeName"] for p in pods if p["nodeName"]}
        hollow_stats = cluster.stop_hollow() if hollow is not None else None
        workload_stats = (cluster.conductor.stop_workload()
                          if workload is not None else None)
        deschedule_stats = (cluster.conductor.stop_deschedulers()
                            if deschedule is not None else None)
        shard_metrics = []
        e2e_hists = []
        watch_decode = []
        fallbacks: Dict[str, int] = {}
        for url in cluster.alive_shard_urls():
            try:
                text = _fetch_metrics(url)  # one GET, parsed three ways
                shard_metrics.append(scrape_metrics(url, text=text))
                for reason, n in scrape_labeled(
                        url, "scheduler_device_path_fallback_total",
                        "reason", text=text).items():
                    fallbacks[reason] = fallbacks.get(reason, 0) + int(n)
                e2e_hists.append(scrape_histogram(
                    url, "scheduler_e2e_scheduling_duration_seconds",
                    text=text))
                # Per-shard decoded events/bytes by wire form — the
                # measurable 1/N of the shard-filtered watch plane — and
                # by codec (core/wire.py): which plane this shard's
                # decode actually ran on, and what it cost in bytes.
                watch_decode.append({
                    "events": scrape_labeled(
                        url, "scheduler_watch_decoded_events", "form",
                        text=text),
                    "bytes": scrape_labeled(
                        url, "scheduler_watch_decoded_bytes", "form",
                        text=text),
                    "events_by_codec": scrape_labeled(
                        url, "scheduler_watch_decoded_events", "codec",
                        text=text),
                    "bytes_by_codec": scrape_labeled(
                        url, "scheduler_watch_decoded_bytes", "codec",
                        text=text)})
            except Exception:  # noqa: BLE001 - a killed shard has no /metrics
                shard_metrics.append({})
                watch_decode.append({})
        api_text = _fetch_metrics(base)
        api_metrics = scrape_metrics(base, text=api_text)
        # Wire-plane summary (apiserver_wire_bytes_total{codec,surface}):
        # server-served bytes by codec and by surface — aggregated over
        # the LEADER and every follower replica (the shards' watch/list
        # reads land on followers when the plane has them) — plus the
        # per-shard decoded-bytes totals by codec: the one detail object
        # that proves WHICH plane (binary vs JSON) ran end-to-end.
        wire_by_codec: Dict[str, float] = {}
        wire_by_surface: Dict[str, float] = {}
        enc_us_by_surface: Dict[str, float] = {}
        deltas = {"minted": 0.0, "applied": 0.0}
        for url in [base] + list(cluster.follower_urls):
            try:
                text = api_text if url == base else _fetch_metrics(url)
                for k, v in scrape_labeled(
                        url, "apiserver_wire_bytes_total", "codec",
                        text=text).items():
                    wire_by_codec[k] = wire_by_codec.get(k, 0.0) + v
                for k, v in scrape_labeled(
                        url, "apiserver_wire_bytes_total", "surface",
                        text=text).items():
                    wire_by_surface[k] = wire_by_surface.get(k, 0.0) + v
                # Encode CPU per surface (PR 18): µs the server spent
                # building frames — divided by events it attributes any
                # shard-scaling gap to encode cost.
                for k, v in scrape_labeled(
                        url, "apiserver_wire_encode_micros_total",
                        "surface", text=text).items():
                    enc_us_by_surface[k] = enc_us_by_surface.get(k, 0.0) + v
                m = scrape_metrics(url, text=text)
                deltas["minted"] += m.get(
                    "apiserver_wire_deltas_minted_total", 0.0)
                deltas["applied"] += m.get(
                    "apiserver_wire_deltas_applied_total", 0.0)
            except Exception:  # noqa: BLE001 - replica down mid-teardown
                continue
        wire_summary = {
            "server_bytes_by_codec": wire_by_codec,
            "server_bytes_by_surface": wire_by_surface,
            "server_encode_us_by_surface": {
                k: round(v, 1) for k, v in enc_us_by_surface.items()},
            "deltas": {k: int(v) for k, v in deltas.items()},
            "shard_decoded_bytes_by_codec": [
                wd.get("bytes_by_codec", {}) for wd in watch_decode],
        }
        # Follower-served /metrics/resources: one scrape off a follower
        # replica proves the per-pod resource read plane serves away from
        # the leader (the same watch-cache snapshot, shared rv space).
        resource_series = None
        try:
            req = urlrequest.Request(
                (cluster.follower_urls[0] if cluster.follower_urls else base)
                + "/metrics/resources")
            with urlrequest.urlopen(req, timeout=30) as resp:
                resource_series = sum(
                    1 for ln in resp.read().decode().splitlines()
                    if ln.startswith("kube_pod_resource_request{"))
        except Exception:  # noqa: BLE001 - replica down mid-teardown
            pass
        # Cross-shard e2e latency truth (queue admission -> bound): merged
        # cumulative buckets, the p50/p99 a sharded perf row reports.
        e2e = merge_histograms(e2e_hists)
        e2e_ms = None
        if e2e is not None and e2e["count"]:
            e2e_ms = {
                "p50": round(histogram_percentile(e2e, 0.50) * 1e3, 3),
                "p99": round(histogram_percentile(e2e, 0.99) * 1e3, 3),
                "count": int(e2e["count"]),
            }
        # Replication detail: per-replica role/lag (leader + followers) —
        # the detail of a sharded perf row with replicas.
        replication = None
        if cluster.follower_urls:
            replication = []
            for url in [base] + cluster.follower_urls:
                try:
                    rm = scrape_metrics(url)
                    replication.append({
                        "url": url,
                        "role": int(rm.get("apiserver_replication_role", 0)),
                        "lag": int(rm.get(
                            "apiserver_replication_lag_records", 0)),
                        "failovers": int(rm.get(
                            "apiserver_failover_total", 0)),
                        # reads THIS replica's watch cache served — the
                        # counter proving follower-served polls landed here
                        "cacheHits": int(rm.get(
                            "apiserver_watch_cache_hits_total", 0)),
                        # paged-plane truth per replica: the shards list
                        # from FOLLOWERS, so the zero-unpaged claim must
                        # hold on every replica, not just the leader
                        "listPages": int(rm.get(
                            "apiserver_list_pages_total", 0)),
                        "listUnpaged": int(rm.get(
                            "apiserver_list_unpaged_total", 0)),
                        # watch-plane health per replica: a relist means a
                        # watcher fell off the cache ring and re-LISTed —
                        # the 100k fusion row pins this to zero everywhere
                        "relistedWatches": int(rm.get(
                            "apiserver_relisted_watches_total", 0)),
                    })
                except Exception:  # noqa: BLE001 - replica down
                    replication.append({"url": url, "role": -1})
        return {
            "shards": n_shards,
            "replicas": replicas,
            # The conductor's consolidated line: stage timeline,
            # per-member supervision state (restarts are NEVER silent),
            # per-role RSS peaks, throughput window, artifact count.
            "fleet": cluster.conductor.detail(),
            "replication": replication,
            "nodes": n_nodes,
            "pods": n_pods,
            "bound": got - warm_pods,
            "all_bound": got >= total,
            "elapsed_s": round(elapsed, 2),
            # Phase split of the measured window: template/wire encode,
            # create POSTs, and the bind tail after the last create — tells
            # an arrival-limited run from a scheduler-limited one.
            "wire_encode_s": round(t_wires - t0, 2),
            "create_s": round(t_created - t_wires, 2),
            "drain_after_create_s": round(t0 + elapsed - t_created, 2),
            "pods_per_sec": round(n_pods / elapsed, 1) if elapsed > 0 else 0.0,
            "distinct_bound_pods": len(bound),
            "killed_shards": list(cluster.killed),
            "e2e_ms": e2e_ms,
            "flightrec_dir": flightrec_dir,
            # Peak per-process RSS (MiB), sampled every progress poll —
            # the bounded-memory claim as a number.
            "rss_mb": cluster.sample_rss(),
            "hollow": hollow_stats,
            # Workload controller-manager stats (HA pair): per-process
            # final stats lines — active/standby split, takeovers,
            # reconcile counters, autoscaler adds/removes.
            "workload": workload_stats,
            # Descheduler manager stats (HA pair): moves by strategy,
            # blocked-by-reason, what-if batch timings, final utilization
            # stddev — the drift-repair plane's exactly-once story pairs
            # with the "eviction" series in the api filter below.
            "deschedule": deschedule_stats,
            # Where the progress/summary reads landed (follower-served read
            # plane) + one follower /metrics/resources scrape's series count.
            "read_plane": dict(read_counts,
                               resource_series=resource_series),
            "watch_decode": watch_decode,
            "wire": wire_summary,
            # Overload plane (core/flowcontrol.py): flood-tenant stats +
            # the leader's per-priority-level admission counters ride the
            # bench detail line ("flowcontrol" matches the api filter).
            "flood": flood_result,
            "flowcontrol": {
                metric: scrape_labeled(
                    base, f"apiserver_flowcontrol_{metric}_total",
                    "priority_level", text=api_text)
                for metric in ("rejected", "dispatched", "queued")
            },
            "api": {k: v for k, v in api_metrics.items()
                    if "conflict" in k or "lease" in k
                    or "replication" in k or "failover" in k
                    or "watch" in k or "list" in k
                    or "snapshot" in k or "heartbeat" in k
                    or "flowcontrol" in k or "eviction" in k},
            # Device→host fallbacks by reason, summed over shards: a
            # measuring entry point fails the run when the breaker was
            # charged (perf/device.py breaker_charges).
            "device_path_fallback": fallbacks,
            "shard_metrics": [
                {k: v for k, v in sm.items()
                 if k.startswith(("scheduler_shard_",
                                  "scheduler_bind_conflict",
                                  "scheduler_hint_",
                                  "scheduler_eviction_requeues",
                                  "scheduler_queue_starvation"))}
                for sm in shard_metrics],
        }
    finally:
        cluster.stop()
