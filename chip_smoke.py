#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py            # needs a TPU; exits non-zero without one

Drives the Filter→Score path once through the entry points a user calls, at
the reference's own scheduler_perf cluster sizes (BASELINE.md; rows of
kubernetes_tpu/perf/configs/performance-config.yaml, scale 1.0):

- library stage — the surface `python -m kubernetes_tpu.perf` measures:
  three 5,000-node rows through perf.harness.run_workload with a
  default TPUScheduler (mesh="auto");
- server stage — the deployed shape: one `python -m
  kubernetes_tpu.core.apiserver` process, one `python -m kubernetes_tpu
  --api-url ... --platform tpu` process, 5,000 nodes and 2,000
  SchedulingBasic pods POSTed over HTTP, bound pods read back from the
  apiserver, device counters from the scheduler's /metrics.

A chip belongs to one process. This parent never imports JAX; it runs the
two stages as children one after the other, each the only process holding
the chip, with the plain reference — the pure-Python host
`core.Scheduler(deterministic_ties=True)` over the same opcode lists — in
JAX_PLATFORMS=cpu children beside them.

Per stage it checks, and exits non-zero on any miss: the backend is a TPU
(as `jax.devices()` reports it, never from the environment); every pod of
every row bound exactly once, no node over capacity; assignments identical
to the reference over the full 5,000-node width; the chip did the work
(device batches dispatched, no host-path pods, the device-path breaker
never charged, hint hits zero on the rows hints cannot serve); compile is
counted (cold seconds before the first bound pod of each row, backend
compile seconds, the cache directory and its entry counts).

The last stdout line is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

`--rehearsal` is the only form that runs without a chip: the same stages
and checks at 1/50 scale on whatever backend JAX has, labelled
`"rehearsal": true` with the platform JAX reports (tests/test_chip_smoke.py
drives it on the CPU).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(ROOT, "kubernetes_tpu", "perf", "configs",
                      "performance-config.yaml")
# (row, hints can serve its measured pods). The four plugins the north star
# puts on the device: NodeResourcesFit/BalancedAllocation/TaintToleration,
# PodTopologySpread (50 zones, maxSkew 1), InterPodAffinity (hostname anti).
ROWS = (("SchedulingBasic/5000Nodes_10000Pods", True),
        ("TopologySpreading/5000Nodes_5000Pods", False),
        ("SchedulingPodAntiAffinity/5000Nodes_2000Pods", False))
SERVER_ROW = ROWS[0][0]   # the server stage posts this row's nodes and the
SERVER_PODS = 2000        # first SERVER_PODS of its pods, in order
REHEARSAL_SCALE = 0.02
MIN_COMPARED = 1000       # per shape, at full scale
BUDGET_S = 1140.0         # the contract allows 1200 s, compile included
_T0 = time.monotonic()


def _left() -> float:
    return BUDGET_S - (time.monotonic() - _T0)


def _say(msg: str) -> None:
    print(f"[chip_smoke {time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


class Failed(Exception):
    """A phase of the smoke failed; the message says which and why."""


def _workloads(scale: float) -> dict:
    from kubernetes_tpu.perf.harness import load_config
    return {f"{w.testcase}/{w.name}": w for w in load_config(CONFIG, scale)}


def capacity_violations(pods, nodes) -> list:
    """Nodes whose bound pods exceed allocatable cpu/memory/pod count, over
    wire-format dicts (core/apiserver.py pod_to_wire / node_to_wire)."""
    used: dict = {}
    for p in pods:
        if p["nodeName"]:
            u = used.setdefault(p["nodeName"], [0, 0, 0])
            u[0] += p["requests"]["cpu"]
            u[1] += p["requests"]["memory"]
            u[2] += 1
    alloc = {n["name"]: n["allocatable"] for n in nodes}
    bad = []
    for name, (cpu, mem, cnt) in used.items():
        a = alloc.get(name)
        if a is None or cpu > a["cpu"] or mem > a["memory"] or cnt > a["pods"]:
            bad.append(name)
    return bad


# -- oracle stage (child, JAX_PLATFORMS=cpu) --------------------------------

def stage_oracle(row: str, scale: float, out: str) -> int:
    """The plain reference: the pure-Python host scheduler over the row's
    opcode list. Importing the perf harness's run_workload pulls in JAX
    (ops/kernel.py builds constants at import), which is why this child is
    pinned to the CPU by its environment — it runs beside the process that
    holds the chip."""
    from kubernetes_tpu.core import Scheduler
    from kubernetes_tpu.perf.harness import run_workload

    # CPU-bound for minutes beside the chip owner, whose XLA compiles run on
    # one core each: the reference yields on a shared host. (On the chip
    # this did not shorten the first compile — PERF.md, PR 21.)
    os.nice(10)
    wl = _workloads(scale)[row]
    sched = Scheduler(deterministic_ties=True)
    t0 = time.perf_counter()
    run_workload(wl, sched=sched)
    cs = sched.clientset
    with open(out, "w") as f:
        json.dump({"row": row, "wall_s": round(time.perf_counter() - t0, 1),
                   "assignments": {p.name: p.node_name
                                   for p in cs.pods.values()}}, f)
    return 0


# -- library stage (child, owns the chip) -----------------------------------

class _CompileMeter:
    """Compile accounting from JAX's own monitoring events, so a compile is
    counted wherever it happens (warm-up or mid-row)."""

    def __init__(self):
        import jax.monitoring as mon
        self.backend_compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_writes = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compile_s += secs
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1  # emitted when an entry is written

    def snapshot(self) -> dict:
        return {"backend_compile_s": round(self.backend_compile_s, 2),
                "compiles": self.compiles, "cache_hits": self.cache_hits,
                "cache_writes": self.cache_writes}


def _first_bound_after(sched, t0: float, stop: threading.Event, out: dict):
    """Seconds from row start to the first bound pod — node creation, plan
    build and the cold compile of the first kernel all land before it."""
    while not stop.wait(0.01):
        if sched.scheduled:
            out["first_bound_s"] = round(time.perf_counter() - t0, 2)
            return


def _cpu_child_beside_chip() -> dict:
    """What every harness spawn is (shard/harness.py _env): a
    JAX_PLATFORMS=cpu child of a process that holds the chip. It must come
    up on the CPU, promptly — not fail or hang on the parent's chip."""
    from kubernetes_tpu.shard.harness import _env
    t0 = time.perf_counter()
    try:
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.devices()[0].platform)"],
            env=_env(), capture_output=True, text=True, timeout=120)
        got = (out.stdout.split() or [f"exit code {out.returncode}"])[-1]
    except subprocess.TimeoutExpired:
        got = "hung"
    return {"platform": got, "seconds": round(time.perf_counter() - t0, 1)}


def stage_library(scale: float, rehearsal: bool, workdir: str) -> int:
    from kubernetes_tpu.perf.device import device_info, fallbacks_by_reason
    device = device_info()
    if device["platform"] != "tpu" and not rehearsal:
        print(f"chip_smoke: no TPU — jax.devices() reports "
              f"{device['platform']!r} ({device['kind']}, "
              f"{device['count']} device(s))", file=sys.stderr)
        return 3
    import jax
    from kubernetes_tpu.compile_cache import cache_dir, entry_count
    from kubernetes_tpu.core.apiserver import node_to_wire, pod_to_wire
    from kubernetes_tpu.models import TPUScheduler
    from kubernetes_tpu.perf.harness import run_workload

    meter = _CompileMeter()
    wls = _workloads(scale)
    report = {"device": device, "rows": {},
              "cpu_child_beside_chip": _cpu_child_beside_chip()}
    _say(f"library: on {device}; a JAX_PLATFORMS=cpu child beside it came "
         f"up as {report['cpu_child_beside_chip']}")
    for row, _hintable in ROWS:
        wl = wls[row]
        # A default scheduler, exactly what run_workload(wl) builds itself;
        # handed in only so its counters can be read afterwards.
        sched = TPUScheduler()
        before = meter.snapshot()
        entries0 = entry_count()
        timing: dict = {}
        stop = threading.Event()
        t0 = time.perf_counter()
        sampler = threading.Thread(
            target=_first_bound_after, args=(sched, t0, stop, timing),
            daemon=True)
        sampler.start()
        try:
            res = run_workload(wl, sched=sched)
        finally:
            stop.set()
            sampler.join(timeout=5)
        wall = time.perf_counter() - t0
        # one batch retired at the very end: the sampler never saw it
        timing.setdefault("first_bound_s", round(wall, 2))
        after = meter.snapshot()
        cs = sched.clientset
        pods = list(cs.pods.values())
        # Resident node state (arrays a later dispatch donated are gone).
        state = [a for a in sched.mirror._device or ()
                 if not a.is_deleted()]
        rec = {
            "nodes": len(cs.nodes), "pods_created": len(pods),
            "scheduled": sched.scheduled, "failed_attempts": sched.failures,
            "wall_s": round(wall, 2),
            "first_bound_s": timing.get("first_bound_s"),
            "compile": {k: round(after[k] - before[k], 2) for k in after},
            "cache_entries": [entries0, entry_count()],
            "window": res.detail.get("in_window", {}),
            "device_batches": sched.device_batches,
            "device_scheduled": sched.device_scheduled,
            "host_path_pods": sched.host_path_pods,
            "hint_hits": sched.hint_hits,
            "shard_map_dispatches": sched.shard_map_dispatches,
            "fallbacks": fallbacks_by_reason(sched),
            "breaker_state": sched.metrics.device_breaker_state.value(),
            "mesh": (dict(sched.mesh.shape) if sched.mesh is not None
                     else None),
            # Where the resident node state lives: shards per array and
            # the devices holding them (all of them under a mesh, not
            # everything on device 0).
            "state_shards": {
                "min_per_array": min((len(a.addressable_shards)
                                      for a in state), default=0),
                "devices": sorted({s.device.id for a in state
                                   for s in a.addressable_shards})},
            "capacity_violations": capacity_violations(
                map(pod_to_wire, pods), map(node_to_wire, cs.nodes.values())),
        }
        report["rows"][row] = rec
        with open(os.path.join(workdir, _slug(row) + ".device.json"),
                  "w") as f:
            json.dump({p.name: p.node_name for p in pods}, f)
        _say(f"library {row}: scheduled {rec['scheduled']}/"
             f"{rec['pods_created']} in {rec['wall_s']}s, first bound after "
             f"{rec['first_bound_s']}s, compile {rec['compile']}")
    report["cache"] = {"dir": cache_dir(),
                       "configured": jax.config.jax_compilation_cache_dir,
                       "entries": entry_count()}
    with open(os.path.join(workdir, "library.json"), "w") as f:
        json.dump(report, f)
    return 0


# -- parent -----------------------------------------------------------------

def _slug(row: str) -> str:
    return row.replace("/", "_")


def _child_env(cpu: bool) -> dict:
    """Environment of a stage child: no TPU_SCHED_* variable set, one
    compile cache for every process (compile_cache.py), and — for every
    child that must stay off the chip — the CPU by name."""
    from kubernetes_tpu.compile_cache import export
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("TPU_SCHED_")}
    env["PYTHONPATH"] = ROOT
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    return export(env)


class _Children:
    """Every process the smoke starts, so that every one is stopped."""

    def __init__(self):
        self.procs: list = []

    def spawn(self, cmd, env, log_path, share_stdout=False):
        """Output goes to `log_path`; with `share_stdout` the child's
        progress lines go straight to this process's stdout instead."""
        with open(log_path, "w") as log:
            proc = subprocess.Popen(
                cmd, cwd=ROOT, env=env, stderr=log,
                stdout=None if share_stdout else log)
        self.procs.append(proc)
        return proc

    def adopt(self, proc):
        self.procs.append(proc)
        return proc

    def stop_all(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.monotonic() + 10
        for p in self.procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=10)


def _tail(path: str, n: int = 30) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def _wait(proc, what: str, log_path: str) -> None:
    try:
        rc = proc.wait(timeout=max(1.0, _left()))
    except subprocess.TimeoutExpired:
        raise Failed(f"{what}: over the {BUDGET_S:.0f}s budget\n"
                     + _tail(log_path))
    if rc != 0:
        raise Failed(f"{what}: exit code {rc}\n" + _tail(log_path))


def stage_server(scale: float, rehearsal: bool, workdir: str,
                 children: _Children) -> dict:
    """The deployed shape, driven over HTTP by this JAX-free parent."""
    from urllib import request as urlrequest

    from kubernetes_tpu.compile_cache import entry_count
    from kubernetes_tpu.core.apiserver import (fetch_paged, node_to_wire,
                                               pod_to_wire)
    from kubernetes_tpu.perf.harness import (_make_node_from_template,
                                             _make_pod_from_template)
    from kubernetes_tpu.shard.harness import (_call, _fetch_metrics,
                                              scrape_labeled, scrape_metrics)
    from kubernetes_tpu.testing.faults import drain_pipe, spawn_ready

    wl = _workloads(scale)[SERVER_ROW]
    n_nodes = int(wl.params["nodes"])
    n_pods = max(1, int(SERVER_PODS * scale))
    node_tpl = wl.ops[0]["nodeTemplate"]
    pod_tpl = wl.default_pod_template
    ready = r"serving on 127\.0\.0\.1:(\d+)(.*)"
    entries0 = entry_count()

    api, m = spawn_ready(
        [sys.executable, "-m", "kubernetes_tpu.core.apiserver", "--port", "0"],
        ready, cwd=ROOT, env=_child_env(cpu=True), timeout=120)
    children.adopt(api)
    drain_pipe(api)
    base = f"http://127.0.0.1:{m.group(1)}"

    # A cold start reaches the chip in ~15 s; the kernels compile later, at
    # the first batch, inside the bind deadline below — not this one.
    t_spawn = time.monotonic()
    sched, m = spawn_ready(
        [sys.executable, "-m", "kubernetes_tpu", "--api-url", base,
         "--platform", "cpu" if rehearsal else "tpu", "--port", "0"],
        ready, cwd=ROOT, env=_child_env(cpu=rehearsal),
        timeout=min(300.0, max(1.0, _left())))
    children.adopt(sched)
    sched_tail = drain_pipe(sched)
    startup_s = round(time.monotonic() - t_spawn, 1)
    sched_url = f"http://127.0.0.1:{m.group(1)}"
    rl = re.search(r"backend=(\S+) device_kind='([^']*)' devices=(\d+)",
                   m.group(2))
    if rl is None:
        raise Failed(f"server: ready line names no backend: {m.group(0)!r}")
    device = {"platform": rl.group(1), "kind": rl.group(2),
              "count": int(rl.group(3))}
    _say(f"server: scheduler ready after {startup_s}s on {device}")

    def post_in_order(path: str, wires: list) -> None:
        for i in range(0, len(wires), 500):
            _call(base, "POST", path, wires[i:i + 500], timeout=120)

    def get_text(url: str) -> str:
        with urlrequest.urlopen(url, timeout=60) as resp:
            return resp.read().decode()

    def poll(what: str, fn, target: int) -> None:
        got = -1
        while _left() > 0:
            if sched.poll() is not None:
                raise Failed(f"server: scheduler exited rc={sched.returncode}"
                             f"\n{''.join(sched_tail)[-3000:]}")
            got = fn()
            if got >= target:
                return
            time.sleep(0.25)
        raise Failed(f"server: {what}: {got}/{target} inside the budget\n"
                     f"{''.join(sched_tail)[-3000:]}")

    post_in_order("/api/v1/nodes", [
        node_to_wire(_make_node_from_template(i, node_tpl))
        for i in range(n_nodes)])
    # Nodes and pods ride separate watch streams: post pods only once the
    # scheduler's cache holds every node, in creation order, so the run is
    # comparable to the reference pod for pod.
    poll("nodes in the scheduler cache",
         lambda: len(re.findall(r"^  node-\d+: ", get_text(
             sched_url + "/debug/cache"), re.M)), n_nodes)

    t_post = time.monotonic()
    post_in_order("/api/v1/pods", [
        pod_to_wire(_make_pod_from_template(f"pod-{i}", pod_tpl))
        for i in range(n_pods)])
    first: dict = {}

    def bound() -> int:
        n = _call(base, "GET", "/api/v1/pods?summary=true", timeout=60)["bound"]
        if n and not first:
            first["s"] = round(time.monotonic() - t_post, 2)
        return n

    poll("pods bound", bound, n_pods)
    wall = round(time.monotonic() - t_post, 2)

    pods = fetch_paged(base, "pods", limit=1000)
    nodes = fetch_paged(base, "nodes", limit=1000)
    text = _fetch_metrics(sched_url)
    totals = scrape_metrics(sched_url, text=text)

    def by(name: str, label: str) -> dict:
        return scrape_labeled(sched_url, name, label, text=text)

    with open(os.path.join(workdir, "server.device.json"), "w") as f:
        json.dump({p["name"]: p["nodeName"] for p in pods}, f)
    rec = {
        "device": device, "nodes": len(nodes), "pods_created": len(pods),
        "bound": sum(1 for p in pods if p["nodeName"]),
        "distinct_pods": len({p["name"] for p in pods}),
        "scheduler_startup_s": startup_s,
        "first_bound_s": first.get("s"), "wall_s": wall,
        "cache_entries": [entries0, entry_count()],
        # every bound pod feeds the e2e histogram exactly once
        "scheduled": totals.get(
            "scheduler_e2e_scheduling_duration_seconds_count", 0.0),
        "device_batches": by("scheduler_batch_attempts_total",
                             "result").get("dispatched", 0.0),
        "device_scheduled": totals.get(
            "scheduler_device_scheduled_pods_total", 0.0),
        "host_path_pods": totals.get("scheduler_host_path_pods_total", 0.0),
        "hint_hits": totals.get("scheduler_hint_cache_hits_total", 0.0),
        "fallbacks": by("scheduler_device_path_fallback_total", "reason"),
        "breaker_state": totals.get("scheduler_device_breaker_state", 0.0),
        "plan_rebuilds_by_plane": by("scheduler_plan_rebuild_total", "plane"),
        "capacity_violations": capacity_violations(pods, nodes),
    }
    _say(f"server: bound {rec['bound']}/{rec['pods_created']} in {wall}s, "
         f"first bound after {rec['first_bound_s']}s, "
         f"{rec['device_batches']:.0f} device batches")
    return rec


def _check(problems: list, ok: bool, msg: str) -> None:
    if not ok:
        problems.append(msg)


def _check_counters(problems: list, name: str, rec: dict) -> None:
    """What every stage must show: all pods bound once within capacity, and
    the chip — not the host fallback — did the scheduling."""
    _check(problems, rec["scheduled"] == rec["pods_created"],
           f"{name}: scheduled {rec['scheduled']} != created "
           f"{rec['pods_created']}")
    _check(problems, not rec["capacity_violations"],
           f"{name}: nodes over capacity: {rec['capacity_violations'][:5]}")
    _check(problems, rec["device_batches"] > 0,
           f"{name}: no device batch was dispatched")
    _check(problems, rec["host_path_pods"] == 0,
           f"{name}: {rec['host_path_pods']} pods took the host path")
    _check(problems, not any(rec["fallbacks"].values()),
           f"{name}: device-path fallbacks {rec['fallbacks']}")
    _check(problems, rec["breaker_state"] == 0,
           f"{name}: device breaker open")


def _compare(problems: list, name: str, got: dict, want: dict,
             n: int, min_compared: int) -> int:
    """Assignments identical to the reference over its first `n` pods."""
    names = [f"pod-{i}" for i in range(n)]
    diffs = [(k, want.get(k), got.get(k)) for k in names
             if not want.get(k) or want.get(k) != got.get(k)]
    _check(problems, not diffs,
           f"{name}: {len(diffs)}/{n} assignments differ from the host "
           f"oracle, e.g. {diffs[:3]}")
    _check(problems, n >= min_compared,
           f"{name}: only {n} pods compared (< {min_compared})")
    return n


def parent(rehearsal: bool, scale: float) -> int:
    if not os.path.isfile(CONFIG):
        print("chip_smoke: the kubernetes_tpu package is not beside this "
              "script — nothing to run", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    from kubernetes_tpu.compile_cache import cache_dir, entry_count

    min_compared = 1 if rehearsal else MIN_COMPARED
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    children = _Children()
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    me = [sys.executable, os.path.abspath(__file__)]
    flags = ["--scale", str(scale)] + (["--rehearsal"] if rehearsal else [])
    problems: list = []
    try:
        cache = {"dir": cache_dir(), "entries_before": entry_count()}
        _say(f"compile cache {cache['dir']} "
             f"({cache['entries_before']} entries)")
        lib_log = os.path.join(workdir, "library.log")
        lib = children.spawn(
            me + ["--stage", "library", "--workdir", workdir] + flags,
            _child_env(cpu=rehearsal), lib_log, share_stdout=True)
        oracles = {}
        for row, _h in ROWS:
            log = os.path.join(workdir, _slug(row) + ".oracle.log")
            oracles[row] = (children.spawn(
                me + ["--stage", "oracle", "--row", row, "--out",
                      os.path.join(workdir, _slug(row) + ".oracle.json")]
                + flags, _child_env(cpu=True), log), log)

        _wait(lib, "library stage", lib_log)
        with open(os.path.join(workdir, "library.json")) as f:
            library = json.load(f)
        cache["entries_after_library"] = entry_count()

        server = stage_server(scale, rehearsal, workdir, children)
        cache["entries_after_server"] = entry_count()

        want = {}
        for row, (proc, log) in oracles.items():
            _wait(proc, f"oracle {row}", log)
            with open(os.path.join(workdir,
                                   _slug(row) + ".oracle.json")) as f:
                o = json.load(f)
            want[row] = o["assignments"]
            _say(f"oracle {row}: {len(want[row])} pods in {o['wall_s']}s")

        device = library["device"]
        multi = device["count"] > 1
        _check(problems, rehearsal or device["platform"] == "tpu",
               f"library: backend is {device['platform']}, not tpu")
        _check(problems, server["device"] == device,
               f"server: ready line reports {server['device']}, the library "
               f"stage {device}")
        _check(problems,
               library["cpu_child_beside_chip"]["platform"] == "cpu",
               f"library: a JAX_PLATFORMS=cpu child of the chip-holding "
               f"process: {library['cpu_child_beside_chip']}")
        _check(problems, library["cache"]["configured"] == cache["dir"],
               f"library: cache configured at "
               f"{library['cache']['configured']}, expected {cache['dir']}")
        compared = {}
        for row, hintable in ROWS:
            rec = library["rows"][row]
            _check_counters(problems, row, rec)
            with open(os.path.join(workdir, _slug(row) + ".device.json")) as f:
                got = json.load(f)
            compared[row] = _compare(problems, row, got, want[row],
                                     rec["pods_created"], min_compared)
            if not hintable:
                _check(problems, rec["window"].get("device_batches", 0) > 0,
                       f"{row}: no device batch inside the measured window")
                _check(problems, rec["hint_hits"] == 0,
                       f"{row}: {rec['hint_hits']} hint hits on a shape "
                       "hints cannot serve")
            want_mesh = {"cells": 1, "nodes": device["count"]} if multi \
                else None
            _check(problems, rec["mesh"] == want_mesh,
                   f"{row}: mesh {rec['mesh']}, expected {want_mesh}")
            shards = rec["state_shards"]
            _check(problems,
                   shards["min_per_array"] == device["count"]
                   and len(shards["devices"]) == device["count"],
                   f"{row}: node state shards {shards} on "
                   f"{device['count']} device(s)")
        if multi:
            _check(problems,
                   library["rows"][ROWS[0][0]]["shard_map_dispatches"] > 0,
                   f"{ROWS[0][0]}: no shard_map dispatch under the mesh")
            _check(problems, server["plan_rebuilds_by_plane"].get("mesh"),
                   "server: no plan built on the mesh plane")
        _check_counters(problems, "server", server)
        _check(problems,
               server["bound"] == server["distinct_pods"]
               == server["pods_created"],
               f"server: bound {server['bound']} of "
               f"{server['pods_created']} pods "
               f"({server['distinct_pods']} distinct)")
        with open(os.path.join(workdir, "server.device.json")) as f:
            got = json.load(f)
        compared["server"] = _compare(
            problems, "server", got, want[SERVER_ROW],
            server["pods_created"], min_compared)

        _check(problems, "jax" not in sys.modules,
               "the parent imported JAX (it would hold the chip)")
        report = {"ok": not problems, "rehearsal": rehearsal,
                  "device": device, "compared_pods": compared,
                  "cache": cache,
                  "cpu_child_beside_chip": library["cpu_child_beside_chip"],
                  "library": library["rows"],
                  "server": server, "problems": problems,
                  "wall_s": round(time.monotonic() - _T0, 1)}
        out_dir = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "chip_smoke_report.json"), "w") as f:
            json.dump(report, f, indent=1)
        print(json.dumps({"report": report}), flush=True)
    except Failed as e:
        problems.append(str(e))
    finally:
        children.stop_all()
        shutil.rmtree(workdir, ignore_errors=True)
    if problems:
        for p in problems:
            print(f"chip_smoke: FAILED: {p}", file=sys.stderr)
        return 1
    last = {"ok": True, "device": device}
    if rehearsal:
        last["rehearsal"] = True
    print(json.dumps(last), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="scaled down, on whatever backend JAX has, labelled "
                         "as a rehearsal (the CPU form tier-1 drives)")
    ap.add_argument("--scale", type=float, default=None,
                    help=f"rehearsal only: size relative to the published "
                         f"rows (default {REHEARSAL_SCALE})")
    ap.add_argument("--stage", choices=("library", "oracle"),
                    help=argparse.SUPPRESS)  # children of this script
    ap.add_argument("--row", help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    ap.add_argument("--workdir", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.stage is None:
        if args.scale is not None and not args.rehearsal:
            ap.error("--scale is a rehearsal option; the smoke itself runs "
                     "the published sizes")
        return parent(args.rehearsal, args.scale or (
            REHEARSAL_SCALE if args.rehearsal else 1.0))
    sys.path.insert(0, ROOT)
    if args.stage == "oracle":
        return stage_oracle(args.row, args.scale, args.out)
    return stage_library(args.scale, args.rehearsal, args.workdir)


if __name__ == "__main__":
    sys.exit(main())
