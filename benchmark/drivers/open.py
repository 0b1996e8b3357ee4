"""Traffic driver `open`: the deployed shape, open loop, from the client's side.

Processes: one `python -m kubernetes_tpu.core.apiserver` child; the scheduler
hosted in THIS process by calling the binary's own `main` with the arguments
`python -m kubernetes_tpu --api-url ... --platform tpu` gets (same code path;
in process so that the profiler of this process sees the chip, and the program
is untouched); two client children (`open_client.py`, JAX-free): a sender of
the arrivals and a watcher of the bound events, each with an interpreter lock
of its own. The binary's `main` installs signal handlers
and so runs on the main thread; the run itself is conducted from a second
thread, which ends the scheduler with SIGTERM when it is done.

Set-up: nodes POSTed 500 a request in seeded order, the init pods the same
way, then `warmup_seconds` of the same open loop, unmeasured, so that every
program the window meets has run. The window: Poisson arrivals from the seed
at the traffic file's fixed `rate_pods_per_s`, one pod per POST over
`connections` keep-alive connections. The rate is a number in the traffic
file: the knee is found again by running traffic files of rising rates, one
run each, and reading each run's `backlog mid/end` (README.md).

With `--trace 1` the first `traced_seconds` of the window run under the
profiler; a configuration whose `device_path.trace_init_pods` is set is traced
from its init pods on instead (the same rule as `waves.py`).
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import traceback
from urllib import request as urlrequest

import numpy as np

import objects
import prom
import spans

WITHIN_MS = (50, 100, 200, 500)
READY = re.compile(r"serving on 127\.0\.0\.1:(\d+)")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url: str, timeout: float = 30.0) -> str:
    with urlrequest.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


def _child_env(root: str) -> dict:
    """Children stay off the chip and share this process's compile cache."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("TPU_SCHED_", "BENCH_"))}
    env["PYTHONPATH"] = root
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _spawn_apiserver(root: str, log_path: str):
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubernetes_tpu.core.apiserver", "--port", "0"],
        cwd=root, env=_child_env(root), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    deadline = time.monotonic() + 120
    base = None
    log = open(log_path, "w")
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        log.write(line)
        m = READY.search(line)
        if m:
            base = f"http://127.0.0.1:{m.group(1)}"
            break
    if base is None:
        proc.kill()
        proc.wait()
        raise RuntimeError(f"apiserver never became ready ({log_path})")

    def drain():
        for more in proc.stdout:
            log.write(more)
        log.close()
    threading.Thread(target=drain, daemon=True).start()
    return proc, base


class _Child:
    """One role of `open_client.py` and its line protocol."""

    def __init__(self, ctx, role: str, base: str, extra: list):
        here = os.path.dirname(os.path.abspath(__file__))
        self.role = role
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(here, "open_client.py"),
             "--role", role, "--root", ctx.root, "--base", base] + extra,
            cwd=ctx.root, env=_child_env(ctx.root), stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True)
        self.expect("ready")

    def expect(self, word: str) -> None:
        line = self.proc.stdout.readline()
        if not line.startswith(word):
            raise RuntimeError(f"{self.role} said {line!r}, expected "
                               f"{word!r} (exit code {self.proc.poll()})")

    def tell(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.tell("quit")
                self.proc.wait(timeout=15)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def _within_share(values, ms: float) -> float:
    """Per cent of the samples at or under `ms`."""
    return float(np.mean(np.asarray(values, float) <= ms) * 100.0)


def _conduct(ctx, base: str, sched_url: str, box: dict) -> None:
    """Everything but the scheduler's own loop; runs beside it."""
    from kubernetes_tpu.core.apiserver import node_to_wire, pod_to_wire
    cfg, params, say = ctx.config, ctx.traffic, ctx.say
    children = []
    tracing = contextlib.ExitStack()
    try:
        deadline = time.monotonic() + 600
        while True:
            try:
                _get(sched_url + "/metrics", timeout=5)
                break
            except OSError:
                if box.get("scheduler_exited") or time.monotonic() > deadline:
                    raise RuntimeError("the scheduler never served /metrics")
                time.sleep(0.1)
        say(f"scheduler ready at {sched_url}")
        nodes = objects.cluster(cfg, ctx.seed)

        def post_in_order(path: str, wires: list) -> None:
            for i in range(0, len(wires), 500):
                req = urlrequest.Request(
                    base + path, data=json.dumps(wires[i:i + 500]).encode(),
                    headers={"Content-Type": "application/json"},
                    method="POST")
                urlrequest.urlopen(req, timeout=120).read()

        def wait_for(what: str, fn, target: int, timeout: float = 900.0):
            end = time.monotonic() + timeout
            got = -1
            while time.monotonic() < end:
                if box.get("scheduler_exited"):
                    raise RuntimeError(f"scheduler exited while waiting for "
                                       f"{what}")
                try:
                    got = fn()
                except OSError as e:
                    # e.g. the scheduler's /debug/cache dump racing the node
                    # adds ("dictionary changed size during iteration")
                    say(f"{what}: {type(e).__name__}: {e}; asking again")
                    got = -1
                if got >= target:
                    return
                time.sleep(0.1)
            raise RuntimeError(f"{what}: {got}/{target} after {timeout}s")

        post_in_order("/api/v1/nodes",
                      [node_to_wire(objects.make_node(d)) for d in nodes])
        # A cell whose window never reaches the device by design (score
        # hints bind every arrival on the host) is traced from the init
        # pods on, which the kernel places: every traced run then holds
        # device work, and the idle share says how little (as in waves.py).
        traced_s = float(params.get("traced_seconds", 0)) if ctx.trace else 0
        trace_from_init = bool(
            traced_s and cfg.get("device_path", {}).get("trace_init_pods"))

        def start_tracing():
            from jax.profiler import TraceAnnotation
            tracing.enter_context(ctx.profiler())
            tracing.enter_context(TraceAnnotation("bench.open.traced"))

        if trace_from_init:
            start_tracing()
        wait_for("nodes in the scheduler's cache",
                 lambda: len(re.findall(r"^  node-\d+: ", _get(
                     sched_url + "/debug/cache"), re.M)), len(nodes))
        log, placements = [], {}
        init_tpl = cfg["initPods"]["template"]
        proto = objects.make_pod_prototype(init_tpl, ctx.bench_dir)
        init_names = [f"init-{i}" for i in range(int(cfg["initPods"]["count"]))]
        post_in_order("/api/v1/pods", [pod_to_wire(objects.stamp(proto, n))
                                       for n in init_names])

        def summary() -> dict:
            return json.loads(_get(base + "/api/v1/pods?summary=true"))

        wait_for("init pods bound", lambda: summary()["bound"],
                 len(init_names))
        for p in json.loads(_get(base + "/api/v1/pods")):
            placements[p["name"]] = p["nodeName"]
        log.extend(("create", n, "initPods") for n in init_names)
        say(f"cluster: {len(nodes)} nodes, {len(init_names)} init pods bound")

        watcher = _Child(ctx, "watcher", base, [])
        children.append(watcher)
        sender = _Child(ctx, "sender", base, [
            "--template", json.dumps(cfg["measurePods"]["template"]),
            "--bench-dir", ctx.bench_dir,
            "--connections", str(int(params["connections"])),
            "--seed", str(ctx.seed)])
        children.append(sender)
        grace = float(params.get("grace_seconds", 15.0))
        bound_before = [len(init_names)]

        def finish_phase(tag: str) -> dict:
            """Wait for the sender, then for the binds (or the grace), then
            join the sender's and the watcher's instants by pod name."""
            sender.expect(f"done {tag}")
            sent = _load(os.path.join(ctx.out_dir, f"open_{tag}.json"))
            want = bound_before[0] + len(sent["names"])
            end = time.monotonic() + grace
            while time.monotonic() < end and summary()["bound"] < want:
                time.sleep(0.05)
            dump = os.path.join(ctx.out_dir, "open_watch.json")
            t_gave_up = time.perf_counter()
            watcher.tell(f"dump {dump}")
            watcher.expect("done dump")
            seen = _load(dump)
            names, due = sent["names"], np.asarray(sent["due"])
            bound_at = np.array([seen["bound_at"].get(n, np.nan)
                                 for n in names])
            mine = set(names)
            backlog = {}
            for label, at in (("mid", sent["seconds"] / 2),
                              ("end", sent["seconds"])):
                t = sent["t0"] + at
                backlog[label] = int(np.sum(due <= t)
                                     - np.sum(bound_at <= t))
            got = {
                "offered": len(names),
                "bound": int(np.sum(~np.isnan(bound_at))),
                "n_post_errors": sent["n_post_errors"],
                "post_errors": sent["post_errors"],
                "lag_ms": list((np.asarray(sent["sent"]) - due) * 1e3),
                "post_ms": list((np.asarray(sent["answered"])
                                 - np.asarray(sent["sent"])) * 1e3),
                # an unbound pod waited at least until the client gave up
                "latency_ms": list((np.where(np.isnan(bound_at), t_gave_up,
                                             bound_at) - due) * 1e3),
                "backlog": backlog, "sender_cpu_share": sent["cpu_share"],
                "order": [n for n in seen["order"] if n in mine],
                "placements": {n: seen["node_of"].get(n) for n in names},
            }
            bound_before[0] += got["bound"]
            log.extend(("create", n, "measurePods") for n in got["order"])
            placements.update(got["placements"])
            return got

        def start_phase(tag: str, seconds: float, rate: float) -> None:
            out = os.path.join(ctx.out_dir, f"open_{tag}.json")
            sender.tell(f"phase {tag} {seconds} {rate} {out}")

        rate = float(params["rate_pods_per_s"])
        start_phase("warm", float(params["warmup_seconds"]), rate)
        warm = finish_phase("warm")
        say(f"warm-up: {warm['bound']}/{warm['offered']} bound")

        ctx.window_opens()
        m0 = prom.parse(_get(sched_url + "/metrics"))
        if traced_s and not trace_from_init:
            start_tracing()
        start_phase("w0", ctx.seconds, rate)
        if traced_s:
            time.sleep(min(traced_s, ctx.seconds))
            tracing.close()
        got = finish_phase("w0")
        lat = got["latency_ms"]
        say(f"window: rate {rate:g}/s offered {got['offered']} "
            f"bound {got['bound']} post_errors {got['n_post_errors']} "
            f"backlog mid/end {got['backlog']['mid']}/"
            f"{got['backlog']['end']} bind mean/p50/p90/p95/p99 ms "
            f"{float(np.mean(lat)):.2f}/{_percentile(lat, 50):.2f}/"
            f"{_percentile(lat, 90):.2f}/{_percentile(lat, 95):.2f}/"
            f"{_percentile(lat, 99):.2f} within "
            f"{'/'.join(str(ms) for ms in WITHIN_MS)} ms % "
            f"{'/'.join(f'{_within_share(lat, ms):.2f}' for ms in WITHIN_MS)} "
            f"generator lag p99 ms {_percentile(got['lag_ms'], 99):.3f} "
            f"POST p50/p99 ms {_percentile(got['post_ms'], 50):.2f}/"
            f"{_percentile(got['post_ms'], 99):.2f} "
            f"sender cpu share {got['sender_cpu_share']:.2f}")
        ctx.window_closes()
        series = prom.delta(prom.parse(_get(sched_url + "/metrics")), m0)

        latency = got["latency_ms"]
        failed = got["offered"] - got["bound"] + got["n_post_errors"]
        say(f"window: {got['offered']} pods due, {len(latency)} latency "
            f"samples, {failed} failed; POST errors {got['post_errors'][:3]}")
        fallbacks = prom.by_label(
            series, "scheduler_device_path_fallback_total", "reason")
        charged = sum(v for k, v in fallbacks.items() if k != "unsupported")
        # every percentile and every share of pods bound within a limit that
        # a manifest may name: end to end it reports the ones it lists, and a
        # per-layer reader takes any other from `obs`, the same number. (An
        # unbound pod's sample is the client's giving up: beyond each limit.)
        e2e = {**{f"bind_p{q}_ms": _percentile(latency, q)
                  for q in (50, 90, 95, 99)},
               **{f"bind_within_{ms}ms_share": _within_share(latency, ms)
                  for ms in WITHIN_MS}}
        box["result"] = {
            "attempted": got["offered"], "failed": failed, "e2e": e2e,
            "obs": {"window": {"pods": got["bound"],
                               "elapsed_s": ctx.seconds},
                    "prom": {"scheduler": series},
                    "client": {"latency_ms": latency, "e2e": e2e},
                    "generator": {"lag_ms": got["lag_ms"],
                                  "cpu_share": got["sender_cpu_share"]},
                    "backlog": got["backlog"]},
            "guards": [
                ("host_path_pods",
                 prom.total(series, "scheduler_host_path_pods_total"), 0),
                ("breaker_charges", charged, 0),
                ("breaker_open", prom.total(
                    prom.parse(_get(sched_url + "/metrics")),
                    "scheduler_device_breaker_state"), 0)],
            "log": log, "placements": placements, "nodes": nodes,
            "templates": {"initPods": init_tpl,
                          "measurePods": cfg["measurePods"]["template"]},
        }
    except BaseException:  # noqa: BLE001 - reported by the main thread
        box["error"] = traceback.format_exc()
    finally:
        for child in children:
            child.close()
        tracing.close()
        box["conducted"] = True
        if not box.get("scheduler_exited"):
            os.kill(os.getpid(), signal.SIGTERM)   # ends the binary's loop


def run(ctx) -> dict:
    params = ctx.traffic
    api, base = _spawn_apiserver(
        ctx.root, os.path.join(ctx.out_dir, "apiserver.log"))
    box: dict = {}
    try:
        # import on this thread, before the second one starts: two threads
        # importing the package at once trip over its import cycle
        import kubernetes_tpu.core.apiserver  # noqa: F401
        from kubernetes_tpu.__main__ import main as scheduler_main
        from kubernetes_tpu.models import TPUScheduler
        if ctx.trace and params.get("host_spans"):
            spans.annotate(TPUScheduler, params["host_spans"], ctx.say)
        port = _free_port()
        conductor = threading.Thread(
            target=_conduct, args=(ctx, base, f"http://127.0.0.1:{port}", box),
            daemon=True)
        conductor.start()
        rc = scheduler_main(["--api-url", base, "--port", str(port),
                             "--platform", "cpu" if ctx.rehearse else "tpu"])
        box["scheduler_exited"] = True
        conductor.join(timeout=60)
        if "result" not in box:
            raise RuntimeError(
                f"the open loop did not finish (scheduler rc {rc}):\n"
                + box.get("error", "no error recorded"))
        return box["result"]
    finally:
        api.terminate()
        try:
            api.wait(timeout=15)
        except subprocess.TimeoutExpired:
            api.kill()
            api.wait()
