"""Traffic driver `closed`: the deployed shape, closed loop, in waves.

What an operator gets who scales a Deployment by a wave of replicas against
the deployed apiserver and scheduler binary: `pods_per_s` read on the served
path, where `waves.py` reads it in process.

Processes as in `open.py`, which this file loads by path and leaves as it is:
one apiserver child (`_spawn_apiserver`), the scheduler binary's own `main`
hosted in THIS process (`open.run`, which conducts the run from a second
thread: here `_conduct` below, put in the place of the open loop's in this
file's own copy of that module), the watcher role of `open_client.py`, and
`closed_client.py` as the sender.

Set-up as in `open.py`: nodes POSTed 500 a request in seeded order, the init
pods the same way, then one unmeasured wave of `warmup_pods` pods (a whole
wave where that is smaller). A wave: the configuration's measured pods, stamped
beforehand, sent as POSTs of `pods_per_post` over `connections` keep-alive
connections, all at once. The wave's clock runs from the instant before its
first POST to the last of its bound events on the client's own watch. One wave
is outstanding; while it binds the sender stamps the next, which starts as
soon as this one's last bind is known. No deletes (`restore` "none"). The
window repeats whole waves until `--seconds` are spent; a wave that has begun
is finished.

`pods_per_s` = pods bound in the completed waves / their summed wave time.
For the readers: `obs["prom"]["scheduler"]` is the window's `/metrics` delta
as in `open.py`, and `obs["counters"]` holds, under the names of the
in-process counters (`waves.py` `COUNTERS`), what the same delta says of them:
the loop's stage table (`scheduler_loop_stage_seconds_total`) for
`plan_build_s`, `device_wait_s` and `host_commit_s` as the program's own
views define them, hint hits and misses, pods on the host path.

With `--trace 1` the window's first `traced_waves` waves run under the
profiler, each from its send to the last of its binds as a `bench.wave` span
(`obs["traced"]["waves"]` says how many), so that `progspans` reads the
program's `sched.*` stages inside them as it does for `waves.py`; a
configuration whose `device_path.trace_init_pods` is set is traced from its
init pods on (the same rule as `open.py` and `waves.py`). A traced run also
clocks the cyclic collector of this process, the scheduler's, with
`waves.py`'s `_CollectorClock` (`obs["gc"]`, summed over the window's waves).
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import re
import signal
import subprocess
import sys
import time
import traceback
from urllib import request as urlrequest

import objects
import prom

HERE = os.path.dirname(os.path.abspath(__file__))


# A wave that has not bound after this long is closed with its unbound pods
# counted as failed: some twenty times a served wave of 10,000 pods.
WAVE_TIMEOUT_S = 300.0
# How long the client's watch may trail the apiserver's own count of binds.
WATCH_LAG_S = 10.0


def _load(name: str):
    """A driver beside this one, as a copy of this file's own: `open.py`'s
    `_conduct` is replaced in that copy and in no other."""
    spec = importlib.util.spec_from_file_location(
        "bench_closed_" + name[:-3], os.path.join(HERE, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


open_ = _load("open.py")
_CollectorClock = _load("waves.py")._CollectorClock

# The in-process counters the accepted readers know, from the scheduler's
# /metrics: stage seconds as TPUScheduler's views sum them, and counters.
STAGE_VIEWS = {"plan_build_s": ("plan.build", "plan.ipa"),
               "device_wait_s": ("device.wait",),
               "host_commit_s": ("host.commit", "bind.post")}
SERIES = {"hint_hits": "scheduler_hint_cache_hits_total",
          "hint_misses": "scheduler_hint_cache_misses_total",
          "host_path_pods": "scheduler_host_path_pods_total"}


def counters_from(series: dict) -> dict:
    stage_s = prom.by_label(series, "scheduler_loop_stage_seconds_total",
                            "stage")
    out = {}
    if stage_s:
        for name, stages in STAGE_VIEWS.items():
            out[name] = sum(stage_s.get(s, 0.0) for s in stages)
    for name, metric in SERIES.items():
        if any(n == metric for n, _ in series):
            out[name] = prom.total(series, metric)
    return out


def events_of(watched, names: list, lag_s: float) -> dict:
    """The watcher's dump (`watched()`), asked for again until it holds a
    bound event for each of `names` or `lag_s` have passed. The apiserver
    counts a bind before its event has reached a watch: where it says a wave
    is bound, the last events are still on their way to the client, whose own
    instants are the wave's clock."""
    end = time.monotonic() + lag_s
    seen = watched()
    while (time.monotonic() < end
           and any(n not in seen["bound_at"] for n in names)):
        time.sleep(0.02)
        seen = watched()
    return seen


class _Sender(open_._Child):
    """`closed_client.py` and its line protocol (`_Child`'s)."""

    def __init__(self, ctx, base: str, extra: list):
        self.role = "sender"
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "closed_client.py"),
             "--root", ctx.root, "--base", base] + extra,
            cwd=ctx.root, env=open_._child_env(ctx.root),
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self.expect("ready")


def _conduct(ctx, base: str, sched_url: str, box: dict) -> None:
    """Everything but the scheduler's own loop; runs beside it."""
    from kubernetes_tpu.core.apiserver import node_to_wire, pod_to_wire
    from jax.profiler import TraceAnnotation
    cfg, params, say = ctx.config, ctx.traffic, ctx.say
    _get = open_._get
    collector = _CollectorClock() if ctx.trace else None
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

        def summary() -> dict:
            return json.loads(_get(base + "/api/v1/pods?summary=true"))

        def wait_for(what: str, fn, target: int, timeout: float,
                     every: float = 0.1) -> int:
            end = time.monotonic() + timeout
            got = -1
            while time.monotonic() < end:
                if box.get("scheduler_exited"):
                    raise RuntimeError(f"scheduler exited while waiting for "
                                       f"{what}")
                try:
                    got = fn()
                except OSError as e:
                    say(f"{what}: {type(e).__name__}: {e}; asking again")
                    got = -1
                if got >= target:
                    break
                time.sleep(every)
            return got

        def must(what: str, fn, target: int) -> None:
            got = wait_for(what, fn, target, 900.0)
            if got < target:
                raise RuntimeError(f"{what}: {got}/{target} after 900s")

        post_in_order("/api/v1/nodes",
                      [node_to_wire(objects.make_node(d)) for d in nodes])
        traced_waves = int(params.get("traced_waves", 0)) if ctx.trace else 0
        trace_from_init = bool(
            traced_waves and cfg.get("device_path", {}).get("trace_init_pods"))

        def start_tracing():
            tracing.enter_context(ctx.profiler())
            tracing.enter_context(TraceAnnotation("bench.closed.traced"))

        if trace_from_init:
            start_tracing()
        must("nodes in the scheduler's cache",
             lambda: len(re.findall(r"^  node-\d+: ", _get(
                 sched_url + "/debug/cache"), re.M)), len(nodes))
        log, placements = [], {}
        init_tpl = cfg["initPods"]["template"]
        proto = objects.make_pod_prototype(init_tpl, ctx.bench_dir)
        init_names = [f"init-{i}" for i in range(int(cfg["initPods"]["count"]))]
        post_in_order("/api/v1/pods", [pod_to_wire(objects.stamp(proto, n))
                                       for n in init_names])
        must("init pods bound", lambda: summary()["bound"], len(init_names))
        for p in json.loads(_get(base + "/api/v1/pods")):
            placements[p["name"]] = p["nodeName"]
        log.extend(("create", n, "initPods") for n in init_names)
        say(f"cluster: {len(nodes)} nodes, {len(init_names)} init pods bound")

        watcher = open_._Child(ctx, "watcher", base, [])
        children.append(watcher)
        sender = _Sender(ctx, base, [
            "--template", json.dumps(cfg["measurePods"]["template"]),
            "--bench-dir", ctx.bench_dir,
            "--connections", str(int(params["connections"])),
            "--pods-per-post", str(int(params["pods_per_post"]))])
        children.append(sender)
        per_wave = int(cfg["measurePods"]["count"])
        bound_before = [len(init_names)]
        logged = [0]

        def prepare(tag: str, count: int) -> None:
            sender.tell(f"prepare {tag} {count}")

        def watched() -> dict:
            dump = os.path.join(ctx.out_dir, "closed_watch.json")
            watcher.tell(f"dump {dump}")
            watcher.expect("done dump")
            with open(dump) as f:
                return json.load(f)

        def wave(tag: str, next_wave, traced: bool = False) -> dict:
            """Send the prepared wave `tag`; have the next one prepared
            while this one binds; join the sender's and the watcher's
            instants by pod name."""
            sender.expect(f"ready {tag}")
            out = os.path.join(ctx.out_dir, f"closed_{tag}.json")
            gc0 = collector.snapshot() if collector else {}
            with (TraceAnnotation("bench.wave") if traced
                  else contextlib.nullcontext()):
                sender.tell(f"send {tag} {out}")
                sender.expect(f"done {tag}")
                if next_wave:
                    prepare(*next_wave)
                with open(out) as f:
                    sent = json.load(f)
                names = sent["names"]
                want = bound_before[0] + len(names)
                got = wait_for(f"wave {tag} bound",
                               lambda: summary()["bound"], want,
                               WAVE_TIMEOUT_S, every=0.05)
            gc_wave = ({k: v - gc0[k] for k, v in collector.snapshot().items()}
                       if collector else {})
            t_gave_up = time.perf_counter()
            seen = events_of(watched, names,
                             WATCH_LAG_S if got >= want else 0.0)
            bound_at = [seen["bound_at"].get(n) for n in names]
            bound = sum(1 for t in bound_at if t is not None)
            # an unbound pod holds its wave open until the client gave up
            last = max((t for t in bound_at if t is not None),
                       default=t_gave_up)
            if bound < len(names):
                last = t_gave_up
            bound_before[0] += bound
            log.extend(("create", n, "measurePods")
                       for n in seen["order"][logged[0]:]
                       if not n.startswith("init-"))
            logged[0] = len(seen["order"])
            placements.update({n: seen["node_of"].get(n) for n in names})
            return {"tag": tag, "created": len(names), "bound": bound,
                    "wave_s": last - sent["t_first_post"], "gc": gc_wave,
                    "posts_s": max(sent["answered"]) - sent["t_first_post"],
                    "n_post_errors": sent["n_post_errors"],
                    "post_errors": sent["post_errors"]}

        warm_pods = min(int(params["warmup_pods"]), per_wave)
        prepare("warm", warm_pods)
        warm = wave("warm", ("w0", per_wave))
        say(f"warm-up wave: {warm['bound']}/{warm['created']} bound in "
            f"{warm['wave_s']:.3f}s")

        ctx.window_opens()
        m0 = prom.parse(_get(sched_url + "/metrics"))
        if traced_waves and not trace_from_init:
            start_tracing()
        waves = []
        t_open = time.perf_counter()
        spent = 0.0
        while spent < ctx.seconds:
            w0 = time.perf_counter()
            waves.append(wave(f"w{len(waves)}",
                              (f"w{len(waves) + 1}", per_wave),
                              traced=len(waves) < traced_waves))
            spent += time.perf_counter() - w0
            if len(waves) == traced_waves:
                tracing.close()
        elapsed = time.perf_counter() - t_open
        ctx.window_closes()
        series = prom.delta(prom.parse(_get(sched_url + "/metrics")), m0)

        for i, w in enumerate(waves):
            say(f"wave {i}: {w['bound']}/{w['created']} bound in "
                f"{w['wave_s']:.4f}s, POSTs answered at "
                f"+{w['posts_s']:.4f}s, post errors {w['n_post_errors']} "
                f"{w['post_errors'][:3]}"
                + (f" gc {w['gc']}" if w["gc"] else ""))
        wave_s = sum(w["wave_s"] for w in waves)
        bound = sum(w["bound"] for w in waves)
        created = sum(w["created"] for w in waves)
        failed = created - bound + sum(w["n_post_errors"] for w in waves)
        counters = counters_from(series)
        say(f"window: {len(waves)} waves, {bound}/{created} bound in "
            f"{wave_s:.3f}s of waves ({elapsed:.3f}s elapsed), {failed} "
            f"failed; counters {counters}")
        fallbacks = prom.by_label(
            series, "scheduler_device_path_fallback_total", "reason")
        charged = sum(v for k, v in fallbacks.items() if k != "unsupported")
        obs = {"window": {"waves": len(waves), "wave_s": wave_s,
                          "pods": bound, "elapsed_s": elapsed},
               "counters": counters,
               "gc": ({k: sum(w["gc"][k] for w in waves)
                       for k in waves[0]["gc"]} if collector else None),
               "prom": {"scheduler": series},
               "cluster": {"nodes": len(nodes),
                           "zones": len({d["zone"] for d in nodes})}}
        if traced_waves:
            obs["traced"] = {"waves": min(traced_waves, len(waves))}
        box["result"] = {
            "attempted": created, "failed": failed,
            "e2e": {"pods_per_s": bound / wave_s},
            "obs": obs,
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
    """`open.run`: the apiserver child, the binary's `main` on this thread,
    the conductor beside it; the conductor is this file's."""
    open_._conduct = _conduct
    return open_.run(ctx)
