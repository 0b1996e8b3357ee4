"""Traffic driver `waves`: the library surface, closed loop, in process.

The surface `python -m kubernetes_tpu.perf` drives: a default
`TPUScheduler` on this thread, creates from a client thread through the
in-process clientset, `schedule_one` until drained. One wave is the
configuration's measured phase: create its measured pods, drain until all are
bound. Then the wave's pods are deleted (the restore: untimed, but inside the
run and reported as the `restore` idle gap), so that the next wave meets the
post-init cluster again with only the rotating start index moved.

The wave's clock starts at the first create and the loop drains while the
client thread creates, as the reference's scheduler_perf does. The pods of a
wave are stamped from the template before the clock starts: that is the
client's own work, and while it ran beside the drain (a quarter of a second
of Python a wave, on the scheduler's interpreter lock) it decided the wave's
time between two modes (PERF.md, Findings). Creating 5,000 stamped pods takes
the client thread a hundredth of a second.

Set-up builds the cluster, binds the init pods and runs `warmup_waves` whole
waves with their restores, so that every program a window can meet has been
compiled (or loaded) and has run before the window opens. The window then
repeats waves until `--seconds` have passed; a wave that has begun is
finished. With `--trace 1` the first `traced_waves` waves and their restores
run under the profiler.
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time

import objects
import spans

# Program counters read as window deltas (kubernetes_tpu/perf/harness.py
# `_ThroughputCollector.WINDOW_COUNTERS`); one missing is left out.
COUNTERS = ("plan_build_s", "device_wait_s", "host_commit_s",
            "device_scheduled", "host_path_pods", "device_batches",
            "plan_rebuilds_full", "plan_rebuilds_delta",
            "plan_rebuilds_resume", "hint_hits", "hint_misses",
            "hint_invalidations", "scheduled", "failures")


def _counters(sched) -> dict:
    return {c: getattr(sched, c) for c in COUNTERS if hasattr(sched, c)}


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after}


def _drain(sched, creator: threading.Thread) -> None:
    """Drive scheduling until the client is done and the queue yields
    nothing more (perf/harness.py `_drain`, without its tickers)."""
    while True:
        if sched.schedule_one():
            continue
        sched.queue.flush_backoff_completed()
        sched.flush_expired_waiters()
        if sched.schedule_one():
            continue
        if creator.is_alive():
            sched.drain_event_inbox() or time.sleep(0.0002)
            continue
        if not sched.drain_event_inbox():
            return


class _CollectorClock:
    """Seconds the interpreter's cyclic collector ran, by generation, from
    `gc.callbacks` (traced runs only: the end-to-end runs stay as they are)."""

    def __init__(self):
        self.seconds = [0.0, 0.0, 0.0]
        self.runs = [0, 0, 0]
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            g = info["generation"]
            self.seconds[g] += time.perf_counter() - self._t
            self.runs[g] += 1

    def snapshot(self) -> dict:
        return {"gc_s": sum(self.seconds), "gc_full_s": self.seconds[2],
                "gc_full_runs": self.runs[2]}


def run(ctx) -> dict:
    cfg, params, say = ctx.config, ctx.traffic, ctx.say
    collector = _CollectorClock() if ctx.trace else None
    from jax.profiler import TraceAnnotation
    from kubernetes_tpu.models import TPUScheduler
    from kubernetes_tpu.perf.device import breaker_charges, fallbacks_by_reason

    nodes = objects.cluster(cfg, ctx.seed)
    sched = TPUScheduler()
    cs = sched.clientset
    for desc in nodes:
        cs.create_node(objects.make_node(desc))
    log = []                                   # what the reference replays
    placements = {}
    init_proto = objects.make_pod_prototype(
        cfg["initPods"]["template"], ctx.bench_dir)
    wave_proto = objects.make_pod_prototype(
        cfg["measurePods"]["template"], ctx.bench_dir)
    # A cell whose waves never reach the device by design (score hints bind
    # them) is traced from the init pods on, which the kernel places: every
    # traced run then holds device work, and the idle share says how little.
    tracing = contextlib.ExitStack()
    traced_waves = int(params.get("traced_waves", 0)) if ctx.trace else 0
    trace_from_init = bool(
        traced_waves and cfg.get("device_path", {}).get("trace_init_pods"))

    def start_tracing():
        tracing.enter_context(ctx.profiler())
        spans.annotate(sched, params.get("host_spans", {}), say)

    if trace_from_init:
        start_tracing()
    with TraceAnnotation("bench.init"):
        for i in range(int(cfg["initPods"]["count"])):
            cs.create_pod(objects.stamp(init_proto, f"init-{i}"))
            log.append(("create", f"init-{i}", "initPods"))
        sched.run_until_idle()
    placements.update({p.name: p.node_name for p in cs.pods.values()})
    say(f"cluster: {len(nodes)} nodes, {len(placements)} init pods bound, "
        f"device_batches {sched.device_batches}")
    per_wave = int(cfg["measurePods"]["count"])

    def wave(tag: str) -> dict:
        stamped = [objects.stamp(wave_proto, f"{tag}-{i}")
                   for i in range(per_wave)]
        pods = []
        created_at = []

        def create():
            for pod in stamped:
                pods.append(cs.create_pod(pod))
            created_at.append(time.perf_counter())

        before = _counters(sched)
        full0 = gc.get_stats()[2]["collections"]
        gc0 = collector.snapshot() if collector else {}
        creator = threading.Thread(target=create, daemon=True)
        with TraceAnnotation("bench.wave"):
            cpu0 = time.process_time()
            t0 = time.perf_counter()
            creator.start()
            _drain(sched, creator)
            t1 = time.perf_counter()
            cpu_s = time.process_time() - cpu0
        gc_wave = _delta(collector.snapshot(), gc0) if collector else {}
        full_collections = gc.get_stats()[2]["collections"] - full0
        # One list per wave and nothing per pod: what the harness keeps
        # alive inside the window must not steer the collector of the
        # process it measures (PERF.md, Findings).
        landed = [cs.pods[p.uid].node_name for p in pods]
        bound = sum(1 for node in landed if node)
        with TraceAnnotation("bench.restore"):
            r0 = time.perf_counter()
            for p in pods:
                cs.delete_pod(cs.pods[p.uid])
            _drain(sched, creator)
            r1 = time.perf_counter()
        return {"tag": tag, "landed": landed, "wave_s": t1 - t0,
                "create_s": created_at[0] - t0, "cpu_s": cpu_s,
                "restore_s": r1 - r0, "created": per_wave, "bound": bound,
                "counters": _delta(_counters(sched), before), "gc": gc_wave,
                "full_collections": full_collections}

    warmups = []
    for w in range(int(params["warmup_waves"])):
        got = wave(f"warm{w}")
        warmups.append(got)
        say(f"warm-up wave {w}: {got['bound']}/{got['created']} bound in "
            f"{got['wave_s']:.3f}s, creates done at +{got['create_s']:.3f}s, "
            f"restore {got['restore_s']:.3f}s, "
            f"{got['counters']}")

    ctx.window_opens()
    c0 = _counters(sched)
    fallbacks0 = fallbacks_by_reason(sched)
    waves, traced = [], None
    if traced_waves and not trace_from_init:
        start_tracing()
    # The window's clock is the time the waves took, stamping and restores
    # included: what the harness does between them (stopping the profiler
    # takes many seconds) does not shorten a traced run's window.
    t_open = time.perf_counter()
    spent = 0.0
    with tracing:
        while spent < ctx.seconds:
            w0 = time.perf_counter()
            waves.append(wave(f"w{len(waves)}"))
            spent += time.perf_counter() - w0
            if len(waves) == traced_waves:
                tracing.close()
                traced = list(waves)
    elapsed = time.perf_counter() - t_open
    ctx.window_closes()
    counters = _delta(_counters(sched), c0)
    for w in warmups + waves:
        for i, node in enumerate(w["landed"]):
            name = f"{w['tag']}-{i}"
            log.append(("create", name, "measurePods"))
            placements[name] = node
        log.extend(("delete", f"{w['tag']}-{i}", None)
                   for i in range(len(w["landed"])))
    wave_s = sum(w["wave_s"] for w in waves)
    bound = sum(w["bound"] for w in waves)
    created = sum(w["created"] for w in waves)
    for i, w in enumerate(waves):
        c = w["counters"]
        say(f"wave {i}: {w['bound']}/{w['created']} bound in "
            f"{w['wave_s']:.4f}s (cpu {w['cpu_s']:.3f}), creates done at "
            f"+{w['create_s']:.4f}s, restore {w['restore_s']:.4f}s; plan "
            f"{c.get('plan_build_s', 0):.3f} wait {c.get('device_wait_s', 0):.3f} "
            f"commit {c.get('host_commit_s', 0):.3f} batches "
            f"{c.get('device_batches', 0)} hints {c.get('hint_hits', 0)} "
            f"rebuilds full/delta/resume {c.get('plan_rebuilds_full', 0)}/"
            f"{c.get('plan_rebuilds_delta', 0)}/"
            f"{c.get('plan_rebuilds_resume', 0)} full collections "
            f"{w['full_collections']}"
            + (f" gc {w['gc']}" if w["gc"] else ""))
    charged = breaker_charges(_delta(fallbacks_by_reason(sched), fallbacks0))
    guards = [
        ("host_path_pods", counters.get("host_path_pods", 0), 0),
        ("breaker_charges", sum(charged.values()), 0),
        ("failed_attempts", counters.get("failures", 0), 0),
    ]
    least = int(cfg.get("device_path", {}).get("min_device_batches", 0))
    if counters.get("device_batches", 0) < least * len(waves):
        guards.append(("device_batches_short_of_minimum",
                       least * len(waves) - counters.get("device_batches", 0),
                       0))
    obs = {
        "window": {"waves": len(waves), "wave_s": wave_s, "pods": bound,
                   "elapsed_s": elapsed,
                   "restore_s": sum(w["restore_s"] for w in waves)},
        "counters": counters,
        "gc": ({k: sum(w["gc"][k] for w in waves) for k in waves[0]["gc"]}
               if collector else None),
        "cluster": {"nodes": len(nodes), "zones": len({d["zone"] for d in nodes}),
                    "max_batch": int(sched.max_batch)},
    }
    if traced:
        tc = {}
        for w in traced:
            for k, v in w["counters"].items():
                tc[k] = tc.get(k, 0) + v
        obs["traced"] = {"counters": tc, "waves": len(traced)}
    return {
        "attempted": created, "failed": created - bound,
        "e2e": {"pods_per_s": bound / wave_s},
        "obs": obs, "guards": guards, "log": log, "placements": placements,
        "nodes": nodes,
        "templates": {"initPods": cfg["initPods"]["template"],
                      "measurePods": cfg["measurePods"]["template"]},
    }
