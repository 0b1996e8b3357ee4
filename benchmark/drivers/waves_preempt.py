"""Traffic driver `waves_preempt`: `waves.py`'s closed loop with the
reference's churn op of `PreemptionAsync` beside the measured pods, its
interval restated in pods.

A wave is the configuration's measured pods, all created at once from a
client thread while this thread drives the loop, as in every `.waves` cell,
and one preemptor (the configuration's `preemptors` template) for every
`preemptor_every_bound_pods` measured pods the wave has bound: preemptor k is
created, from the client thread too, when the wave's own count of bound
measured pods is at or over `every` x k, for every k whose mark the wave
reaches (114, 228, ..., 4,902: 43 a wave). The client's watch
(`on_pod_event`) counts the binds on the thread that makes them, so the count
is checked at every bind, and this thread looks again between turns of the
loop. A preemptor's create is parked in the scheduler's inbox as a watch's
would be and the loop takes it where it does; which preemptors meet the
backlog and which come after it is the run's own, and the log's to say.

Every pod created gets its ordinal as `creation_ts`, the start time the
reference takes (a pod's place among the log's creates; the program's pods
are stamped from one prototype and would otherwise all carry the
prototype's): init pods, measured pods, preemptors and the init pods a
restore creates anew, in the order in which they are created.

The log is read from what the program showed, as the toy's driver reads it
(`tests/benchmark/toy_bench/drivers/preempt.py`), beside a draining loop:

- a bind on the watch is the attempt that placed the pod: a measured pod's is
  counted (the measured pods are placed in the order they were created), a
  preemptor's is its `create` if it never failed, else a `retry`;
- a `FailedScheduling` event on a preemptor (`sched.recorder`) is an attempt
  that found no node, put in front of what the watch shows next: the
  scheduler's count of failed attempts is looked at at every bind and between
  turns, and when it has moved the waiting preemptors' events say whose it
  was, oldest first;
- a delete inside a wave is an eviction (the driver deletes only in the
  restore). With the in-process clientset the victims are deleted inside the
  preemptor's cycle, so the deletes reach the watch BEFORE that attempt's
  event: they are held until the event names the preemptor and logged behind
  the attempt, which is where the cache saw them; a victim goes to the
  preemptor that failed in the same look and is nominated to the victim's
  node, and the nomination is read off the pod;
- anything left without a place is counted (`attempts_without_a_place`,
  limit 0): an eviction nobody was nominated for, a preemptor that failed
  twice between two looks.

The wave's clock runs from the first create to the drain's end: the later of
the last measured bind and the bind of the last preemptor, queue and
dispatcher drained. The restore (inside the run, outside the ratio) deletes
the wave's measured pods and preemptors and creates anew as many init pods as
were evicted (same template, new names, new ordinals), so every wave starts
on the configuration's init pods, four a node. `pods_per_s` is the measured
pods bound in completed waves over the summed wave clocks; the preemptors are
the churn, as in the source, and are not counted.

A program without the nominated retry on the device path (the parent of the
PR that added it) is refused at once, with exit code 1, before the cluster is
built: every retry would take the host path there.

Set-up, window and tracing are `waves.py`'s; set-up also warms what the
window will meet where the program offers it (`warm_for(pod, nominated=True)`
for both templates, `warm_for_preemption`), beside two warm-up waves that meet
all of it. `scheduler` (`device`, the cell's, or `host`, for the tests) names
the program's scheduler.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import os
import threading
import time

import objects
import spans

HERE = os.path.dirname(os.path.abspath(__file__))
STUCK_S = 300.0     # a wave this long has a client waiting for ever
PREEMPTOR = "preemptor-0"


def _load(name: str):
    spec = importlib.util.spec_from_file_location(
        "bench_waves_preempt_" + name[:-3], os.path.join(HERE, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_waves = _load("waves.py")
_counters, _delta, _CollectorClock = (
    _waves._counters, _waves._delta, _waves._CollectorClock)


def _scheduler(kind: str, max_batch=None):
    if kind == "device":
        from kubernetes_tpu.models import TPUScheduler
        return TPUScheduler(max_batch=max_batch)
    if kind == "host":
        from kubernetes_tpu.core import Scheduler
        return Scheduler(deterministic_ties=True)
    raise ValueError(f"traffic names scheduler {kind!r}: host or device")


def marks(params: dict, per_wave: int, rehearse: bool) -> list:
    """The counts of bound measured pods at which a wave's preemptors are
    created: every multiple of the traffic's `preemptor_every_bound_pods`
    (a rehearsal's own, for its smaller wave) that the wave reaches."""
    every = int((params.get("rehearse", {}) if rehearse else {}).get(
        "preemptor_every_bound_pods", params["preemptor_every_bound_pods"]))
    return list(range(every, per_wave + 1, every))


def wave_log(tag: str, trail: list, tried: set) -> list:
    """A wave's part of the log, up to its restore, from what its watch and
    looks left (`trail`): `("bound", n)` the measured pods in creation order
    up to n, `("failed", preemptor)` an attempt that found no node and
    `("placed", preemptor)` the attempt that bound it (the pod's `create` if
    the log has none yet, `tried`, else a `retry`), `("evicted", victim)` a
    delete the cache saw."""
    part, logged = [], 0
    for what, value in trail:
        if what == "bound":
            part.extend(("create", f"{tag}-{i}", "measurePods")
                        for i in range(logged, value))
            logged = value
        elif what == "evicted":
            part.append(("delete", value, None))
        elif what in ("failed", "placed"):
            part.append(("retry", value, None) if value in tried
                        else ("create", value, PREEMPTOR))
            tried.add(value)
        else:
            raise ValueError(f"trail entry {what!r}")
    return part


def _preemption_counts(sched) -> dict:
    """The preemption series the guards read as deltas (a program without
    the two of PR 43 is refused before this is reached)."""
    m = sched.metrics
    return {"preemption_attempts": m.preemption_attempts.value(),
            "preemption_victims": m.preemption_victims.sum(),
            "preemptions": m.preemption_victims.count(),
            "dry_runs_device": m.preemption_dry_runs.value("device"),
            "dry_runs_host": m.preemption_dry_runs.value("host"),
            "nominated_bound": m.nominated_evaluations.value("bound"),
            "nominated_fell_through":
                m.nominated_evaluations.value("fell_through")}


def run(ctx) -> dict:
    cfg, params, say = ctx.config, ctx.traffic, ctx.say
    collector = _CollectorClock() if ctx.trace else None
    from jax.profiler import TraceAnnotation
    from kubernetes_tpu.perf.device import breaker_charges, fallbacks_by_reason

    kind = params.get("scheduler", "device")
    sched = _scheduler(
        kind,
        params.get("rehearse", {}).get("max_batch") if ctx.rehearse else None)
    if not hasattr(sched.metrics, "nominated_evaluations"):
        raise SystemExit(
            "waves_preempt: this program has no evaluation of a nominated "
            "pod's own node on the device path (no "
            "scheduler_nominated_evaluations_total): every preemptor's retry "
            "would take the host path, and its choice of the node parts from "
            "the source's (PERF.md section 4); the cell cannot run here")
    nodes = objects.cluster(cfg, ctx.seed)
    cs = sched.clientset
    for desc in nodes:
        cs.create_node(objects.make_node(desc))
    (group,) = objects.groups(cfg, "preemptors")     # one template
    templates = {"initPods": cfg["initPods"]["template"],
                 "measurePods": cfg["measurePods"]["template"],
                 PREEMPTOR: group["template"]}
    protos = {g: objects.make_pod_prototype(t, ctx.bench_dir)
              for g, t in templates.items()}
    per_wave = int(cfg["measurePods"]["count"])
    due_at = marks(params, per_wave, ctx.rehearse)
    log, placements, evictions, nominations = [], {}, {}, {}
    created = [0]                   # ordinals given out so far

    def stamp(which: str, name: str):
        pod = objects.stamp(protos[which], name)
        pod.creation_ts = float(created[0])
        created[0] += 1
        return pod

    def init_pods(names: list) -> None:
        for name in names:
            cs.create_pod(stamp("initPods", name))
            log.append(("create", name, "initPods"))

    with TraceAnnotation("bench.init"):
        init_pods([f"init-{i}" for i in range(int(cfg["initPods"]["count"]))])
        sched.run_until_idle()
    placements.update({p.name: p.node_name for p in cs.pods.values()})
    # what the window will meet, beside the warm-up waves that meet it all:
    # both templates' scheduling programs with and without a nominated lane
    # (the nominated retry is the preemptor's own), the dry run at the
    # cluster's victim width
    if hasattr(sched, "warm_for"):
        sched.warm_for(objects.stamp(protos["measurePods"], "warm-m"),
                       nominated=True)
        sched.warm_for(objects.stamp(protos[PREEMPTOR], "warm-p"),
                       nominated=True)
    if hasattr(sched, "warm_for_preemption"):
        sched.warm_for_preemption(objects.stamp(protos[PREEMPTOR], "warm-d"))
    say(f"cluster: {len(nodes)} nodes, {len(placements)} init pods bound; a "
        f"wave is {per_wave} measured pods and {len(due_at)} preemptors, one "
        f"at every {due_at[0] if due_at else 0} bound"
        f"{' up to ' + str(due_at[-1]) if due_at else ''}")
    watch = [None]                  # the open wave's reading of a pod event

    def watched(kind_, old, new) -> None:
        if watch[0] is not None:
            watch[0](kind_, old, new)

    cs.on_pod_event(watched)
    tracing = contextlib.ExitStack()
    traced_waves = int(params.get("traced_waves", 0)) if ctx.trace else 0
    tried = set()                   # preemptors the log has a `create` for
    restored = [0]                  # init pods created anew so far

    def wave(tag: str) -> dict:
        # the client's own work, before the clock: the wave's pods stamped
        stamped = [stamp("measurePods", f"{tag}-{i}")
                   for i in range(per_wave)]
        highs = [stamp(PREEMPTOR, f"{tag}-high-{k}")
                 for k in range(1, len(due_at) + 1)]
        high_names = {p.name for p in highs}
        pods, created_at, live = [], [], {}
        due = threading.Semaphore(0)        # a preemptor's mark has passed

        def client():
            for pod in stamped:
                pods.append(cs.create_pod(pod))
            created_at.append(time.perf_counter())
            for pod in highs:
                due.acquire()
                live[pod.name] = cs.create_pod(pod)

        trail = []      # ("bound", n) | ("failed"|"placed", preemptor)
        #                 | ("evicted", victim)
        issued = []     # per preemptor created: bound count, clock
        waiting = {name: (None, 0) for name in high_names}  # events seen
        landed = {}     # preemptor -> node
        held = []       # evictions seen, their attempt not yet
        seen = {"bound": 0, "logged": 0, "failed": sched.failures,
                "misplaced": 0, "evicted": 0, "nominated": 0,
                "failed_attempts": 0}
        failed0 = sched.failures
        before = _counters(sched)
        pre0 = _preemption_counts(sched)
        full0 = gc.get_stats()[2]["collections"]
        gc0 = collector.snapshot() if collector else {}
        creator = threading.Thread(target=client, daemon=True)
        agg = sched.recorder._agg

        def log_the_bound() -> None:
            if seen["bound"] != seen["logged"]:
                trail.append(("bound", seen["bound"]))
                seen["logged"] = seen["bound"]

        def failures() -> None:
            """The attempts that found no node since the last look, oldest
            first, each with the evictions it made, behind the pods bound
            by then."""
            seen["failed"] = sched.failures
            failed = []
            for name, (event, count) in waiting.items():
                pod = live.get(name)
                if pod is None:
                    continue        # not created yet
                new = agg.get((f"{pod.namespace}/{pod.name}",
                               "FailedScheduling"))
                if new is None or (new is event and new.count == count):
                    continue
                if new is event and new.count > count + 1:
                    seen["misplaced"] += new.count - count - 1
                waiting[name] = (new, new.count)
                failed.append((new.timestamp, name))
            if failed or held:
                log_the_bound()
            for _, name in sorted(failed):
                trail.append(("failed", name))
                seen["failed_attempts"] += 1
                node = cs.pods[live[name].uid].nominated_node_name
                mine = [v for v in held if node and placements.get(v) == node]
                if mine:
                    nominations[name] = node
                    seen["nominated"] += 1
                for victim in mine:
                    held.remove(victim)
                    evictions[victim] = name
                    trail.append(("evicted", victim))
                    seen["evicted"] += 1
            seen["misplaced"] += len(held)
            del held[:]

        def look() -> None:
            """What the loop has done since the last look, for the log, and
            whether the next preemptor's mark has passed."""
            if sched.failures != seen["failed"] or held:
                failures()
            while (len(issued) < len(due_at)
                   and seen["bound"] >= due_at[len(issued)]):
                issued.append((seen["bound"], time.perf_counter()))
                due.release()

        def on_pod(kind_, old, new) -> None:
            # the client's watch, on the thread that makes the event. (The
            # program assumes a pod on the very object the store holds, so a
            # bind's `old` carries the node too.)
            if kind_ == "update" and new.node_name:
                name = new.name
                if name in high_names:
                    if name in landed:
                        return
                    failures()
                    log_the_bound()
                    trail.append(("placed", name))
                    landed[name] = new.node_name
                    waiting.pop(name, None)
                    return
                # a failed attempt since the last look lies BEFORE this bind
                if sched.failures != seen["failed"]:
                    failures()
                seen["bound"] += 1
                if (len(issued) < len(due_at)
                        and seen["bound"] >= due_at[len(issued)]):
                    look()
            elif kind_ == "delete":
                held.append(new.name)

        watch[0] = on_pod
        with TraceAnnotation("bench.wave"):
            cpu0 = time.process_time()
            t0 = time.perf_counter()
            creator.start()
            while True:             # waves.py `_drain`, with the look
                progressed = sched.schedule_one()
                look()
                if progressed:
                    continue
                sched.queue.flush_backoff_completed()
                sched.flush_expired_waiters()
                if sched.schedule_one():
                    look()
                    continue
                if creator.is_alive():
                    if time.perf_counter() - t0 > STUCK_S:
                        raise RuntimeError(
                            f"wave {tag}: {seen['bound']} of {len(pods)} "
                            f"created pods bound after {STUCK_S:.0f}s, "
                            f"preemptors {len(issued)} issued, {len(landed)} "
                            f"bound: the client waits for a mark that does "
                            f"not pass")
                    sched.drain_event_inbox() or time.sleep(0.0002)
                    continue
                if not sched.drain_event_inbox():
                    break
            look()
            t1 = time.perf_counter()
            cpu_s = time.process_time() - cpu0
        watch[0] = None
        log_the_bound()
        gc_wave = _delta(collector.snapshot(), gc0) if collector else {}
        full_collections = gc.get_stats()[2]["collections"] - full0
        counters = _delta(_counters(sched), before)
        preempt = _delta(_preemption_counts(sched), pre0)
        landed_measured = [cs.pods[p.uid].node_name for p in pods]
        bound = sum(1 for node in landed_measured if node)
        log.extend(wave_log(tag, trail, tried))
        for i, node in enumerate(landed_measured):
            placements[f"{tag}-{i}"] = node
        for name in live:
            placements[name] = landed.get(name)
        never = (len(landed_measured) - bound
                 + sum(1 for name in high_names if name not in landed))
        with TraceAnnotation("bench.restore"):
            r0 = time.perf_counter()
            for p in pods:
                cs.delete_pod(cs.pods[p.uid])
                log.append(("delete", p.name, None))
            for name, pod in live.items():
                cs.delete_pod(cs.pods[pod.uid])
                log.append(("delete", name, None))
            anew = [f"init-r{restored[0] + j}"
                    for j in range(seen["evicted"])]
            restored[0] += len(anew)
            init_pods(anew)
            _waves._drain(sched, creator)
            for name in anew:
                placements[name] = cs.pods[name].node_name
            r1 = time.perf_counter()
        return {"tag": tag, "wave_s": t1 - t0,
                "create_s": created_at[0] - t0, "cpu_s": cpu_s,
                "restore_s": r1 - r0, "created": len(pods), "bound": bound,
                "counters": counters, "preempt": preempt, "gc": gc_wave,
                "full_collections": full_collections,
                "issued": [{"k": k + 1, "mark": due_at[k], "bound": b,
                            "at_s": round(t - t0, 6)}
                           for k, (b, t) in enumerate(issued)],
                "preemptors_bound": len(landed), "never_bound": never,
                "evicted": seen["evicted"], "nominated": seen["nominated"],
                "misplaced": seen["misplaced"],
                "failed_in_log": seen["failed_attempts"],
                "failed_attempts": sched.failures - failed0,
                "restored_unbound": sum(1 for name in anew
                                        if not placements[name])}

    def tell(kind_: str, i: int, w: dict) -> None:
        c, p = w["counters"], w["preempt"]
        under = sum(1 for s in w["issued"] if s["bound"] < per_wave)
        say(f"{kind_} {i}: {w['bound']}/{w['created']} bound in "
            f"{w['wave_s']:.4f}s (cpu {w['cpu_s']:.3f}), creates done at "
            f"+{w['create_s']:.4f}s, restore {w['restore_s']:.4f}s; "
            f"preemptors {len(w['issued'])} issued ({under} before the last "
            f"measured bind) {w['preemptors_bound']} bound, failed attempts "
            f"{w['failed_attempts']} evictions {w['evicted']} nominations "
            f"{w['nominated']}; dry runs device/host "
            f"{p['dry_runs_device']:.0f}/{p['dry_runs_host']:.0f}"
            f" nominated retries bound/fell through "
            f"{p['nominated_bound']:.0f}/"
            f"{p['nominated_fell_through']:.0f}; plan "
            f"{c.get('plan_build_s', 0):.3f} wait "
            f"{c.get('device_wait_s', 0):.3f} commit "
            f"{c.get('host_commit_s', 0):.3f} batches "
            f"{c.get('device_batches', 0)} hints {c.get('hint_hits', 0)} "
            f"host path {c.get('host_path_pods', 0)} "
            f"rebuilds full/delta/resume {c.get('plan_rebuilds_full', 0)}/"
            f"{c.get('plan_rebuilds_delta', 0)}/"
            f"{c.get('plan_rebuilds_resume', 0)} full collections "
            f"{w['full_collections']}"
            + (f" gc {w['gc']}" if w["gc"] else ""))

    warmups = []
    for w in range(int(params["warmup_waves"])):
        warmups.append(wave(f"warm{w}"))
        tell("warm-up wave", w, warmups[-1])

    ctx.window_opens()
    c0 = _counters(sched)
    p0 = _preemption_counts(sched)
    fallbacks0 = fallbacks_by_reason(sched) if kind == "device" else {}
    waves, traced = [], None
    if traced_waves:
        tracing.enter_context(ctx.profiler())
        spans.annotate(sched, params.get("host_spans", {}), say)
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
    if traced_waves and traced is None:
        traced = list(waves)    # a window shorter than the waves to trace
    elapsed = time.perf_counter() - t_open
    ctx.window_closes()
    counters = _delta(_counters(sched), c0)
    preempt = _delta(_preemption_counts(sched), p0)
    charged = (breaker_charges(_delta(fallbacks_by_reason(sched), fallbacks0))
               if kind == "device" else {})
    say(f"log: {len(log)} operations, {len(evictions)} evictions, "
        f"{len(nominations)} nominations")

    for i, w in enumerate(waves):
        tell("wave", i, w)
    every_wave = warmups + waves
    wave_s = sum(w["wave_s"] for w in waves)
    bound = sum(w["bound"] for w in waves)
    made = sum(w["created"] for w in waves)
    want = len(due_at)
    guards = [
        ("host_path_pods", counters.get("host_path_pods", 0), 0),
        ("breaker_charges", sum(charged.values()), 0),
        ("attempts_without_a_place",
         sum(w["misplaced"] for w in every_wave), 0),
        ("pods_never_bound",
         sum(w["never_bound"] + w["restored_unbound"] for w in every_wave),
         0),
        # every preemptor evicts three init pods of one node and is
        # nominated there, once
        ("waves_off_their_evictions",
         sum(1 for w in every_wave if w["evicted"] != 3 * want), 0),
        ("waves_off_their_nominations",
         sum(1 for w in every_wave if w["nominated"] != want), 0),
        # the program's failed attempts are the log's attempts that found no
        # node: a measured pod never fails, a preemptor once
        ("failed_attempts_off_the_log",
         sum(abs(w["failed_attempts"] - w["failed_in_log"])
             for w in every_wave), 0),
        ("failed_attempts", sum(w["failed_attempts"] for w in every_wave),
         want * len(every_wave)),
    ]
    if kind == "device":
        # every what-if is the kernel's: no host loop, no host recompute
        # after a candidate the host verify refused
        guards.append(("dry_runs_not_on_the_device",
                       int(sum(w["preempt"]["dry_runs_host"]
                               + w["preempt"]["preemption_attempts"]
                               - w["preempt"]["dry_runs_device"]
                               for w in every_wave)), 0))
    obs = {
        "window": {"waves": len(waves), "wave_s": wave_s, "pods": bound,
                   "elapsed_s": elapsed,
                   "restore_s": sum(w["restore_s"] for w in waves)},
        "counters": counters,
        "gc": ({k: sum(w["gc"][k] for w in waves) for k in waves[0]["gc"]}
               if collector else None),
        "cluster": {"nodes": len(nodes),
                    "zones": len({d["zone"] for d in nodes}),
                    "max_batch": int(getattr(sched, "max_batch", 1))},
        "preempt": {"per_wave": want, "marks": due_at, "window": preempt,
                    "waves": [{"wave_s": w["wave_s"], "issued": w["issued"],
                               "evicted": w["evicted"],
                               "nominated": w["nominated"],
                               "failed_attempts": w["failed_attempts"],
                               "preempt": w["preempt"],
                               "batches":
                                   w["counters"].get("device_batches", 0),
                               "rebuilds_full":
                                   w["counters"].get("plan_rebuilds_full", 0)}
                              for w in waves]},
    }
    if traced:
        tc = {}
        for w in traced:
            for k, v in w["counters"].items():
                tc[k] = tc.get(k, 0) + v
        obs["traced"] = {"counters": tc, "waves": len(traced)}
    return {
        "attempted": made, "failed": made - bound,
        "e2e": {"pods_per_s": bound / wave_s},
        "obs": obs, "guards": guards, "log": log, "placements": placements,
        "nodes": nodes, "templates": templates, "may_pend": [PREEMPTOR],
        "evictions": evictions, "nominations": nominations,
    }
