"""Traffic driver `waves_churn`: `waves.py`'s closed loop with the reference's
churn op beside the measured pods, its interval restated in pods.

A wave is the configuration's measured pods, all created from a client thread
while this thread drives the loop, as in every `.waves` cell, and
`steps_per_wave` churn steps: step k is due when the wave's count of bound
measured pods is at or over `step_every_bound_pods` x k. In `recreate` mode
with `churn_number` 1 an odd step creates the configuration's churn objects in
the order of `churn_objects` and the next, even step deletes them, so a wave
with an even count of steps ends on the cluster it began with. An object the
configuration lists as `left_out` (the service: the program has no such
object, and the reference scheduler no handler for it) is skipped and said so.

Where a step falls. The client's own watch (`on_pod_event`) counts the binds,
on the thread that makes them, and this thread looks again between turns of
the loop (`schedule_one`): a turn of the device scheduler's loop is a session,
so the first mark passes inside one, and the count is checked at every bind.
A step that is due is issued from the client thread, so its events are parked
in the scheduler's inbox as a watch's would be, and the loop takes them when
it does: a session that finds a node event parked stops refilling, retires
what is in flight and ends, and the next turn replays the event (a program
whose sessions do not is refused at once: it would run the whole wave before
the first step and send the batch in flight down the host path). Steps are
issued in order, each once the loop has taken the one before: the source's
scheduler has long tried the churn pod when the next tick deletes it (one
tick a second against 710 pods/s), and here two batches in flight are more
pods than lie between two marks, so a create and its delete parked together
would cancel in one drain and no churn pod would ever be attempted. A step is
therefore issued at the first bind at or past its mark at which the step
before it has been taken; `obs` says for every step of every wave the count
it was issued at and the count the loop took it at.

The log is read, not scripted. At each bind and between turns the driver
looks at the scheduler's event journal (`EventJournal.seq` and `since`,
`kubernetes_tpu/core/cache.py`: a node added or removed is a `structural`
record keyed by the node's name) and reads the failed attempts from the
scheduler's counter. A node event goes into the log where the journal had it,
behind the pods bound by then; a churn pod where it was attempted; the
measured pods in the order they were created, which is the order they are
admitted and placed in. A journal record that is not the event the wave
issues next, or a journal that has overrun, is counted
(`node_events_without_a_place`, limit 0). Where the events land depends on
how the two threads interleave, so two runs of one seed need not give one
log; each run's own log is what the reference replays.

Set-up, window, tracing, the wave's clock (first create to the drain's end,
which is the later of the last bind and the replay of the last step), the
restore and `pods_per_s` are `waves.py`'s. `scheduler` (`device`, the cell's,
or `host`, for the tests) names the program's scheduler.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import os
import threading
import time

import objects
import prom
import reference
import spans

HERE = os.path.dirname(os.path.abspath(__file__))
WAIT_SERIES = "scheduler_cluster_event_wait_seconds"
STUCK_S = 300.0     # a wave this long has a client waiting for ever


def _load(name: str):
    spec = importlib.util.spec_from_file_location(
        "bench_waves_churn_" + name[:-3], os.path.join(HERE, name))
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


def step_every(params: dict, per_wave: int) -> int:
    """The traffic's `step_every_bound_pods`, or for a wave too small for it
    (a rehearsal's) the spacing that keeps the last mark at nine tenths of
    the wave, where the cell's lies."""
    steps = int(params["steps_per_wave"])
    return min(int(params["step_every_bound_pods"]),
               per_wave * 9 // 10 // steps)


def step_objects(cfg: dict, params: dict, say=None) -> list:
    """The churn objects a step creates or deletes, in the traffic's order,
    without those the configuration leaves out."""
    kept = []
    for kind in params["churn_objects"]:
        why = cfg["churn"][kind].get("left_out")
        if why is None:
            kept.append(kind)
        elif say:
            say(f"churn object {kind!r} left out: {why}")
    unknown = set(kept) - {"node", "pod"}
    if unknown:
        raise reference.Unmodelled(f"churn objects {sorted(unknown)}")
    return kept


def wave_events(tag: str, steps: int, kinds: list, node_template: dict,
                position: int) -> list:
    """The node events a wave issues, in order, as the log holds them: an odd
    step's `node_add` (`position` is the place in the cluster's numbering of
    the wave's first churn node), the next step's `node_delete`."""
    events = []
    if "node" in kinds:
        for k in range(1, steps + 1):
            name = f"{tag}-churn-node-{k if k % 2 else k - 1}"
            events.append(
                ("node_add", name, reference.node_description(
                    name, position + (k - 1) // 2, node_template))
                if k % 2 else ("node_delete", name, None))
    return events


def wave_log(tag: str, trail: list, events: list, churn_pods: list,
             measured: int) -> tuple:
    """A wave's part of the log from what its watch and turns left
    (`trail`): the measured pods in creation order up to each count of bound
    pods, a node event where the journal had it, a churn pod where it was
    attempted and its delete behind its node's; then the restore's deletes.
    Returns the part and the churn pods attempted."""
    part, logged, attempted = [], 0, 0
    for what, value in trail:
        if what == "bound":
            part.extend(("create", f"{tag}-{i}", "measurePods")
                        for i in range(logged, value))
            logged = value
        elif what == "failed":
            for name in churn_pods[attempted:attempted + value]:
                part.append(("create", name, "churnPod"))
            attempted += value
        else:
            part.append(events[value])
            if events[value][0] == "node_delete" and attempted > 0:
                # the step's other delete: the pod parked since the step
                # before
                part.append(("delete", churn_pods[attempted - 1], None))
    part.extend(("delete", f"{tag}-{i}", None) for i in range(measured))
    return part, churn_pods[:attempted]


def _scheduler_series(sched) -> dict:
    """The one series the readers want of the program's `/metrics` page."""
    return {k: v for k, v in prom.parse(sched.metrics.expose()).items()
            if k[0].startswith(WAIT_SERIES)}


def run(ctx) -> dict:
    cfg, params, say = ctx.config, ctx.traffic, ctx.say
    if params["churn_mode"] != "recreate" or int(params["churn_number"]) != 1:
        raise reference.Unmodelled(
            f"churn mode {params['churn_mode']!r} number "
            f"{params['churn_number']}: recreate, 1")
    collector = _CollectorClock() if ctx.trace else None
    from jax.profiler import TraceAnnotation
    from kubernetes_tpu.core.cache import EV_STRUCTURAL
    from kubernetes_tpu.perf.device import breaker_charges, fallbacks_by_reason

    # a rehearsal's wave is a tenth of the cell's, and so are its batches
    sched = _scheduler(
        params.get("scheduler", "device"),
        params.get("rehearse", {}).get("max_batch") if ctx.rehearse else None)
    if not hasattr(sched, "cluster_events_parked"):
        raise SystemExit(
            "waves_churn: this program's sessions do not take a parked node "
            "event between batches (no Scheduler.cluster_events_parked): a "
            "step would wait for the whole wave, and the batch in flight when "
            "it is seen would take the host path; the cell cannot run here")
    nodes = objects.cluster(cfg, ctx.seed)
    cs = sched.clientset
    for desc in nodes:
        cs.create_node(objects.make_node(desc))
    templates = {"measurePods": cfg["measurePods"]["template"],
                 "churnPod": cfg["churn"]["pod"]["template"]}
    wave_proto = objects.make_pod_prototype(templates["measurePods"],
                                            ctx.bench_dir)
    churn_proto = objects.make_pod_prototype(templates["churnPod"],
                                             ctx.bench_dir)
    node_template = cfg["churn"]["node"]["template"]
    kinds = step_objects(cfg, params, say)
    per_wave = int(cfg["measurePods"]["count"])
    steps = int(params["steps_per_wave"])
    every = step_every(params, per_wave)
    log, placements = [], {}
    init = cfg.get("initPods", {"count": 0})
    if int(init["count"]):
        templates["initPods"] = init["template"]
        init_proto = objects.make_pod_prototype(init["template"],
                                                ctx.bench_dir)
        for i in range(int(init["count"])):
            cs.create_pod(objects.stamp(init_proto, f"init-{i}"))
            log.append(("create", f"init-{i}", "initPods"))
        sched.run_until_idle()
        placements.update({p.name: p.node_name for p in cs.pods.values()})
    say(f"cluster: {len(nodes)} nodes, {len(placements)} init pods bound; "
        f"a wave is {per_wave} pods and {steps} churn steps of {kinds}, one "
        f"every {every} bound pods")
    added = [len(nodes)]            # positions given to churn nodes so far
    watch = [None]                  # the open wave's reading of a pod event

    def watched(kind, old, new) -> None:
        if watch[0] is not None:
            watch[0](kind, old, new)

    cs.on_pod_event(watched)
    tracing = contextlib.ExitStack()
    traced_waves = int(params.get("traced_waves", 0)) if ctx.trace else 0

    def wave(tag: str) -> dict:
        # the client's own work, before the clock: the wave's pods and the
        # churn objects of its steps, stamped and described
        stamped = [objects.stamp(wave_proto, f"{tag}-{i}")
                   for i in range(per_wave)]
        events = wave_events(tag, steps, kinds, node_template, added[0])
        added[0] += (steps + 1) // 2
        churn = []                  # per odd step: (node, pod)
        for k in range(1, steps + 1, 2):
            desc = events[k - 1][2] if events else None
            churn.append((desc and objects.make_node(desc),
                          objects.stamp(churn_proto, f"{tag}-churn-pod-{k}")))
        pods, created_at = [], []
        churn_landed = {}
        due = threading.Semaphore(0)        # this thread: a step is due

        def step(k: int) -> None:
            node, pod = churn[(k - 1) // 2]
            if k % 2:
                if "node" in kinds:
                    cs.create_node(node)
                if "pod" in kinds:
                    cs.create_pod(pod)
            else:
                if "node" in kinds:
                    cs.delete_node(node.name)
                if "pod" in kinds:
                    live = cs.pods[pod.uid]
                    churn_landed[pod.name] = live.node_name
                    cs.delete_pod(live)

        def client():
            for pod in stamped:
                pods.append(cs.create_pod(pod))
            created_at.append(time.perf_counter())
            for k in range(1, steps + 1):
                due.acquire()
                step(k)

        # what the client's watch and this thread read, kept small: a count
        # for the pods, an entry for what happens between them
        trail = []                  # ("bound", n) | ("node", i) | ("failed", n)
        marks = []                  # per step issued: bound count, clock
        taken = []                  # per node event taken: bound count, clock
        failed0 = sched.failures
        journal = sched.journal
        before = _counters(sched)
        full0 = gc.get_stats()[2]["collections"]
        gc0 = collector.snapshot() if collector else {}
        creator = threading.Thread(target=client, daemon=True)
        seen = {"seq": journal.seq, "bound": 0, "logged": 0, "failed": 0,
                "misplaced": 0}

        def log_the_bound() -> None:
            if seen["bound"] != seen["logged"]:
                trail.append(("bound", seen["bound"]))
                seen["logged"] = seen["bound"]

        def journal_moved() -> None:
            """The cache applied something since the last look: a node event
            goes into the log here, behind the pods bound so far."""
            found = journal.since(seen["seq"])
            seen["seq"] = journal.seq
            if found is None:
                seen["misplaced"] += 1      # more than the journal retains
                return
            for e in found:
                if e.kind != EV_STRUCTURAL:
                    continue
                at = len(taken)
                if at >= len(marks) or events[at][1] != e.key:
                    seen["misplaced"] += 1  # not the event issued next
                    continue
                log_the_bound()
                trail.append(("node", at))
                taken.append((seen["bound"], time.perf_counter()))

        def look() -> None:
            """What the loop has done since the last look, for the log, and
            whether the next step is due: its mark passed and the step
            before it taken (its node event in the journal, its churn pod
            attempted)."""
            if journal.seq != seen["seq"]:
                journal_moved()
            failed = sched.failures - failed0
            if failed != seen["failed"]:
                log_the_bound()
                trail.append(("failed", failed - seen["failed"]))
                seen["failed"] = failed
            k = len(marks) + 1
            if (k <= steps and seen["bound"] >= every * k
                    and len(taken) >= (k - 1 if events else 0)
                    and failed >= (k // 2 if "pod" in kinds else 0)):
                marks.append((seen["bound"], time.perf_counter()))
                due.release()

        def bound_one(kind, old, new) -> None:
            # the client's watch: a bind, on the thread that made it, with
            # the journal as it stood when the pod was placed
            if kind == "update" and new.node_name:
                if journal.seq != seen["seq"]:
                    journal_moved()
                seen["bound"] += 1
                if seen["bound"] >= every * (len(marks) + 1):
                    look()

        watch[0] = bound_one
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
                            f"created pods bound after {STUCK_S:.0f}s, steps "
                            f"{len(marks)}, taken {len(taken)}: the client "
                            f"waits for a step that does not come due")
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
        landed = [cs.pods[p.uid].node_name for p in pods]
        bound = sum(1 for node in landed if node)
        nodes_at_end = len(cs.nodes)
        with TraceAnnotation("bench.restore"):
            r0 = time.perf_counter()
            for p in pods:
                cs.delete_pod(cs.pods[p.uid])
            _waves._drain(sched, creator)
            r1 = time.perf_counter()
        return {"tag": tag, "landed": landed, "wave_s": t1 - t0,
                "create_s": created_at[0] - t0, "cpu_s": cpu_s,
                "restore_s": r1 - r0, "created": len(pods), "bound": bound,
                "counters": _delta(_counters(sched), before), "gc": gc_wave,
                "full_collections": full_collections,
                "trail": trail, "events": events,
                "churn_landed": churn_landed,
                "churn_pods": [pod.name for _node, pod in churn],
                "steps": [{"k": k + 1, "mark": every * (k + 1), "bound": b,
                           "at_s": round(t - t0, 6)}
                          for k, (b, t) in enumerate(marks)],
                "taken": [{"bound": b, "at_s": round(t - t0, 6)}
                          for b, t in taken],
                "misplaced": seen["misplaced"], "nodes_at_end": nodes_at_end,
                "failed_attempts": sched.failures - failed0}

    def tell(kind: str, i: int, w: dict) -> None:
        c = w["counters"]
        say(f"{kind} {i}: {w['bound']}/{w['created']} bound in "
            f"{w['wave_s']:.4f}s (cpu {w['cpu_s']:.3f}), creates done at "
            f"+{w['create_s']:.4f}s, restore {w['restore_s']:.4f}s; steps "
            f"{len(w['steps'])} issued at bound "
            f"{[s['bound'] for s in w['steps']]} +s "
            f"{[round(s['at_s'], 3) for s in w['steps']]}; node events taken "
            f"at bound {[t['bound'] for t in w['taken']]} +s "
            f"{[round(t['at_s'], 3) for t in w['taken']]}; failed attempts "
            f"{w['failed_attempts']} nodes at end {w['nodes_at_end']}; plan "
            f"{c.get('plan_build_s', 0):.3f} wait "
            f"{c.get('device_wait_s', 0):.3f} commit "
            f"{c.get('host_commit_s', 0):.3f} batches "
            f"{c.get('device_batches', 0)} hints {c.get('hint_hits', 0)} "
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
    fallbacks0 = fallbacks_by_reason(sched)
    series0 = _scheduler_series(sched)
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
    series = prom.delta(_scheduler_series(sched), series0)
    charged = breaker_charges(_delta(fallbacks_by_reason(sched), fallbacks0))

    every_wave = warmups + waves
    never_tried = 0
    for w in every_wave:
        part, tried = wave_log(w["tag"], w["trail"], w["events"],
                               w["churn_pods"], len(w["landed"]))
        for i, node in enumerate(w["landed"]):
            placements[f"{w['tag']}-{i}"] = node
        for name in tried:
            placements[name] = w["churn_landed"].get(name)
        never_tried += len(w["churn_pods"]) - len(tried)
        log.extend(part)
    say(f"log: {len(log)} operations")

    for i, w in enumerate(waves):
        tell("wave", i, w)
    wave_s = sum(w["wave_s"] for w in waves)
    bound = sum(w["bound"] for w in waves)
    created = sum(w["created"] for w in waves)
    churn_bound = sum(1 for w in every_wave
                      for node in w["churn_landed"].values() if node)
    churn_pods = (steps + 1) // 2 if "pod" in kinds else 0
    guards = [
        ("host_path_pods", counters.get("host_path_pods", 0), 0),
        ("breaker_charges", sum(charged.values()), 0),
        ("steps_short_or_over",
         sum(abs(len(w["steps"]) - steps) for w in every_wave), 0),
        ("nodes_off_at_a_waves_end",
         sum(abs(w["nodes_at_end"] - len(nodes)) for w in every_wave), 0),
        ("churn_pods_bound", churn_bound, 0),
        ("node_events_without_a_place",
         sum(w["misplaced"] + len(w["events"]) - len(w["taken"])
             for w in every_wave), 0),
        # one attempt for each churn pod: nothing that happens while it is
        # parked can admit it, so no second one, and none is deleted untried
        ("churn_pods_never_attempted", never_tried if churn_pods else 0, 0),
        ("failed_attempts", sum(w["failed_attempts"] for w in every_wave),
         churn_pods * len(every_wave)),
    ]
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
        "prom": {"scheduler": series},
        "churn": {"steps_per_wave": steps, "every": every, "objects": kinds,
                  "waves": [{"wave_s": w["wave_s"], "steps": w["steps"],
                             "taken": w["taken"],
                             "failed_attempts": w["failed_attempts"],
                             "batches": w["counters"].get("device_batches", 0),
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
        "attempted": created, "failed": created - bound,
        "e2e": {"pods_per_s": bound / wave_s},
        "obs": obs, "guards": guards, "log": log, "placements": placements,
        "nodes": nodes, "templates": templates, "may_pend": ["churnPod"],
    }
