"""Toy driver `preempt`: preemptors among plain pods, the log read from what
the program showed, not scripted. Every step ends with `run_until_idle()`;
inside a step the order is the scheduler's own (the queue pops the higher
priority first, a requeued pod comes back when the queue lets it), and the
driver reads it as a client could:

- a bind (the pod watch, `on_pod_event`) is the attempt that placed the pod:
  its `create` in the log if it is the pod's first attempt, a `retry` if not;
- a `FailedScheduling` event on a pod (`sched.recorder`, the events a client
  would list) is an attempt that found no node: `create` or `retry` alike,
  put in front of whatever the watch shows next;
- a delete the driver did not issue is an eviction. With the fake clientset
  the program deletes its victims inside the preemptor's cycle, so the
  deletes reach the watch BEFORE the attempt's `FailedScheduling`; the driver
  holds them until that event names the preemptor, and logs the attempt
  first and the deletes behind it, which is where the cache saw them: before
  the next pod was tried. A victim goes to the preemptor that failed in the
  same look and was nominated to the victim's node; the nomination is read
  off the pod (`status.nominatedNodeName`), and kept only where victims of
  that look lie on the node (the field outlives the attempt that set it).
  Anything left without a place is counted (`attempts_without_a_place`,
  limit 0): an eviction nobody was nominated for, a pod that failed twice
  between two looks.

Steps, after the nodes and the init pods (one group after the other): a round
creates `measured_per_round` measured pods and then ONE preemptor, of the
configuration's `preemptors` templates in turn, at once, and runs the loop
until idle. `warmup_rounds` rounds are the warm-up, `rounds` the window; all
are in the log. Every pod created gets its ordinal as `creation_ts`, the
start time the reference takes (the program's pods are stamped from one
prototype and would otherwise all carry the prototype's).

The traffic file's `scheduler` names the program's scheduler (`host`: the
sequential `Scheduler` with deterministic ties; `device`: `TPUScheduler`).
"""

from __future__ import annotations

import time

import objects


def _scheduler(kind: str):
    if kind == "host":
        from kubernetes_tpu.core import Scheduler
        return Scheduler(deterministic_ties=True)
    if kind == "device":
        from kubernetes_tpu.models import TPUScheduler
        return TPUScheduler()
    raise ValueError(f"traffic names scheduler {kind!r}: host or device")


def run(ctx) -> dict:
    cfg, params, say = ctx.config, ctx.traffic, ctx.say
    nodes = objects.cluster(cfg, ctx.seed)
    sched = _scheduler(params["scheduler"])
    cs = sched.clientset
    for desc in nodes:
        cs.create_node(objects.make_node(desc))
    templates = {"measurePods": cfg["measurePods"]["template"]}
    for g, group in enumerate(objects.groups(cfg, "initPods")):
        templates[f"initPods-{g}"] = group["template"]
    preemptors = [f"preemptor-{k}"
                  for k in range(len(objects.groups(cfg, "preemptors")))]
    for which, group in zip(preemptors, objects.groups(cfg, "preemptors")):
        templates[which] = group["template"]
    protos = {g: objects.make_pod_prototype(t, ctx.bench_dir)
              for g, t in templates.items()}

    log, placements, evictions, nominations = [], {}, {}, {}
    group_of, live = {}, {}         # pod -> template group; pod -> object
    tried = set()                   # pods the log has a `create` for
    waiting = {}                    # unbound preemptors -> events seen
    held = []                       # evictions seen, their attempt not yet
    seen = {"created": 0, "misplaced": 0}

    def attempt(name):
        log.append(("retry", name, None) if name in tried
                   else ("create", name, group_of[name]))
        tried.add(name)

    def failures():
        """The attempts that found no node since the last look, oldest
        first, each with the evictions it made."""
        failed = []
        for name, (event, count) in list(waiting.items()):
            pod = live[name]
            new = sched.recorder._agg.get(
                (f"{pod.namespace}/{pod.name}", "FailedScheduling"))
            if new is None or (new is event and new.count == count):
                continue
            if new is event and new.count > count + 1:
                seen["misplaced"] += new.count - count - 1
            waiting[name] = (new, new.count)
            failed.append((new.timestamp, name))
        for _, name in sorted(failed):
            attempt(name)
            node = cs.pods[live[name].uid].nominated_node_name
            mine = [v for v in held if node and placements[v] == node]
            if mine:
                nominations[name] = node
            for victim in mine:
                held.remove(victim)
                evictions[victim] = name
                log.append(("delete", victim, None))
        seen["misplaced"] += len(held)
        del held[:]

    def watched(kind, old, new):
        # (the program assumes a pod on the very object the store holds,
        # so a bind's `old` carries the node too)
        if (kind == "update" and new.node_name
                and new.name not in placements):
            failures()
            attempt(new.name)
            placements[new.name] = new.node_name
            waiting.pop(new.name, None)
        elif kind == "delete":
            held.append(new.name)

    cs.on_pod_event(watched)

    def create(names, which):
        for name in names:
            pod = objects.stamp(protos[which], name)
            pod.creation_ts = float(seen["created"])
            seen["created"] += 1
            group_of[name] = which
            if which in preemptors:
                waiting[name] = (None, 0)
            live[name] = cs.create_pod(pod)

    def idle():
        sched.run_until_idle()
        failures()

    def rounds(tag, count, first):
        per = int(params["measured_per_round"])
        evicted0 = len(evictions)
        for r in range(count):
            create([f"{tag}-{r}-{i}" for i in range(per)], "measurePods")
            create([f"{tag}-high-{r}"],
                   preemptors[(first + r) % len(preemptors)])
            idle()
        return {"created": count * (per + 1),
                "evictions": len(evictions) - evicted0}

    for g, group in enumerate(objects.groups(cfg, "initPods")):
        create([f"init-{g}-{i}" for i in range(int(group["count"]))],
               f"initPods-{g}")
        idle()
    warm = rounds("warm", int(params["warmup_rounds"]), 0)
    say(f"warm-up rounds: {warm}")
    ctx.window_opens()
    t0 = time.perf_counter()
    got = rounds("w", int(params["rounds"]), int(params["warmup_rounds"]))
    elapsed = time.perf_counter() - t0
    ctx.window_closes()
    unbound = sorted(name for name in live if not placements.get(name))
    say(f"window rounds: {got}; evictions {len(evictions)}, nominations "
        f"{len(nominations)}, retries "
        f"{sum(1 for op, _, _ in log if op == 'retry')}, unbound "
        f"{unbound[:5]}; device batches "
        f"{getattr(sched, 'device_batches', None)}, pods on the host path "
        f"{getattr(sched, 'host_path_pods', None)}, failed attempts "
        f"{sched.failures}")
    for name in unbound:
        placements[name] = None
    return {
        "attempted": got["created"], "failed": 0,
        "e2e": {"pods_per_s": got["created"] / elapsed},
        "obs": {}, "log": log, "placements": placements, "nodes": nodes,
        "templates": templates, "may_pend": preemptors,
        "evictions": evictions, "nominations": nominations,
        # what the rounds are there for: a pass in which nobody is evicted
        # would pin nothing
        "guards": [("attempts_without_a_place", seen["misplaced"], 0),
                   ("passes_without_an_eviction",
                    int(warm["evictions"] == 0) + int(got["evictions"] == 0),
                    0),
                   ("pods_never_bound", len(unbound), 0)],
    }
