"""Toy driver `events`: cluster events between the pods, deterministic by
construction. Every step ends with `run_until_idle()`, so the order of the
log is the order in which the program's cache applied what happened, with no
thread and no clock in it (a driver of a real cell reads that order from the
program's event journal; this one needs no such reading).

One pass, after the nodes and the init pods:

    create the pod no node can hold (it must stay pending to the pass's end)
    remove the node of the first zone that holds the most pods
    create pods      add a node to that zone (its turn is not the list's end)
    create pods      add a second node, in the zone its number gives
    create pods      remove that second node with the pods it drew
    delete the first pods created (some sit on a removed node)
    create pods      delete the pending pod

The traffic file's `scheduler` names the program's scheduler (`host`: the
sequential `Scheduler` with deterministic ties; `device`: `TPUScheduler`).
The pass runs once as warm-up and once as the window, both in the log.
"""

from __future__ import annotations

import time

import objects
import reference


def _scheduler(kind: str):
    if kind == "host":
        from kubernetes_tpu.core import Scheduler
        return Scheduler(deterministic_ties=True)
    if kind == "device":
        from kubernetes_tpu.models import TPUScheduler
        return TPUScheduler()
    raise ValueError(f"traffic names scheduler {kind!r}: host or device")


def run(ctx) -> dict:
    cfg, say = ctx.config, ctx.say
    nodes = objects.cluster(cfg, ctx.seed)
    sched = _scheduler(ctx.traffic["scheduler"])
    cs = sched.clientset
    for desc in nodes:
        cs.create_node(objects.make_node(desc))
    templates = {g: cfg[g]["template"]
                 for g in ("initPods", "measurePods", "cannotFit")}
    protos = {g: objects.make_pod_prototype(t, ctx.bench_dir)
              for g, t in templates.items()}
    group = objects.node_groups(cfg)[0]["template"]
    zones = int(group["zones"])
    log, placements, live = [], {}, {}
    per = int(cfg["measurePods"]["count"])
    added = [len(nodes)]            # positions given to nodes added so far

    def create(names, which):
        for name in names:
            live[name] = cs.create_pod(objects.stamp(protos[which], name))
            log.append(("create", name, which))
        sched.run_until_idle()

    def delete(names):
        for name in names:
            pod = cs.pods[live.pop(name).uid]
            placements[name] = pod.node_name
            cs.delete_pod(pod)
            log.append(("delete", name, None))
        sched.run_until_idle()

    def add_node(name, zone=None):
        index = added[0]
        while zone is not None and f"zone-{index % zones}" != zone:
            index += 1
        added[0] = index + 1
        desc = reference.node_description(name, index, group)
        cs.create_node(objects.make_node(desc))
        log.append(("node_add", name, desc))
        sched.run_until_idle()

    def remove_node(name):
        cs.delete_node(name)
        log.append(("node_delete", name, None))
        sched.run_until_idle()

    def fullest_of(zone):
        here = {n.name: 0 for n in cs.nodes.values()
                if n.labels["topology.kubernetes.io/zone"] == zone}
        for pod in cs.pods.values():
            if pod.node_name in here:
                here[pod.node_name] += 1
        return max(sorted(here), key=here.get)

    def one_pass(tag):
        names = [[f"{tag}-{k}-{i}" for i in range(per)] for k in range(4)]
        create([f"{tag}-large"], "cannotFit")
        first_zone = nodes[0]["zone"]
        remove_node(fullest_of(first_zone))
        create(names[0], "measurePods")
        add_node(f"{tag}-added-a", first_zone)
        create(names[1], "measurePods")
        add_node(f"{tag}-added-b")
        create(names[2], "measurePods")
        drew = sum(1 for p in cs.pods.values()
                   if p.node_name == f"{tag}-added-b")
        remove_node(f"{tag}-added-b")
        delete(names[0])
        create(names[3], "measurePods")
        pending = cs.pods[live[f"{tag}-large"].uid].node_name
        delete([f"{tag}-large"])
        return {"created": 4 * per + 1, "left_with_its_node": drew,
                "large_bound_to": pending}

    create([f"init-{i}" for i in range(int(cfg["initPods"]["count"]))],
           "initPods")
    warm = one_pass("warm")
    say(f"warm-up pass: {warm}")
    ctx.window_opens()
    t0 = time.perf_counter()
    got = one_pass("w")
    elapsed = time.perf_counter() - t0
    ctx.window_closes()
    say(f"window pass: {got}; device batches "
        f"{getattr(sched, 'device_batches', None)}, pods on the host path "
        f"{getattr(sched, 'host_path_pods', None)}, failed attempts "
        f"{sched.failures}")
    for name, pod in live.items():
        placements[name] = cs.pods[pod.uid].node_name
    bound = sum(1 for n in placements.values() if n)
    return {
        "attempted": got["created"], "failed": 0,
        "e2e": {"pods_per_s": got["created"] / elapsed},
        "obs": {}, "log": log, "placements": placements, "nodes": nodes,
        "templates": templates, "may_pend": ["cannotFit"],
        # what the steps are there for: an added node that draws no pod, or
        # a removed node that holds none, would pin nothing
        "guards": [("removed_node_held_no_pod",
                    int(warm["left_with_its_node"] == 0)
                    + int(got["left_with_its_node"] == 0), 0),
                   ("pods_bound_short_of_created",
                    len(placements) - 2 - bound, 0)],
    }
